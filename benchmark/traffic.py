"""The one traffic generator: a workload file's ``traffic`` group in, a
schedule out.  New mixes are new data files; nothing here names a cell.

``traffic`` keys:

* ``process``: ``"open_poisson"`` — requests are due at instants that do
  not depend on the answers (independent users); ``"closed"`` — ``clients``
  callers, each sending its next call when the last has returned.
* ``rate_per_s`` (open): offered requests a second, fixed in the file;
  ``phase_from_seed`` (optional, default true): whether the seed turns the
  one fixed succession of gaps to another starting point.
* ``pairs_per_call`` (closed): same-shape pairs in one call.
* ``pool_pairs``: distinct seeded pairs the requests draw from.
* ``image_hw``: the pairs' size.

Every seed gets the same work in another order: an open schedule's gaps are
the n = rate x seconds mid-quantiles of the exponential distribution in one
fixed succession, which the seed turns to another starting point; which pair a request carries is a seeded permutation of the pool,
repeated.  Runs with different seeds then differ in phase only, and a tail
does not swing with the bursts a seed happened to draw.
"""

from __future__ import annotations

import time
from concurrent.futures import ThreadPoolExecutor
from typing import Callable, List, Sequence

import numpy as np

GAP_ORDER = [0, 0xA221]     # the seed of the one succession of gaps


def open_schedule(rate_per_s: float, seconds: float, seed: int,
                  phase_from_seed: bool = True) -> np.ndarray:
    """Due instants (seconds from the window's start) of every request of
    an open-loop window.  A function of its arguments alone.

    The gaps are the n mid-quantiles of the exponential distribution in one
    fixed order (``GAP_ORDER``, no run's and no cell's to choose); the seed
    only turns that ring of gaps to another starting point.  So every seed
    sees the same bursts and lulls, in the same succession, from another
    phase: a tail then reads the system and not the draw.  With
    ``phase_from_seed`` false every seed gets the ring from its start (a
    cell above capacity: there the phase decides how soon the queue is long
    enough to fill the batcher, and completions follow the phase, not the
    system)."""
    n = max(1, int(round(rate_per_s * seconds)))
    u = (np.arange(n) + 0.5) / n
    gaps = -np.log1p(-u) / rate_per_s
    # the mid-quantile gaps sum to a little under n / rate: stretch them so
    # the last request is due just inside the window, for every seed alike
    gaps *= (seconds * (n - 0.5) / n) / gaps.sum()
    np.random.default_rng(GAP_ORDER).shuffle(gaps)
    return np.cumsum(np.roll(gaps, -(seed % n) if phase_from_seed else 0))


def pair_order(n_requests: int, pool: int, seed: int) -> np.ndarray:
    """Which pool pair each request (or each row of each call) carries:
    seeded permutations of the pool, one after another."""
    rng = np.random.default_rng([seed, 0x9A1F])
    reps = -(-n_requests // pool)
    return np.concatenate([rng.permutation(pool)
                           for _ in range(reps)])[:n_requests]


def sample_ids(n_requests: int, k: int, seed: int) -> List[int]:
    """The requests whose answers are kept for the comparison: ``k`` of
    them drawn from the seed, the last request always among them."""
    rng = np.random.default_rng([seed, 0xC0DE])
    k = min(k, n_requests)
    if k <= 0:
        return []
    ids = rng.choice(n_requests - 1, size=k - 1, replace=False).tolist()
    return sorted(ids + [n_requests - 1])


class OpenLoopResult:
    def __init__(self, n: int):
        self.due = np.zeros(n)          # seconds from the window's start
        self.sent = np.full(n, np.nan)
        self.done = np.full(n, np.nan)  # last byte of the answer
        self.ok = np.zeros(n, bool)
        self.info: List[object] = [None] * n
        self.t0 = 0.0                   # monotonic clock at the start

    @property
    def latency_s(self) -> np.ndarray:
        """From the instant each request was DUE, for the answered ones."""
        return (self.done - self.due)[self.ok]

    @property
    def late_s(self) -> np.ndarray:
        """How late the generator sent each request."""
        return (self.sent - self.due)[~np.isnan(self.sent)]


def run_open_loop(due: Sequence[float], send: Callable[[int], object],
                  max_in_flight: int = 96, drain_s: float = 60.0
                  ) -> OpenLoopResult:
    """Send request ``i`` at ``due[i]`` whatever became of the earlier ones
    (one pacing thread, a pool of senders), then wait up to ``drain_s`` past
    the last due instant for the answers.  ``send(i)`` returns anything, or
    raises for a failed request; an answer that comes late is late, not
    failed.  Times are from the window's start on the monotonic clock."""
    n = len(due)
    res = OpenLoopResult(n)
    res.due[:] = due
    t0 = res.t0 = time.monotonic()

    def one(i: int) -> None:
        res.sent[i] = time.monotonic() - t0
        try:
            res.info[i] = send(i)
            res.ok[i] = True
        except Exception as e:  # noqa: BLE001 — a failed request is counted
            res.info[i] = e
        res.done[i] = time.monotonic() - t0

    pool = ThreadPoolExecutor(max_workers=max_in_flight,
                              thread_name_prefix="load")
    futures = []
    for i in range(n):
        wait = due[i] - (time.monotonic() - t0)
        if wait > 0:
            time.sleep(wait)
        futures.append(pool.submit(one, i))
    deadline = t0 + float(due[-1]) + drain_s
    for f in futures:
        try:
            f.result(timeout=max(0.0, deadline - time.monotonic()))
        except Exception:  # noqa: BLE001 — never answered: counted as missing
            pass
    pool.shutdown(wait=False, cancel_futures=True)
    return res


def closed_loop_calls(seconds: float, call: Callable[[int], None]) -> tuple:
    """One caller: call ``call(k)`` for k = 0, 1, ... until ``seconds`` have
    passed; the call that is running then is finished and counted.  Returns
    ``(calls completed, seconds from the first call's start to the last
    call's end)`` — all the work over all the time."""
    t0 = time.monotonic()
    k = 0
    while time.monotonic() - t0 < seconds:
        call(k)
        k += 1
    return k, time.monotonic() - t0
