"""The arithmetic the per-layer readers (``metrics/<name>.py``) share.
Each takes ``observed`` — what a traced run saw: the window's counters as
``/metrics`` grew, the generator's sample, the reduced trace — and returns
a number, or None where there is nothing to read (the metric is then left
out of the line; a share of a peak is never reported as 0).
"""

from __future__ import annotations

from typing import Optional

from benchmark import flops, harness, prom


def histogram_mean_ms(obs: dict, name: str) -> Optional[float]:
    """Mean of a ``/metrics`` histogram over the window, in ms."""
    count = prom.total(obs["counters"], name + "_count")
    if count <= 0:
        return None
    return 1e3 * prom.total(obs["counters"], name + "_sum") / count


def dispatch_pairs_mean(obs: dict) -> Optional[float]:
    batches = prom.total(obs["counters"], "serve_batches_total")
    if batches <= 0:
        return None
    return prom.total(obs["counters"],
                      "serve_requests_completed_total") / batches


def latency_percentile_ms(obs: dict, q: float) -> Optional[float]:
    if not obs.get("latency_ms"):
        return None
    return harness.percentile(obs["latency_ms"], q, obs["attempted"])


def _padded_hw(obs: dict):
    h, w = obs["cell"]["workload"]["traffic"]["image_hw"]
    return -(-h // 32) * 32, -(-w // 32) * 32


def step_mfu_pct(obs: dict) -> Optional[float]:
    """Model FLOPs of the pairs completed in the window, over the window,
    over the chip's bf16 peak."""
    if not obs.get("pairs_completed"):
        return None
    cell = obs["cell"]
    per_pair = flops.forward_flops(cell["config"]["model"], *_padded_hw(obs),
                                   cell["workload"]["iters"])
    peak = harness.peaks_for(obs["device_kind"])["bf16_flops_per_s"]
    return 100.0 * per_pair * obs["pairs_completed"] / obs["seconds"] / peak


def scope_share_pct(obs: dict, scope: str) -> Optional[float]:
    tr = obs.get("trace")
    if not tr or not tr["scopes"].get(scope) or tr["busy_s"] <= 0:
        return None
    return 100.0 * tr["scopes"][scope] / tr["busy_s"]


def device_idle_pct(obs: dict) -> Optional[float]:
    tr = obs.get("trace")
    if not tr or tr["window_s"] <= 0 or tr["busy_s"] <= 0:
        return None
    return 100.0 * (1.0 - tr["busy_s"] / tr["window_s"])


def kernel_roofline_pct(obs: dict, kernel: str, work_fn) -> Optional[float]:
    """Least time by the table's peaks for the lookups the trace holds,
    over the kernel's device time there.  Both come from the trace alone:
    the lookups are the elements the kernel's launches wrote (each event's
    own shape) over the taps one lookup of one pair writes, so neither the
    host's clock nor the way the kernel splits a lookup into launches (one a
    level, or one for all) enters."""
    tr = obs.get("trace")
    k = tr and tr["kernels"].get(kernel)
    if not k or not k.get("out_elements") or k["seconds"] <= 0:
        return None
    cell = obs["cell"]
    model = cell["config"]["model"]
    h, w = _padded_hw(obs)
    lookups = k["out_elements"] / flops.lookup_taps(model, h, w)
    itemsize = 2 if model["mixed_precision"] and not model["corr_fp32"] else 4
    work = work_fn(model, h, w, itemsize)
    least, _bound = flops.least_seconds(
        {key: v * lookups for key, v in work.items()},
        harness.peaks_for(obs["device_kind"]))
    return 100.0 * least / k["seconds"]
