"""Whose time the device's idle time was.  The program scopes its host phases
as ``jax.profiler.TraceAnnotation`` spans (``serve.*`` in the serving engine,
``infer.*`` in the runner), which the profiler writes into the same trace as
the device's operations, on the same clock.  This takes the run's own
``.xplane.pb``, takes the device's operations where ``trace_reduce`` takes
them (same plane, same lines, same stand-in where a ``harness.TestRig``
rehearses on the CPU), and gives each instant in which none ran to the
innermost program span open at that instant on the worker's line.

All of it over the stretch that the worker's spans cover, from the first
one's start to the last one's end, and not over the reduction's window: a
capture records the host's spans over a shorter stretch than the device's
operations, and its window runs on past both, so the window's edges hold
idle time that no span can own (1.8-2.4 s of spans in a 4.0-4.2 s window,
my chip runs, PR 25).  The shares therefore sum to the idle share of that
stretch, not to ``device_idle_pct``.

The worker's line is the one that holds the ``*.execute`` events (the names
of OS threads are not reliable).  A capture holds a span only if it opened
inside it, so a wait for work that began earlier is missing: the stretch
from a ``serve.respond``'s end to the next ``serve.assemble``'s start counts
as ``serve.wait_work`` whether or not the event is there.  Where the trace
holds no program span, as with a program older than the spans, every
function here returns None: a reader then leaves its metric out, and never
reports 0.

Also here, because the same readers want it: the mean per dispatch of the
``serve_phase_seconds{phase=}`` histograms that the same scopes feed.
"""

from __future__ import annotations

import bisect
import functools
import glob
import os
import statistics
from typing import Dict, List, Optional, Sequence, Tuple

from benchmark import harness, prom, trace_reduce

PROGRAM_PREFIXES = ("serve.", "infer.")
UNATTRIBUTED = "unattributed"
# the worker's phases of a dispatch other than the wait for work and the
# wait for the device
ENGINE_SPANS = ("serve.assemble", "serve.upload", "serve.fetch",
                "serve.account", "serve.respond")
QUEUE_HOLD_SPANS = ("serve.wait_work",)

Span = Tuple[float, float, str]          # start ns, end ns, name


def _is_execute(name: str) -> bool:
    return name.startswith(PROGRAM_PREFIXES) and name.endswith(".execute")


def device_events(pd, host_stand_in: bool = False
                  ) -> Optional[List[Span]]:
    """The operations ``trace_reduce.reduce_profile`` takes its idle gaps
    from: the first device plane's, or the stand-in's; None where the trace
    has neither."""
    planes = trace_reduce.device_planes(pd)
    if planes:
        return [(a, b, name) for ln in planes[0].lines
                if ln.name in trace_reduce.OPS_LINES
                for a, b, name, _ in trace_reduce._events(ln)]
    if not host_stand_in:
        return None
    return [(a, b, name) for p in pd.planes if p.name.startswith("/host:CPU")
            for ln in p.lines
            if "XLAPjRt" in ln.name or "XLAEigen" in ln.name
            for a, b, name, _ in trace_reduce._events(ln) if "::" not in name]


def worker_spans(pd) -> List[Span]:
    """The program's spans on the line that holds most ``*.execute`` events;
    empty where no line holds one."""
    best, best_n = [], 0
    for p in pd.planes:
        if p.name.startswith("/device:"):
            continue
        for ln in p.lines:
            spans = [(float(e.start_ns), float(e.start_ns + e.duration_ns),
                      e.name) for e in ln.events
                     if e.name.startswith(PROGRAM_PREFIXES)]
            n = sum(_is_execute(name) for _, _, name in spans)
            if n > best_n:
                best, best_n = spans, n
    return sorted(best)


def waits_for_work(spans: Sequence[Span]) -> List[Span]:
    """From each ``serve.respond``'s end to the next ``serve.assemble``'s
    start on the worker's line, as ``serve.wait_work``: the worker does
    nothing else between two dispatches."""
    starts = sorted(a for a, _, name in spans if name == "serve.assemble")
    out = []
    for _, b, name in spans:
        if name != "serve.respond":
            continue
        i = bisect.bisect_left(starts, b)
        if i < len(starts):
            out.append((b, starts[i], QUEUE_HOLD_SPANS[0]))
    return out


def innermost(spans: Sequence[Span]) -> List[Span]:
    """``spans`` (nested or side by side, one thread's) as stretches that do
    not overlap, each named after the innermost span open in it."""
    out: List[Span] = []
    stack: List[Span] = []
    t = 0.0

    def close_until(limit: float) -> None:
        nonlocal t
        while stack and stack[-1][1] <= limit:
            _, end, name = stack.pop()
            if end > t:
                out.append((t, end, name))
                t = end

    for a, b, name in sorted(spans, key=lambda s: (s[0], -s[1])):
        close_until(a)
        if stack and a > t:
            out.append((t, a, stack[-1][2]))
        t = max(t, a) if stack else a
        stack.append((a, b, name))
    close_until(float("inf"))
    return out


def split_idle(idle: Sequence[Span], spans: Sequence[Span]
               ) -> Dict[str, float]:
    """Seconds of ``idle`` (gaps as ``trace_reduce.gaps`` gives them) by
    the name of the innermost of ``spans`` open then; what no span covers
    goes to ``unattributed``."""
    stretches = innermost(spans)
    starts = [a for a, _, _ in stretches]
    out: Dict[str, float] = {}
    for ga, gb, _ in idle:
        covered = 0.0
        i = max(bisect.bisect_right(starts, ga) - 1, 0)
        while i < len(stretches) and stretches[i][0] < gb:
            a, b, name = stretches[i]
            part = min(b, gb) - max(a, ga)
            if part > 0:
                out[name] = out.get(name, 0.0) + part * 1e-9
                covered += part
            i += 1
        rest = (gb - ga) - covered
        if rest > 0:
            out[UNATTRIBUTED] = out.get(UNATTRIBUTED, 0.0) + rest * 1e-9
    return out


def execute_skew_ms(spans: Sequence[Span], ops: Sequence[Span]
                    ) -> List[float]:
    """Per ``*.execute`` span that holds a device operation: the span's end
    less the end of the last operation that started inside it, in ms — how
    long after the device had finished the host knew, plus whatever the two
    planes' clocks differ by."""
    ops = sorted(ops)
    starts = [a for a, _, _ in ops]
    latest, m = [], float("-inf")
    for _, b, _ in ops:
        m = max(m, b)
        latest.append(m)
    out = []
    for a, b, name in spans:
        if not _is_execute(name):
            continue
        i = bisect.bisect_left(starts, b) - 1
        if i >= 0 and starts[i] >= a:
            out.append((b - latest[i]) * 1e-6)
    return out


def attribute(pd, host_stand_in: bool = False) -> Optional[dict]:
    """Idle seconds of the device by program span over the stretch that the
    worker's spans cover; None where the trace has no device operations (and
    no stand-in was asked for) or no program span."""
    ops = device_events(pd, host_stand_in)
    spans = worker_spans(pd)
    if not ops or not spans:
        return None
    lo, hi = spans[0][0], max(b for _, b, _ in spans)
    idle = trace_reduce.gaps([op for op in ops if op[1] > lo and op[0] < hi],
                             (lo, hi))
    skew = execute_skew_ms(spans, ops)
    return {
        "stretch_s": (hi - lo) * 1e-9,
        "idle_s": sum(b - a for a, b, _ in idle) * 1e-9,
        "idle_by_span": split_idle(idle, spans + waits_for_work(spans)),
        "executes": sum(_is_execute(name) for _, _, name in spans),
        "skew_ms": ({"median": statistics.median(skew), "max": max(skew)}
                    if skew else None),
    }


@functools.lru_cache(maxsize=2)
def _attribute_file(path: str, _mtime: float, host_stand_in: bool
                    ) -> Optional[dict]:
    from jax.profiler import ProfileData

    return attribute(ProfileData.from_file(path), host_stand_in)


def trace_file(cell_name: str) -> Optional[str]:
    """The run's own trace, found as the entries find it: the newest
    ``.xplane.pb`` under the cell's work directory."""
    files = glob.glob(os.path.join(harness.WORK_ROOT, cell_name, "**",
                                   "*.xplane.pb"), recursive=True)
    return max(files, key=os.path.getmtime) if files else None


def for_run(observed: dict) -> Optional[dict]:
    """``attribute`` of the traced run that ``observed`` is of; the several
    readers of one run share one reading of the file."""
    reduced = observed.get("trace")
    path = reduced and trace_file(observed["cell"]["name"])
    if not path:
        return None
    return _attribute_file(path, os.path.getmtime(path),
                           bool(reduced.get("stand_in_host_plane")))


def idle_share_pct(observed: dict, names: Optional[Sequence[str]]
                   ) -> Optional[float]:
    """Share of the stretch that the worker's spans cover in which the
    device was idle under the spans ``names``; with ``names`` None, under
    none of ``ENGINE_SPANS`` and ``QUEUE_HOLD_SPANS``: under ``*.execute``
    (the rest of the upload, the launch, the completion's notice, the
    clocks' skew) or under no span at all."""
    att = for_run(observed)
    if not att or att["stretch_s"] <= 0:
        return None
    by = att["idle_by_span"]
    if names is None:
        seconds = att["idle_s"] - sum(
            by.get(n, 0.0) for n in ENGINE_SPANS + QUEUE_HOLD_SPANS)
    else:
        seconds = sum(by.get(n, 0.0) for n in names)
    return 100.0 * seconds / att["stretch_s"]


def phase_ms_per_dispatch(observed: dict, phases: Sequence[str]
                          ) -> Optional[float]:
    """Mean per dispatch, over the whole window, of the host time the
    engine's ``serve_phase_seconds{phase=}`` histograms hold for ``phases``,
    in ms; None where ``/metrics`` has no such histogram."""
    counters = observed["counters"]
    labels = [f'phase="{p}"' for p in phases]
    if not all(any(k.startswith("serve_phase_seconds_sum{") and lab in k
                   for k in counters) for lab in labels):
        return None
    batches = prom.total(counters, "serve_batches_total")
    if batches <= 0:
        return None
    return 1e3 * sum(prom.total(counters, "serve_phase_seconds_sum", lab)
                     for lab in labels) / batches
