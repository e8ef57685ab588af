"""From a profiler trace (``.xplane.pb``, read with
``jax.profiler.ProfileData`` and nothing else) to the numbers the per-layer
metrics and the result line need: device busy time as the union of the
intervals in which an operation ran, the traced window, time under a named
scope, a kernel's time and launches, the operations that took most time, and
the longest idle gaps.

A device plane is one whose name starts with ``/device:`` and that has an
operations line.  A trace with none is refused.  Only the benchmark's own
tests and rehearsal (``harness.TestRig``, which no cell's files can carry)
pass ``host_stand_in``: the host plane's XLA-client lines then stand in,
so that the reduction can be rehearsed on the CPU.
"""

from __future__ import annotations

import math
import re
from typing import Dict, Iterable, List, Tuple

from benchmark.harness import BenchError

OPS_LINES = ("XLA Ops",)


def short_name(name: str) -> str:
    """An event's name is the whole HLO instruction on the TPU; keep its
    own name, its result's shape and its kind: ``%fusion.7 = f32[..] fusion``."""
    head = re.sub(r"\{[^}]*\}", "", name).split("(", 1)[0].strip()
    if " custom-call" in name and "tpu_custom_call" in name:
        head += " [tpu_custom_call]"
    return head[:96]


def result_elements(name: str) -> int:
    """Elements of an operation's result, read from its name (the HLO
    instruction: ``%gru_iter.31 = f32[96,312,9]{2,1,0:...} custom-call(``);
    of a tuple's first member; 0 where the name carries no shape (the CPU's
    stand-in events)."""
    m = re.match(r"\s*%?[\w.\-]+\s*=\s*\(?\s*\w+\[([\d,]*)\]", name)
    if not m:
        return 0
    return math.prod(int(d) for d in m.group(1).split(",") if d)


def _matches(name: str, pattern) -> bool:
    """``pattern``: a substring, or a list of substrings that all have to
    be in the name."""
    parts = [pattern] if isinstance(pattern, str) else list(pattern)
    return all(part in name for part in parts)


def scope_intervals(events, pattern) -> List[Tuple[float, float]]:
    """Intervals under a named scope.  The TPU's trace names an operation
    after the innermost scope it was staged in and carries no scope path,
    so a loop counts as under the scope when an operation inside it is named
    for it: the whole ``while`` of the refinement loop is ``gru_iter``'s,
    not only the kernels that happen to bear its name."""
    marked = [(a, b) for a, b, name, scope in events
              if _matches(name, pattern) or _matches(scope, pattern)]
    loops = [(a, b) for a, b, name, _ in events
             if name.lstrip("%").startswith("while")]
    out = list(marked)
    for la, lb in loops:
        if any(la <= a and b <= lb for a, b in marked):
            out.append((la, lb))
    return out


def _events(line) -> List[Tuple[float, float, str, str]]:
    out = []
    for e in line.events:
        if e.duration_ns <= 0:
            continue
        stats = {k: v for k, v in e.stats}
        scope = " ".join(str(v) for k, v in stats.items()
                         if isinstance(v, str) and k not in ("hlo_module",))
        out.append((float(e.start_ns), float(e.start_ns + e.duration_ns),
                    e.name, scope))
    return out


def union_seconds(intervals: Iterable[Tuple[float, float]]) -> float:
    total, end = 0.0, None
    for a, b in sorted(intervals):
        if end is None or a > end:
            total += b - a
            end = b
        elif b > end:
            total += b - end
            end = b
    return total * 1e-9


def gaps(intervals: Iterable[Tuple[float, float, str]], window
         ) -> List[Tuple[float, float, str]]:
    """Idle stretches inside ``window`` as (start, end, name of the
    operation that ran last before it)."""
    out, end, last = [], window[0], "window start"
    for a, b, name in sorted(intervals):
        if a > end:
            out.append((end, a, last))
        if b > end:
            end, last = b, name
    if window[1] > end:
        out.append((end, window[1], last))
    return out


def self_times(events: List[Tuple[float, float, str, str]]
               ) -> Dict[str, float]:
    """Seconds by operation name, each event's time less its children's
    (an operation that holds others, as a loop holds its body, is charged
    only what is its own)."""
    out: Dict[str, float] = {}
    stack: List[list] = []          # [end, name, self_ns]

    def close():
        end, name, own = stack.pop()
        out[name] = out.get(name, 0.0) + max(own, 0.0) * 1e-9

    for a, b, name, _ in sorted(events, key=lambda e: (e[0], -e[1])):
        while stack and a >= stack[-1][0]:
            close()
        if stack:
            stack[-1][2] -= (min(b, stack[-1][0]) - a)
        stack.append([b, name, b - a])
    while stack:
        close()
    return out


def device_planes(pd) -> list:
    planes = [p for p in pd.planes if p.name.startswith("/device:")
              and any(ln.name in OPS_LINES for ln in p.lines)]
    return planes


def reduce_profile(pd, scopes: Dict[str, object], kernels: Dict[str, object],
                   window_s: float = None, host_stand_in: bool = False
                   ) -> dict:
    """``scopes``: metric key to the pattern that marks an operation as
    under that named scope.  ``kernels``: metric key to the pattern of a
    kernel's operation name.  ``window_s``: the traced window where the
    caller clocked it (whole calls between the profiler's start and stop);
    otherwise the span of the trace's own events.  A kernel's entry holds
    its device seconds, its launches, and the elements its launches wrote
    (from each event's own shape), so that a reader can count the work the
    trace holds from the trace and from nothing else."""
    planes = device_planes(pd)
    stand_in = not planes
    if stand_in and not host_stand_in:
        raise BenchError(
            f"the trace has no device plane with an operations line (planes: "
            f"{[p.name for p in pd.planes]}): nothing ran on a device, or "
            f"the profiler did not see it")
    per_plane = []
    if stand_in:
        host = [p for p in pd.planes if p.name.startswith("/host:CPU")]
        evs = [e for p in host for ln in p.lines
               if "XLAPjRt" in ln.name or "XLAEigen" in ln.name
               for e in _events(ln) if "::" not in e[2]]
        per_plane.append(evs)
    else:
        for p in planes:
            per_plane.append([e for ln in p.lines if ln.name in OPS_LINES
                              for e in _events(ln)])
    lo = min((e.start_ns for p in pd.planes for ln in p.lines
              for e in ln.events), default=0.0)
    hi = max((e.start_ns + e.duration_ns for p in pd.planes
              for ln in p.lines for e in ln.events), default=0.0)
    n = max(len(per_plane), 1)
    busy = sum(union_seconds((a, b) for a, b, _, _ in evs)
               for evs in per_plane) / n
    out_scopes = {}
    for key, sub in scopes.items():
        out_scopes[key] = sum(union_seconds(scope_intervals(evs, sub))
                              for evs in per_plane) / n
    out_kernels = {}
    for key, sub in kernels.items():
        hits = [(a, b, name) for evs in per_plane for a, b, name, _ in evs
                if _matches(name, sub)]
        if hits:
            out_kernels[key] = {
                "seconds": sum(b - a for a, b, _ in hits) * 1e-9 / n,
                "launches": len(hits) / n,
                "out_elements": sum(result_elements(name)
                                    for _, _, name in hits) / n}
    first = [(a, b, short_name(name), scope)
             for a, b, name, scope in (per_plane[0] if per_plane else [])]
    own = self_times(first)
    top = sorted(own.items(), key=lambda kv: -kv[1])[:10]
    idle = gaps(((a, b, name) for a, b, name, _ in first), (lo, hi))
    by_name: Dict[str, float] = {}
    for a, b, name in idle:
        key = f"after {name}"
        by_name[key] = by_name.get(key, 0.0) + (b - a) * 1e-9
    top_idle = [kv for kv in sorted(by_name.items(), key=lambda kv: -kv[1])
                if kv[1] >= 1e-6][:10]
    return {"busy_s": busy,
            "window_s": window_s if window_s else (hi - lo) * 1e-9,
            "device_planes": [p.name for p in planes],
            "stand_in_host_plane": stand_in,
            "scopes": out_scopes, "kernels": out_kernels,
            "breakdown": {"device_ops": [[k, v] for k, v in top],
                          "idle_gaps": [[k, v] for k, v in top_idle]}}


def reduce_file(path: str, scopes: Dict[str, object],
                kernels: Dict[str, object], window_s: float = None,
                host_stand_in: bool = False) -> dict:
    from jax.profiler import ProfileData

    return reduce_profile(ProfileData.from_file(path), scopes, kernels,
                          window_s, host_stand_in)


def describe(path: str, limit: int = 12) -> str:
    """What a trace holds, for a look by hand: planes, lines, and each
    line's most frequent event names with one event's stats."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    rows = []
    for p in pd.planes:
        rows.append(f"PLANE {p.name}")
        for ln in p.lines:
            evs = list(ln.events)
            rows.append(f"  LINE {ln.name}: {len(evs)} events")
            seen: Dict[str, list] = {}
            for e in evs:
                seen.setdefault(e.name, [0, 0.0, e])
                seen[e.name][0] += 1
                seen[e.name][1] += e.duration_ns
            for name, (cnt, ns, e) in sorted(
                    seen.items(), key=lambda kv: -kv[1][1])[:limit]:
                stats = {k: (v if not isinstance(v, str) else v[:160])
                         for k, v in e.stats}
                rows.append(f"    {name[:90]} x{cnt} {ns * 1e-6:.3f}ms "
                            f"{stats}")
    return "\n".join(rows)
