"""The comparison that decides ``correct``: each sampled answer of the
timed path against the plain reference's answer for the same pair, in
numbers that each have a limit of their own (PERF.md §2 gives the readings
each limit was set from).
"""

from __future__ import annotations

from typing import Dict, List, Optional

import numpy as np


def answer_numbers(got: np.ndarray, want: np.ndarray,
                   unit: Optional[np.ndarray] = None,
                   tail: Optional[dict] = None) -> Dict[str, float]:
    """One answer against the reference's, both (H, W) disparity maps, in
    pixels of disparity (absolute gaps read alike from seed to seed where
    the configuration states float32; gaps relative to the mean disparity
    swing with the scene the random weights happen to see: my chip runs,
    PR 24).

    Where the configuration states bfloat16 the gaps in pixels do NOT read
    alike: how far bfloat16's rounding carries through the loop depends on
    the seed's weights (mean gap 0.08 to 0.33 px over 12 seeds), and no
    limit in pixels separates the program from the int8 control on every
    seed.  There ``unit`` is the reference's own answer for the same pair
    with every product's inputs rounded to that stated precision, and the
    program's gaps are counted against ITS gaps from ``want``:
    ``share_over_unit_tail`` is the share of pixels that lie further from
    the float32 reference than ``tail["times"]`` times the
    ``tail["percentile"]``-th percentile of the unit's own gaps on that
    pair, plus ``tail["plus_px"]`` (what the program rounds outside any
    product, its stored state and its answer, does not grow with the
    unit)."""
    if got.shape != want.shape or not np.isfinite(got).all():
        out = {"mean_gap_px": float("inf"), "p99_gap_px": float("inf"),
               "max_gap_px": float("inf"), "mean_abs_disparity_px": 0.0}
        if unit is not None:
            out.update(unit_p99_gap_px=0.0, p99_gap_units=float("inf"),
                       share_over_unit_tail=1.0)
        return out
    want = want.astype(np.float64)
    gap = np.abs(got.astype(np.float64) - want)
    out = {"mean_gap_px": float(gap.mean()),
           "p99_gap_px": float(np.percentile(gap, 99)),
           "max_gap_px": float(gap.max()),
           "mean_abs_disparity_px": float(np.abs(want).mean())}
    if unit is not None:
        unit_gap = np.abs(unit.astype(np.float64) - want)
        unit_tail = float(np.percentile(unit_gap, tail["percentile"]))
        out["unit_p99_gap_px"] = float(np.percentile(unit_gap, 99))
        out["p99_gap_units"] = out["p99_gap_px"] / out["unit_p99_gap_px"]
        out["share_over_unit_tail"] = float(
            (gap > tail["times"] * unit_tail + tail["plus_px"]).mean())
    return out


def decide(per_answer: List[Dict[str, float]], limits: Dict[str, float]
           ) -> List[dict]:
    """The worst reading of each limited number over the sample, beside
    its limit."""
    out = []
    for name, limit in limits.items():
        worst = max((a[name] for a in per_answer), default=float("inf"))
        out.append({"name": name, "value": worst, "limit": limit,
                    "ok": bool(worst <= limit)})
    return out
