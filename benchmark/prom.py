"""Prometheus text, as ``/metrics`` serves it, to numbers.  Stdlib only."""

from __future__ import annotations

from typing import Dict


def parse(text: str) -> Dict[str, float]:
    """``{sample name with its label set: value}``; a histogram's
    ``_sum`` / ``_count`` / ``_bucket{le=...}`` samples keep their names."""
    out: Dict[str, float] = {}
    for line in text.splitlines():
        if not line or line.startswith("#"):
            continue
        head, _, value = line.rpartition(" ")
        try:
            out[head] = float(value)
        except ValueError:
            continue
    return out


def delta(before: Dict[str, float], after: Dict[str, float]
          ) -> Dict[str, float]:
    """What each sample grew by over the window (counters and histogram
    sums; a gauge's difference means nothing and is the reader's to
    ignore)."""
    return {k: v - before.get(k, 0.0) for k, v in after.items()}


def total(samples: Dict[str, float], name: str, label: str = "") -> float:
    """Sum of the samples called ``name`` whose label set holds ``label``;
    0.0 where there is none."""
    return sum(v for k, v in samples.items()
               if k.split("{")[0] == name and label in k)
