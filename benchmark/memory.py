"""The fullest chip's peak, as the runtime reports it.

On the v5e's runtime ``peak_bytes_in_use`` leaves a running program's
temporaries out (PR 22 read 0.94e9 B after a step whose executable needs
12.95e9 B).  The temporaries are counted under ``bytes_reserved``: my chip
run of PR 24 read ``peak_bytes_reserved`` 1.31e9 / 2.61e9 / 6.16e9 /
11.81e9 B after the accuracy forward at batch 1 / 2 / 4 / 8, against
1.37e9 / 2.68e9 / 6.23e9 / 11.9e9 B of temp + arguments + outputs from
``memory_analysis()``.  So the peak is the sum of the two counters' peaks
(an upper estimate where the two peaks fall at different instants, by at
most the live buffers: weights and one batch).  Where the runtime reports no
reservation counter, the largest executable's ``memory_analysis()`` plus
the live-buffer peak stands in, and the source line says so.
"""

from __future__ import annotations

from typing import Tuple


def _largest_executable(executables: dict) -> int:
    """Largest ``hbm_bytes`` (arguments + outputs + temp, net of aliasing)
    in a ``GET /debug/compiles`` payload."""
    return max((int(rec.get("hbm_bytes") or 0)
                for rec in (executables or {}).get("executables", [])),
               default=0)


def peak_bytes(memory: dict) -> Tuple[int, str]:
    """``(bytes on the fullest chip, where the number comes from)``."""
    best, source = 0, "nothing reported"
    for stats in memory.get("memory_stats") or []:
        live = int(stats.get("peak_bytes_in_use", 0))
        if "peak_bytes_reserved" in stats:
            total = live + int(stats["peak_bytes_reserved"])
            src = "memory_stats peak_bytes_in_use + peak_bytes_reserved"
        else:
            total = live + _largest_executable(memory.get("executables"))
            src = ("memory_stats peak_bytes_in_use + memory_analysis() of "
                   "the largest executable")
        if total > best:
            best, source = total, src
    return best, source
