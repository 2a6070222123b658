"""Least time of a search call, from the benchmark's own count of the
work its configured algorithm needs (`counts/<kind>.py`) and the chip's
peaks (`peaks.json`), and the share of device busy time it makes up.

Least time is the larger of bytes over HBM bandwidth and operations over
the peak of the unit that does them. The count never follows what an
engine happens to do, so the share reads the same whatever implements
the search; padding and repeated reads lower it.
"""

from __future__ import annotations

from typing import Optional

PEAK_KEY = {"bf16": "bf16_flops", "int8": "int8_ops"}


def least_s(work: dict, peak: dict) -> float:
    return max(work["bytes"] / peak["hbm_bytes_per_s"],
               work["ops"] / peak[PEAK_KEY[work["ops_dtype"]]])


def share(run) -> Optional[float]:
    """Least time of every device call in the window over the device's
    busy time there, in %; None without a trace or a call to count."""
    if run.trace is None or not run.calls or run.counts is None:
        return None
    least = sum(least_s(run.counts.least(run.cfg, nq), run.peak)
                for nq in run.calls)
    return 100.0 * least / run.trace["busy_s"]
