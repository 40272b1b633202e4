"""Roofline share of one category of device programs in a traced window."""
from __future__ import annotations


def share(rec, category: str) -> float | None:
    """Least time for the category's counted work at the chip's peaks, over
    its device time, in percent; None where nothing was counted or timed."""
    work, trace, peaks = rec.work, rec.trace, rec.peaks
    if not work or trace is None or peaks is None:
        return None
    flops = work.get(f"{category}_flops", 0) * len(rec.requests)
    bytes_ = work.get(f"{category}_bytes", 0) * len(rec.requests)
    seconds = trace["category_s"].get(category, 0.0)
    if flops <= 0 or seconds <= 0:
        return None
    least = max(flops / peaks["bf16_flops_per_s"],
                bytes_ / peaks["hbm_bytes_per_s"])
    return least / seconds * 100.0
