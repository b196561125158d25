"""A kernel's share of its roofline, read from the device trace.

The least time is the bytes the entry calls' algorithm must move (each
call charged for the rows it was handed, ``harness/work.py``) at the
chip's peak HBM bandwidth; the kernel time is the device busy time
inside that entry's annotations.  No VPU integer peak is published for
the v5e, so every roofline here is bounded by HBM bytes.
"""

from __future__ import annotations


def share_pct(ctx, label: str):
    """Percent of the roofline for the entries named ``label``, or None
    where the trace holds nothing to read."""
    view = ctx.trace
    if view is None or not view.chips or not view.intervals(label):
        return None
    nbytes = ctx.work_bytes.get(label)
    if not nbytes:
        return None
    device_ns = sum(view.label_busy_ns(label, c)
                    for c in view.chips) / len(view.chips)
    if device_ns <= 0:
        return None
    least_s = nbytes / float(ctx.peaks["hbm_bytes_per_s"])
    return 100.0 * least_s / (device_ns / 1e9)
