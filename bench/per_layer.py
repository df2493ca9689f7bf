"""What the per-layer readers share: per-unit times from the reduced
trace (``tracing.reduce``) and roofline shares from the byte counts."""
from __future__ import annotations


def per_unit_ms(ctx: dict, category: str):
    """Milliseconds per epoch (or round) in ``category``, or None when
    the trace has none of it."""
    seconds = ctx["trace"]["op_s"][category]
    if seconds <= 0:
        return None
    return 1000.0 * seconds / ctx["units"]


def roofline(ctx: dict, category: str, bytes_per_unit: float):
    """Least time the chip's HBM bandwidth allows for ``bytes_per_unit``,
    over the measured time, in percent; None without the kernel."""
    ms = per_unit_ms(ctx, category)
    if ms is None:
        return None
    least_s = bytes_per_unit / ctx["peaks"]["hbm_bytes_per_s"]
    return 100.0 * least_s / (ms / 1000.0)
