"""Collective time per epoch during which the chip ran nothing else."""
from per_layer import per_unit_ms


def read(ctx):
    if per_unit_ms(ctx, "collective") is None:
        return None
    return 1000.0 * ctx["trace"]["op_s"]["collective_exposed"] / ctx["units"]
