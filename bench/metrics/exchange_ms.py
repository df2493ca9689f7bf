"""Device time per epoch in the `asybadmm.exchange` scope of the sharded
epoch (core/sharded.py): the collectives collective_ms reads plus the
copies and slices around them."""
from spans import stage_ms


def read(ctx):
    return stage_ms(ctx, "asybadmm.exchange")
