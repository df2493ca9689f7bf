"""Device time per epoch in the `asybadmm.select` scope: the block
selector and the participation mask (core/space.py)."""
from spans import stage_ms


def read(ctx):
    return stage_ms(ctx, "asybadmm.select")
