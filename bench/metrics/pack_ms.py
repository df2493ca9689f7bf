"""Device time per epoch in the `asybadmm.pack` scope: from_blocks and
to_blocks between the (M, dblk) table and the flat vector
(core/blocks.py), transposes included."""
from spans import stage_ms


def read(ctx):
    return stage_ms(ctx, "asybadmm.pack")
