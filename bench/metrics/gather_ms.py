"""Device time per epoch in the `asybadmm.gather` scope: the delay draw
and the read of z~ from the z_hist ring (core/space.py::asybadmm_epoch)."""
from spans import stage_ms


def read(ctx):
    return stage_ms(ctx, "asybadmm.gather")
