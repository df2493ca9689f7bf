"""Device time per epoch in the `asybadmm.push` scope: the push of the
new z onto the z_hist ring (core/async_sim.py::push_history)."""
from spans import stage_ms


def read(ctx):
    return stage_ms(ctx, "asybadmm.push")
