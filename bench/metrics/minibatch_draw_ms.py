"""Device time per epoch in the `asybadmm.minibatch` scope: the uniform
draw, its argsort and the row gather of each worker's minibatch
(core/async_sim.py::subsample_worker_data)."""
from spans import stage_ms


def read(ctx):
    return stage_ms(ctx, "asybadmm.minibatch")
