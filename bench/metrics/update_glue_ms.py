"""Device time per epoch in the `asybadmm.worker_update` and
`asybadmm.server_update` scopes other than the two kernels: their
wrappers' pads, reshapes and merges, and rho_sum."""
from spans import stage_ms


def read(ctx):
    return stage_ms(ctx, "asybadmm.worker_update", "asybadmm.server_update")
