"""Device time per epoch of the glue (what the gradient and the two
kernels leave) in no program scope: what the scopes miss."""
from spans import UNSCOPED


def read(ctx):
    program = ctx.get("program")
    if program is None:
        return None
    return 1000.0 * program["stage_s"][UNSCOPED] / ctx["units"]
