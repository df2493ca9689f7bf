"""Device time per epoch in the `asybadmm.grad` scope that the loss's own
instructions (grad_ms) leave: the layout loops, copies and sorts the
compiler puts around the gradient's gather and scatter."""
from spans import stage_ms


def read(ctx):
    return stage_ms(ctx, "asybadmm.grad")
