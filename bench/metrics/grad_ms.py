"""Device time per epoch of the worker gradient: the instructions in the
benchmark loss's named scope, forward and transposed."""
from per_layer import per_unit_ms


def read(ctx):
    return per_unit_ms(ctx, "grad")
