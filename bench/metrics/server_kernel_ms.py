"""Device time per epoch of the server kernel (kernels/prox_update.py)."""
from per_layer import per_unit_ms


def read(ctx):
    return per_unit_ms(ctx, "server_kernel")
