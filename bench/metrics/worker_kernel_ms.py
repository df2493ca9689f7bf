"""Device time per epoch of the worker kernel (kernels/admm_update.py)."""
from per_layer import per_unit_ms


def read(ctx):
    return per_unit_ms(ctx, "worker_kernel")
