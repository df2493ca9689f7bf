"""The worker kernel's share of its HBM roofline (kernel_bytes.py)."""
import kernel_bytes
from per_layer import roofline


def read(ctx):
    return roofline(ctx, "worker_kernel",
                    kernel_bytes.worker_update(ctx["shapes"]))
