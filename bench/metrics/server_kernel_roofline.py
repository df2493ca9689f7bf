"""The server kernel's share of its HBM roofline (kernel_bytes.py)."""
import kernel_bytes
from per_layer import roofline


def read(ctx):
    return roofline(ctx, "server_kernel",
                    kernel_bytes.server_update(ctx["shapes"]))
