"""Device time per epoch outside the gradient and the two kernels: the
epoch's glue (delayed gather, minibatch draw, selection, packing, merges
and, on a mesh, the collectives)."""


def read(ctx):
    t = ctx["trace"]
    op = t["op_s"]
    rest = t["busy_s"] - op["grad"] - op["worker_kernel"] - op["server_kernel"]
    return 1000.0 * rest / ctx["units"]
