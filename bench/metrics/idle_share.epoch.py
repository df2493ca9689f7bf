"""Share of the traced window in which the chip ran no instruction: the
host dispatch layer's cost (``api.ConsensusSession`` and the window's dispatch
loop)."""


def read(ctx):
    t = ctx["trace"]
    return 100.0 * (1.0 - t["busy_s"] / t["window_s"])
