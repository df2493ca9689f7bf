"""Device busy time per PS round (ps/engine.py's jitted ops)."""


def read(ctx):
    return 1000.0 * ctx["trace"]["busy_s"] / ctx["units"]
