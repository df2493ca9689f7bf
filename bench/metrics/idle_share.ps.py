"""Share of the traced PS rounds in which the chip ran no instruction:
the host path of the runtime (ps/runtime.py, ps/engine.py dispatch)."""


def read(ctx):
    t = ctx["trace"]
    return 100.0 * (1.0 - t["busy_s"] / t["window_s"])
