"""Backend compiles (`asybadmm.compile` markers) inside the traced window
of the epoch cells: 0 unless something recompiled."""


def read(ctx):
    program = ctx.get("program")
    return None if program is None else program["compiles"]
