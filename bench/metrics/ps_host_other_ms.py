"""Host time per PS round on the runtime's thread in no program span:
the event scheduler, the sink and the benchmark loop."""
from spans import host_spans


def read(ctx):
    if host_spans(ctx, "ps.") is None:
        return None
    return 1000.0 * ctx["program"]["host_other_s"] / ctx["units"]
