"""Host time per PS round in the `ps.sync.*` spans: the blocking reads of
a loss and of a selection, the time the host waits on the chip."""
from spans import host_ms


def read(ctx):
    return host_ms(ctx, "ps.sync.")
