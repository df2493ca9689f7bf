"""Host self time per PS round in the `ps.worker.*` spans: z~ join and
embed, the eager minibatch draw, and the dispatch of the worker ops and
pushes (ps/worker.py)."""
from spans import host_ms


def read(ctx):
    return host_ms(ctx, "ps.worker.")
