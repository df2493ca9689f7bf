"""Host self time per PS round in `ps.server.fold` and `ps.server.commit`:
the block servers' push folds and commit dispatches (ps/server.py)."""
from spans import host_ms


def read(ctx):
    return host_ms(ctx, "ps.server.fold", "ps.server.commit")
