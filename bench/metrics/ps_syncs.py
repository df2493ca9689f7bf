"""Blocking device-to-host reads per PS round (`ps.sync.*` spans)."""
from spans import host_spans


def read(ctx):
    found = host_spans(ctx, "ps.sync.")
    if found is None:
        return None
    return sum(v["count"] for v in found) / ctx["units"]
