"""HBM bytes that Algorithm 1 needs at each kernel's interface, from the
shapes and the epoch's own selected fraction — the numerators of the
roofline shares. Counted per chip, float32, whole (dblk-wide) rows.

* worker update, eqs. (11), (12), (9): for each selected (worker, block)
  row it reads g, y and z~ and writes y' and w (and x', where the session
  keeps x);
* server update, eq. (13) as written: for every block it reads the cached
  w of each edge worker and z, and writes z. On a mesh the kernel is the
  prox alone, after the exchange: it reads the reduced w and z and
  writes z.

A kernel that moves fewer rows, or aliases in place, reads a higher
share and never more than 100%.
"""
from __future__ import annotations

F32 = 4


def worker_update(s: dict) -> float:
    rows = s["selected_fraction"] * s["num_workers"] * s["num_blocks"]
    per_row = 3 + (3 if s["track_x"] else 2)
    return rows * per_row * s["block_dim"] * F32


def server_update(s: dict) -> float:
    if s["sharded"]:
        return 3 * s["num_blocks"] * s["block_dim"] * F32
    return (s["edges"] + 2 * s["num_blocks"]) * s["block_dim"] * F32
