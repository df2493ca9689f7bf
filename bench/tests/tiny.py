"""Tiny copies of the cells for CPU rehearsals: the cell's own files with
the rows and features cut to a few hundred and 64K, and the Pallas
kernels in interpret mode.

    python bench/tests/tiny.py kddb.full4 [fault]

runs one tiny cell through ``run.measure`` on the CPU devices this
process sees (four, with ``--xla_force_host_platform_device_count=4``)
and prints the result line; ``fault`` breaks the timed path first.
"""
from __future__ import annotations

import json
import pathlib
import sys

BENCH = pathlib.Path(__file__).resolve().parents[1]
for p in (str(BENCH), str(BENCH.parent / "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

import run  # noqa: E402

ROWS_PER_WORKER = 256
FEATURES = 64 * 1024 + 37
SEED = 2**31 + 99


def tiny_cell(name: str) -> dict:
    cell = run.load_cell(name)
    cfg = dict(cell["config"])
    cfg.update(samples=cfg["num_workers"] * ROWS_PER_WORKER,
               features=FEATURES,
               admm=dict(cfg["admm"], backend="pallas"))
    cell["config"] = cfg
    return cell


def measure(name: str, seconds: float = 1.0, seed: int = SEED) -> dict:
    import jax
    cell = tiny_cell(name)
    return run.measure(cell, seed, seconds, False,
                       jax.devices()[:cell["chips"]])


if __name__ == "__main__":
    if len(sys.argv) > 2:
        import faults
        faults.FAULTS[sys.argv[2]]()
    print(json.dumps(measure(sys.argv[1])))
