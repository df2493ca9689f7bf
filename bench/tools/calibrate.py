#!/usr/bin/env python3
"""The readings that the limits of ``correct`` are set from, many seeds
in one process (the compile is paid once):

    python3 bench/tools/calibrate.py kdda.minibatch --seeds 12 --control 3 \
        --faults half_batch

For each seed: the cell's set-up drives the program through its checked
epochs, the program is dropped and the float32 reference repeats them;
``program`` is the comparison of the two. For the first ``--control``
seeds the reference also runs in bfloat16 in the program's place
(``control``), and with each fault of ``--faults`` planted in it
(``faults``, from ``bench/tests/faults.py``); ``state_unchanged`` reads
a step that hands its state back, and ``z_support`` where the
reference's last z is nonzero. One JSON line per seed on stdout.
"""
from __future__ import annotations

import argparse
import importlib
import json
import pathlib
import sys
import time

BENCH = pathlib.Path(__file__).resolve().parents[1]
for p in (str(BENCH), str(BENCH / "tests"), str(BENCH.parent / "src")):
    sys.path.insert(0, p)

import numpy as np  # noqa: E402

import check  # noqa: E402
import faults  # noqa: E402
import run  # noqa: E402

FIRST_SEED = 1_000_003


def planted(name: str, cfg: dict, fn):
    """``fn()`` with the reference fault ``name`` planted, then removed."""
    saved = []

    def patch(obj, attr, value):
        saved.append((obj, attr, getattr(obj, attr)))
        setattr(obj, attr, value)
    faults.REFERENCE_FAULTS[name](cfg, patch)
    try:
        return fn()
    finally:
        for obj, attr, value in reversed(saved):
            setattr(obj, attr, value)


def z_support(z: np.ndarray, cfg: dict) -> dict:
    """Share of nonzero coordinates of z in each block, and nonzero
    coordinates on each model shard's blocks."""
    m = cfg["num_blocks"]
    u = -(-z.size // m)
    nz = np.pad(z != 0, (0, m * u - z.size)).reshape(m, u)
    share = nz.mean(axis=1)
    shards = (cfg.get("mesh") or {}).get("model", 1)
    return {"block0": float(share[0]),
            "others_max": float(share[1:].max()),
            "others_median": float(np.median(share[1:])),
            "blocks_nonzero": int((share > 0).sum()),
            "nonzero_by_model_shard": [int(c.sum()) for c in
                                       np.split(nz, shards)]}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("workload")
    ap.add_argument("--seeds", type=int, default=12)
    ap.add_argument("--control", type=int, default=3)
    ap.add_argument("--faults", default="",
                    help="comma-separated reference faults")
    ap.add_argument("--first-seed", type=int, default=FIRST_SEED)
    args = ap.parse_args(argv)
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    import jax
    import jax.numpy as jnp
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    cell = run.load_cell(args.workload)
    run.chips(cell["chips"])
    driver = importlib.import_module(f"drivers.{cell['traffic']['driver']}")
    names = [f for f in args.faults.split(",") if f]
    for k in range(args.seeds):
        seed = args.first_seed + 7919 * k
        t0 = time.perf_counter()
        r = driver.setup(cell, seed)
        t1 = time.perf_counter()
        driver.drop_program(r)
        ref = driver.reference_run(r, jnp.float32)
        t2 = time.perf_counter()
        out = {"workload": args.workload, "seed": seed,
               "program": check.gaps(r.checked, ref),
               "setup_s": t1 - t0, "reference_s": t2 - t1}
        out["compare_s"] = time.perf_counter() - t2
        if k < args.control:
            out["control"] = check.gaps(
                driver.reference_run(r, jnp.bfloat16), ref)
            out["faults"] = {"state_unchanged": check.gaps(
                faults.unchanged(ref), ref)}
            for name in names:
                out["faults"][name] = check.gaps(planted(
                    name, cell["config"],
                    lambda: driver.reference_run(r, jnp.float32)), ref)
            out["z_support"] = z_support(ref[-1][0], cell["config"])
        print(json.dumps(out), flush=True)
        del r, ref
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
