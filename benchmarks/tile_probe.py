"""Time the two epoch kernels alone, one tile at a time, on a TPU.

    PYTHONPATH=src python benchmarks/tile_probe.py [--shape N M DBLK]
        [--blk-d 512 ...] [--reps 10]

For each tile (blk_m, blk_d) the chip accepts -- blk_m a multiple of 8
dividing M, or M itself; blk_d a lane multiple dividing DBLK -- it jits
``admm_worker_select_update_3d`` and ``server_prox_fused_2d`` with that
tile forced, runs one warm-up call and reports the median host-clock
time of ``--reps`` calls, each ended by ``block_until_ready``. The jnp
oracles of ``kernels/ref.py`` are timed the same way as the reference
rows (``blk_m=0 blk_d=0``). The shape defaults to the kdda_like table
(N=8 workers, M=64 blocks, dblk=315904); the operands are made on the
device from a fixed key.

These are host-clock times of a single kernel, not profiler kernel
times. It refuses to run without a TPU: interpreted kernels say nothing
about tiles. The last line of stdout is a JSON object naming the
device.
"""
from __future__ import annotations

import argparse
import json
import statistics
import sys
import time

import jax
import jax.numpy as jnp

from repro.kernels.admm_update import admm_worker_select_update_3d
from repro.kernels.prox_update import server_prox_fused_2d
from repro.kernels.ref import (admm_worker_select_update_ref,
                               server_prox_update_ref)
from repro.kernels.tiling import LANE, SUBLANE

KDDA_LIKE = (8, 64, 315904)
GAMMA, L1, CLIP = 0.1, 1e-3, 1e4


def chip_tiles(M: int, d: int, blk_ds):
    """(blk_m, blk_d) pairs the chip accepts, smallest blk_m first."""
    ms = sorted({m for m in range(SUBLANE, M + 1, SUBLANE) if M % m == 0}
                | {M})
    return [(m, b) for m in ms for b in blk_ds
            if b % LANE == 0 and d % b == 0]


def operands(N: int, M: int, d: int, seed: int = 0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    big = lambda k: jax.random.normal(k, (N, M, d), jnp.float32)
    g, y, zt, w = (big(k) for k in ks[:4])
    sel = jax.random.bernoulli(ks[4], 0.5, (N, M))
    rho = jnp.full((N,), 2.0, jnp.float32)
    z_cur = jax.random.normal(ks[5], (M, d), jnp.float32)
    rho_sum = jnp.sum(jnp.where(sel, rho[:, None], 0.0), axis=0)
    return dict(g=g, y=y, zt=zt, w=w, sel=sel, rho=rho, z_cur=z_cur,
                rho_sum=rho_sum)


def median_ms(fn, args, reps: int) -> float:
    jax.block_until_ready(fn(*args))                  # compile + warm-up
    times = []
    for _ in range(reps):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        times.append((time.perf_counter() - t0) * 1e3)
    return statistics.median(times)


def probe(N: int, M: int, d: int, blk_ds=(512,), reps: int = 10,
          interpret=None):
    """Rows of (kernel, blk_m, blk_d, median ms) at one (N, M, d)."""
    o = operands(N, M, d)
    smask = o["sel"].astype(jnp.float32)[..., None]
    worker_args = (o["g"], o["y"], o["zt"], o["w"], smask, o["rho"])
    server_args = (o["z_cur"], o["w"], smask, o["rho_sum"][:, None])
    rows = []
    for blk_m, blk_d in chip_tiles(M, d, blk_ds):
        worker = jax.jit(lambda *a, m=blk_m, b=blk_d:
                         admm_worker_select_update_3d(
                             *a, interpret=interpret, blk_m=m, blk_d=b))
        server = jax.jit(lambda *a, m=blk_m, b=blk_d: server_prox_fused_2d(
            *a, GAMMA, L1, CLIP, interpret=interpret, blk_m=m, blk_d=b))
        rows.append(("worker", blk_m, blk_d,
                     median_ms(worker, worker_args, reps)))
        rows.append(("server", blk_m, blk_d,
                     median_ms(server, server_args, reps)))
    worker_jnp = jax.jit(admm_worker_select_update_ref)
    server_jnp = jax.jit(lambda *a: server_prox_update_ref(
        *a, GAMMA, L1, CLIP))
    rows.append(("worker_jnp", 0, 0, median_ms(
        worker_jnp, (o["g"], o["y"], o["zt"], o["w"], o["sel"], o["rho"]),
        reps)))
    rows.append(("server_jnp", 0, 0, median_ms(
        server_jnp, (o["z_cur"], o["w"], o["sel"], o["rho_sum"]), reps)))
    return rows


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--shape", type=int, nargs=3, default=KDDA_LIKE,
                    metavar=("N", "M", "DBLK"))
    ap.add_argument("--blk-d", type=int, nargs="+", default=[512])
    ap.add_argument("--reps", type=int, default=10)
    args = ap.parse_args(argv)

    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"tile_probe: no TPU (jax's default backend is "
              f"{dev.platform!r})", file=sys.stderr)
        return 1
    for name, blk_m, blk_d, ms in probe(*args.shape, blk_ds=args.blk_d,
                                        reps=args.reps):
        print(f"{name:<12} blk_m={blk_m:>3} blk_d={blk_d:>4} "
              f"median {ms:.3f} ms", flush=True)
    print(json.dumps({"device": dev.device_kind}), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
