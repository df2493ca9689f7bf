#!/usr/bin/env python3
"""Smoke test of the AsyBADMM main path on a TPU, at the paper's table.

    python chip_smoke.py [--seed 0]
    python chip_smoke.py --chips 4      # sharded epoch on a 2x2 host

One process. With no arguments it needs one TPU chip and runs, in order
(any failure exits non-zero; nothing is caught and passed over):

1. device check: the default backend must be a TPU;
2. epoch: ``ConsensusSession.flat`` at the ``kdda_like`` block table
   (N=8 workers, M=64 blocks, dblk=315904, dim 20,217,856) on the
   paper's l1-regularized logistic loss (eq. 22), with data from
   ``make_sparse_logreg`` (KDDa's ~36 nonzeros per row, 4 samples per
   worker). ``backend="auto"`` must resolve to the fused Pallas kernels
   and the compiled epoch must hold them natively
   (``tpu_custom_call``). The same epochs on the jnp backend must give
   the same z within 1e-5, with a finite, falling objective;
3. PS runtime: ``run_ps`` at the same table with real compute; its
   recorded DelayTrace replayed through the epoch must give the same z.

With ``--chips 4`` it runs only the SPMD epoch on a (data=2, model=2)
mesh of four chips and the single-device epoch it must match.

Earlier lines report what ran (compile and epoch wall times are
informational, not metrics). The last line of stdout is one JSON
object naming the device.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import pathlib
import sys
import time

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent / "src"))

# the kdda_like block table (benchmarks/kernels_bench.py) and KDDa's
# row density (~305M nonzeros over ~8.4M samples, paper §5)
N_WORKERS, N_BLOCKS, DBLK = 8, 64, 315904
DIM = N_BLOCKS * DBLK
SAMPLES_PER_WORKER = 4
NNZ_PER_ROW = 36
EPOCHS = 3                       # warm epochs per timed run
PS_ROUNDS = 2
TOL = 1e-5                       # tests/test_backend_parity.py, test_spmd_parity.py
# tests/test_ps_runtime.py: a pallas replay is bitwise, a jnp one within
REPLAY_RTOL, REPLAY_ATOL = 1e-5, 1e-6
NATIVE_KERNEL = "tpu_custom_call"


def log(msg: str) -> None:
    print(f"[chip_smoke] {msg}", flush=True)


def require(ok: bool, msg: str) -> None:
    if not ok:
        print(f"[chip_smoke] FAILED: {msg}", file=sys.stderr, flush=True)
        raise SystemExit(1)


def logreg_loss(z, d):
    """Smooth part of eq. (22): mean logistic loss of one worker."""
    import jax.numpy as jnp
    X, y = d
    return jnp.mean(jnp.log1p(jnp.exp(-y * (X @ z))))


def make_data(seed: int, n: int = N_WORKERS, dim: int = DIM,
              samples: int = SAMPLES_PER_WORKER, nnz: int = NNZ_PER_ROW):
    from repro.data import make_sparse_logreg
    return make_sparse_logreg(num_workers=n, samples_per_worker=samples,
                              dim=dim, density=(nnz + 0.5) / dim, seed=seed)


def admm_config(seed: int, num_blocks: int = N_BLOCKS):
    from repro.configs.base import ADMMConfig
    return ADMMConfig(rho=2.0, gamma=0.1, max_delay=1, block_fraction=0.5,
                      num_blocks=num_blocks, l1_coef=1e-3, clip=1e4,
                      seed=seed)


def session(data, support, dim: int, cfg, **kw):
    from repro.api import ConsensusSession
    return ConsensusSession.flat(logreg_loss, data, dim=dim, cfg=cfg,
                                 support=support, **kw)


def objective_fn(sess):
    """Jitted eq. (1) objective taking the data as an argument (a
    closed-over data array would be baked into the program)."""
    import jax
    return jax.jit(lambda z, d: dataclasses.replace(
        sess.problem, data=d).objective(z))


def compile_epoch(sess, name: str):
    """AOT-compile the session's jitted epoch; returns (compiled, state0)."""
    state = sess.init()
    t0 = time.perf_counter()
    compiled = sess.step_fn().lower(state, sess.data).compile()
    log(f"{name}: epoch compile {time.perf_counter() - t0:.3f} s")
    return compiled, state


def run_epochs(sess, compiled, state, epochs: int, name: str):
    """Run warm epochs; returns (final state, [z per epoch], [objective])."""
    import jax
    import numpy as np
    objective = objective_fn(sess)
    objs = [float(objective(sess.z(state), sess.data))]
    zs = []
    for t in range(epochs):
        t0 = time.perf_counter()
        state, info = compiled(state, sess.data)
        jax.block_until_ready(state)
        dt = time.perf_counter() - t0
        zs.append(np.asarray(sess.z(state)))
        objs.append(float(objective(sess.z(state), sess.data)))
        log(f"{name}: epoch {t + 1} wall {dt:.6f} s, loss "
            f"{float(info['loss']):.6f}, objective {objs[-1]:.6f}")
    return state, zs, objs


def compare(name: str, got, want, rtol: float, atol: float) -> float:
    import numpy as np
    delta = max(float(np.max(np.abs(g - w))) for g, w in zip(got, want))
    log(f"{name}: max |dz| {delta:.3e} over {len(got)} epoch(s)")
    for t, (g, w) in enumerate(zip(got, want)):
        require(bool(np.all(np.abs(g - w) <= atol + rtol * np.abs(w))),
                f"{name}: z differs beyond rtol={rtol} atol={atol} at "
                f"epoch {t + 1} (max |dz| {float(np.max(np.abs(g - w)))})")
    return delta


def phase_epoch(data, support, dim: int, cfg, epochs: int):
    """Pallas epoch (native kernels) vs the jnp epoch, same chip."""
    import numpy as np
    sess = session(data, support, dim, cfg, backend="auto")
    require(sess.spec.space.backend == "pallas",
            f"backend auto resolved to {sess.spec.space.backend!r}, "
            f"not pallas")
    require(bool(getattr(sess.spec.reg, "fusable", False)),
            "the l1+box regularizer is not fusable into the server kernel")
    log("epoch: backend auto -> pallas, regularizer fusable")
    compiled, state = compile_epoch(sess, "pallas")
    require(NATIVE_KERNEL in compiled.as_text(),
            f"compiled pallas epoch holds no {NATIVE_KERNEL}: the kernels "
            f"did not lower natively")
    log(f"epoch: compiled pallas epoch holds {NATIVE_KERNEL}")
    _, z_pallas, objs = run_epochs(sess, compiled, state, epochs, "pallas")
    require(all(np.isfinite(objs)), f"objective not finite: {objs}")
    require(objs[-1] < objs[0],
            f"objective did not fall: {objs[0]} -> {objs[-1]}")
    del compiled, state

    sess_j = session(data, support, dim, cfg, backend="jnp")
    compiled, state = compile_epoch(sess_j, "jnp")
    _, z_jnp, objs_j = run_epochs(sess_j, compiled, state, epochs, "jnp")
    del compiled, state
    compare("pallas vs jnp", z_pallas, z_jnp, TOL, TOL)
    require(all(np.isfinite(objs_j)), f"jnp objective not finite: {objs_j}")
    return sess


def phase_ps(sess, data, support, dim: int, cfg, rounds: int):
    """run_ps with real compute, replayed through the epoch."""
    import numpy as np
    from repro.core.space import TraceDelay
    t0 = time.perf_counter()
    res = sess.run_ps(rounds, discipline="lockfree", record_z=False)
    log(f"ps: {rounds} lockfree rounds wall {time.perf_counter() - t0:.3f} s,"
        f" makespan {res.makespan} (virtual)")
    require(res.trace.complete, "PS trace is incomplete")
    require(res.z_final is not None, "PS run returned no z")
    delay = res.to_delay_model()
    require(isinstance(delay, TraceDelay), "PS trace is not a TraceDelay")
    replay = session(data, support, dim, cfg, backend="auto",
                     delay_model=delay)
    compiled, state = compile_epoch(replay, "replay")
    for _ in range(rounds):
        state, _ = compiled(state, replay.data)
    z_replay = np.asarray(replay.z(state))
    z_ps = np.asarray(res.z_final)
    compare("ps vs replay", [z_replay], [z_ps], REPLAY_RTOL, REPLAY_ATOL)
    bitwise = bool(np.array_equal(z_replay.view(np.uint32),
                                  z_ps.view(np.uint32)))
    log(f"ps: replay bitwise equal: {bitwise}")
    if replay.spec.space.backend == "pallas":
        require(bitwise, "pallas PS run and its replay differ bitwise")


def phase_sharded(data, support, dim: int, cfg, epochs: int, devices):
    """SPMD epoch on (data=2, model=2) vs the single-device epoch."""
    import jax
    from repro.launch.mesh import make_test_mesh
    mesh = make_test_mesh(devices=4, model=2)
    sess = session(data, support, dim, cfg, backend="auto", mesh=mesh)
    require(sess.spec.space.backend == "pallas",
            f"backend auto resolved to {sess.spec.space.backend!r}")
    compiled, state = compile_epoch(sess, "sharded")
    require(NATIVE_KERNEL in compiled.as_text(),
            f"compiled sharded epoch holds no {NATIVE_KERNEL}")
    state, z_sh, objs = run_epochs(sess, compiled, state, epochs, "sharded")
    mesh_devs = set(mesh.devices.flat)
    for name, arr in (("y", state.y), ("w_cache", state.w_cache),
                      ("z_hist", state.z_hist),
                      ("data X", jax.tree.leaves(sess.data)[0])):
        shards = {s.device for s in arr.addressable_shards}
        log(f"sharded: {name} {arr.shape} on {len(shards)} devices, "
            f"shard {arr.addressable_shards[0].data.shape}")
        require(shards == mesh_devs, f"{name} is not spread over the mesh")
    del compiled, state, sess

    one = jax.tree.map(lambda a: jax.device_put(a, devices[0]), data)
    sess_1 = session(one, support, dim, cfg, backend="auto")
    compiled, state = compile_epoch(sess_1, "single")
    _, z_1, _ = run_epochs(sess_1, compiled, state, epochs, "single")
    compare("sharded vs single-device", z_sh, z_1, TOL, TOL)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    args = ap.parse_args(argv)

    from repro.launch.compile_cache import enable_compile_cache
    cache = enable_compile_cache()
    import jax
    import jax.numpy as jnp

    devices = jax.devices()
    dev = devices[0]
    require(dev.platform == "tpu",
            f"no TPU: jax's default backend is {dev.platform!r}")
    require(len(devices) >= args.chips,
            f"--chips {args.chips} needs {args.chips} chips; jax sees "
            f"{len(devices)}")
    log(f"device {dev.device_kind} x{len(devices)}, compile cache {cache}")

    t0 = time.perf_counter()
    raw = make_data(args.seed)
    log(f"data: {raw.X.shape} dense design, "
        f"{int((raw.X != 0).sum())} nonzeros, made in "
        f"{time.perf_counter() - t0:.3f} s")
    cfg = admm_config(args.seed)
    if args.chips == 4:
        phase_sharded((raw.X, raw.y), raw.support, DIM, cfg, EPOCHS,
                      devices)
    else:
        data = (jnp.asarray(raw.X), jnp.asarray(raw.y))
        sess = phase_epoch(data, raw.support, DIM, cfg, EPOCHS)
        phase_ps(sess, data, raw.support, DIM, cfg, PS_ROUNDS)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": args.chips}}), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
