"""Kernel-level benchmark: measured HBM bytes for the kernel-backed
(pallas) epoch vs the unfused jnp epoch vs the SPMD-sharded per-shard
program, measured wall-clock per epoch, plus the seed's analytic
roofline projections.

On this CPU container, interpret-mode wall time is not TPU-predictive;
what is meaningful and machine-independent is the HBM traffic each
formulation implies. We measure it from real lowered programs:

* both epochs are lowered through ``asybadmm_epoch`` (the single
  Algorithm 1 implementation) and costed by
  ``analysis/hlo_cost.analyze_hlo`` on the op-level (pre-optimization)
  HLO — every jnp op charged its operand+result traffic, i.e. the
  *unfused* execution the fusion claim is measured against;
* the pallas epoch is lowered with ``backend="pallas_stub"``: each
  fused kernel appears as a single opaque boundary op charged exactly
  its operand+result bytes — the same boundary model ``hlo_cost``
  applies to XLA fusions, and exactly the kernels' VMEM DMA contract;
* the SPMD epoch is costed *per shard*: ``core.sharded``'s
  ``per_shard_cost_program`` lowers one (data=4, model=2) shard of the
  sharded epoch (collectives replaced by shape-faithful single-device
  stand-ins, state shrunk to its local tile) — the gate checks the
  per-shard bytes shrink toward 1/(data*model) of the fused epoch.

Wall-clock is additionally *executed* at the smoke shape (jit + warmup,
then median of 5 ``block_until_ready`` epochs) for jnp vs
pallas(interpret) vs sharded-pallas on an 8-host-device mesh, so
BENCH_kernels.json carries a real measured trajectory next to the cost
model (CPU-relative numbers; the byte counts are the portable claim).

Sizes follow the paper's kddA workload (~20.2M features; here split
into M=64 lane-aligned blocks over N=8 workers) plus a small smoke
case. Results land in ``BENCH_kernels.json`` at the repo root.

``--smoke`` additionally runs a numeric jnp<->pallas(interpret) parity
+ NaN check and compares everything against
``benchmarks/kernels_baseline.json``, exiting nonzero on regression —
wired into ``scripts/ci.sh``.

CSV columns: name, us_per_call (projected TPU v5e us), derived.
"""
import argparse
import json
import os
import sys
import time
from pathlib import Path

# The sharded wall-clock run needs a (data=4, model=2) host-device mesh,
# and the device count must be pinned before jax first initializes.
# No-op when jax is already imported (this module imported from
# elsewhere) — the sharded timing then degrades to a skip note.
if "jax" not in sys.modules:
    _flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in _flags:
        os.environ["XLA_FLAGS"] = (
            _flags + " --xla_force_host_platform_device_count=8").strip()

import jax
import jax.numpy as jnp
import numpy as np

from repro.analysis.hlo_cost import analyze_hlo
from repro.api import ConsensusSession
from repro.configs.base import ADMMConfig
from repro.core.sharded import per_shard_cost_program
from repro.core.space import asybadmm_epoch, init_consensus_state

REPO = Path(__file__).resolve().parent.parent
OUT_JSON = REPO / "BENCH_kernels.json"
BASELINE_JSON = REPO / "benchmarks" / "kernels_baseline.json"

HBM_BW = 819e9
BYTES = 4  # f32

# (name, N workers, M blocks, per-block dim) — kdda_like ~= the paper's
# kddA sparse logistic regression scale (20.2M coords, lane-aligned)
CASES = [
    ("smoke", 4, 8, 256),
    ("kdda_like", 8, 64, 315904),
]

# (data, model) shards for the per-shard / sharded-wall-clock rows
MESH_SHAPE = (4, 2)


# ---------------------------------------------------------------------------
# analytic single-op roofline rows (the seed bench, kept for reference)
# ---------------------------------------------------------------------------

def admm_update_traffic(n):
    fused = (3 + 3) * n * BYTES          # read g,y,z~ ; write x,y',w
    # unfused: x = z-(g+y)/rho (r3,w1); y' = -g (r1,w1); w = rho*x+y' (r2,w1)
    unfused = (3 + 1 + 1 + 1 + 2 + 1) * n * BYTES
    return fused, unfused


def prox_traffic(n):
    fused = (2 + 1) * n * BYTES          # read z~,w_sum ; write z'
    # unfused: v=(g z+w)/mu (r2,w1); soft-thresh (r1,w1); clip (r1,w1)
    unfused = (3 + 2 + 2) * n * BYTES
    return fused, unfused


def _analytic_rows(emit):
    for n in (1 << 20, 1 << 24, 1 << 27):
        f, u = admm_update_traffic(n)
        emit(f"kern_admm_update_n{n},{f/HBM_BW*1e6:.1f},"
             f"unfused_us={u/HBM_BW*1e6:.1f};saving={1-f/u:.2%}")
        f, u = prox_traffic(n)
        emit(f"kern_prox_update_n{n},{f/HBM_BW*1e6:.1f},"
             f"unfused_us={u/HBM_BW*1e6:.1f};saving={1-f/u:.2%}")
    # logreg grad: arithmetic intensity of the two matmul passes
    m, d = 1 << 20, 1 << 14
    flops = 2 * 2 * m * d                 # Xw and X^T v
    bytes_ = (2 * m * d + 2 * (m + d)) * BYTES
    emit(f"kern_logreg_grad_m{m}_d{d},{flops/197e12*1e6:.1f},"
         f"ai={flops/bytes_:.2f}flops/B;"
         f"mem_us={bytes_/HBM_BW*1e6:.1f}")


# ---------------------------------------------------------------------------
# measured epoch cost (op-level HLO, kernels at their DMA boundary)
# ---------------------------------------------------------------------------

def _quad_loss(z, c):
    return 0.5 * jnp.sum(jnp.square(z - c))


def _session(backend, N, M, dblk, mesh=None, data=None):
    dim = M * dblk
    cfg = ADMMConfig(rho=2.0, gamma=0.1, max_delay=1, block_fraction=0.5,
                     num_blocks=M, l1_coef=1e-3, clip=1.0, backend=backend,
                     autotune="cached")
    if data is None:
        data = jax.ShapeDtypeStruct((N, dim), jnp.float32)
    return ConsensusSession.flat(_quad_loss, data, dim=dim, cfg=cfg,
                                 mesh=mesh)


def _abstract_mesh():
    """Shape-only (data, model) mesh — per-shard costing needs no devices."""
    from jax.sharding import AbstractMesh
    return AbstractMesh(MESH_SHAPE, ("data", "model"))


def _tree_spec(backend, N, M, dblk, mesh=None, concrete=False):
    """A ragged pytree spec at the same packed scale as the flat case:
    block j packs two leaves (dblk-128, 128), the last block only one —
    a genuinely ragged BlockLayout exercised end to end. The per-worker
    data (and loss) are per-leaf, matching how a params-pytree workload
    actually feeds batches — the loss never concatenates the pytree into
    one flat vector (that concat's transpose alone used to cost ~28 GB
    per kddA epoch). ``concrete=False`` builds ShapeDtypeStructs only
    (costing at full kddA scale); ``concrete=True`` allocates seeded
    arrays for the wall-clock runs."""
    from repro.core.blocks import TreeBlocks, make_block_layout
    from repro.core.space import TreeSpace, make_spec

    shapes = {f"w{j:03d}a": (dblk - 128,) for j in range(M)}
    shapes.update({f"w{j:03d}b": (128,) for j in range(M - 1)})
    names = sorted(shapes)                    # == jax dict flatten order
    if concrete:
        rng = np.random.RandomState(0)
        params = {n: jnp.asarray(rng.randn(*shapes[n]), jnp.float32)
                  for n in names}
        data = {n: jnp.asarray(rng.randn(N, *shapes[n]), jnp.float32)
                for n in names}
    else:
        params = {n: jax.ShapeDtypeStruct(shapes[n], jnp.float32)
                  for n in names}
        data = {n: jax.ShapeDtypeStruct((N,) + shapes[n], jnp.float32)
                for n in names}
    tblocks = TreeBlocks(num_blocks=M,
                         leaf_block_ids=tuple(int(n[1:4]) for n in names),
                         treedef=jax.tree.structure(params))
    space = TreeSpace(blocks=tblocks, num_workers=N,
                      layout=make_block_layout(params, tblocks))
    cfg = ADMMConfig(rho=2.0, gamma=0.1, max_delay=1, block_fraction=0.5,
                     num_blocks=M, l1_coef=1e-3, clip=1.0, backend=backend,
                     autotune="cached")

    def tree_loss(p, c):
        return 0.5 * sum(jnp.sum(jnp.square(p[n] - c[n])) for n in names)

    spec = make_spec(space, cfg, tree_loss, backend=backend, mesh=mesh)
    return spec, params, data


def _tree_session(backend, N, M, dblk, mesh=None):
    """Concrete TreeSpace session for the wall-clock rows."""
    spec, params, data = _tree_spec(backend, N, M, dblk, mesh=mesh,
                                    concrete=True)
    cfg = ADMMConfig(num_blocks=M, backend=backend, autotune="cached")
    return ConsensusSession(spec=spec, cfg=cfg, z0=params, data=data)


def _tree_epoch_cost(backend, N, M, dblk):
    """HLO cost of one TreeSpace asybadmm_epoch (packed layout)."""
    spec, params, data = _tree_spec(backend, N, M, dblk)
    state = jax.eval_shape(lambda p: init_consensus_state(spec, p), params)
    hlo = (jax.jit(lambda s, b: asybadmm_epoch(spec, s, b))
           .lower(state, data)
           .compiler_ir(dialect="hlo").as_hlo_text())
    return analyze_hlo(hlo)


def _tree_shard_epoch_cost(N, M, dblk):
    """HLO cost of ONE shard of the TreeSpace SPMD epoch — native block
    servers over `model` since the packed-layout lowering."""
    spec, params, data = _tree_spec("pallas_stub", N, M, dblk,
                                    mesh=_abstract_mesh())
    fn, args = per_shard_cost_program(spec, data, z0=params)
    hlo = (jax.jit(fn).lower(*args)
           .compiler_ir(dialect="hlo").as_hlo_text())
    return analyze_hlo(hlo)


def _epoch_cost(backend, N, M, dblk):
    """HLO cost of one asybadmm_epoch, lowered abstractly (no real
    arrays — works at full kddA scale)."""
    sess = _session(backend, N, M, dblk)
    spec = sess.spec
    state = jax.eval_shape(lambda: init_consensus_state(spec, None))
    hlo = (jax.jit(lambda s, b: asybadmm_epoch(spec, s, b))
           .lower(state, sess.data)
           .compiler_ir(dialect="hlo").as_hlo_text())
    return analyze_hlo(hlo)


def _shard_epoch_cost(N, M, dblk):
    """HLO cost of ONE shard of the SPMD epoch (kernels at their DMA
    boundary, collectives as shape-faithful stand-ins)."""
    sess = _session("pallas_stub", N, M, dblk, mesh=_abstract_mesh())
    fn, args = per_shard_cost_program(sess.spec, sess.data)
    hlo = (jax.jit(fn).lower(*args)
           .compiler_ir(dialect="hlo").as_hlo_text())
    return analyze_hlo(hlo)


def measure_cases(emit):
    from repro.kernels.autotune import device_kind, lookup_tile
    out = []
    shards = MESH_SHAPE[0] * MESH_SHAPE[1]
    for name, N, M, dblk in CASES:
        jnp_cost = _epoch_cost("jnp", N, M, dblk)
        pl_cost = _epoch_cost("pallas_stub", N, M, dblk)
        sh_cost = _shard_epoch_cost(N, M, dblk)
        tr_cost = _tree_epoch_cost("pallas_stub", N, M, dblk)
        tr_sh_cost = _tree_shard_epoch_cost(N, M, dblk)
        saving = 1.0 - pl_cost.hbm_bytes / jnp_cost.hbm_bytes
        shard_frac = sh_cost.hbm_bytes / pl_cost.hbm_bytes
        tree_shard_frac = tr_sh_cost.hbm_bytes / tr_cost.hbm_bytes
        tree_flat_ratio = tr_cost.hbm_bytes / pl_cost.hbm_bytes
        tiles = {op: lookup_tile(op, N, M, dblk)
                 for op in ("worker_select_update", "server_prox_fused")}
        rec = {
            "name": name, "N": N, "M": M, "dblk": dblk, "dim": M * dblk,
            "jnp": {"hbm_bytes": int(jnp_cost.hbm_bytes),
                    "flops": int(jnp_cost.flops),
                    "v5e_us": jnp_cost.hbm_bytes / HBM_BW * 1e6},
            "pallas": {"hbm_bytes": int(pl_cost.hbm_bytes),
                       "flops": int(pl_cost.flops),
                       "v5e_us": pl_cost.hbm_bytes / HBM_BW * 1e6},
            "pallas_sharded": {
                "hbm_bytes_per_shard": int(sh_cost.hbm_bytes),
                "flops_per_shard": int(sh_cost.flops),
                "v5e_us": sh_cost.hbm_bytes / HBM_BW * 1e6,
                "mesh": f"data={MESH_SHAPE[0]},model={MESH_SHAPE[1]}",
                "shard_bytes_frac": shard_frac,
                "ideal_frac": 1.0 / shards,
            },
            # tree space, packed-layout lowering: the ragged pytree's
            # epoch + ONE shard of its SPMD epoch (native block servers
            # over model — flipped from the old replicated-z fallback)
            "tree_pallas": {"hbm_bytes": int(tr_cost.hbm_bytes),
                            "flops": int(tr_cost.flops),
                            "v5e_us": tr_cost.hbm_bytes / HBM_BW * 1e6,
                            "flat_bytes_ratio": tree_flat_ratio},
            "tree_pallas_sharded": {
                "hbm_bytes_per_shard": int(tr_sh_cost.hbm_bytes),
                "flops_per_shard": int(tr_sh_cost.flops),
                "v5e_us": tr_sh_cost.hbm_bytes / HBM_BW * 1e6,
                "mesh": f"data={MESH_SHAPE[0]},model={MESH_SHAPE[1]}",
                "shard_bytes_frac": tree_shard_frac,
                "ideal_frac": 1.0 / shards,
            },
            "bytes_saving_frac": saving,
            # tuned tiles the pallas dispatch uses at this shape (cached
            # winners from benchmarks/kernels_tuned.json; null = miss,
            # heuristic tiles apply)
            "autotune": {"device_kind": device_kind(),
                         "tiles": {op: (list(t) if t else None)
                                   for op, t in tiles.items()}},
        }
        out.append(rec)
        emit(f"epoch_{name}_N{N}_M{M},{rec['pallas']['v5e_us']:.1f},"
             f"jnp_us={rec['jnp']['v5e_us']:.1f};"
             f"bytes_saving={saving:.2%}")
        emit(f"epoch_{name}_tree_vs_flat,{rec['tree_pallas']['v5e_us']:.1f},"
             f"tree_flat_bytes_ratio={tree_flat_ratio:.2f}")
        emit(f"epoch_{name}_shard_d{MESH_SHAPE[0]}m{MESH_SHAPE[1]},"
             f"{rec['pallas_sharded']['v5e_us']:.1f},"
             f"shard_bytes_frac={shard_frac:.3f};ideal={1.0/shards:.3f}")
        emit(f"epoch_{name}_tree_shard_d{MESH_SHAPE[0]}m{MESH_SHAPE[1]},"
             f"{rec['tree_pallas_sharded']['v5e_us']:.1f},"
             f"tree_shard_bytes_frac={tree_shard_frac:.3f};"
             f"ideal={1.0/shards:.3f}")
    return out


# ---------------------------------------------------------------------------
# measured wall-clock per epoch (real execution, smoke shape)
# ---------------------------------------------------------------------------

def _median_epoch_ms(sess, data, epochs=5):
    state = sess.init()
    step = sess.step_fn()
    state, _ = step(state, data)                # compile + warm the caches
    jax.block_until_ready(state)
    times = []
    for _ in range(epochs):
        t0 = time.perf_counter()
        state, _ = step(state, data)
        jax.block_until_ready(state)
        times.append((time.perf_counter() - t0) * 1e3)
    return float(np.median(times)), len(times)


def measure_walltime(emit):
    """jit + block_until_ready, median of 5 — jnp vs pallas(interpret)
    vs sharded-pallas, plus the TreeSpace lowering (tree_pallas /
    tree_pallas_sharded), at the smoke shape. CPU-relative numbers
    (pallas runs in interpret mode here); recorded so the perf
    trajectory of the epoch is measured, not only modeled. The pallas
    variants dispatch with autotune="cached", so the tuned tiles in use
    are part of the measurement (recorded per case in the cost rows)."""
    name, N, M, dblk = CASES[0]
    dim = M * dblk
    rng = np.random.RandomState(0)
    data = jnp.asarray(rng.randn(N, dim), jnp.float32)
    need = MESH_SHAPE[0] * MESH_SHAPE[1]
    mesh = None
    if jax.device_count() >= need:
        from repro.launch.mesh import make_test_mesh
        mesh = make_test_mesh(need, model=MESH_SHAPE[1])
    variants = [("jnp", "jnp", None, False),
                ("pallas", "pallas", None, False),
                ("pallas_sharded", "pallas", mesh, False),
                ("tree_pallas", "pallas", None, True),
                ("tree_pallas_sharded", "pallas", mesh, True)]
    entries = []
    for label, backend, m, tree in variants:
        if label.endswith("sharded") and m is None:
            emit(f"wallclock_{name}_{label},0,skipped;"
                 f"need_{need}_devices_have_{jax.device_count()}")
            continue
        if tree:
            sess = _tree_session(backend, N, M, dblk, mesh=m)
            ms, n = _median_epoch_ms(sess, sess.data)
        else:
            ms, n = _median_epoch_ms(_session(backend, N, M, dblk, mesh=m,
                                              data=data), data)
        entries.append({"variant": label, "median_ms": ms, "n": n})
        emit(f"wallclock_{name}_{label},{ms * 1e3:.0f},median_of_{n};ms={ms:.3f}")
    return {"case": name, "shape": {"N": N, "M": M, "dblk": dblk},
            "device_count": jax.device_count(),
            "method": "jit + block_until_ready, median of 5 epochs "
                      "(pallas in interpret mode on CPU; pallas variants "
                      "use autotune=cached tiles)",
            "entries": entries}


def parity_check(epochs=5):
    """Numeric jnp vs pallas(interpret) agreement on a real small run."""
    N, M, dblk = 3, 8, 32
    dim = M * dblk
    rng = np.random.RandomState(0)
    centers = jnp.asarray(rng.randn(N, dim), jnp.float32)
    cfg = ADMMConfig(rho=2.0, gamma=0.1, max_delay=1, block_fraction=0.5,
                     num_blocks=M, l1_coef=1e-3, clip=1.0)
    zs = {}
    for backend in ("jnp", "pallas"):
        sess = ConsensusSession.flat(_quad_loss, centers, dim=dim, cfg=cfg,
                                     backend=backend)
        state = sess.init()
        step = sess.step_fn()
        for _ in range(epochs):
            state, _ = step(state, centers)
        zs[backend] = np.asarray(sess.z(state))
    err = float(np.max(np.abs(zs["jnp"] - zs["pallas"])))
    finite = bool(np.isfinite(zs["jnp"]).all()
                  and np.isfinite(zs["pallas"]).all())
    return err, finite


def main(emit=print, smoke: bool = False) -> None:
    _analytic_rows(emit)
    cases = measure_cases(emit)
    report = {
        "hbm_bw_gbps": HBM_BW / 1e9,
        "method": ("op-level (pre-optimization) HLO costed by "
                   "analysis.hlo_cost; pallas kernels charged at their "
                   "operand+result DMA boundary via backend=pallas_stub; "
                   "pallas_sharded = ONE (data=4, model=2) shard of the "
                   "SPMD epoch (core.sharded.per_shard_cost_program)"),
        "cases": cases,
        "walltime": measure_walltime(emit),
    }
    failures = []
    if smoke:
        err, finite = parity_check()
        report["parity"] = {"max_err": err, "finite": finite}
        emit(f"epoch_backend_parity,0,max_err={err:.2e};finite={finite}")
        baseline = json.loads(BASELINE_JSON.read_text())
        min_saving = baseline["min_bytes_saving_frac"]
        max_shard_frac = baseline["max_shard_bytes_frac"]
        if not finite:
            failures.append("NaN/Inf in epoch outputs")
        if err > baseline["max_parity_err"]:
            failures.append(f"parity err {err:.2e} > "
                            f"{baseline['max_parity_err']:.0e}")
        for rec in cases:
            if rec["bytes_saving_frac"] < min_saving:
                failures.append(
                    f"{rec['name']}: bytes saving "
                    f"{rec['bytes_saving_frac']:.2%} < {min_saving:.0%}")
        # sharding gate: per-shard bytes of the SPMD epoch must shrink
        # toward 1/(data*model) of the fused single-device epoch at the
        # paper-scale shape (the small smoke case is padding-dominated)
        kdda = next(r for r in cases if r["name"] == "kdda_like")
        frac = kdda["pallas_sharded"]["shard_bytes_frac"]
        if frac > max_shard_frac:
            failures.append(
                f"kdda_like: per-shard bytes frac {frac:.3f} > "
                f"{max_shard_frac} (ideal 1/{MESH_SHAPE[0] * MESH_SHAPE[1]}"
                f" = {1.0 / (MESH_SHAPE[0] * MESH_SHAPE[1]):.3f})")
        # tree gate: the packed-layout lowering must keep TreeSpace's
        # per-shard bytes shrinking like the flat block servers (no
        # regression back toward the old replicated-z fallback, whose
        # state path would not shrink over model at all)
        max_tree_frac = baseline["max_tree_shard_bytes_frac"]
        tfrac = kdda["tree_pallas_sharded"]["shard_bytes_frac"]
        if tfrac > max_tree_frac:
            failures.append(
                f"kdda_like: TREE per-shard bytes frac {tfrac:.3f} > "
                f"{max_tree_frac} (ideal "
                f"1/{MESH_SHAPE[0] * MESH_SHAPE[1]} = "
                f"{1.0 / (MESH_SHAPE[0] * MESH_SHAPE[1]):.3f})")
        # tree/flat gate: the lane-aligned layout + dynamic-slice unpack
        # must keep the ragged pytree epoch's HBM traffic within a small
        # multiple of the flat epoch (it was ~8.3x before the layout
        # refactor — per-leaf row slices charged the full table per leaf)
        max_ratio = baseline["max_tree_flat_bytes_ratio"]
        ratio = kdda["tree_pallas"]["flat_bytes_ratio"]
        if ratio > max_ratio:
            failures.append(
                f"kdda_like: tree/flat epoch HBM ratio {ratio:.2f} > "
                f"{max_ratio}")
    OUT_JSON.write_text(json.dumps(report, indent=2) + "\n")
    emit(f"bench_json,0,written={OUT_JSON.name}")
    if failures:
        for f in failures:
            emit(f"kernels_bench_REGRESSION,0,{f}")
        raise SystemExit(1)


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="also run numeric parity/NaN checks and fail on "
                         "regression vs benchmarks/kernels_baseline.json")
    args = ap.parse_args()
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    main(smoke=args.smoke)
