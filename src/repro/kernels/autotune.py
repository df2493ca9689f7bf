"""Deterministic per-device tile autotuner for the fused ADMM kernels.

Stop hand-picking tile shapes: sweep the (blk_m, blk_d) grid/VMEM-
accumulator candidates for the two epoch-native fused ops —
``admm_worker_select_update_3d`` (op key ``worker_select_update``) and
``server_prox_fused_2d`` (op key ``server_prox_fused``) — score each
candidate, and persist the winner keyed by
``(device_kind, op, N, M, dblk, dtype)``.

Scoring is measured, not claimed, in both regimes:

* **real devices** (``jax.default_backend() == "tpu"``): median
  wall-clock of the jitted kernel with that tile (seeded inputs, warmup
  excluded);
* **interpret / CI** (CPU containers): a deterministic proxy built on
  the same accounting ``analysis/hlo_cost.py`` established — HBM
  operand+result bytes at the kernel boundary (tile-invariant) plus a
  per-grid-step overhead term, with a VMEM-residency feasibility cap.
  The proxy is pure arithmetic on static shapes: same inputs, same
  winner, on every machine.

Winners are persisted to ``benchmarks/kernels_tuned.json`` (an in-repo
default table, generated under the proxy for the benchmark shapes,
ships with the repo; ``REPRO_KERNELS_TUNED`` overrides the path).
Tile choice never reorders accumulation — the fused prox reduces over
the worker grid axis in the same order for every (blk_m, blk_d) — so
tuned tiles are bitwise-equivalent to the heuristics; the ``--smoke``
CLI pins that plus table validity, and ``scripts/ci.sh`` runs it.

The knob: ``ADMMConfig(autotune="off" | "cached" | "sweep")``, threaded
through ``make_spec`` / ``ConsensusSession`` / ``launch.train
--autotune``. "off" uses the static heuristics in ``admm_update.py`` /
``prox_update.py``; "cached" consults this table (heuristic fallback on
a miss); "sweep" measures the session's shapes up front, persists the
winners, then behaves like "cached".
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import pathlib
import time
from typing import Dict, Iterable, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from . import admm_update as _admm
from . import prox_update as _prox
from .tiling import LANE, SUBLANE

OPS = ("worker_select_update", "server_prox_fused")
MODES = ("off", "cached", "sweep")

#: VMEM residency budget per grid step (bytes). Cores have ~16 MiB; the
#: sweep keeps double-buffered operand+result tiles under half of it.
VMEM_BUDGET = 8 * 1024 * 1024
#: f32 tiles resident per grid step (operands + results), per op.
_TILES_PER_STEP = {"worker_select_update": 8, "server_prox_fused": 4}
#: proxy constants: HBM bandwidth and per-grid-step launch overhead.
_HBM_BYTES_PER_US = 1.2e6
_STEP_OVERHEAD_US = 1.0

_TABLE_ENV = "REPRO_KERNELS_TUNED"
_SCHEMA = ("entries: {device_kind|op|N<N>|M<M>|d<dblk>|<dtype>: "
           "{blk_m, blk_d, score_us, method}}")


@dataclasses.dataclass(frozen=True)
class TileConfig:
    blk_m: int
    blk_d: int
    score_us: float
    method: str                     # "wallclock" | "proxy"


def default_table_path() -> pathlib.Path:
    env = os.environ.get(_TABLE_ENV)
    if env:
        return pathlib.Path(env)
    return (pathlib.Path(__file__).resolve().parents[3]
            / "benchmarks" / "kernels_tuned.json")


def device_kind() -> str:
    """Normalized device kind of the default backend ("cpu" on the CPU
    backend, e.g. "TPU_v5_lite" on a v5e). A backend that cannot be read
    raises: its tiles must never be filed under another device's key."""
    return str(jax.devices()[0].device_kind).strip().replace(" ", "_")


def table_key(dev: str, op: str, N: int, M: int, d: int,
              dtype: str = "float32") -> str:
    return f"{dev}|{op}|N{N}|M{M}|d{d}|{dtype}"


# ---------------------------------------------------------------------------
# table persistence (module-level cache; session sweeps merge into it)
# ---------------------------------------------------------------------------

_table_cache: Optional[Dict[str, dict]] = None


def load_table(path: Optional[pathlib.Path] = None,
               refresh: bool = False) -> Dict[str, dict]:
    global _table_cache
    if _table_cache is not None and not refresh and path is None:
        return _table_cache
    p = path or default_table_path()
    entries: Dict[str, dict] = {}
    try:
        with open(p) as f:
            entries = dict(json.load(f).get("entries", {}))
    except (OSError, ValueError):
        entries = {}
    if path is None:
        _table_cache = entries
    return entries


def save_table(entries: Dict[str, dict],
               path: Optional[pathlib.Path] = None) -> bool:
    """Merge ``entries`` into the persisted table (best effort — a
    read-only checkout degrades to the in-memory cache)."""
    global _table_cache
    merged = dict(load_table(path))
    merged.update(entries)
    if path is None:
        _table_cache = merged
    p = path or default_table_path()
    try:
        p.parent.mkdir(parents=True, exist_ok=True)
        with open(p, "w") as f:
            json.dump({"_schema": _SCHEMA,
                       "entries": {k: merged[k] for k in sorted(merged)}},
                      f, indent=2, sort_keys=False)
            f.write("\n")
        return True
    except OSError:
        return False


def lookup(op: str, N: int, M: int, d: int, dtype: str = "float32",
           dev: Optional[str] = None) -> Optional[TileConfig]:
    """Cached winner for this exact (device, op, shape) key, or None."""
    entry = load_table().get(
        table_key(dev or device_kind(), op, N, M, d, dtype))
    if not entry:
        return None
    return TileConfig(blk_m=int(entry["blk_m"]), blk_d=int(entry["blk_d"]),
                      score_us=float(entry.get("score_us", 0.0)),
                      method=str(entry.get("method", "proxy")))


def lookup_tile(op: str, N: int, M: int, d: int,
                dtype: str = "float32") -> Optional[Tuple[int, int]]:
    """(blk_m, blk_d) for kernel dispatch, validated against the
    divisibility rules; None on a miss (heuristics apply)."""
    cfg = lookup(op, N, M, d, dtype)
    if cfg is None:
        return None
    if M % cfg.blk_m or (cfg.blk_m % SUBLANE and cfg.blk_m != M) \
            or d % cfg.blk_d or cfg.blk_d % LANE:
        return None                       # stale or chip-refused entry
    return cfg.blk_m, cfg.blk_d


# ---------------------------------------------------------------------------
# candidate enumeration + scoring
# ---------------------------------------------------------------------------

def tile_candidates(op: str, N: int, M: int, d: int) -> List[Tuple[int, int]]:
    """Feasible (blk_m, blk_d) grid tiles: blk_m a multiple of 8
    dividing M, or M itself when 8 does not divide it (the M grid is
    never padded — block-id contract — and Mosaic refuses any other
    sublane tile), blk_d a lane multiple dividing d, double-buffered
    VMEM residency under budget."""
    if d % LANE != 0:
        raise ValueError(f"autotune sweep requires lane-aligned d "
                         f"(d % {LANE} == 0), got d={d}")
    blk_ms = [bm for bm in (SUBLANE, 2 * SUBLANE) if M % bm == 0] or [M]
    blk_ds = [bd for bd in (LANE, 256, 512, 1024, 2048, 4096, 8192)
              if bd <= d and d % bd == 0]
    if d <= 8192 and d not in blk_ds:
        blk_ds.append(d)
    tiles_per_step = _TILES_PER_STEP[op]
    out = []
    for bm in blk_ms:
        for bd in blk_ds:
            resident = 2 * tiles_per_step * bm * bd * 4   # double-buffered f32
            if resident <= VMEM_BUDGET:
                out.append((bm, bd))
    if not out:
        raise ValueError(f"no feasible tile for {op} at N={N} M={M} d={d}")
    return out


def _op_bytes(op: str, N: int, M: int, d: int) -> int:
    """HBM boundary bytes of the fused op (f32), tile-invariant — the
    same operand+result accounting analysis/hlo_cost.py charges."""
    if op == "worker_select_update":
        # in: rho, sel, g, y, z~, w_old; out: y', w'
        return (4 * N * M * d + 2 * N * M * d + N * M + N) * 4
    # server_prox_fused — in: z, rho_sum, edge, w_cache; out: z'
    return (N * M * d + 2 * M * d + N * M + M) * 4


def _grid_steps(op: str, N: int, M: int, d: int, bm: int, bd: int) -> int:
    return N * (M // bm) * (d // bd)


def proxy_score_us(op: str, N: int, M: int, d: int,
                   bm: int, bd: int) -> float:
    """Deterministic off-device score: bandwidth floor + grid overhead."""
    return (_op_bytes(op, N, M, d) / _HBM_BYTES_PER_US
            + _grid_steps(op, N, M, d, bm, bd) * _STEP_OVERHEAD_US)


def _op_inputs(op: str, N: int, M: int, d: int):
    key = jax.random.PRNGKey(0)
    t = lambda i: jax.random.normal(jax.random.fold_in(key, i), (N, M, d),
                                    jnp.float32)
    if op == "worker_select_update":
        return (t(0), t(1), t(2), t(3),
                jnp.ones((N, M, 1), jnp.float32),
                jnp.full((N,), 2.0, jnp.float32))
    return (t(0)[0], t(1), jnp.ones((N, M, 1), jnp.float32),
            jnp.full((M, 1), 6.0, jnp.float32))


def run_op(op: str, args, bm: int, bd: int, *, interpret: bool):
    if op == "worker_select_update":
        g, y, zt, w, sel, rho = args
        return _admm.admm_worker_select_update_3d(
            g, y, zt, w, sel, rho, interpret=interpret, blk_m=bm, blk_d=bd)
    z, w, e, rs = args
    return _prox.server_prox_fused_2d(z, w, e, rs, 0.01, 0.001, 1.0,
                                      interpret=interpret, blk_m=bm, blk_d=bd)


def wallclock_score_us(op: str, N: int, M: int, d: int,
                       bm: int, bd: int, reps: int = 5) -> float:
    """Median wall-clock of the jitted kernel on the real device."""
    args = _op_inputs(op, N, M, d)
    fn = jax.jit(lambda *a: run_op(op, a, bm, bd, interpret=False))
    jax.block_until_ready(fn(*args))                      # compile + warmup
    times = []
    for _ in range(reps):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        times.append((time.perf_counter() - t0) * 1e6)
    return float(np.median(times))


def sweep_op(op: str, N: int, M: int, d: int, dtype: str = "float32",
             measure: Optional[str] = None) -> TileConfig:
    """Sweep all feasible tiles for one op/shape; deterministic winner
    (score, then larger blk_d, then larger blk_m breaks ties)."""
    if measure is None:
        measure = ("wallclock" if jax.default_backend() == "tpu"
                   else "proxy")
    best = None
    for bm, bd in tile_candidates(op, N, M, d):
        if measure == "wallclock":
            score = wallclock_score_us(op, N, M, d, bm, bd)
        else:
            score = proxy_score_us(op, N, M, d, bm, bd)
        cand = (score, -bd, -bm, TileConfig(bm, bd, score, measure))
        if best is None or cand[:3] < best[:3]:
            best = cand
    return best[3]


def sweep_shapes(shapes: Iterable[Tuple[int, int, int]],
                 dtype: str = "float32", measure: Optional[str] = None,
                 persist: bool = True) -> Dict[str, dict]:
    """Sweep both fused ops over (N, M, dblk) shapes; merge winners into
    the cached table (and the JSON file when ``persist``)."""
    dev = device_kind()
    entries: Dict[str, dict] = {}
    for (N, M, d) in shapes:
        for op in OPS:
            cfg = sweep_op(op, N, M, d, dtype, measure=measure)
            entries[table_key(dev, op, N, M, d, dtype)] = {
                "blk_m": cfg.blk_m, "blk_d": cfg.blk_d,
                "score_us": round(cfg.score_us, 3), "method": cfg.method}
    if persist:
        save_table(entries)
    else:
        load_table().update(entries)
    return entries


def sweep_for_space(N: int, M: int, d: int, mesh=None,
                    dtype: str = "float32", persist: bool = True) -> None:
    """Eager sweep at spec-build time (never during a trace): the full
    epoch shape plus, under a mesh, the local (N/data, M/model) shard
    shape the kernels actually see."""
    shapes = [(N, M, d)]
    if mesh is not None:
        dsz = int(mesh.shape.get("data", 1))
        msz = int(mesh.shape.get("model", 1))
        if N % max(dsz, 1) == 0 and M % max(msz, 1) == 0:
            local = (max(N // max(dsz, 1), 1), max(M // max(msz, 1), 1), d)
            if local != shapes[0]:
                shapes.append(local)
    sweep_shapes(shapes, dtype=dtype, persist=persist)


def resolve_autotune(mode: Optional[str]) -> str:
    mode = "off" if mode in (None, "") else str(mode)
    if mode not in MODES:
        raise ValueError(f"unknown autotune mode {mode!r}; "
                         f"expected one of {MODES}")
    return mode


# ---------------------------------------------------------------------------
# CLI: --smoke validates the cached table; --sweep regenerates entries
# ---------------------------------------------------------------------------

def _smoke(shapes: List[Tuple[int, int, int]]) -> int:
    """Cached-mode smoke for CI (interpret backend): every cached entry
    is shape-valid and VMEM-feasible, the proxy sweep reproduces the
    committed winners for this device kind, and tuned tiles are
    bitwise-identical to the heuristic tiles on a small case."""
    dev = device_kind()
    entries = load_table(refresh=True)
    checked = 0
    for key, e in entries.items():
        parts = key.split("|")
        if len(parts) != 6:
            raise SystemExit(f"[autotune] malformed key {key!r}")
        kdev, op = parts[0], parts[1]
        N, M, d = (int(parts[i][1:]) for i in (2, 3, 4))
        bm, bd = int(e["blk_m"]), int(e["blk_d"])
        if op not in OPS:
            raise SystemExit(f"[autotune] unknown op in key {key!r}")
        if M % bm or (bm % SUBLANE and bm != M) or d % bd or bd % LANE:
            raise SystemExit(f"[autotune] invalid tile {bm}x{bd} for {key}")
        if 2 * _TILES_PER_STEP[op] * bm * bd * 4 > VMEM_BUDGET:
            raise SystemExit(f"[autotune] tile {bm}x{bd} over VMEM budget "
                             f"for {key}")
        if kdev == dev and e.get("method") == "proxy":
            want = sweep_op(op, N, M, d, measure="proxy")
            if (want.blk_m, want.blk_d) != (bm, bd):
                raise SystemExit(
                    f"[autotune] stale winner for {key}: table {bm}x{bd} "
                    f"vs proxy sweep {want.blk_m}x{want.blk_d} — rerun "
                    f"--sweep")
        checked += 1
    # tuned-vs-heuristic bitwise parity on a small interpret case
    N, M, d = 2, 3, 256
    for op in OPS:
        args = _op_inputs(op, N, M, d)
        base = run_op(op, args, None, None, interpret=True)
        for bm, bd in tile_candidates(op, N, M, d):
            out = run_op(op, args, bm, bd, interpret=True)
            for a, b in zip(jax.tree.leaves(base), jax.tree.leaves(out)):
                if not bool(jnp.all(a == b)):
                    raise SystemExit(f"[autotune] tile {bm}x{bd} changed "
                                     f"{op} output — tiling must be inert")
    # cached lookups for the benchmark shapes resolve (the in-repo table)
    misses = [s for s in shapes
              if lookup_tile("worker_select_update", *s) is None]
    if misses and dev == "cpu":
        raise SystemExit(f"[autotune] in-repo default table misses cpu "
                         f"entries for {misses} — rerun --sweep")
    print(f"[autotune] smoke ok: {checked} cached entries valid, tiling "
          f"bitwise-inert, defaults cover {len(shapes) - len(misses)}/"
          f"{len(shapes)} bench shapes on {dev}")
    return 0


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--smoke", action="store_true",
                    help="validate the cached table (CI, interpret mode)")
    ap.add_argument("--sweep", action="store_true",
                    help="sweep the benchmark shapes and persist winners")
    ap.add_argument("--shape", action="append", default=[],
                    metavar="N,M,DBLK",
                    help="extra shape(s) to sweep/validate")
    args = ap.parse_args(argv)
    # the kernels_bench.py case shapes — the in-repo defaults cover these
    shapes = [(4, 8, 256), (8, 64, 315904)]
    for s in args.shape:
        N, M, d = (int(x) for x in s.split(","))
        shapes.append((N, M, d))
    if args.sweep:
        entries = sweep_shapes(shapes)
        for k in sorted(entries):
            e = entries[k]
            print(f"[autotune] {k}: blk_m={e['blk_m']} blk_d={e['blk_d']} "
                  f"({e['method']} {e['score_us']}us)")
        return 0
    return _smoke(shapes)


if __name__ == "__main__":
    raise SystemExit(main())
