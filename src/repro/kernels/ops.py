"""Public jit'd wrappers around the Pallas kernels.

Lane alignment is a property of the layout, not a per-call pad: since
the lane-aligned packed refactor, ``core/blocks.py`` rounds every block
row up to the 128-lane boundary at layout-build time, so these wrappers
*require* aligned inputs and always take the no-copy fast path (the old
pad-copy branches burned an extra HBM round trip per epoch on ragged
layouts). Unaligned buffers raise an actionable error pointing at the
layout constructors. The MXU ops (``matmul`` / ``logreg_grad``) still
pad internally — data matrices are not layout-controlled.
``interpret=None`` (the default) compiles the kernels with Mosaic on a
TPU and runs them in the Pallas interpreter on any other backend
(``kernels.tiling.default_interpret``); tests pass it explicitly.

Tile shapes (``blk_m``, ``blk_d``) default to the static heuristics in
``admm_update.py`` / ``prox_update.py``; the fused epoch ops accept a
static ``tile=(blk_m, blk_d)`` override, which ``core/space.py`` feeds
from the per-device autotuner table (``kernels/autotune.py``) when
``ADMMConfig(autotune=)`` is "cached" or "sweep".

``rho`` enters every ADMM op as a *traced array operand* — never a jit
static — so rho sweeps and heterogeneous per-worker rho_vec share one
compilation.

The two epoch-native fused ops (``admm_worker_select_update`` /
``server_prox_update``) also accept ``boundary_stub=True``, which lowers
the op as a single opaque callback custom-call instead of a Pallas
kernel. The stub is never executed for real work — it exists so
``analysis/hlo_cost.py`` can charge the fused op exactly its
operand+result HBM traffic (the same boundary model it applies to XLA
fusions) when the benchmark costs the kernel-backed epoch.
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from . import admm_update as _admm
from . import logreg_grad as _lg
from . import prox_update as _prox
from . import ref as _ref
from .tiling import LANE, SUBLANE


def _round_up(n: int, m: int) -> int:
    return -(-n // m) * m


def _to_2d(v, lane=LANE, sublane=SUBLANE):
    """Flatten an (sublane*lane)-aligned buffer to (R, lane), R % sublane
    == 0 — a pure reshape, never a zero-fill + scatter copy. Raises for
    unaligned element counts: lane alignment is the layout's job."""
    flat = v.reshape(-1)
    n = flat.shape[0]
    if n % (sublane * lane) != 0:
        raise ValueError(
            f"buffer of {n} elements (shape {v.shape}) is not "
            f"({sublane}x{lane})-vreg aligned; kernel ops require "
            f"lane-aligned buffers. Pack through a lane-aligned layout "
            f"(core.blocks.make_flat_blocks / make_block_layout round "
            f"block_dim up to {lane}) instead of passing raw leaves.")
    return flat.reshape(n // lane, lane), (v.shape, n)


def _from_2d(a2d, orig):
    shape, n = orig
    return a2d.reshape(-1)[:n].reshape(shape)


def _rho_operand(rho):
    """Scalar or () / (1,) array rho -> (1, 1) f32 traced operand."""
    return jnp.asarray(rho, jnp.float32).reshape(1, 1)


@functools.partial(jax.jit, static_argnames=("interpret",))
def admm_worker_update(g, y, z_tilde, rho,
                       interpret: Optional[bool] = None):
    """Fused eqs. (11)+(12)+(9) on arbitrarily-shaped buffers. ``rho`` is
    a traced operand (python float or 0-d array) — distinct rho values
    share one compilation."""
    g2, orig = _to_2d(g)
    y2, _ = _to_2d(y)
    z2, _ = _to_2d(z_tilde)
    x2, yn2, w2 = _admm.admm_worker_update_2d(g2, y2, z2, _rho_operand(rho),
                                              interpret=interpret)
    return (_from_2d(x2, orig), _from_2d(yn2, orig), _from_2d(w2, orig))


def _prox_stub(zt, ws, rs, gamma, l1, clip):
    return np.asarray(_ref.prox_consensus_ref(
        jnp.asarray(zt), jnp.asarray(ws), jnp.asarray(rs), gamma, l1, clip))


@functools.partial(jax.jit,
                   static_argnames=("gamma", "l1", "clip", "interpret",
                                    "boundary_stub", "tile"))
def prox_consensus(z_tilde, w_sum, rho_sum, gamma: float, l1: float = 0.0,
                   clip: float = 0.0, interpret: Optional[bool] = None, *,
                   boundary_stub: bool = False,
                   tile: Optional[Tuple[int, int]] = None):
    """Fused eq. (13). z_tilde, w_sum: (M, d) lane-aligned; rho_sum: (M,)
    or (M, 1). ``tile=(blk_m, blk_d)`` statically overrides the grid."""
    M, d = z_tilde.shape
    rho_sum = rho_sum.reshape(M, 1).astype(z_tilde.dtype)
    if boundary_stub:
        return jax.pure_callback(
            functools.partial(_prox_stub, gamma=gamma, l1=l1, clip=clip),
            jax.ShapeDtypeStruct(z_tilde.shape, z_tilde.dtype),
            z_tilde, w_sum, rho_sum)
    _require_lane_aligned(d, "prox_consensus")
    bm, bd = tile if tile is not None else (None, None)
    return _prox.prox_consensus_2d(z_tilde, w_sum, rho_sum, gamma, l1, clip,
                                   interpret=interpret, blk_m=bm, blk_d=bd)


# ---------------------------------------------------------------------------
# epoch-native fused ops (the VariableSpace pallas backend)
# ---------------------------------------------------------------------------

def _require_lane_aligned(d: int, op: str) -> None:
    if d % LANE != 0:
        raise ValueError(
            f"{op}: block row width d={d} is not a multiple of {LANE}; "
            f"lane alignment is a property of the layout — build blocks "
            f"via core.blocks.make_flat_blocks / make_block_layout (which "
            f"round block_dim up to {LANE}) rather than padding per call.")


def _worker_stub(g, y, zt, w_old, smask, rho2, x_old):
    out = _ref.admm_worker_select_update_ref(
        jnp.asarray(g), jnp.asarray(y), jnp.asarray(zt), jnp.asarray(w_old),
        jnp.asarray(smask)[..., 0] > 0, jnp.asarray(rho2).reshape(-1),
        None if x_old is None else jnp.asarray(x_old))
    return tuple(np.asarray(o) for o in out)


@functools.partial(jax.jit,
                   static_argnames=("interpret", "boundary_stub", "tile"))
def admm_worker_select_update(g, y, z_tilde, w_old, sel, rho_vec,
                              x_old=None, *,
                              interpret: Optional[bool] = None,
                              boundary_stub: bool = False,
                              tile: Optional[Tuple[int, int]] = None):
    """Worker side of one epoch of Algorithm 1, fused: eqs. (11)+(12)+(9)
    plus the sel-masked merge of y / w_cache [/ x] in one HBM pass.

    g, y, z_tilde, w_old [, x_old] : (N, M, dblk) with dblk lane-aligned;
    sel     : (N, M) bool — the selected (worker, block) pairs;
    rho_vec : (N,) per-worker penalties (traced — heterogeneous rho_i);
    tile    : static (blk_m, blk_d) grid override (autotuner winners).

    Returns (y', w'[, x']).
    """
    N, M, d = g.shape
    smask = sel.astype(g.dtype)[..., None]
    rho2 = rho_vec.astype(jnp.float32).reshape(N)
    if boundary_stub:
        shapes = [jax.ShapeDtypeStruct(g.shape, g.dtype)] * (
            2 if x_old is None else 3)
        args = (g, y, z_tilde, w_old, smask, rho2)
        if x_old is None:
            cb = lambda *a: _worker_stub(*a, x_old=None)
        else:
            cb = lambda *a: _worker_stub(*a[:-1], x_old=a[-1])
            args = args + (x_old,)
        return jax.pure_callback(cb, tuple(shapes), *args)
    _require_lane_aligned(d, "admm_worker_select_update")
    bm, bd = tile if tile is not None else (None, None)
    out = _admm.admm_worker_select_update_3d(g, y, z_tilde, w_old, smask,
                                             rho2, x_old,
                                             interpret=interpret,
                                             blk_m=bm, blk_d=bd)
    return tuple(out)


def _server_stub(z_cur, w_cache, emask, rs, gamma, l1, clip):
    return np.asarray(_ref.server_prox_update_ref(
        jnp.asarray(z_cur), jnp.asarray(w_cache),
        jnp.asarray(emask)[..., 0] > 0, jnp.asarray(rs).reshape(-1),
        gamma, l1, clip))


@functools.partial(jax.jit, static_argnames=("gamma", "l1", "clip",
                                             "interpret", "boundary_stub",
                                             "tile"))
def server_prox_update(z_cur, w_cache, edge, rho_sum, gamma: float,
                       l1: float = 0.0, clip: float = 0.0, *,
                       interpret: Optional[bool] = None,
                       boundary_stub: bool = False,
                       tile: Optional[Tuple[int, int]] = None):
    """Server side of one epoch of Algorithm 1, fused: the edge-masked
    reduction of the stale-w cache over workers AND the prox step (13)
    in one kernel — the (M, d) w_sum intermediate never touches HBM.

    z_cur: (M, d) lane-aligned; w_cache: (N, M, d); edge: (N, M) bool;
    rho_sum: (M,) traced per-block penalty sums; ``tile=(blk_m, blk_d)``
    statically overrides the grid. Returns z_new (M, d).
    """
    N, M, d = w_cache.shape
    emask = edge.astype(z_cur.dtype)[..., None]
    rs = rho_sum.astype(jnp.float32).reshape(M, 1)
    if boundary_stub:
        return jax.pure_callback(
            functools.partial(_server_stub, gamma=gamma, l1=l1, clip=clip),
            jax.ShapeDtypeStruct(z_cur.shape, z_cur.dtype),
            z_cur, w_cache, emask, rs)
    _require_lane_aligned(d, "server_prox_update")
    bm, bd = tile if tile is not None else (None, None)
    return _prox.server_prox_fused_2d(z_cur, w_cache, emask, rs,
                                      gamma, l1, clip, interpret=interpret,
                                      blk_m=bm, blk_d=bd)


# ---------------------------------------------------------------------------
# matmul / logistic-regression gradient
# ---------------------------------------------------------------------------

def _pad2(a, rm, cm):
    r, c = a.shape
    rp, cp = _round_up(r, rm), _round_up(c, cm)
    if (rp, cp) == (r, c):
        return a
    return jnp.pad(a, ((0, rp - r), (0, cp - c)))


@functools.partial(jax.jit, static_argnames=("transpose_a", "interpret"))
def matmul(a, b, transpose_a: bool = False,
           interpret: Optional[bool] = None):
    if transpose_a:
        K, M = a.shape
    else:
        M, K = a.shape
    N = b.shape[1]
    ap = _pad2(a, _lg.BLK, _lg.BLK)
    bp = _pad2(b, _lg.BLK, _lg.BLK)
    out = _lg.matmul(ap, bp, transpose_a=transpose_a, interpret=interpret)
    return out[:M, :N]


@functools.partial(jax.jit, static_argnames=("interpret",))
def logreg_grad(X, y, w, interpret: Optional[bool] = None):
    """Gradient of mean logistic loss: X (m, d), y (m,) in {-1,+1},
    w (d,). Composition of three kernels; X^T never materialized."""
    m, d = X.shape
    Xp = _pad2(X, _lg.BLK, _lg.BLK)
    mp, dp = Xp.shape
    wp = jnp.zeros((dp, LANE), X.dtype).at[:d, 0].set(w)
    s = _lg.matmul(Xp, wp, interpret=interpret)            # (mp, 128)
    yp = jnp.zeros((mp, LANE), X.dtype).at[:m, 0].set(y)
    mask = jnp.zeros((mp, LANE), X.dtype).at[:m, 0].set(1.0)
    v = _lg.margin(s, yp, interpret=interpret) * mask      # zero padded rows
    g = _lg.matmul(Xp, v, transpose_a=True, interpret=interpret)  # (dp, 128)
    return g[:d, 0] / m
