"""Pallas TPU kernels: fused AsyBADMM worker update — eqs. (11)+(12)+(9).

The worker update is the per-step hot loop of the paper: three
elementwise expressions over gradient-sized buffers. Unfused, XLA
materializes x and y' between HBM round-trips; fused, each (g, y, z~)
tile is read once from HBM into VMEM and all three outputs (x, y', w)
are produced in-register — the op becomes strictly HBM-bandwidth-bound
at its arithmetic-intensity floor.

Two entry points:

* ``admm_worker_update_2d`` — the original (R, 128) 2D form used by the
  per-leaf wrappers. ``rho`` is a (1, 1) *traced operand* (not a static
  jit argument), so sweeping rho never recompiles.
* ``admm_worker_select_update_3d`` — the epoch-native batched form: a
  (N, M, dblk) grid that additionally fuses Algorithm 1's sel-masked
  select writes for y / w_cache / x. One pass over the worker bundles
  instead of four (update + three ``jnp.where`` merges), with a
  per-worker rho vector (N,) in SMEM so heterogeneous rho_i (the
  paper's general form) is native.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .tiling import LANE, pick_blk_m, pick_lane_tile, resolve_interpret

BLK_R = 256
BLK_D = 2048          # lane tile cap of the (N, M, dblk) worker grid


# ---------------------------------------------------------------------------
# 2D form (per-leaf wrappers)
# ---------------------------------------------------------------------------

def _kernel_2d(rho_ref, g_ref, y_ref, zt_ref, x_ref, ynew_ref, w_ref):
    g = g_ref[...]
    y = y_ref[...]
    zt = zt_ref[...]
    rho = rho_ref[0, 0]
    x = zt - (g + y) / rho
    y_new = -g                      # identity (25): y' = y + rho(x - z~) = -g
    w = rho * x + y_new
    x_ref[...] = x.astype(x_ref.dtype)
    ynew_ref[...] = y_new.astype(ynew_ref.dtype)
    w_ref[...] = w.astype(w_ref.dtype)


def admm_worker_update_2d(g, y, z_tilde, rho, *,
                          interpret: Optional[bool] = None):
    """g, y, z_tilde: (R, 128)-aligned 2D arrays; rho: (1, 1) array —
    a traced operand, NOT a compile-time constant. Returns (x, y_new, w).
    ``interpret=None`` compiles on a TPU and interprets elsewhere."""
    R, C = g.shape
    assert C % LANE == 0 and R % 8 == 0, (R, C)
    blk_r = min(BLK_R, R)
    grid = (R // blk_r,)
    spec = pl.BlockSpec((blk_r, C), lambda i: (i, 0))
    rho_spec = pl.BlockSpec((1, 1), lambda i: (0, 0))
    out_shape = [jax.ShapeDtypeStruct(g.shape, g.dtype)] * 3
    return pl.pallas_call(
        _kernel_2d,
        grid=grid,
        in_specs=[rho_spec, spec, spec, spec],
        out_specs=[spec, spec, spec],
        out_shape=out_shape,
        interpret=resolve_interpret(interpret),
    )(rho, g, y, z_tilde)


# ---------------------------------------------------------------------------
# batched (N, M, dblk) form with fused select writes
# ---------------------------------------------------------------------------

def _kernel_3d(rho_ref, m_ref, g_ref, y_ref, zt_ref, w_ref, *refs,
               with_x: bool):
    if with_x:
        x_ref, yo_ref, wo_ref, xo_ref = refs
    else:
        yo_ref, wo_ref = refs
    rho = rho_ref[pl.program_id(0)]            # this worker's rho_i (SMEM)
    keep = m_ref[0] > 0.0                     # (blk_m, 1) — broadcasts
    g = g_ref[0]
    y = y_ref[0]
    zt = zt_ref[0]
    x = zt - (g + y) / rho
    y_new = -g
    w = rho * x + y_new
    yo_ref[0] = jnp.where(keep, y_new, y).astype(yo_ref.dtype)
    wo_ref[0] = jnp.where(keep, w, w_ref[0]).astype(wo_ref.dtype)
    if with_x:
        xo_ref[0] = jnp.where(keep, x, x_ref[0]).astype(xo_ref.dtype)


def admm_worker_select_update_3d(g, y, z_tilde, w_old, sel_mask, rho,
                                 x_old=None, *,
                                 interpret: Optional[bool] = None,
                                 blk_m: Optional[int] = None,
                                 blk_d: Optional[int] = None):
    """Fused worker update + Alg. 1 select writes, epoch-native.

    g, y, z_tilde, w_old [, x_old] : (N, M, d) with d % 128 == 0
        (lane-aligned layout rows); the M grid tiles at 8 rows when 8
        divides M, else at M — never padded;
    sel_mask : (N, M, 1) float — 1.0 where the (worker, block) pair was
        selected this epoch, 0.0 otherwise;
    rho      : (N,) per-worker penalties (traced operand), held whole in
        SMEM and read at the worker grid index;
    blk_m, blk_d : optional tile overrides (autotuner winners; validated
        against the divisibility rules, heuristic fallback otherwise).

    Returns (y', w'[, x']): selected entries take the fresh update,
    unselected keep the old value — one pass over HBM instead of four.
    """
    N, M, d = g.shape
    blk_m = pick_blk_m(M, tuned=blk_m)
    blk_d = pick_lane_tile(d, BLK_D, tuned=blk_d, rows=blk_m)
    grid = (N, M // blk_m, d // blk_d)
    tspec = pl.BlockSpec((1, blk_m, blk_d), lambda n, i, j: (n, i, j))
    mspec = pl.BlockSpec((1, blk_m, 1), lambda n, i, j: (n, i, 0))
    rspec = pl.BlockSpec(memory_space=pltpu.SMEM)
    with_x = x_old is not None
    n_out = 3 if with_x else 2
    operands = [rho, sel_mask, g, y, z_tilde, w_old]
    in_specs = [rspec, mspec, tspec, tspec, tspec, tspec]
    if with_x:
        operands.append(x_old)
        in_specs.append(tspec)
    return pl.pallas_call(
        functools.partial(_kernel_3d, with_x=with_x),
        grid=grid,
        in_specs=in_specs,
        out_specs=[tspec] * n_out,
        out_shape=[jax.ShapeDtypeStruct(g.shape, g.dtype)] * n_out,
        interpret=resolve_interpret(interpret),
    )(*operands)
