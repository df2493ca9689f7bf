"""Pallas TPU kernels: fused AsyBADMM server update — eq. (13).

Two entry points:

* ``prox_consensus_2d`` — gamma-stabilized weighted average + prox of
  h = l1*||.||_1 + box(clip) in one VMEM pass over a pre-reduced
  (M, d) w_sum. The per-block rho_sum = sum_{i in N(j)} rho_i enters as
  a (M, 1) column so heterogeneous neighborhoods N(j) (the general-form
  sparse case) are supported without a gather.
* ``server_prox_fused_2d`` — the epoch-native deeper fusion: the
  edge-masked reduction over the worker axis N runs *inside* the grid
  (innermost grid dimension, accumulating into a VMEM scratch tile), so
  the (M, d) ``w_sum`` intermediate is never materialized in HBM. One
  read of w_cache + z, one write of z'.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .tiling import pick_blk_m, pick_lane_tile, resolve_interpret

BLK_D = 512           # lane tile cap of the prox grids


def _prox_tail(v, mu, l1: float, clip: float):
    if l1 > 0.0:
        thr = l1 / mu
        v = jnp.sign(v) * jnp.maximum(jnp.abs(v) - thr, 0.0)
    if clip > 0.0:
        v = jnp.clip(v, -clip, clip)
    return v


def _kernel(zt_ref, ws_ref, rs_ref, z_ref, *, gamma: float, l1: float,
            clip: float):
    zt = zt_ref[...]
    ws = ws_ref[...]
    rs = rs_ref[...]                      # (blk_m, 1) broadcast column
    mu = gamma + rs
    v = _prox_tail((gamma * zt + ws) / mu, mu, l1, clip)
    z_ref[...] = v.astype(z_ref.dtype)


def prox_consensus_2d(z_tilde, w_sum, rho_sum, gamma: float, l1: float,
                      clip: float, *, interpret: Optional[bool] = None,
                      blk_m: Optional[int] = None,
                      blk_d: Optional[int] = None):
    """z_tilde, w_sum: (M, d) with d % 128 == 0 (lane-aligned rows; the
    M grid tiles at 8 rows when 8 divides M, else at M, never padded);
    rho_sum: (M, 1); blk_m/blk_d optionally override the grid tiles
    (autotuner winners). Returns z_new (M, d)."""
    M, d = z_tilde.shape
    blk_m = pick_blk_m(M, tuned=blk_m)
    blk_d = pick_lane_tile(d, BLK_D, tuned=blk_d, rows=blk_m)
    grid = (M // blk_m, d // blk_d)
    spec = pl.BlockSpec((blk_m, blk_d), lambda i, j: (i, j))
    rs_spec = pl.BlockSpec((blk_m, 1), lambda i, j: (i, 0))
    return pl.pallas_call(
        functools.partial(_kernel, gamma=float(gamma), l1=float(l1),
                          clip=float(clip)),
        grid=grid,
        in_specs=[spec, spec, rs_spec],
        out_specs=spec,
        out_shape=jax.ShapeDtypeStruct(z_tilde.shape, z_tilde.dtype),
        interpret=resolve_interpret(interpret),
    )(z_tilde, w_sum, rho_sum)


# ---------------------------------------------------------------------------
# fused edge-masked worker reduction + prox (w_sum never hits HBM)
# ---------------------------------------------------------------------------

def _fused_kernel(z_ref, rs_ref, e_ref, w_ref, out_ref, acc_ref, *,
                  gamma: float, l1: float, clip: float, n_workers: int):
    n = pl.program_id(2)

    @pl.when(n == 0)
    def _():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    keep = e_ref[0] > 0.0                          # (blk_m, 1)
    acc_ref[...] += jnp.where(keep, w_ref[0].astype(jnp.float32), 0.0)

    @pl.when(n == n_workers - 1)
    def _():
        rs = rs_ref[...]
        mu = gamma + rs
        v = (gamma * z_ref[...].astype(jnp.float32) + acc_ref[...]) / mu
        out_ref[...] = _prox_tail(v, mu, l1, clip).astype(out_ref.dtype)


def server_prox_fused_2d(z_cur, w_cache, edge_mask, rho_sum, gamma: float,
                         l1: float, clip: float, *,
                         interpret: Optional[bool] = None,
                         blk_m: Optional[int] = None,
                         blk_d: Optional[int] = None):
    """Eq. (13) with the worker reduction fused into the grid.

    z_cur   : (M, d), d % 128 == 0 (lane-aligned rows; the M grid tiles
        at 8 rows when 8 divides M, else at M — M=1 PS commits included);
    w_cache : (N, M, d) stale-w cache across all workers;
    edge_mask: (N, M, 1) float — 1.0 where (i, j) in E, else 0.0;
    rho_sum : (M, 1) per-block sum of rho_i over the neighborhood;
    blk_m, blk_d : optional tile overrides (autotuner winners).

    The grid is (M/blk_m, d/blk_d, N) with the worker axis innermost:
    each (block, d) tile accumulates its edge-masked w contribution in a
    VMEM scratch across the N sweeps, and the prox fires on the last
    worker — the reduced w_sum never exists as an HBM buffer. The tile
    choice never reorders the N accumulation, so tuned tiles are
    bitwise-equivalent to the heuristic.
    """
    N, M, d = w_cache.shape
    assert z_cur.shape == (M, d), (N, M, d)
    blk_m = pick_blk_m(M, tuned=blk_m)
    blk_d = pick_lane_tile(d, BLK_D, tuned=blk_d, rows=blk_m)
    grid = (M // blk_m, d // blk_d, N)
    spec = pl.BlockSpec((blk_m, blk_d), lambda i, j, n: (i, j))
    rs_spec = pl.BlockSpec((blk_m, 1), lambda i, j, n: (i, 0))
    e_spec = pl.BlockSpec((1, blk_m, 1), lambda i, j, n: (n, i, 0))
    w_spec = pl.BlockSpec((1, blk_m, blk_d), lambda i, j, n: (n, i, j))
    return pl.pallas_call(
        functools.partial(_fused_kernel, gamma=float(gamma), l1=float(l1),
                          clip=float(clip), n_workers=N),
        grid=grid,
        in_specs=[spec, rs_spec, e_spec, w_spec],
        out_specs=spec,
        out_shape=jax.ShapeDtypeStruct(z_cur.shape, z_cur.dtype),
        scratch_shapes=[pltpu.VMEM((blk_m, blk_d), jnp.float32)],
        interpret=resolve_interpret(interpret),
    )(z_cur, rho_sum, edge_mask, w_cache)
