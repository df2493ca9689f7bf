"""Pallas TPU kernels for the paper's workload hot-spot: the sparse
logistic-regression gradient (eq. 22 smooth part),

    g = X^T ( -y * sigmoid(-y * (X @ w)) ) / m.

Built from two MXU-aligned tiled primitives:

* ``matmul`` — 128x128x128 blocked matmul with an f32 VMEM accumulator
  scratch, K innermost in the grid so each (i, j) output tile is
  revisited across K steps (zero-init at k==0, flush at k==K-1).
  ``transpose_a`` contracts over the *row* axis of A without ever
  materializing X^T in HBM — that is the X^T v pass.
* ``margin`` — elementwise v = -y*sigmoid(-y*s) on (8,128) vreg tiles.

Note on matvecs: w and v are carried as (d, 128)/(m, 128) single-column
panels. On the MXU this is free — the systolic array processes 128
lanes per pass regardless — so the "padded matvec" IS the TPU-native
formulation, not a workaround.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .tiling import resolve_interpret

BLK = 128


def _matmul_kernel(a_ref, b_ref, o_ref, acc_ref, *, k_steps: int,
                   transpose_a: bool):
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    a = a_ref[...]
    if transpose_a:
        a = a.T
    acc_ref[...] += jnp.dot(a, b_ref[...],
                            preferred_element_type=jnp.float32)

    @pl.when(k == k_steps - 1)
    def _():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


def matmul(a, b, *, transpose_a: bool = False,
           interpret: Optional[bool] = None,
           blk_m: int = BLK, blk_n: int = BLK, blk_k: int = BLK):
    """C = A^T B if transpose_a else A B.  All dims must be tile-aligned
    (ops.py pads)."""
    if transpose_a:
        K, M = a.shape
    else:
        M, K = a.shape
    K2, N = b.shape
    assert K == K2, (a.shape, b.shape)
    blk_m, blk_n, blk_k = min(blk_m, M), min(blk_n, N), min(blk_k, K)
    assert M % blk_m == 0 and N % blk_n == 0 and K % blk_k == 0
    grid = (M // blk_m, N // blk_n, K // blk_k)
    if transpose_a:
        a_spec = pl.BlockSpec((blk_k, blk_m), lambda i, j, k: (k, i))
    else:
        a_spec = pl.BlockSpec((blk_m, blk_k), lambda i, j, k: (i, k))
    return pl.pallas_call(
        functools.partial(_matmul_kernel, k_steps=grid[2],
                          transpose_a=transpose_a),
        grid=grid,
        in_specs=[a_spec,
                  pl.BlockSpec((blk_k, blk_n), lambda i, j, k: (k, j))],
        out_specs=pl.BlockSpec((blk_m, blk_n), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((M, N), a.dtype),
        scratch_shapes=[pltpu.VMEM((blk_m, blk_n), jnp.float32)],
        interpret=resolve_interpret(interpret),
    )(a, b)


def _margin_kernel(s_ref, y_ref, v_ref):
    s = s_ref[...]
    y = y_ref[...]
    v_ref[...] = (-y * jax.nn.sigmoid(-y * s)).astype(v_ref.dtype)


def margin(s, y, *, interpret: Optional[bool] = None):
    """s, y: (m, C) tile-aligned. v = -y*sigmoid(-y*s)."""
    M, C = s.shape
    blk_m = min(256, M)
    assert M % blk_m == 0
    spec = pl.BlockSpec((blk_m, C), lambda i: (i, 0))
    return pl.pallas_call(
        _margin_kernel,
        grid=(M // blk_m,),
        in_specs=[spec, spec],
        out_specs=spec,
        out_shape=jax.ShapeDtypeStruct(s.shape, s.dtype),
        interpret=resolve_interpret(interpret),
    )(s, y)
