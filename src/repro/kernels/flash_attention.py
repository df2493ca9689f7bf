"""Pallas TPU kernel: flash attention (tile-resident online softmax).

Motivated by the §Perf hillclimb on chameleon-34b x prefill_32k: pure-XLA
attention — naive, kv-chunked, or q-chunked — always round-trips the
(S x T) score tiles through HBM, because XLA cannot fuse
matmul -> softmax -> matmul into one kernel. At S = T = 32768 that is
the dominant memory-roofline term. This kernel keeps the score tile, the
online-softmax statistics (m, l) and the output accumulator in VMEM
scratch across the K-tile loop; HBM sees only Q/K/V reads and one output
write — the O(S^2) term disappears from the roofline.

Grid: (batch*heads, S/BQ, T/BK), K innermost. Tiles default to
(128, head_dim) — MXU-aligned (128 lanes, head_dim multiple of 128 for
the assigned archs).
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .tiling import resolve_interpret

DEFAULT_BLOCK = 128
NEG_INF = -1e30


def _kernel(q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr, *,
            k_steps: int, scale: float, causal: bool, bq: int, bk: int):
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q = q_ref[0].astype(jnp.float32)                  # (bq, hd)
    k = k_ref[0].astype(jnp.float32)                  # (bk, hd)
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
    if causal:
        qi = pl.program_id(1)
        q_pos = qi * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
        k_pos = ki * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
        s = jnp.where(k_pos <= q_pos, s, NEG_INF)

    m_prev = m_scr[...]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
    p = jnp.exp(s - m_new)
    corr = jnp.exp(m_prev - m_new)
    l_scr[...] = l_scr[...] * corr + jnp.sum(p, axis=-1, keepdims=True)
    m_scr[...] = m_new
    v = v_ref[0].astype(jnp.float32)
    acc_scr[...] = acc_scr[...] * corr + jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)

    @pl.when(ki == k_steps - 1)
    def _():
        o_ref[0] = (acc_scr[...] / jnp.maximum(l_scr[...], 1e-30)
                    ).astype(o_ref.dtype)


def flash_attention_bhsd(q, k, v, *, causal: bool = True,
                         block_q: int = DEFAULT_BLOCK,
                         block_k: int = DEFAULT_BLOCK,
                         scale: float = None,
                         interpret: Optional[bool] = None):
    """q: (BH, S, hd); k, v: (BH, T, hd); S % block_q == T % block_k == 0.
    ``interpret=None`` compiles on a TPU and interprets elsewhere."""
    BH, S, hd = q.shape
    T = k.shape[1]
    bq, bk = min(block_q, S), min(block_k, T)
    assert S % bq == 0 and T % bk == 0, (S, T, bq, bk)
    grid = (BH, S // bq, T // bk)
    if scale is None:
        scale = 1.0 / (hd ** 0.5)
    return pl.pallas_call(
        functools.partial(_kernel, k_steps=grid[2], scale=scale,
                          causal=causal, bq=bq, bk=bk),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, bq, hd), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, bk, hd), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((1, bk, hd), lambda b, i, j: (b, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, bq, hd), lambda b, i, j: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((BH, S, hd), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq, 1), jnp.float32),     # running max m
            pltpu.VMEM((bq, 1), jnp.float32),     # running denom l
            pltpu.VMEM((bq, hd), jnp.float32),    # output accumulator
        ],
        interpret=resolve_interpret(interpret),
    )(q, k, v)
