"""Pallas kernels vs pure-jnp oracles: shape/dtype sweeps (interpret mode
executes the kernel body on CPU; on TPU the same code compiles).

Since the lane-aligned layout refactor the kernel ops CONSUME alignment
instead of producing it: buffers must be (8x128)-vreg aligned (flat
ops) / have d % 128 == 0 (batched ops) — the layouts in core/blocks.py
guarantee this, and raw ragged buffers raise actionable errors, pinned
below.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref

# every shape is (8*128)-element aligned — the layout's output contract
SHAPES = [(1024,), (2048,), (8, 128), (2, 8, 128), (4, 2, 128)]
DTYPES = [jnp.float32, jnp.bfloat16]


@pytest.mark.parametrize("shape", SHAPES)
@pytest.mark.parametrize("dtype", DTYPES)
@pytest.mark.parametrize("rho", [0.5, 100.0])
def test_admm_worker_update(shape, dtype, rho):
    rng = np.random.RandomState(hash((shape, rho)) % 2**31)
    g, y, z = [jnp.asarray(rng.randn(*shape), dtype) for _ in range(3)]
    x, yn, w = ops.admm_worker_update(g, y, z, rho, interpret=True)
    # oracle in f32 (bf16 kernel vs bf16 ref would compare two rounding
    # orders; the contract is closeness to the exact math)
    xe, yne, we = ref.admm_worker_update_ref(*(a.astype(jnp.float32)
                                               for a in (g, y, z)), rho)
    if dtype == jnp.float32:
        rtol, atol = 1e-5, 1e-4
    else:
        # bf16 has ~8 mantissa bits; outputs scale with rho*|z|
        rtol, atol = 4e-2, 4e-2 * max(1.0, rho)
    for o, e in zip((x, yn, w), (xe, yne, we)):
        assert o.shape == shape and o.dtype == dtype
        np.testing.assert_allclose(np.asarray(o, np.float32),
                                   np.asarray(e, np.float32),
                                   rtol=rtol, atol=atol)


@pytest.mark.parametrize("shape", [(64,), (7, 33), (3, 5, 17), (513,)])
def test_worker_update_rejects_unaligned(shape):
    """Ragged buffers no longer get a silent pad copy — the error names
    the layout builders that produce aligned tables."""
    a = jnp.ones(shape, jnp.float32)
    with pytest.raises(ValueError, match="make_flat_blocks"):
        ops.admm_worker_update(a, a, a, 1.0, interpret=True)


def test_admm_worker_y_identity():
    """Eq. 25: kernel's y' must equal -g exactly."""
    g = jnp.asarray(np.random.randn(1024), jnp.float32)
    o = jnp.ones(1024)
    _, yn, _ = ops.admm_worker_update(g, o, o, 3.0, interpret=True)
    np.testing.assert_array_equal(np.asarray(yn), -np.asarray(g))


@pytest.mark.parametrize("M,d", [(1, 128), (5, 256), (16, 1024), (3, 384)])
@pytest.mark.parametrize("l1,clip", [(0.0, 0.0), (0.05, 0.0), (0.05, 0.4)])
def test_prox_consensus(M, d, l1, clip):
    rng = np.random.RandomState(0)
    zt = jnp.asarray(rng.randn(M, d), jnp.float32)
    ws = jnp.asarray(rng.randn(M, d) * 3, jnp.float32)
    rs = jnp.asarray(rng.rand(M) * 5 + 0.5, jnp.float32)
    out = ops.prox_consensus(zt, ws, rs, gamma=0.1, l1=l1, clip=clip,
                             interpret=True)
    exp = ref.prox_consensus_ref(zt, ws, rs[:, None], 0.1, l1, clip)
    assert out.shape == (M, d)
    np.testing.assert_allclose(out, exp, rtol=1e-5, atol=1e-6)
    if clip > 0:
        assert float(jnp.max(jnp.abs(out))) <= clip + 1e-6


def test_prox_consensus_rejects_ragged_rows():
    zt = jnp.ones((3, 129), jnp.float32)
    with pytest.raises(ValueError, match="prox_consensus.*129"):
        ops.prox_consensus(zt, zt, jnp.ones(3), gamma=0.1, interpret=True)


@pytest.mark.parametrize("m,k,n", [(128, 128, 128), (256, 384, 128),
                                   (100, 50, 30), (129, 257, 65)])
@pytest.mark.parametrize("transpose_a", [False, True])
def test_matmul(m, k, n, transpose_a):
    rng = np.random.RandomState(1)
    a_shape = (k, m) if transpose_a else (m, k)
    A = jnp.asarray(rng.randn(*a_shape), jnp.float32)
    B = jnp.asarray(rng.randn(k, n), jnp.float32)
    C = ops.matmul(A, B, transpose_a=transpose_a, interpret=True)
    Ce = (A.T if transpose_a else A) @ B
    assert C.shape == (m, n)
    np.testing.assert_allclose(C, Ce, rtol=1e-4, atol=1e-3)


@pytest.mark.parametrize("m,d", [(64, 32), (200, 300), (129, 257)])
def test_logreg_grad(m, d):
    rng = np.random.RandomState(2)
    X = jnp.asarray(rng.randn(m, d), jnp.float32)
    y = jnp.asarray(rng.choice([-1.0, 1.0], m), jnp.float32)
    w = jnp.asarray(rng.randn(d) * 0.2, jnp.float32)
    g = ops.logreg_grad(X, y, w, interpret=True)
    ge = ref.logreg_grad_ref(X, y, w)
    assert g.shape == (d,)
    np.testing.assert_allclose(g, ge, rtol=1e-4, atol=1e-5)


def test_logreg_grad_matches_autodiff():
    rng = np.random.RandomState(3)
    X = jnp.asarray(rng.randn(50, 20), jnp.float32)
    y = jnp.asarray(rng.choice([-1.0, 1.0], 50), jnp.float32)
    w = jnp.asarray(rng.randn(20) * 0.3, jnp.float32)

    def loss(w_):
        return jnp.mean(jnp.log1p(jnp.exp(-y * (X @ w_))))
    np.testing.assert_allclose(ops.logreg_grad(X, y, w, interpret=True),
                               jax.grad(loss)(w), rtol=1e-4, atol=1e-5)


@pytest.mark.parametrize("N,M,d", [(3, 4, 128), (2, 8, 128), (4, 12, 256),
                                   (1, 1, 128)])
@pytest.mark.parametrize("with_x", [False, True])
def test_admm_worker_select_update(N, M, d, with_x):
    """Batched worker kernel: update (11)(12)(9) + sel-masked merges in
    one pass, per-worker heterogeneous rho as a traced operand."""
    rng = np.random.RandomState(N * 100 + M)
    g, y, zt, w, x = [jnp.asarray(rng.randn(N, M, d), jnp.float32)
                      for _ in range(5)]
    sel = jnp.asarray(rng.rand(N, M) < 0.5)
    rho = jnp.asarray(rng.rand(N) * 3 + 0.5, jnp.float32)
    x_old = x if with_x else None
    out = ops.admm_worker_select_update(g, y, zt, w, sel, rho, x_old,
                                        interpret=True)
    exp = ref.admm_worker_select_update_ref(g, y, zt, w, sel, rho, x_old)
    assert len(out) == (3 if with_x else 2)
    for o, e in zip(out, exp):
        assert o.shape == (N, M, d)
        np.testing.assert_allclose(np.asarray(o), np.asarray(e),
                                   rtol=1e-6, atol=1e-6)
    # unselected (worker, block) pairs keep their old values exactly
    keep = ~np.asarray(sel)
    np.testing.assert_array_equal(np.asarray(out[0])[keep],
                                  np.asarray(y)[keep])


@pytest.mark.parametrize("N,M,d", [(3, 4, 128), (2, 8, 128), (4, 12, 256)])
@pytest.mark.parametrize("l1,clip", [(0.0, 0.0), (0.05, 0.4)])
def test_server_prox_update(N, M, d, l1, clip):
    """Fused server kernel: edge-masked worker reduction + prox (13)
    with the reduction running inside the grid (w_sum never in HBM)."""
    rng = np.random.RandomState(M * 10 + d)
    zc = jnp.asarray(rng.randn(M, d), jnp.float32)
    w = jnp.asarray(rng.randn(N, M, d), jnp.float32)
    edge = jnp.asarray(rng.rand(N, M) < 0.7)
    rs = jnp.asarray(rng.rand(M) * 4 + 0.5, jnp.float32)
    out = ops.server_prox_update(zc, w, edge, rs, gamma=0.1, l1=l1,
                                 clip=clip, interpret=True)
    exp = ref.server_prox_update_ref(zc, w, edge, rs, 0.1, l1, clip)
    assert out.shape == (M, d)
    np.testing.assert_allclose(np.asarray(out), np.asarray(exp),
                               rtol=1e-5, atol=1e-6)
    if clip > 0:
        assert float(jnp.max(jnp.abs(out))) <= clip + 1e-6


@pytest.mark.parametrize("op,args", [
    ("admm_worker_select_update",
     lambda a3, sel, rho: ops.admm_worker_select_update(
         a3, a3, a3, a3, sel, rho, interpret=True)),
    ("server_prox_update",
     lambda a3, sel, rho: ops.server_prox_update(
         a3[0], a3, sel, rho[0] * jnp.ones(a3.shape[1]), gamma=0.1,
         interpret=True)),
])
def test_batched_ops_reject_ragged_rows(op, args):
    """d % 128 != 0 raises the layout-pointing error instead of the old
    silent non-termination of the tile-decrement loop."""
    a3 = jnp.ones((2, 4, 129), jnp.float32)
    sel = jnp.ones((2, 4), bool)
    rho = jnp.ones(2, jnp.float32)
    with pytest.raises(ValueError, match=f"{op}.*129"):
        args(a3, sel, rho)


def test_pick_lane_tile_contract():
    """The lane-tile picker: actionable error off the lane grid, tuned
    winners consulted verbatim only when they are lane multiples
    dividing d, heuristic fallback otherwise."""
    from repro.kernels.admm_update import BLK_D
    from repro.kernels.tiling import pick_blk_m, pick_lane_tile

    def _pick_lane_tile(d, tuned=None, rows=8):
        return pick_lane_tile(d, BLK_D, tuned=tuned, rows=rows)

    with pytest.raises(ValueError, match="d % 128 == 0, got d=136"):
        _pick_lane_tile(136)
    assert _pick_lane_tile(4096) == 2048          # heuristic: cap at 2048
    assert _pick_lane_tile(3 * 128) == 384        # largest lane divisor
    assert _pick_lane_tile(4096, tuned=512) == 512    # tuned divides -> used
    assert _pick_lane_tile(4096, tuned=384) == 2048   # tuned !divides -> fallback
    assert _pick_lane_tile(4096, tuned=100) == 2048   # tuned !lane-mult -> fallback
    assert _pick_lane_tile(4096, rows=1) == 2048      # < 8 rows fill 8 sublanes
    assert _pick_lane_tile(4096, rows=12) == 1024     # taller tile, narrower
    # sublane tile: a multiple of 8 dividing M, or M itself (Mosaic's rule)
    assert pick_blk_m(64) == 8 and pick_blk_m(12) == 12 and pick_blk_m(1) == 1
    assert pick_blk_m(64, tuned=16) == 16
    assert pick_blk_m(12, tuned=12) == 12
    assert pick_blk_m(12, tuned=6) == pick_blk_m(12)  # chip-refused ignored
    assert pick_blk_m(12, tuned=5) == pick_blk_m(12)  # non-divisor ignored


def test_admm_worker_update_rho_is_traced():
    """Sweeping rho must not recompile: rho is an array operand, not a
    jit-static argument (each distinct value used to trigger a fresh
    Mosaic compile)."""
    ops.admm_worker_update._clear_cache()
    g = jnp.asarray(np.random.randn(1024), jnp.float32)
    o = jnp.ones(1024)
    for rho in (0.5, 2.0, 100.0, 3.7):
        x, yn, w = ops.admm_worker_update(g, o, o, rho, interpret=True)
        xe, yne, we = ref.admm_worker_update_ref(g, o, o, rho)
        np.testing.assert_allclose(np.asarray(x), np.asarray(xe),
                                   rtol=1e-5, atol=1e-5)
    assert ops.admm_worker_update._cache_size() == 1


def test_to_2d_aligned_is_reshape_only():
    """(8*128)-aligned buffers must pass through _to_2d without a
    zero-fill + scatter copy (no `pad` / `scatter` in the jaxpr), and
    unaligned buffers are a layout bug — they raise, never pad."""
    from repro.kernels.ops import _from_2d, _to_2d

    def roundtrip(v):
        a2d, orig = _to_2d(v)
        return _from_2d(a2d, orig)

    aligned = jnp.ones((8, 128))
    jaxpr = str(jax.make_jaxpr(roundtrip)(aligned))
    assert "pad" not in jaxpr and "scatter" not in jaxpr, jaxpr
    np.testing.assert_array_equal(np.asarray(roundtrip(aligned)),
                                  np.ones((8, 128)))
    with pytest.raises(ValueError, match="vreg aligned"):
        _to_2d(jnp.ones((3, 5, 17)))
