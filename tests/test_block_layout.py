"""BlockLayout (core/blocks.py): the canonical packed block layout that
lowers pytree consensus onto the flat (M, dblk) block table.

Pins the two properties every layer above relies on:

* **bitwise round-trip** — ``to_blocks`` -> ``from_blocks`` reproduces
  every leaf exactly, for ragged/odd-shaped pytrees, mixed float
  dtypes (f32/bf16/f16 all embed losslessly in the f32 compute dtype),
  leading batch axes (worker N, ring depth), and blocks left empty by
  the assignment;
* **inert padding** — pad lanes are zero after packing and stay
  exactly zero through real epochs (worker update, w reduction, prox),
  so they never leak into w_sum, the prox step, or gradient norms.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.api import ConsensusSession
from repro.configs.base import ADMMConfig
from repro.core.blocks import (LANE, BlockLayout, make_block_layout,
                               make_flat_blocks, make_tree_blocks,
                               round_up_to_lane)


def _ragged_tree():
    """Odd shapes on purpose: scalars, vectors, matrices, 3-d leaves."""
    r = np.random.RandomState(0)
    return {
        "bias": jnp.asarray(r.randn(), jnp.float32),
        "w1": jnp.asarray(r.randn(7), jnp.float32),
        "w2": jnp.asarray(r.randn(3, 5), jnp.float32),
        "deep": {"w3": jnp.asarray(r.randn(2, 2, 3), jnp.float32),
                 "w4": jnp.asarray(r.randn(11), jnp.float32)},
    }


def test_roundtrip_ragged_tree():
    tree = _ragged_tree()
    for m in (1, 2, 3, 7):                     # 7 > num leaves: empty blocks
        layout = make_block_layout(tree, num_blocks=m)
        packed = layout.to_blocks(tree)
        assert packed.shape == (m, layout.block_dim)
        assert max(layout.block_sizes) <= layout.block_dim
        back = layout.from_blocks(packed)
        for a, b in zip(jax.tree.leaves(tree), jax.tree.leaves(back)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        # padding is zero and exactly where the mask says
        mask = layout.padding_mask()
        np.testing.assert_array_equal(np.asarray(packed)[~mask], 0.0)


def test_roundtrip_leading_batch_axes():
    """Worker bundles (N, ...) and ring buffers (D+1, ...) pack through
    the same layout — leading axes pass straight through."""
    tree = _ragged_tree()
    layout = make_block_layout(tree, num_blocks=3)
    for lead in ((4,), (2, 4)):
        batched = jax.tree.map(
            lambda a: jnp.broadcast_to(a, lead + a.shape).copy(), tree)
        packed = layout.to_blocks(batched)
        assert packed.shape == lead + (3, layout.block_dim)
        back = layout.from_blocks(packed)
        for a, b in zip(jax.tree.leaves(batched), jax.tree.leaves(back)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_roundtrip_mixed_dtypes_bitwise():
    """bf16/f16 leaves embed losslessly in the f32 compute dtype — the
    round-trip is bit-exact, not merely close."""
    r = np.random.RandomState(1)
    tree = {
        "f32": jnp.asarray(r.randn(9), jnp.float32),
        "bf16": jnp.asarray(r.randn(4, 3), jnp.float32).astype(jnp.bfloat16),
        "f16": jnp.asarray(r.randn(5), jnp.float32).astype(jnp.float16),
    }
    layout = make_block_layout(tree, num_blocks=2)
    back = layout.from_blocks(layout.to_blocks(tree))
    for a, b in zip(jax.tree.leaves(tree), jax.tree.leaves(back)):
        assert a.dtype == b.dtype
        np.testing.assert_array_equal(
            np.asarray(a).view(np.uint8), np.asarray(b).view(np.uint8))


def test_layout_validates_structure():
    tree = _ragged_tree()
    layout = make_block_layout(tree, num_blocks=2)
    with pytest.raises(ValueError, match="structure"):
        layout.to_blocks({"other": jnp.zeros(3)})
    with pytest.raises(ValueError, match="shape"):
        bad = dict(tree, w1=jnp.zeros((8,)))   # w1 is (7,) in the layout
        layout.to_blocks(bad)
    with pytest.raises(ValueError, match="empty"):
        make_block_layout({}, num_blocks=2)
    blocks = make_tree_blocks(tree, 2)
    with pytest.raises(ValueError, match="structure"):
        make_block_layout({"other": jnp.zeros(3)}, blocks)


def test_block_id_contract():
    """Block ids follow TreeBlocks' assignment and rows pack the
    block's leaves in leaf order at the recorded offsets."""
    tree = {"a": jnp.arange(3.0), "b": jnp.arange(3.0, 7.0),
            "c": jnp.arange(7.0, 9.0)}
    blocks = make_tree_blocks(tree, 2)
    layout = make_block_layout(tree, blocks)
    assert layout.block_ids == blocks.leaf_block_ids
    assert isinstance(layout, BlockLayout)
    packed = np.asarray(layout.to_blocks(tree))
    leaves = jax.tree.leaves(tree)
    for k, leaf in enumerate(leaves):
        j, off = layout.block_ids[k], layout.leaf_offsets[k]
        np.testing.assert_array_equal(packed[j, off:off + leaf.size],
                                      np.asarray(leaf).ravel())


def test_block_dim_is_lane_rounded():
    """Lane alignment is a property of the LAYOUT: block_dim is the max
    block payload rounded up to the 128-lane boundary, never the raw
    payload — so every kernel below sees vreg-aligned rows without a
    per-call pad copy."""
    tree = _ragged_tree()
    for m in (1, 2, 3):
        layout = make_block_layout(tree, num_blocks=m)
        assert layout.block_dim % LANE == 0
        assert layout.block_dim == round_up_to_lane(max(layout.block_sizes))
    # flat layouts too, including dims already on the boundary
    for dim, m in ((256, 2), (315, 3), (129, 1)):
        fb = make_flat_blocks(dim, m)
        assert fb.block_dim % LANE == 0
        assert fb.block_dim == round_up_to_lane(fb.used_dim)
        assert fb.used_dim * m >= dim


def test_roundtrip_at_lane_boundary_bitwise():
    """Leaf sizes straddling the 128 boundary (127/128/129) round-trip
    bit-exactly in every stored dtype — the rounded row never bleeds
    pad lanes into payload."""
    r = np.random.RandomState(5)
    for size in (127, 128, 129):
        tree = {
            "f32": jnp.asarray(r.randn(size), jnp.float32),
            "bf16": jnp.asarray(r.randn(size), jnp.float32).astype(jnp.bfloat16),
            "f16": jnp.asarray(r.randn(size), jnp.float32).astype(jnp.float16),
        }
        layout = make_block_layout(tree, num_blocks=3)
        packed = layout.to_blocks(tree)
        assert packed.shape[-1] % LANE == 0
        back = layout.from_blocks(packed)
        for a, b in zip(jax.tree.leaves(tree), jax.tree.leaves(back)):
            assert a.dtype == b.dtype
            np.testing.assert_array_equal(
                np.asarray(a).reshape(-1).view(np.uint8),
                np.asarray(b).reshape(-1).view(np.uint8))
        np.testing.assert_array_equal(
            np.asarray(packed)[~layout.padding_mask()], 0.0)


def test_padding_inert_through_prox_and_edge_mask():
    """Zero pad lanes stay exactly zero through the fused server op
    (edge-masked w reduction + prox) and the plain prox: soft-threshold
    of 0 with w_sum 0 is 0, so padding can never contaminate z."""
    from repro.kernels import ops

    N, M = 3, 2
    tree = {"a": jnp.ones((100,), jnp.float32),
            "b": jnp.ones((130,), jnp.float32)}
    layout = make_block_layout(tree, num_blocks=M)
    pad = ~layout.padding_mask()
    assert pad.any()
    r = np.random.RandomState(7)
    z = layout.to_blocks(jax.tree.map(
        lambda a: jnp.asarray(r.randn(*a.shape), a.dtype), tree))
    w_cache = jnp.stack([z * (k + 1) for k in range(N)])
    edge = jnp.asarray(r.rand(N, M) < 0.7)
    rho_sum = jnp.full((M,), 2.0, jnp.float32)
    z_new = ops.server_prox_update(z, w_cache, edge, rho_sum,
                                   gamma=0.1, l1=1e-3, clip=0.5)
    np.testing.assert_array_equal(np.asarray(z_new)[pad], 0.0)
    assert float(np.max(np.abs(np.asarray(z_new)))) > 0.0
    z_prox = ops.prox_consensus(z, z * 0.5, rho_sum, gamma=0.1, l1=1e-3,
                                clip=0.5)
    np.testing.assert_array_equal(np.asarray(z_prox)[pad], 0.0)


def test_sharded_divisibility_of_lane_rounded_layout():
    """Model-axis sharding splits the BLOCK axis, never the lane axis:
    the per-shard state keeps full lane-aligned rows, and indivisible
    block counts still fail eagerly with the num_blocks message."""
    from jax.sharding import AbstractMesh

    mesh = AbstractMesh((4, 2), ("data", "model"))
    params = {"w": jnp.zeros((300,), jnp.float32)}
    cfg = ADMMConfig(rho=1.0, gamma=0.1, num_blocks=4, seed=0)

    def loss(p, c):
        return 0.5 * jnp.sum(jnp.square(p["w"] - c))

    sess = ConsensusSession.pytree(loss, params, cfg, num_workers=4,
                                   mesh=mesh)
    from repro.core.sharded import consensus_state_specs
    state = jax.eval_shape(sess.init)
    specs = consensus_state_specs(sess.spec, state)
    yspec = specs.y
    assert yspec[1] == "model" and yspec[2] is None   # blocks split, lanes whole
    assert state.y.shape[2] % LANE == 0
    assert state.y.shape[1] % 2 == 0                  # M divides the model axis
    with pytest.raises(ValueError, match="num_blocks"):
        ConsensusSession.pytree(loss, params,
                                ADMMConfig(rho=1.0, gamma=0.1, num_blocks=3,
                                           seed=0),
                                num_workers=4, mesh=mesh)


def _ragged_session(max_delay=1, clip=0.8):
    """A pytree session whose LPT assignment leaves real padding in
    some rows (block sizes 13, 12, 4 -> dblk 13)."""
    params = {"w2": jnp.zeros((3, 4), jnp.float32),    # 12 -> own block
              "w1": jnp.zeros((13,), jnp.float32),     # 13 -> own block
              "w0": jnp.zeros((4,), jnp.float32)}      # 4  -> padded block
    cfg = ADMMConfig(rho=2.0, gamma=0.1, max_delay=max_delay,
                     block_fraction=0.5, num_blocks=3, l1_coef=1e-3,
                     clip=clip, seed=0)

    def loss(p, c):
        z = jnp.concatenate([p["w0"].ravel(), p["w1"].ravel(),
                             p["w2"].ravel()])
        return 0.5 * jnp.sum(jnp.square(z - c))
    return ConsensusSession.pytree(loss, params, cfg, num_workers=3)


def test_padding_never_leaks_into_epoch():
    """Pad lanes stay exactly 0 through real epochs: z ring, duals,
    w cache, and the edge-masked w_sum reduction all keep zero padding,
    so the prox never sees (or emits) garbage lanes."""
    sess = _ragged_session()
    layout = sess.spec.space.layout
    pad = ~layout.padding_mask()
    assert pad.any()                          # the case really is ragged
    centers = jnp.asarray(
        np.random.RandomState(3).randn(3, sum(layout.block_sizes)),
        jnp.float32)
    state = sess.init()
    step = sess.step_fn()
    for _ in range(6):
        state, _ = step(state, centers)
        for name, buf in (("z_hist", state.z_hist), ("y", state.y),
                          ("w_cache", state.w_cache)):
            vals = np.asarray(buf)[..., pad]
            np.testing.assert_array_equal(
                vals, 0.0, err_msg=f"padding leaked into {name}")
        w_sum = np.asarray(sess.spec.space.reduce_workers(
            state.w_cache, sess.spec.edge))
        np.testing.assert_array_equal(w_sum[pad], 0.0)
    assert float(np.max(np.abs(np.asarray(state.z_hist)))) > 0.0


try:
    import hypothesis  # noqa: F401
    from hypothesis import given, settings, strategies as st

    _dtypes = st.sampled_from(["float32", "bfloat16", "float16"])
    _shapes = st.lists(st.integers(1, 4), min_size=0, max_size=3)

    @given(leaves=st.lists(st.tuples(_shapes, _dtypes),
                           min_size=1, max_size=6),
           m=st.integers(1, 5), lead=st.integers(0, 2),
           data=st.data())
    @settings(max_examples=40, deadline=None)
    def test_roundtrip_property(leaves, m, lead, data):
        """pack -> unpack is a bitwise round-trip for arbitrary ragged
        pytrees, block counts, and leading batch axes."""
        r = np.random.RandomState(data.draw(st.integers(0, 2**31 - 1)))
        prefix = tuple(data.draw(st.integers(1, 3)) for _ in range(lead))
        tree = {}
        for k, (shape, dt) in enumerate(leaves):
            vals = np.asarray(r.randn(*(prefix + tuple(shape))), np.float32)
            tree[f"l{k}"] = jnp.asarray(vals).astype(dt)
        template = {k: jax.ShapeDtypeStruct(v.shape[lead:], v.dtype)
                    for k, v in tree.items()}
        layout = make_block_layout(template, num_blocks=m)
        assert layout.block_dim % LANE == 0
        packed = layout.to_blocks(tree)
        assert packed.shape == prefix + (m, layout.block_dim)
        back = layout.from_blocks(packed)
        for a, b in zip(jax.tree.leaves(tree), jax.tree.leaves(back)):
            assert a.dtype == b.dtype
            np.testing.assert_array_equal(
                np.asarray(a).reshape(-1).view(np.uint8),
                np.asarray(b).reshape(-1).view(np.uint8))
        # padding is exactly zero at every batch index
        mask = layout.padding_mask()
        np.testing.assert_array_equal(np.asarray(packed)[..., ~mask], 0.0)
except ImportError:                     # pragma: no cover - optional extra
    pass
