"""Flat <-> pytree parity: the same quadratic consensus problem driven
through ``FlatSpace`` and ``TreeSpace`` must produce the SAME z
trajectory (same seed, same config) — for all three block-selection
policies, under bounded delay, heterogeneous rho_i, and a sparse
general-form edge set.

Construction: dim = M * DBLK coordinates; flat block j is the
coordinate slice [j*DBLK, (j+1)*DBLK); the pytree has one leaf per
block ("w0".."w{M-1}", each (DBLK,)) pinned to block j via an explicit
TreeBlocks assignment. Both spaces then draw identical (N, M) delay and
selection randomness from the same key, so every update is elementwise
identical.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.api import ConsensusSession
from repro.configs.base import ADMMConfig
from repro.core.blocks import TreeBlocks

N, M, DBLK = 3, 4, 5
DIM = M * DBLK

# every worker keeps >= 1 block; block 0 is shared by all
EDGE = np.array([[1, 1, 0, 1],
                 [1, 0, 1, 0],
                 [1, 1, 1, 1]], bool)
RHO_SCALE = np.array([0.5, 1.0, 2.0], np.float32)


def _centers():
    rng = np.random.RandomState(7)
    return jnp.asarray(rng.randn(N, DIM).astype(np.float32))


def _flat_loss(z, c):
    return 0.5 * jnp.sum(jnp.square(z - c))


def _tree_params():
    return {f"w{j}": jnp.zeros((DBLK,), jnp.float32) for j in range(M)}


def _tree_loss(p, c):
    z = jnp.concatenate([p[f"w{j}"] for j in range(M)])
    return 0.5 * jnp.sum(jnp.square(z - c))


def _tree_z(sess, state):
    zt = sess.z(state)
    return jnp.concatenate([zt[f"w{j}"] for j in range(M)])


@pytest.mark.parametrize("scheme", ["random", "cyclic", "gauss_southwell"])
def test_flat_tree_same_z_trajectory(scheme):
    cfg = ADMMConfig(rho=2.0, gamma=0.1, max_delay=1, block_fraction=0.5,
                     num_blocks=M, block_selection=scheme, l1_coef=1e-3,
                     seed=0)
    centers = _centers()

    flat = ConsensusSession.flat(_flat_loss, centers, dim=DIM, cfg=cfg,
                                 edge=EDGE, rho_scale=RHO_SCALE)

    params = _tree_params()
    # leaf k of the sorted dict IS flat block k
    tblocks = TreeBlocks(num_blocks=M, leaf_block_ids=tuple(range(M)),
                         treedef=jax.tree.structure(params))
    tree = ConsensusSession.pytree(_tree_loss, params, cfg, num_workers=N,
                                   blocks=tblocks, edge=EDGE,
                                   rho_scale=RHO_SCALE)

    sf = flat.init()
    st = tree.init()
    step_f = flat.step_fn()
    step_t = tree.step_fn()
    traj_err = []
    for t in range(25):
        sf, info_f = step_f(sf, centers)
        st, info_t = step_t(st, centers)
        zf = np.asarray(flat.z(sf))
        zt = np.asarray(_tree_z(tree, st))
        traj_err.append(float(np.max(np.abs(zf - zt))))
        np.testing.assert_allclose(zf, zt, rtol=1e-6, atol=1e-6,
                                   err_msg=f"{scheme} diverged at epoch {t}")
        np.testing.assert_allclose(float(info_f["selected_fraction"]),
                                   float(info_t["selected_fraction"]),
                                   atol=1e-7)
    # and the run actually moved somewhere
    assert float(np.max(np.abs(zf))) > 0.0, traj_err


def test_pytree_edge_set_respected():
    """Workers never touch blocks outside their edge neighborhood: the
    duals y of a (worker, block) pair outside E stay exactly zero."""
    cfg = ADMMConfig(rho=2.0, gamma=0.1, max_delay=1, block_fraction=1.0,
                     num_blocks=M, seed=1)
    params = _tree_params()
    tblocks = TreeBlocks(num_blocks=M, leaf_block_ids=tuple(range(M)),
                         treedef=jax.tree.structure(params))
    sess = ConsensusSession.pytree(_tree_loss, params, cfg, num_workers=N,
                                   blocks=tblocks, edge=EDGE)
    state = sess.init()
    step = sess.step_fn()
    centers = _centers()
    for _ in range(5):
        state, _ = step(state, centers)
    y = np.asarray(state.y)          # packed (N, M, dblk) worker bundle
    for j in range(M):
        outside = ~EDGE[:, j]
        assert np.all(y[outside, j] == 0.0), (j, y)
        inside = EDGE[:, j]
        assert np.any(y[inside, j] != 0.0), (j, y)


def test_pytree_heterogeneous_rho_changes_trajectory():
    """rho_scale is actually honored in pytree mode (not silently
    ignored as before the VariableSpace refactor)."""
    cfg = ADMMConfig(rho=2.0, gamma=0.1, max_delay=0, block_fraction=1.0,
                     num_blocks=M, seed=0)
    params = _tree_params()
    tblocks = TreeBlocks(num_blocks=M, leaf_block_ids=tuple(range(M)),
                         treedef=jax.tree.structure(params))
    centers = _centers()

    def final_z(rho_scale):
        sess = ConsensusSession.pytree(_tree_loss, params, cfg,
                                       num_workers=N, blocks=tblocks,
                                       rho_scale=rho_scale)
        state = sess.init()
        step = sess.step_fn()
        for _ in range(10):
            state, _ = step(state, centers)
        return np.asarray(_tree_z(sess, state))

    z_homog = final_z(None)
    z_heterog = final_z(RHO_SCALE)
    assert np.isfinite(z_homog).all() and np.isfinite(z_heterog).all()
    assert float(np.max(np.abs(z_homog - z_heterog))) > 1e-4


# ---------------------------------------------------------------------------
# worker_grads against a plain vmap of value_and_grad on the user's value
# ---------------------------------------------------------------------------

GN, GROWS, GNNZ = 4, 24, 5


def _sparse_logreg(z, rows):
    idx, val, y = rows
    return jnp.mean(jax.nn.softplus(-y * jnp.sum(val * z[idx], axis=-1)))


def _flat_case():
    """FlatSpace whose used_dim (143) is not a multiple of the lane."""
    from repro.core.blocks import make_flat_blocks
    from repro.core.space import FlatSpace
    dim = 1000
    blocks = make_flat_blocks(dim, 7)
    assert blocks.used_dim % 128 and blocks.dim < blocks.logical_dim
    r = np.random.RandomState(3)
    data = (jnp.asarray(r.randint(0, dim, (GN, GROWS, GNNZ)), jnp.int32),
            jnp.asarray(r.randn(GN, GROWS, GNNZ), jnp.float32),
            jnp.asarray(r.choice([-1.0, 1.0], (GN, GROWS)), jnp.float32))
    z_user = jnp.asarray(r.randn(GN, dim), jnp.float32)
    # the gather's transpose adds the same terms in the same order
    # batched or not: the packed gradient is bitwise the reference's
    return FlatSpace(blocks=blocks, num_workers=GN), _sparse_logreg, \
        z_user, data, True


def _tree_user(p):
    return jnp.concatenate([leaf.reshape(-1) for leaf in jax.tree.leaves(p)])


def _tree_logreg(p, d):
    X, y = d
    return jnp.mean(jax.nn.softplus(-y * (X @ _tree_user(p))))


def _tree_case():
    """TreeSpace over ragged leaves: blocks of unequal packed size."""
    from repro.core.blocks import make_block_layout
    from repro.core.space import TreeSpace
    shapes = {"a": (3, 5), "b": (7,), "c": (2, 2, 3), "d": (1,)}
    params = {k: jnp.zeros(s, jnp.float32) for k, s in shapes.items()}
    layout = make_block_layout(params, num_blocks=3)
    assert len(set(layout.block_sizes)) > 1
    r = np.random.RandomState(4)
    z_user = {k: jnp.asarray(r.randn(GN, *s), jnp.float32)
              for k, s in shapes.items()}
    n = _tree_user(params).shape[0]
    data = (jnp.asarray(r.randn(GN, GROWS, n), jnp.float32),
            jnp.asarray(r.choice([-1.0, 1.0], (GN, GROWS)), jnp.float32))
    space = TreeSpace(blocks=layout.tree, num_workers=GN, layout=layout)
    # a batched matmul reduces in another order than an unbatched one
    return space, _tree_logreg, z_user, data, False


@pytest.mark.parametrize("minibatch", [None, 0.5], ids=["full", "minibatch"])
@pytest.mark.parametrize("case", [_flat_case, _tree_case],
                         ids=["flat_unaligned", "tree_ragged"])
def test_worker_grads_match_vmapped_reference(case, minibatch):
    """The per-worker ``worker_grads`` gives each worker's loss and packed
    gradient exactly as a vmapped ``value_and_grad`` on the user's value
    does, on the same minibatch rows."""
    from repro.core.async_sim import subsample_worker_data
    space, loss_fn, z_user, data, bitwise_grad = case()
    rng = jax.random.PRNGKey(11)
    packer = space.packer
    z_tilde = packer.to_blocks(z_user)

    losses, g = jax.jit(lambda zt, d: space.worker_grads(
        loss_fn, zt, d, minibatch=minibatch, rng=rng))(z_tilde, data)

    rows = subsample_worker_data(rng, data, minibatch)
    ref_losses, ref_g = jax.jit(jax.vmap(jax.value_and_grad(loss_fn)))(
        z_user, rows)
    assert losses.shape == (GN,)
    assert g.shape == z_tilde.shape
    ref_g = packer.to_blocks(ref_g)
    if bitwise_grad:
        np.testing.assert_array_equal(np.asarray(g), np.asarray(ref_g))
    # float32 sums may reduce in another order batched than unbatched:
    # 1e-6 of each array's largest entry (a gradient entry that cancels
    # to near 0 carries the rounding of the terms it summed)
    for got, want in ((losses, ref_losses), (g, ref_g)):
        want = np.asarray(want)
        np.testing.assert_allclose(np.asarray(got), want, rtol=1e-6,
                                   atol=1e-6 * np.abs(want).max())
    assert np.all(np.asarray(g)[:, ~packer.padding_mask()] == 0.0)
