"""SPMD-sharded epoch of Algorithm 1 over a (data..., model) mesh.

The paper's Parameter-Server picture maps onto the pod directly:

  worker i       = a shard of the ``data`` mesh axes — its duals ``y``,
                   stale-w cache and primal ``x`` live with its data;
  block server j = a shard of the ``model`` axis. BOTH spaces split the
                   canonical packed (M, dblk) block table over ``model``
                   (z_hist, prox and the server kernel all run on local
                   (M/model, dblk) tiles) — TreeSpace lowers its leaves
                   onto that table via ``core.blocks.BlockLayout``, so
                   pytree consensus gets native block servers too (the
                   old replicated-z fallback is gone);
  push w_ij      = a partial edge-masked reduce over the *local*
                   workers followed by ONE ``psum`` over ``data`` that
                   lands directly in each block server's local shard —
                   the full (M, dblk) w_sum never materializes
                   unsharded anywhere.

``sharded_epoch`` wraps the epoch body in ``jax.shard_map`` with the
:func:`consensus_state_specs` layout; the PR-2 Pallas kernels then
execute per shard on their local (N/data, M/model, dblk) tiles.

Parity contract (pinned by tests/test_spmd_parity.py): the sharded z
trajectory equals the single-device ``asybadmm_epoch`` trajectory for
both spaces and all three block selectors. Two ingredients make that
exact rather than approximate:

* delay + selection draws are computed at FULL (N, M) shape on every
  device from the replicated rng key and *sliced* to the local shard —
  identical to the single-device draw (``jax_threefry_partitionable``
  is enabled globally for the same reason);
* every elementwise update runs the same math on a slice; only the
  worker reduction's float-sum order changes (partial + psum), which is
  why the test allows fp32 tolerance there.

``_SimCollectives`` swaps the mesh collectives for single-device
shape-faithful stand-ins so ``benchmarks/kernels_bench.py`` can lower
the per-shard program WITHOUT devices and measure its HBM bytes (the
~1/(data*model) shrink gate).
"""
from __future__ import annotations

import dataclasses
from typing import Any, Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import NamedSharding, PartitionSpec as P

from ..launch.mesh import data_axes, model_axis_size, num_workers
from ..obs.profile import stage
from .async_sim import minibatch_rows, validate_minibatch_data
from .space import (ConsensusSpec, ConsensusState, SelectorContext,
                    epoch_keys, participation_mask_for, sample_delay_model)


def _splits_model(space) -> bool:
    """Does this space shard its block axis over ``model``? Since the
    packed-layout refactor both spaces do, whenever the axis exists."""
    return model_axis_size(space.mesh) > 1


def validate_space_mesh(space) -> None:
    """Eager divisibility checks so a bad (mesh, problem) pairing fails
    with an actionable message, not a shard_map shape error."""
    mesh = space.mesh
    names = set(mesh.axis_names)
    if not names <= {"pod", "data", "model"}:
        raise ValueError(f"mesh axes {mesh.axis_names} unknown; expected a "
                         f"subset of ('pod', 'data', 'model')")
    nsh = num_workers(mesh)
    if space.num_workers % nsh != 0:
        raise ValueError(
            f"num_workers={space.num_workers} must divide over the mesh's "
            f"{nsh} data-axis shards ({data_axes(mesh)}); pad the worker "
            f"set or pick a smaller mesh")
    if _splits_model(space):
        msize = model_axis_size(mesh)
        if space.num_blocks % msize != 0:
            raise ValueError(
                f"num_blocks={space.num_blocks} must divide over "
                f"model={msize} block-server shards; choose num_blocks as "
                f"a multiple of the model axis (both spaces shard the "
                f"packed (M, dblk) block table over model)")


# ---------------------------------------------------------------------------
# NamedSharding specs for every state tensor
# ---------------------------------------------------------------------------

def worker_bundle_spec(ndim: int, daxes, mname=None) -> P:
    """Worker-bundle leaf: leading N over data axes, (flat) M over model.
    THE base rule for every (N, ...) ADMM tensor — launch/shardings.py
    overlays its tensor-parallel param dims on top of this."""
    return P(*((daxes, mname) + (None,) * (ndim - 2))[:ndim])


def ring_spec(ndim: int, mname=None) -> P:
    """History leaf: leading ring axis replicated, (flat) M over model."""
    return P(*((None, mname) + (None,) * (ndim - 2))[:ndim])


def consensus_state_specs(spec: ConsensusSpec, state) -> ConsensusState:
    """PartitionSpec for every ``ConsensusState`` tensor on the space's
    mesh — THE canonical ADMM state layout (launch/shardings.py overlays
    its tensor-parallel param dims on top of this base for the dryrun)."""
    space = spec.space
    daxes = data_axes(space.mesh)
    mname = "model" if _splits_model(space) else None
    w = lambda leaf: worker_bundle_spec(leaf.ndim, daxes, mname)
    z = lambda leaf: ring_spec(leaf.ndim, mname)
    return ConsensusState(
        z_hist=jax.tree.map(z, state.z_hist),
        y=jax.tree.map(w, state.y),
        w_cache=jax.tree.map(w, state.w_cache),
        x=jax.tree.map(w, state.x),
        t=P(), rng=P())


def grad_split_size(spec: ConsensusSpec):
    """Workers-per-device of the model-split gradient pass, or None when
    grads replicate over model (no model split, or the local worker
    count does not divide by the model axis)."""
    space = spec.space
    if not _splits_model(space):
        return None
    Nl = space.num_workers // num_workers(space.mesh)
    msize = model_axis_size(space.mesh)
    return Nl // msize if Nl and Nl % msize == 0 else None


def consensus_data_specs(spec: ConsensusSpec, data):
    """Per-worker data: leading worker axis over the data mesh axes —
    and additionally over ``model`` when the gradient pass splits the
    local workers across it (every device then holds exactly the rows
    its grad shard differentiates)."""
    daxes = data_axes(spec.space.mesh)
    ax0 = tuple(daxes) if isinstance(daxes, (tuple, list)) else (daxes,)
    if grad_split_size(spec) is not None:
        ax0 = ax0 + ("model",)
    return jax.tree.map(lambda a: P(*((ax0,) + (None,) * (a.ndim - 1))),
                        data)


def consensus_state_shardings(spec: ConsensusSpec, state) -> ConsensusState:
    """NamedSharding tree for ``jax.device_put`` of the state."""
    mesh = spec.space.mesh
    return jax.tree.map(lambda s: NamedSharding(mesh, s),
                        consensus_state_specs(spec, state),
                        is_leaf=lambda v: isinstance(v, P))


def consensus_data_shardings(spec: ConsensusSpec, data):
    """NamedSharding tree for ``jax.device_put`` of per-worker data."""
    mesh = spec.space.mesh
    return jax.tree.map(lambda s: NamedSharding(mesh, s),
                        consensus_data_specs(spec, data),
                        is_leaf=lambda v: isinstance(v, P))


# ---------------------------------------------------------------------------
# collectives — real mesh axes vs the single-device costing stand-in
# ---------------------------------------------------------------------------

class _MeshCollectives:
    """The real thing: axis-index slicing, all_gather, psum. Every
    collective opens the ``asybadmm.exchange`` scope."""

    def __init__(self, mesh, daxes):
        self.mesh, self.daxes = mesh, daxes

    def worker_shard_index(self):
        wi = jnp.zeros((), jnp.int32)
        for a in self.daxes:                      # row-major over data axes
            wi = wi * self.mesh.shape[a] + lax.axis_index(a)
        return wi

    def model_index(self):
        return lax.axis_index("model")

    def all_gather_model(self, x, axis):
        with stage("asybadmm.exchange"):
            return lax.all_gather(x, "model", axis=axis, tiled=True)

    def all_to_all_model(self, x, split_axis, concat_axis):
        with stage("asybadmm.exchange"):
            return lax.all_to_all(x, "model", split_axis, concat_axis,
                                  tiled=True)

    def all_gather_data(self, x):
        with stage("asybadmm.exchange"):
            return lax.all_gather(x, self.daxes, axis=0, tiled=True)

    def psum_data(self, x):
        with stage("asybadmm.exchange"):
            return lax.psum(x, self.daxes)


class _SimCollectives:
    """Single-device stand-in with the same SHAPE semantics, so the
    per-shard program can be lowered (abstractly) without any devices
    and costed by analysis/hlo_cost. Each stand-in is chosen so its
    generic operand+result charge equals what the analyzer charges the
    REAL collective op's boundary: all-gather -> one pad (local shard in,
    full buffer out), all-to-all -> one reshape (same bytes in and out),
    psum -> one multiply (shard in, shard out)."""

    def __init__(self, nsh: int, msize: int):
        self.nsh, self.msize = nsh, msize

    def worker_shard_index(self):
        return jnp.zeros((), jnp.int32)

    def model_index(self):
        return jnp.zeros((), jnp.int32)

    @staticmethod
    def _gather(x, axis, size):
        cfg = [(0, 0, 0)] * x.ndim
        cfg[axis] = (0, (size - 1) * x.shape[axis], 0)
        return lax.pad(x, jnp.zeros((), x.dtype), cfg)

    def all_gather_model(self, x, axis):
        return self._gather(x, axis, self.msize)

    def all_to_all_model(self, x, split_axis, concat_axis):
        shape = list(x.shape)
        shape[split_axis] //= self.msize
        shape[concat_axis] *= self.msize
        return x.reshape(shape)

    def all_gather_data(self, x):
        return self._gather(x, 0, self.nsh)

    def psum_data(self, x):
        return jax.tree.map(lambda a: a * jnp.float32(self.nsh), x)


# ---------------------------------------------------------------------------
# the per-shard epoch body (Algorithm 1, local view)
# ---------------------------------------------------------------------------

def _epoch_body(spec: ConsensusSpec, space_l, coll, Nl: int, Ml: int,
                state: ConsensusState, data, edge, rho_vec
                ) -> Tuple[ConsensusState, dict]:
    """One epoch on ONE shard. ``space_l`` is the space resized to the
    local worker count (num_workers=Nl, mesh=None); all worker bundles
    in ``state`` are local (Nl, [Ml,] ...) tiles; ``edge`` / ``rho_vec``
    arrive replicated at full (N, M) / (N,) shape."""
    N, M = edge.shape
    split_model = Ml < M
    msize = M // Ml if split_model else 1
    split_grads = split_model and Nl % msize == 0
    Ng = Nl // msize if split_grads else Nl       # local data rows
    rng, r_delay, r_sel, r_batch = epoch_keys(state.rng, spec.minibatch)
    wi = coll.worker_shard_index()
    mi = coll.model_index() if split_model else None

    def rows(a):                                  # full (N, ...) -> local N
        return lax.dynamic_slice_in_dim(a, wi * Nl, Nl, 0)

    def take(a):                                  # local Nl -> grad shard
        if not split_grads:
            return a
        return lax.dynamic_slice_in_dim(a, mi * Ng, Ng, 0)

    def cols(a, axis=1):                          # full M -> local blocks
        if not split_model:
            return a
        return lax.dynamic_slice_in_dim(a, mi * Ml, Ml, axis)

    # --- stale pull: FULL (N, M) replicated draw, sliced to the shard ---
    with stage("asybadmm.gather"):
        delays = sample_delay_model(spec.delay_model, r_delay, N, M, state.t)
        z_tilde = space_l.gather(state.z_hist, cols(rows(delays)))

    # --- minibatch draw, like delay/selection: FULL (N, S) replicated,
    #     sliced to the local worker rows (== the single-device draw).
    #     Data arrives sharded to the rows this device differentiates:
    #     (Nl, ...) normally, (Ng, ...) under the split gradient pass
    #     (consensus_data_specs adds the model axis). ---
    if spec.minibatch is not None and spec.minibatch < 1.0:
        shape = validate_minibatch_data(data)
        if shape is not None:              # leafless data: no-op, like
            S = shape[1]                   # subsample_worker_data
            with stage("asybadmm.minibatch"):
                idx_l = take(rows(minibatch_rows(r_batch, N, S,
                                                 spec.minibatch)))
                data = jax.tree.map(
                    lambda a: a[jnp.arange(Ng)[:, None], idx_l], data)

    # --- grads need every block of z~ for the local workers (the loss
    #     reads the whole variable). The model axis is redundant during
    #     this pass — every model shard would differentiate the same Nl
    #     workers against the same gathered z~ — so when the local
    #     workers divide evenly, split them across it (grads are
    #     per-worker: pure extra data parallelism), then route the
    #     results with one all_to_all (worker axis scattered back, block
    #     axis collected). Per-worker grads and losses are bitwise
    #     identical to the unsplit path, so the trajectory, the
    #     selection draw, and the reported loss are unchanged while the
    #     per-shard gradient traffic shrinks by 1/model instead of
    #     replicating. ---
    if split_grads:
        # NOT take-then-gather: each model shard holds DIFFERENT blocks,
        # so gathering take(z_tilde) would stitch chunk m's blocks onto
        # chunk m's workers. The all_to_all routes every shard's block
        # slice of the destination's worker rows — the exact inverse of
        # the gradient exchange below.
        zt_g = coll.all_to_all_model(z_tilde, 0, 1)   # (Ng, M, dblk)
        space_g = dataclasses.replace(space_l, num_workers=Ng)
        with stage("asybadmm.grad"):
            losses_g, g_g = space_g.worker_grads(spec.loss_fn, zt_g, data)
        losses = coll.all_gather_model(losses_g, axis=0)
        g_cols = coll.all_to_all_model(g_g, 1, 0)     # (Nl, Ml, dblk)
        gnorm_fn = lambda: coll.all_gather_data(
            coll.all_gather_model(space_g.grad_sqnorm(g_g), axis=0))
    else:
        z_tilde_full = (coll.all_gather_model(z_tilde, axis=1)
                        if split_model else z_tilde)
        with stage("asybadmm.grad"):
            losses, g = space_l.worker_grads(spec.loss_fn, z_tilde_full,
                                             data)
            g_cols = cols(g)
        gnorm_fn = lambda: coll.all_gather_data(space_l.grad_sqnorm(g))

    # --- selection at FULL (N, M), replicated — identical to the
    #     single-device draw (Gauss-Southwell additionally gathers the
    #     per-block grad norms over the data axes) ---
    with stage("asybadmm.select"):
        ctx = SelectorContext(
            rng=r_sel, edge=edge, t=state.t,
            block_fraction=spec.block_fraction,
            grad_sqnorm=gnorm_fn)
        sel = spec.selector(ctx)

        # --- partial participation (chaos replay): same full-(N, 1)
        #     mask the single-device epoch ANDs in, applied before
        #     slicing so the local tile sees the identical selection ---
        pmask = participation_mask_for(spec.delay_model, state.t)
        if pmask is not None:
            sel = sel & pmask

    # --- worker update (11)(12)(9) + select writes on the local tile ---
    with stage("asybadmm.worker_update"):
        y, w_cache, x = space_l.worker_select_update(
            g_cols, state.y, z_tilde, state.w_cache, state.x,
            cols(rows(sel)), rows(rho_vec), spec.track_x)

    # --- the paper's w push: partial edge-masked reduce over the LOCAL
    #     workers, then one psum over data that lands in this block
    #     server's shard — w_sum never exists unsharded ---
    with stage("asybadmm.server_update"):
        w_sum = coll.psum_data(space_l.reduce_workers(w_cache,
                                                      cols(rows(edge))))
        rho_sum = cols(jnp.sum(jnp.where(edge, rho_vec[:, None], 0.0),
                               axis=0), axis=0)
        z_new = space_l.server_prox(space_l.current(state.z_hist), w_sum,
                                    rho_sum, spec.gamma, spec.reg)

    loss = coll.psum_data(jnp.sum(losses)) / N
    info = {"loss": loss,
            "selected_fraction": jnp.mean(sel.astype(jnp.float32))}
    with stage("asybadmm.push"):
        z_hist = space_l.push(state.z_hist, z_new)
    new_state = ConsensusState(z_hist=z_hist, y=y, w_cache=w_cache, x=x,
                               t=state.t + 1, rng=rng)
    return new_state, info


def _local_sizes(spec: ConsensusSpec) -> Tuple[int, int]:
    space = spec.space
    Nl = space.num_workers // num_workers(space.mesh)
    Ml = (space.num_blocks // model_axis_size(space.mesh)
          if _splits_model(space) else space.num_blocks)
    return Nl, Ml


def _local_space(spec: ConsensusSpec, Nl: int):
    return dataclasses.replace(spec.space, num_workers=Nl, mesh=None)


# ---------------------------------------------------------------------------
# entry points
# ---------------------------------------------------------------------------

def sharded_epoch(spec: ConsensusSpec, state: ConsensusState, data
                  ) -> Tuple[ConsensusState, dict]:
    """``asybadmm_epoch`` over the space's mesh via shard_map."""
    space = spec.space
    mesh = space.mesh
    daxes = data_axes(mesh)
    Nl, Ml = _local_sizes(spec)
    space_l = _local_space(spec, Nl)
    coll = _MeshCollectives(mesh, daxes)

    def body(st, d, e, r):
        return _epoch_body(spec, space_l, coll, Nl, Ml, st, d, e, r)

    sspecs = consensus_state_specs(spec, state)
    in_specs = (sspecs, consensus_data_specs(spec, data), P(), P())
    out_specs = (sspecs, {"loss": P(), "selected_fraction": P()})
    fn = jax.shard_map(body, mesh=mesh, in_specs=in_specs,
                       out_specs=out_specs, check_vma=False)
    return fn(state, data, spec.edge, spec.rho_vec)


def per_shard_cost_program(spec: ConsensusSpec, data, z0=None):
    """(fn, example_args) lowering ONE shard of the sharded epoch on a
    single (possibly absent) device: collectives are replaced by the
    shape-faithful :class:`_SimCollectives` and all inputs are shrunk to
    their local tile per :func:`consensus_state_specs`. Used by
    benchmarks/kernels_bench.py to measure per-shard HBM bytes — the
    mesh may be an ``AbstractMesh``, nothing is executed. ``z0`` (shape
    structs suffice) is required for TreeSpace, which has no default
    initial value."""
    from .space import init_consensus_state
    space = spec.space
    mesh = space.mesh
    Nl, Ml = _local_sizes(spec)
    space_l = _local_space(spec, Nl)
    coll = _SimCollectives(num_workers(mesh),
                           model_axis_size(mesh) if _splits_model(space)
                           else 1)

    if z0 is None:
        state = jax.eval_shape(lambda: init_consensus_state(spec))
    else:
        state = jax.eval_shape(lambda p: init_consensus_state(spec, p), z0)
    sspecs = consensus_state_specs(spec, state)

    def shrink(sds, pspec):
        shape = list(sds.shape)
        for i, entry in enumerate(pspec):
            if entry is None:
                continue
            axes = entry if isinstance(entry, tuple) else (entry,)
            for a in axes:
                shape[i] //= mesh.shape[a]
        return jax.ShapeDtypeStruct(tuple(shape), sds.dtype)

    local_state = jax.tree.map(shrink, state, sspecs,
                               is_leaf=lambda v: isinstance(v, P))
    local_data = jax.tree.map(shrink, data, consensus_data_specs(spec, data),
                              is_leaf=lambda v: isinstance(v, P))

    def fn(st, d, e, r):
        return _epoch_body(spec, space_l, coll, Nl, Ml, st, d, e, r)

    return fn, (local_state, local_data,
                jax.ShapeDtypeStruct(spec.edge.shape, spec.edge.dtype),
                jax.ShapeDtypeStruct(spec.rho_vec.shape, spec.rho_vec.dtype))
