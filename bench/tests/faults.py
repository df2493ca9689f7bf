"""Faults planted under the timed path, for the tests that see
``correct`` come out false, and the same faults planted in the plain
reference put in the program's place, for readings at a cell's own size
(``bench/tools/calibrate.py --faults``). Each takes a ``setattr``-like
function (pytest's ``monkeypatch.setattr``, or the builtin in a child
process).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

import reference
from repro import api
from repro.core import sharded, space
from repro.ps import engine


def state_unchanged(patch=setattr):
    """The step hands its state back as it came, the counter aside; a PS
    block commit hands back the block as it was."""
    def epoch(spec, state, data):
        _, info = orig(spec, state, data)
        return state._replace(t=state.t + 1), info
    orig = api.asybadmm_epoch
    patch(api, "asybadmm_epoch", epoch)
    patch(engine.SpaceEngine, "commit_block",
          lambda self, j, z_content, cache: z_content)


def half_batch(patch=setattr):
    """Each worker differentiates the first half of its batch, the mean
    taken over those rows."""
    orig = space.subsample_worker_data

    def subsample(rng, data, fraction):
        return jax.tree.map(lambda a: a[:, :a.shape[1] // 2],
                            orig(rng, data, fraction))
    patch(space, "subsample_worker_data", subsample)


def no_exchange(patch=setattr):
    """The psum over the data axes is left out: each block server sees
    only its own data shard's workers."""
    patch(sharded._MeshCollectives, "psum_data", lambda self, x: x)


def no_model_exchange(patch=setattr):
    """The all_to_all over the model axis is left out: each shard
    regroups its own rows where it would receive its peers' (z~ on the
    way to the split gradient, the gradients on the way back)."""
    def local(self, x, split_axis, concat_axis):
        parts = jnp.split(x, self.mesh.shape["model"], axis=split_axis)
        return jnp.concatenate(parts, axis=concat_axis)
    patch(sharded._MeshCollectives, "all_to_all_model", local)


def _flip_largest(z):
    flat = z.reshape(-1)
    k = jnp.argmax(jnp.abs(flat))
    return flat.at[k].set(-flat[k]).reshape(z.shape)


def answer_altered(patch=setattr):
    """The committed z has its largest entry negated where it is made."""
    fused = space._PackedOps.server_consensus_update
    prox = space._PackedOps.server_prox
    patch(space._PackedOps, "server_consensus_update",
          lambda self, *a: _flip_largest(fused(self, *a)))
    patch(space._PackedOps, "server_prox",
          lambda self, *a: _flip_largest(prox(self, *a)))


FAULTS = {f.__name__: f for f in (state_unchanged, half_batch, no_exchange,
                                  no_model_exchange, answer_altered)}


# --- the same faults in the reference, at a cell's own size -------------

def ref_half_batch(cfg, patch=setattr):
    """Each worker's gradient and loss over the first half of its batch."""
    orig = reference._grads

    def grads(p, z_t, rows, dtype, mesh):
        rows = tuple(a[:, :a.shape[1] // 2] for a in rows)
        return orig(p, z_t, rows, dtype, mesh)
    patch(reference, "_grads", grads)


def ref_no_exchange(cfg, patch=setattr):
    """Each block server sums the w of the first data shard's workers."""
    n = cfg["num_workers"] // cfg["mesh"]["data"]
    patch(reference, "_push", lambda p, w: jnp.sum(w[:n], axis=0))


def ref_no_model_exchange(cfg, patch=setattr):
    """The gradients of the blocks held past the first model shard come
    back from the neighbouring worker."""
    orig = reference._grads
    m = cfg["num_blocks"] // cfg["mesh"]["model"]

    def grads(p, z_t, rows, dtype, mesh):
        losses, g = orig(p, z_t, rows, dtype, mesh)
        split = m * p.block_len
        return losses, jnp.concatenate(
            [g[:, :split], jnp.roll(g[:, split:], 1, axis=0)], axis=1)
    patch(reference, "_grads", grads)


REFERENCE_FAULTS = {f.__name__[4:]: f for f in (
    ref_half_batch, ref_no_exchange, ref_no_model_exchange)}


def unchanged(ref):
    """The trajectory of a step that hands its state back unchanged:
    z and w stay at their start, 0, and every epoch's loss is the
    first's (z~ = 0)."""
    return [(None if z is None else 0 * z, ref[0][1],
             None if w is None else 0 * w) for z, _, w in ref]
