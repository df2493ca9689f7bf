"""SpaceEngine — the PS runtime's bridge to the real `VariableSpace`.

The runtime's workers and servers execute the SAME jitted hot-path ops
the vectorized epoch runs — ``worker_grads`` + ``worker_select_update``
on the worker side, ``server_consensus_update`` on the server side —
so the jnp and pallas backends both execute under the event-driven
runtime, and a recorded trace replays through ``asybadmm_epoch``
(structurally exact; bitwise on pallas, fp32-ulp cross-program XLA
fusion on jnp). Exactness rests on two verified properties of those
ops:

* **row locality** — every worker-side op is row-independent over the
  leading worker axis, so calling it at the epoch's FULL (N, ...)
  shape with only worker i's row live (zeros elsewhere) yields worker
  i's row bit-identical to the epoch's batched call (a per-worker
  N=1 vmap would NOT: XLA batched-matmul accumulation differs across
  batch sizes);
* **column locality** — the server reduce+prox on a single block's
  (N, 1, dblk) column equals that block's column of the full-grid
  call, so lock-free per-block commits are exact.

The engine also owns the epoch's per-round rng chain (delay key burned,
selection/minibatch keys consumed), the block split/join of the
consensus representation, and per-block caches — everything numeric;
the runtime modules own only *time*.

Both spaces arrive here in the canonical packed block representation
(z is an (M, dblk) table, worker bundles (N, M, dblk) — TreeSpace
lowers its leaves onto it via ``core.blocks.BlockLayout``), so block j
of EVERY space is row j: the lock domains' block ids, the per-block
caches and the column-local commits are one code path, and pytree
models run under ``lockfree``/``locked`` identically to flat ones.
"""
from __future__ import annotations

import dataclasses
from typing import List, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..core.space import (BLOCK_SELECTORS, ConsensusSpec, SelectorContext,
                          epoch_keys)
from ..core.async_sim import subsample_worker_data
from ..obs.profile import host_span, stage


class SpaceEngine:
    """Numeric services for one :class:`ConsensusSpec`."""

    def __init__(self, spec: ConsensusSpec):
        space = spec.space
        if getattr(space, "mesh", None) is not None:
            # the runtime IS the distribution model; numerics run local
            space = dataclasses.replace(space, mesh=None)
        self.spec = spec
        self.space = space
        self.N = space.num_workers
        self.M = space.num_blocks
        self.edge = np.asarray(spec.edge, bool)
        self.rho_sum = jnp.sum(
            jnp.where(spec.edge, spec.rho_vec[:, None], 0.0), axis=0)
        # epoch rng chain: (r_delay, r_sel, r_batch) per round — the
        # delay key is burned unused (the runtime's delays are OBSERVED,
        # not drawn), which keeps the chain identical to a TraceDelay
        # replay, where sample() ignores the same key
        self._rng = jax.random.PRNGKey(spec.seed)
        self._keys: List[Tuple] = []
        self._sel_cache = {}               # t -> (N, M) bool, grad-free only
        self._jit_cache = {}

    # ------------------------------------------------------------------
    # rng chain + selection + minibatch
    # ------------------------------------------------------------------
    def keys(self, t: int) -> Tuple:
        while len(self._keys) <= t:
            nxt, r_delay, r_sel, r_batch = epoch_keys(
                self._rng, self.spec.minibatch)
            self._rng = nxt
            self._keys.append((r_delay, r_sel, r_batch))
        return self._keys[t]

    def needs_grads_for_select(self) -> bool:
        """Whether the selector must see real gradient norms. The
        built-in ``random``/``cyclic`` policies are known gradient-free,
        as is any selector carrying a truthy ``gradient_free`` attribute
        (the ``zipf`` family sets it — ``make_zipf_selector`` returns
        fresh closures, so identity against the registry can't cover
        them); everything else (gauss_southwell, custom registrations)
        is conservatively fed worker i's true grad_sqnorm row — the
        runtime evaluates the selector at full (N, M) shape with only
        that row live, so any selector whose row i depends only on row
        i of grad_sqnorm replays exactly."""
        sel = self.spec.selector
        if getattr(sel, "gradient_free", False):
            return False
        return sel not in (BLOCK_SELECTORS.get("random"),
                           BLOCK_SELECTORS.get("cyclic"))

    def select(self, t: int, i: int, gnorm_row) -> np.ndarray:
        """Worker i's round-t block selection — the epoch's selector
        evaluated on the epoch's r_sel key; returns a bool (M,) row.
        Gradient-free selectors depend only on (key, t), so their full
        (N, M) matrix is computed once per round and served row-wise."""
        if gnorm_row is None:
            cached = self._sel_cache.get(t)
            if cached is None:
                cached = self._sel_cache[t] = self._select_full(t, None, 0)
            return cached[i]
        return self._select_full(t, gnorm_row, i)[i]

    def _select_full(self, t: int, gnorm_row, i: int) -> np.ndarray:
        fn = self._jit("sel", self._build_sel)
        buf = jnp.zeros((self.N, self.M), jnp.float32)
        if gnorm_row is not None:
            buf = buf.at[i].set(jnp.asarray(gnorm_row, jnp.float32))
        out = fn(self.keys(t)[1], jnp.asarray(t, jnp.int32), buf)
        with host_span("ps.sync.select", round=t):
            return np.asarray(out)

    def _build_sel(self):
        spec = self.spec

        def sel_fn(key, t, gnorm_buf):
            ctx = SelectorContext(rng=key, edge=spec.edge, t=t,
                                  block_fraction=spec.block_fraction,
                                  grad_sqnorm=lambda: gnorm_buf)
            with stage("asybadmm.select"):
                return spec.selector(ctx)
        return jax.jit(sel_fn)

    def round_data(self, t: int, data):
        """The round-t (possibly minibatched) full-N data — the same
        subsample the epoch's ``worker_grads(minibatch=, rng=)`` draws."""
        if self.spec.minibatch is None:
            return data
        return subsample_worker_data(self.keys(t)[2], data,
                                     self.spec.minibatch)

    # ------------------------------------------------------------------
    # state construction
    # ------------------------------------------------------------------
    def init(self, z0=None):
        """(z0_repr, y, w_cache, x) — Algorithm 1 lines 1-2, the same
        init as ``init_consensus_state`` minus the ring buffer (the
        servers' version lists play that role)."""
        space, spec = self.space, self.spec
        z0r = space.init_repr(z0)
        y = space.zeros_workers(z0r)
        w = space.workers_scaled(z0r, spec.rho_vec)
        x = space.broadcast_workers(z0r) if spec.track_x else ()
        return z0r, y, w, x

    # ------------------------------------------------------------------
    # block split / join of the packed consensus representation
    # ------------------------------------------------------------------
    def split_blocks(self, z) -> list:
        """Packed z (M, dblk) -> per-block contents ((dblk,) rows —
        block j of either space IS row j of the packed table)."""
        return [z[j] for j in range(self.M)]

    def join_blocks(self, contents: list):
        """Per-block (dblk,) rows -> packed z (M, dblk)."""
        return jnp.stack(contents)

    # ------------------------------------------------------------------
    # worker side — epoch-shaped calls with one live row
    # ------------------------------------------------------------------
    def z_tilde_buffer(self, i: int, contents: list):
        """Embed worker i's mixed-version pull (per-block contents) as
        row i of an otherwise-zero full (N, ...) z~ bundle."""
        z_row = self.join_blocks(contents)
        fn = self._jit("embed", self._build_embed)
        return fn(z_row, jnp.asarray(i, jnp.int32))

    def _build_embed(self):
        N = self.N

        def embed(z_row, i):
            with stage("asybadmm.gather"):
                return jax.tree.map(
                    lambda zl: jnp.zeros((N,) + zl.shape,
                                         zl.dtype).at[i].set(zl),
                    z_row)
        return jax.jit(embed)

    def grads(self, z_buf, data):
        """THE epoch gradient call (full-N ``space.worker_grads``) plus
        per-block sq-norms; rows other than the live one are garbage."""
        fn = self._jit("grads", self._build_grads)
        return fn(z_buf, data)

    def _build_grads(self):
        spec, space = self.spec, self.space

        def g(z_buf, data):
            with stage("asybadmm.grad"):
                losses, grad = space.worker_grads(spec.loss_fn, z_buf, data)
                return losses, grad, space.grad_sqnorm(grad)
        return jax.jit(g)

    def update(self, i: int, g_buf, zt_buf, y, w, x, sel_row):
        """THE epoch worker update (full-N ``worker_select_update``)
        with only row i's selection live; merges row i of the outputs
        back into the (y, w, x) stores and returns the new stores."""
        fn = self._jit("update", self._build_update)
        sel_buf = jnp.zeros((self.N, self.M), bool).at[i].set(
            jnp.asarray(sel_row, bool))
        return fn(g_buf, zt_buf, y, w, x, sel_buf, jnp.asarray(i, jnp.int32))

    def _build_update(self):
        spec, space = self.spec, self.space

        def upd(g_buf, zt_buf, y, w, x, sel_buf, i):
            with stage("asybadmm.worker_update"):
                y2, w2, x2 = space.worker_select_update(
                    g_buf, y, zt_buf, w, x, sel_buf, spec.rho_vec,
                    spec.track_x)
                merge = lambda store, out: jax.tree.map(
                    lambda s, o: s.at[i].set(o[i]), store, out)
                return merge(y, y2), merge(w, w2), (
                    merge(x, x2) if spec.track_x else x)
        return jax.jit(upd)

    # ------------------------------------------------------------------
    # server side — per-block caches + commits
    # ------------------------------------------------------------------
    def block_cache(self, w_store, j: int):
        """Block j's server-side stale-w~ cache: column j of the packed
        (N, M, dblk) bundle, an (N, dblk) slab."""
        return w_store[:, j]

    def push_value(self, w_store, i: int, j: int):
        """Worker i's fresh w for block j (what a push carries)."""
        return w_store[i, j]

    def apply_push(self, cache, i: int, value):
        """Overwrite worker i's row of a block cache with a pushed w."""
        return cache.at[i].set(value)

    def commit_block(self, j: int, z_content, cache):
        """Block j's server update (13) — the REAL jitted
        ``server_consensus_update`` on the block's column (exact vs the
        full-grid epoch call; see module docstring). ONE compilation
        serves every block of either space — all columns share the
        packed (N, dblk) shape."""
        fn = self._jit("commit", self._build_commit)
        return fn(z_content, cache, jnp.asarray(self.edge[:, j:j + 1]),
                  self.rho_sum[j:j + 1])

    def _build_commit(self):
        spec, space = self.spec, self.space

        def commit(z_col, w_col, e_col, rs):
            with stage("asybadmm.server_update"):
                out = space.server_consensus_update(
                    z_col[None], w_col[:, None, :], e_col, rs,
                    spec.gamma, spec.reg)
                return out[0]
        return jax.jit(commit)

    # ------------------------------------------------------------------
    def _jit(self, key, builder):
        fn = self._jit_cache.get(key)
        if fn is None:
            fn = self._jit_cache[key] = builder()
        return fn
