"""`VariableSpace` — one abstraction over flat-vector and pytree AsyBADMM.

The paper's Algorithm 1 is representation-agnostic: it needs a consensus
variable split into M blocks, a bounded-staleness history per block, a
per-(worker, block) edge set E, and elementwise worker/server updates.
This module owns those mechanics once, behind two interchangeable
implementations:

* ``FlatSpace``  — the decision variable is a flat vector, blocked by
  :class:`~repro.core.blocks.FlatBlocks` (the paper's own workloads:
  sparse logistic regression, eq. 22);
* ``TreeSpace``  — the decision variable is a params pytree, leaves
  assigned to logical blocks by :class:`~repro.core.blocks.TreeBlocks`
  and *lowered* onto the same packed (M, dblk) block table via
  :class:`~repro.core.blocks.BlockLayout` (consensus training of
  transformers). Both spaces share one block-server code path
  (:class:`_PackedOps`); only the user-representation codec differs.

On top of the space sit two pluggable policies:

* **block selection** (Alg. 1 line 4) — a registry shared by both modes:
  ``random`` (Gumbel top-k over the edge neighborhood), ``cyclic``
  (Gauss-Seidel sweep), ``gauss_southwell`` (largest gradient-norm
  blocks) [Hong et al. 2016b];
* **delay model** (Assumption 3) — how per-(i, j) staleness is drawn;
  ``UniformDelay`` reproduces the seed's U{0..D} semantics and
  ``ConstantDelay`` pins a worst-case lag.

``asybadmm_epoch`` is the single generic implementation of one epoch of
Algorithm 1 (all workers + all servers); the flat driver
(``core/consensus.py``), the pytree trainer (``training/trainer.py``)
and the user-facing ``repro.api.ConsensusSession`` are all thin
adapters over it.

Each space carries a **compute backend** for the epoch's elementwise
hot path (``backend="jnp" | "pallas"``, resolved from ``"auto"`` by
:func:`resolve_backend`):

* ``jnp``    — the pure-jnp reference composition (worker update, three
  sel-masked merges, edge-masked reduce, prox);
* ``pallas`` — the fused kernels in ``kernels/admm_update.py`` /
  ``kernels/prox_update.py``: ONE pass over the (N, M, dblk) worker
  bundles for update (11)(12)(9) + the select writes, and a server
  kernel that reduces over workers inside the grid so ``w_sum`` never
  materializes in HBM. Off-TPU the kernels run in interpret mode
  (validation); proxes outside the l1+box family fall back to jnp.

Each space also optionally carries a **mesh** (``mesh=`` on
``ADMMConfig`` / ``ConsensusSession`` / :func:`make_spec`): when set,
``asybadmm_epoch`` dispatches to the SPMD-sharded implementation in
``core/sharded.py`` — worker state sharded over the ``data`` axes,
block servers (both spaces — the packed (M, dblk) table) sharded over
``model``, the paper's w push lowered to a ``psum`` that lands in each
block server's local shard. See ``core/sharded.py`` and API.md's
support matrix.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable, Dict, NamedTuple, Optional, Protocol, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..kernels import ops as kernel_ops
from ..obs.profile import stage
from .admm import server_update, worker_update
from .async_sim import (gather_delayed, push_history, sample_delays,
                        select_blocks, subsample_worker_data)
from .blocks import FlatBlocks, TreeBlocks
from .prox import Regularizer, make_prox


# ---------------------------------------------------------------------------
# compute backends (the epoch's elementwise hot path)
# ---------------------------------------------------------------------------

BACKENDS = ("jnp", "pallas", "pallas_stub")


def resolve_backend(backend: Optional[str] = None) -> str:
    """Resolve a space compute backend name.

    ``"auto"``/None picks ``pallas`` on TPU (compiled Mosaic kernels)
    and ``jnp`` everywhere else. An explicit ``"pallas"`` off-TPU runs
    the same kernels in interpret mode (jnp-parity validation — pinned
    by tests/test_backend_parity.py). ``"pallas_stub"`` is internal:
    the fused ops lower as single opaque boundary ops so
    ``analysis/hlo_cost.py`` can charge them exactly their
    operand+result HBM traffic (used by benchmarks/kernels_bench.py).
    """
    if backend in (None, "auto"):
        return "pallas" if jax.default_backend() == "tpu" else "jnp"
    if backend not in BACKENDS:
        raise ValueError(f"unknown backend {backend!r}; "
                         f"expected one of jnp | pallas | auto")
    return backend


# ---------------------------------------------------------------------------
# delay models (Assumption 3 hook)
# ---------------------------------------------------------------------------

class DelayModel(Protocol):
    """How per-(worker, block) staleness tau_ij is drawn each epoch."""

    @property
    def depth(self) -> int:
        """Ring-buffer depth the history must keep (max delay + 1)."""

    def sample(self, rng: jax.Array, n_workers: int, n_blocks: int,
               *, t=None) -> jax.Array:
        """Return (N, M) int32 delays in [0, depth). ``t`` is the epoch
        counter — stochastic models ignore it, :class:`TraceDelay`
        indexes its recorded trace with it."""


def sample_delay_model(dm, rng, n_workers: int, n_blocks: int, t):
    """Call ``dm.sample`` passing the epoch counter, tolerating older
    custom models whose ``sample`` signature predates the ``t=``
    keyword (detected by signature inspection, so a TypeError raised
    INSIDE a t-aware model still surfaces)."""
    import inspect
    try:
        params = inspect.signature(dm.sample).parameters
        has_t = "t" in params or any(
            p.kind is inspect.Parameter.VAR_KEYWORD
            for p in params.values())
    except (TypeError, ValueError):        # builtins/partials: assume new
        has_t = True
    if has_t:
        return dm.sample(rng, n_workers, n_blocks, t=t)
    return dm.sample(rng, n_workers, n_blocks)


def participation_mask_for(dm, t) -> Optional[jax.Array]:
    """(N, 1) bool participation mask for epoch ``t``, or None when the
    delay model has no notion of partial participation (every model but
    :class:`TraceDelay` with recorded absences). Shared by the
    single-device and SPMD epochs so both apply the identical
    ``sel & mask`` contraction."""
    fn = getattr(dm, "participation_mask", None)
    return fn(t) if fn is not None else None


@dataclasses.dataclass(frozen=True)
class UniformDelay:
    """tau_ij ~ U{0..max_delay} i.i.d. per epoch — the seed's semantics."""
    max_delay: int

    @property
    def depth(self) -> int:
        return self.max_delay + 1

    def sample(self, rng, n_workers, n_blocks, *, t=None):
        return sample_delays(rng, n_workers, n_blocks, self.max_delay)


@dataclasses.dataclass(frozen=True)
class ConstantDelay:
    """Every read is exactly ``delay`` epochs stale (worst-case lag)."""
    delay: int

    @property
    def depth(self) -> int:
        return self.delay + 1

    def sample(self, rng, n_workers, n_blocks, *, t=None):
        return jnp.full((n_workers, n_blocks), self.delay, jnp.int32)


@dataclasses.dataclass(frozen=True)
class ParetoDelay:
    """Heavy-tailed straggler staleness, clipped at the history depth:

        tau_ij = clip(floor(Pareto(alpha, x_m=1)) - 1, 0, max_delay)

    Most reads are fresh, but a Pareto tail of (worker, block) pairs
    lags by the full bounded-delay window — the realistic cluster
    profile behind the paper's Table-1 speedup story (a few stragglers
    must not stall the block servers). Smaller ``alpha`` = heavier tail
    (alpha <= 1 has infinite mean before clipping); ``alpha ~ 1.1-1.5``
    matches the straggler measurements in the AD-ADMM line of work."""
    max_delay: int
    alpha: float = 1.2

    @property
    def depth(self) -> int:
        return self.max_delay + 1

    def sample(self, rng, n_workers, n_blocks, *, t=None):
        if self.max_delay == 0:
            return jnp.zeros((n_workers, n_blocks), jnp.int32)
        u = jax.random.uniform(rng, (n_workers, n_blocks),
                               minval=jnp.finfo(jnp.float32).tiny, maxval=1.0)
        tau = jnp.floor(u ** (-1.0 / self.alpha)) - 1.0
        return jnp.clip(tau, 0, self.max_delay).astype(jnp.int32)


@dataclasses.dataclass(frozen=True, eq=False)
class TraceDelay:
    """Replay the exact (rounds, N, M) staleness matrix a PS-runtime run
    recorded (``repro.ps.trace.DelayTrace``) through the fast vectorized
    epoch: ``sample`` ignores the rng draw (the key split still happens,
    so the selection chain is untouched) and returns ``delays[t]``.

    Replaying a trace through ``asybadmm_epoch`` reproduces the
    runtime's z trajectory exactly — pinned by tests/test_ps_runtime.py
    for both spaces, both backends, and the SPMD epoch. Epochs past the
    end of the trace clamp to its final round (replays are meant to run
    exactly ``num_rounds`` epochs).

    ``participation`` (optional, (rounds, N) bool) encodes partial
    participation from elastic/chaos runs: where False, worker i was
    absent for round t (crashed, left, or not yet joined) and
    contributed no edge updates. The epoch ANDs the mask into the
    block-selection matrix, so an absent worker's y / w_cache / x rows
    — and its server-cache contribution — stay frozen for that round,
    exactly matching what a dead worker leaves behind on the servers
    (the partial-participation regime of Chang et al.,
    arXiv:1509.02597). Delay entries of absent rows may be recorded as
    -1 (unobserved) and are sanitized to 0 here; they only feed the
    gather for a row whose effect the mask discards.

    Traces from runs with ``server_crash`` faults replay unchanged:
    WAL recovery (``repro.ps.recovery``) rebuilds exactly the
    committed version history, so every (t, tau) pair the trace
    records is a read of the same ``z^{t-tau}`` the epoch computes —
    the recovery gap costs sim time (stalls, retransmissions), never a
    divergent version."""
    delays: Any                       # (rounds, N, M) int array
    participation: Any = None         # (rounds, N) bool, or None = all
    max_delay: int = dataclasses.field(init=False)

    def __post_init__(self):
        d = np.asarray(self.delays, np.int32)
        if d.ndim != 3 or d.shape[0] < 1:
            raise ValueError(f"trace delays must be (rounds, N, M); "
                             f"got shape {d.shape}")
        if self.participation is not None:
            p = np.asarray(self.participation, bool)
            if p.shape != d.shape[:2]:
                raise ValueError(
                    f"participation must be (rounds, N) = {d.shape[:2]}; "
                    f"got shape {p.shape}")
            if d[p].size and d[p].min() < 0:
                raise ValueError("trace contains negative delays for "
                                 "participating (round, worker) entries")
            d = np.where(p[:, :, None], d, 0)
            # normalize full participation to None so fault-free traces
            # trace the exact pre-elasticity epoch graph
            object.__setattr__(self, "participation", None if p.all() else p)
        elif d.min() < 0:
            raise ValueError("trace contains negative delays")
        object.__setattr__(self, "delays", d)
        object.__setattr__(self, "max_delay", int(d.max()))

    @property
    def num_rounds(self) -> int:
        return self.delays.shape[0]

    @property
    def depth(self) -> int:
        return self.max_delay + 1

    @classmethod
    def load(cls, path) -> "TraceDelay":
        from ..ps.trace import DelayTrace      # lazy: ps imports core.space
        return DelayTrace.load(path).to_delay_model()

    def participation_mask(self, t) -> Optional[jax.Array]:
        """(N, 1) bool mask for epoch ``t`` (clamped like ``sample``),
        or None when the trace has full participation — the epoch then
        skips the AND entirely, keeping fault-free replay graphs
        identical to the pre-elasticity ones."""
        if self.participation is None:
            return None
        R = self.participation.shape[0]
        idx = jnp.clip(jnp.asarray(t, jnp.int32), 0, R - 1)
        return jnp.asarray(self.participation)[idx][:, None]

    def sample(self, rng, n_workers, n_blocks, *, t=None):
        if t is None:
            raise ValueError(
                "TraceDelay needs the epoch counter; drive it through "
                "asybadmm_epoch (which passes t=state.t), not directly")
        R, N, M = self.delays.shape
        if (N, M) != (n_workers, n_blocks):
            raise ValueError(
                f"trace was recorded for (N={N}, M={M}) but the epoch "
                f"asks for (N={n_workers}, M={n_blocks})")
        idx = jnp.clip(jnp.asarray(t, jnp.int32), 0, R - 1)
        return jnp.asarray(self.delays)[idx]


DELAY_MODELS = {"uniform": UniformDelay, "constant": ConstantDelay,
                "pareto": ParetoDelay, "trace": TraceDelay}


# ---------------------------------------------------------------------------
# block-selection policies (Alg. 1 line 4) — one registry for both modes
# ---------------------------------------------------------------------------

class SelectorContext(NamedTuple):
    """Everything a selection policy may look at.

    ``grad_sqnorm`` is a thunk returning the (N, M) per-block squared
    gradient norms — only Gauss-Southwell forces it, and XLA dead-code
    eliminates it otherwise.
    """
    rng: jax.Array
    edge: jax.Array              # (N, M) bool
    t: jax.Array                 # () int32 epoch counter
    block_fraction: float
    grad_sqnorm: Callable[[], jax.Array]


BlockSelector = Callable[[SelectorContext], jax.Array]

BLOCK_SELECTORS: Dict[str, BlockSelector] = {}


def register_block_selector(name: str):
    def deco(fn: BlockSelector) -> BlockSelector:
        BLOCK_SELECTORS[name] = fn
        return fn
    return deco


def resolve_block_selector(sel) -> BlockSelector:
    if callable(sel):
        return sel
    try:
        return BLOCK_SELECTORS[sel]
    except KeyError:
        raise ValueError(
            f"unknown block_selection {sel!r}; "
            f"registered: {sorted(BLOCK_SELECTORS)}") from None


@register_block_selector("random")
def random_selector(ctx: SelectorContext) -> jax.Array:
    """Each worker samples ~frac*M blocks uniformly from its neighborhood."""
    return select_blocks(ctx.rng, ctx.edge, ctx.block_fraction)


@register_block_selector("cyclic")
def cyclic_selector(ctx: SelectorContext) -> jax.Array:
    """Gauss-Seidel sweep: every worker updates block (t mod M); workers
    whose edge set misses that block fall back to a random draw."""
    M = ctx.edge.shape[1]
    j = jnp.mod(ctx.t, M)
    sel = jax.nn.one_hot(j, M, dtype=bool)[None, :] & ctx.edge
    fallback = (~jnp.any(sel, axis=1, keepdims=True)
                & select_blocks(ctx.rng, ctx.edge, ctx.block_fraction))
    return sel | fallback


def make_zipf_selector(a: float = 1.1) -> BlockSelector:
    """Hot/cold block skew: each worker still picks ~frac*M blocks from
    its edge neighborhood, but block j is drawn with weight
    ``(j+1)^-a`` — low-index blocks are hot, the tail is cold. This is
    weighted sampling WITHOUT replacement via the Gumbel-top-k trick
    (add log-weights to the Gumbel scores, then take the same top-k the
    uniform selector uses), so determinism and the exact-count property
    carry over from ``random_selector`` unchanged.

    ``a`` is the Zipf exponent: 0 recovers the uniform selector's
    distribution, ~1.1 matches web-style traffic skew, larger values
    concentrate almost all traffic on the first few blocks. Registered
    as ``"zipf"`` with the default exponent; pass
    ``make_zipf_selector(a)`` (or ``ADMMConfig(zipf_a=...)``) to tune."""
    if not np.isfinite(a) or a < 0.0:
        raise ValueError(f"zipf exponent must be finite and >= 0; got {a}")

    def zipf_selector(ctx: SelectorContext) -> jax.Array:
        N, M = ctx.edge.shape
        k = max(1, min(M, int(round(ctx.block_fraction * M))))
        logw = -a * jnp.log(jnp.arange(1, M + 1, dtype=jnp.float32))
        g = jax.random.gumbel(ctx.rng, (N, M)) + logw[None, :]
        scored = jnp.where(ctx.edge, g, -jnp.inf)
        thresh = jax.lax.top_k(scored, k)[0][:, -1:]
        return (scored >= thresh) & ctx.edge

    zipf_selector.gradient_free = True
    return zipf_selector


register_block_selector("zipf")(make_zipf_selector())


@register_block_selector("gauss_southwell")
def gauss_southwell_selector(ctx: SelectorContext) -> jax.Array:
    """Greedy: exactly the top-k blocks by gradient norm within the edge
    set. Ties are broken deterministically toward the lower block index
    (``top_k`` is stable), so the selected count per worker is always
    min(k, |edge row|) — a ``gnorm >= thresh`` test would over-select
    whole tie groups."""
    M = ctx.edge.shape[1]
    gnorm = jnp.where(ctx.edge, ctx.grad_sqnorm(), -jnp.inf)
    k = max(1, min(M, int(round(ctx.block_fraction * M))))
    _, idx = jax.lax.top_k(gnorm, k)
    sel = jnp.any(jax.nn.one_hot(idx, M, dtype=bool), axis=-2)
    return sel & ctx.edge


# ---------------------------------------------------------------------------
# the space protocol and its two implementations
# ---------------------------------------------------------------------------

class VariableSpace(Protocol):
    """Owns the representation-specific mechanics of Algorithm 1.

    Worker bundles (y, w, x, z~, g) carry a leading worker axis N; the
    consensus value z and its ring-buffer history are worker-free. All
    methods must be pure and jit-traceable.
    """
    num_workers: int

    @property
    def num_blocks(self) -> int: ...
    def init_repr(self, z0: Optional[Any]) -> Any: ...
    def to_user(self, z: Any) -> Any: ...
    def init_history(self, z0: Any, depth: int) -> Any: ...
    def current(self, z_hist: Any) -> Any: ...
    def push(self, z_hist: Any, z_new: Any) -> Any: ...
    def gather(self, z_hist: Any, delays: jax.Array) -> Any: ...
    def worker_grads(self, loss_fn, z_tilde, data, minibatch=None,
                     rng=None) -> Tuple[jax.Array, Any]: ...
    def grad_sqnorm(self, g: Any) -> jax.Array: ...
    def worker_update(self, g, y, z_tilde, rho_vec) -> Tuple[Any, Any, Any]: ...
    def select(self, sel: jax.Array, new: Any, old: Any) -> Any: ...
    def worker_select_update(self, g, y, z_tilde, w_cache, x, sel, rho_vec,
                             track_x: bool) -> Tuple[Any, Any, Any]: ...
    def reduce_workers(self, w: Any, edge: jax.Array) -> Any: ...
    def server_update(self, z_cur, w_sum, rho_sum, gamma, prox) -> Any: ...
    def server_consensus_update(self, z_cur, w_cache, edge, rho_sum, gamma,
                                reg) -> Any: ...
    def zeros_workers(self, z0: Any) -> Any: ...
    def broadcast_workers(self, z0: Any) -> Any: ...
    def workers_scaled(self, z0: Any, rho_vec: jax.Array) -> Any: ...
    def worker_leaves(self, bundle: Any) -> list: ...


class _PackedOps:
    """Shared mechanics of the canonical packed block representation.

    Both spaces lower onto the SAME layout: z is an (M, dblk) block
    table, worker bundles are (N, M, dblk) arrays — the Pallas kernels'
    native shape, so the ``pallas`` backend dispatches without reshapes,
    the SPMD epoch shards (N, M) over (data, model), and the PS runtime
    splits block servers on rows. Subclasses supply the *packer* (the
    user-representation codec: :class:`~repro.core.blocks.FlatBlocks`
    for flat vectors, :class:`~repro.core.blocks.BlockLayout` for params
    pytrees) plus ``init_repr``; everything else — history, gather,
    worker/server updates, kernel dispatch — lives here once.

    With ``mesh`` set the epoch runs SPMD: worker bundles shard
    ``(data, model)`` over their leading (N, M) axes, z_hist shards
    ``model`` over M — the kernels then see local (N/data, M/model,
    dblk) tiles (see core/sharded.py)."""

    @property
    def packer(self):
        return self.blocks

    @property
    def num_blocks(self) -> int:
        return self.packer.num_blocks

    def _use_kernels(self) -> bool:
        return self.backend != "jnp"

    def _stub(self) -> bool:
        return self.backend == "pallas_stub"

    def _tile(self, op: str, N: int, M: int, d: int):
        """Static (blk_m, blk_d) for this kernel dispatch from the
        autotuner table ("cached"/"sweep" modes); None -> the kernels'
        heuristics. Shapes are static at trace time, so this is a pure
        host-side lookup — it never enters the jaxpr."""
        if getattr(self, "autotune", "off") == "off":
            return None
        from ..kernels.autotune import lookup_tile
        return lookup_tile(op, N, M, d)

    # ---- representation -------------------------------------------------
    def to_user(self, z):
        return self.packer.from_blocks(z)

    # ---- history --------------------------------------------------------
    def init_history(self, z0, depth):
        return jnp.broadcast_to(z0, (depth,) + z0.shape).copy()

    def current(self, z_hist):
        return z_hist[0]

    def push(self, z_hist, z_new):
        return push_history(z_hist, z_new)

    def gather(self, z_hist, delays):
        return gather_delayed(z_hist, delays)

    # ---- worker side ----------------------------------------------------
    def worker_grads(self, loss_fn, z_tilde, data, minibatch=None, rng=None):
        with stage("asybadmm.minibatch"):
            data = subsample_worker_data(rng, data, minibatch)

        def vg(zb, di):
            with stage("asybadmm.pack"):
                zv = self.packer.from_blocks(zb)
            return jax.value_and_grad(loss_fn)(zv, di)
        losses, g = jax.vmap(vg)(z_tilde, data)
        with stage("asybadmm.pack"):
            return losses, self.packer.to_blocks(g)

    def grad_sqnorm(self, g):
        return jnp.sum(jnp.square(g), axis=-1)

    def worker_update(self, g, y, z_tilde, rho_vec):
        return worker_update(g, y, z_tilde, rho_vec[:, None, None])

    def select(self, sel, new, old):
        return jnp.where(sel[..., None], new, old)

    def worker_select_update(self, g, y, z_tilde, w_cache, x, sel, rho_vec,
                             track_x):
        if self._use_kernels():
            N, M, d = g.shape
            out = kernel_ops.admm_worker_select_update(
                g, y, z_tilde, w_cache, sel, rho_vec,
                x if track_x else None, boundary_stub=self._stub(),
                tile=self._tile("worker_select_update", N, M, d))
            return out if track_x else (out[0], out[1], x)
        x_new, y_new, w_new = self.worker_update(g, y, z_tilde, rho_vec)
        return (self.select(sel, y_new, y),
                self.select(sel, w_new, w_cache),
                self.select(sel, x_new, x) if track_x else x)

    # ---- server side ----------------------------------------------------
    def reduce_workers(self, w, edge):
        return jnp.sum(jnp.where(edge[..., None], w, 0.0), axis=0)

    def server_update(self, z_cur, w_sum, rho_sum, gamma, prox):
        return server_update(z_cur, w_sum, rho_sum[:, None], gamma, prox)

    def server_consensus_update(self, z_cur, w_cache, edge, rho_sum, gamma,
                                reg):
        if self._use_kernels() and getattr(reg, "fusable", False):
            N, M, d = w_cache.shape
            return kernel_ops.server_prox_update(
                z_cur, w_cache, edge, rho_sum, gamma, reg.l1_coef,
                0.0 if reg.clip is None else reg.clip,
                boundary_stub=self._stub(),
                tile=self._tile("server_prox_fused", N, M, d))
        w_sum = self.reduce_workers(w_cache, edge)
        return self.server_update(z_cur, w_sum, rho_sum, gamma, reg.prox)

    def server_prox(self, z_cur, w_sum, rho_sum, gamma, reg):
        """Prox step (13) from an already-reduced w_sum — the SPMD path,
        where the worker reduction is a partial sum + psum over ``data``
        and only the prox remains local to the block-server shard."""
        if self._use_kernels() and getattr(reg, "fusable", False):
            return kernel_ops.prox_consensus(
                z_cur, w_sum, rho_sum, gamma, reg.l1_coef,
                0.0 if reg.clip is None else reg.clip,
                boundary_stub=self._stub())
        return self.server_update(z_cur, w_sum, rho_sum, gamma, reg.prox)

    # ---- state construction --------------------------------------------
    def zeros_workers(self, z0):
        return jnp.zeros((self.num_workers,) + z0.shape)

    def broadcast_workers(self, z0):
        return jnp.broadcast_to(z0, (self.num_workers,) + z0.shape).copy()

    def workers_scaled(self, z0, rho_vec):
        return rho_vec[:, None, None] * jnp.broadcast_to(
            z0, (self.num_workers,) + z0.shape)

    def worker_leaves(self, bundle):
        return [bundle]


@dataclasses.dataclass(frozen=True)
class FlatSpace(_PackedOps):
    """Flat-vector consensus: z is (M, dblk) blocks of a padded vector
    (:class:`~repro.core.blocks.FlatBlocks`); worker bundles are
    (N, M, dblk) arrays. All mechanics come from :class:`_PackedOps`."""
    blocks: FlatBlocks
    num_workers: int
    backend: str = "jnp"
    mesh: Any = None
    autotune: str = "off"

    def init_repr(self, z0):
        if z0 is None:
            return jnp.zeros((self.blocks.num_blocks, self.blocks.block_dim))
        return self.blocks.to_blocks(z0)


@dataclasses.dataclass(frozen=True)
class TreeSpace(_PackedOps):
    """Pytree consensus, LOWERED onto the packed block layout: z is the
    same (M, dblk) block table flat mode uses, built by packing block
    j's leaves into row j (:class:`~repro.core.blocks.BlockLayout`,
    zero-padded, bitwise round-trip). Worker bundles are (N, M, dblk)
    arrays; arithmetic runs in the layout's float32 compute dtype and
    leaves cast back to their stored dtype at ``to_user`` (bf16-safe
    under dryrun). Packing touches only the epoch's boundary (the z~
    unpack / gradient repack inside ``worker_grads``) — the hot path,
    kernels, SPMD sharding, and PS block servers all see the packed
    table, identical to ``FlatSpace``.

    Consequences (vs the pre-layout per-leaf fork):

    * the ``pallas`` backend runs the batched (N, M, dblk) kernels
      natively — no per-leaf (N, 1, leaf) views;
    * with ``mesh`` set, z_hist + prox shard over ``model`` exactly like
      flat block servers (no replicated-z fallback);
    * ``Regularizer.fusable`` is honored once per spec (the shared
      server path), not re-decided per leaf;
    * the PS runtime's lock domains key off the layout's block ids for
      both spaces.
    """
    blocks: TreeBlocks
    num_workers: int
    backend: str = "jnp"
    mesh: Any = None
    autotune: str = "off"
    layout: Any = None                    # BlockLayout (required to run)

    @property
    def packer(self):
        if self.layout is None:
            raise ValueError(
                "TreeSpace needs its packed BlockLayout; build the space "
                "via ConsensusSession.pytree / ADMMTrainer, or pass "
                "layout=make_block_layout(params, blocks)")
        return self.layout

    def init_repr(self, z0):
        if z0 is None:
            raise ValueError("TreeSpace needs an initial params pytree")
        return self.packer.to_blocks(z0)


# ---------------------------------------------------------------------------
# the generic state / spec / epoch
# ---------------------------------------------------------------------------

class ConsensusState(NamedTuple):
    """State of Algorithm 1, shared by both spaces.

    z_hist : bounded-staleness ring buffer, leading axis depth (= D+1),
             index 0 newest;
    y      : per-(worker, block) duals (== -last gradient, appendix 25);
    w_cache: server-side stale w~ cache;
    x      : last primal iterates (kept only when the spec tracks them —
             the stationarity metric needs them; () otherwise);
    t      : epoch counter; rng: PRNG key.
    """
    z_hist: Any
    y: Any
    w_cache: Any
    x: Any
    t: jax.Array
    rng: jax.Array

    @property
    def z_blocks(self):
        """Newest consensus blocks (M, dblk) — the packed table both
        spaces share."""
        return self.z_hist[0]


@dataclasses.dataclass(frozen=True)
class ConsensusSpec:
    """Everything one epoch of Algorithm 1 needs besides state + data."""
    space: Any                         # VariableSpace
    loss_fn: Callable                  # loss_fn(z_user, worker_data) -> scalar
    edge: jax.Array                    # (N, M) bool — the paper's E
    rho_vec: jax.Array                 # (N,) per-worker penalties rho_i
    reg: Regularizer
    gamma: float
    block_fraction: float
    selector: BlockSelector
    delay_model: DelayModel
    track_x: bool = False
    seed: int = 0
    # incremental/stochastic workers (Hong 2014): fraction of each
    # worker's samples drawn fresh per epoch (None/1.0 = full batch)
    minibatch: Optional[float] = None


def epoch_keys(rng, minibatch):
    """The per-epoch key split shared by ``asybadmm_epoch``, the SPMD
    body, and the PS runtime: (next_rng, r_delay, r_sel[, r_batch]).
    The split widens to 4 only when minibatching, so full-batch runs
    keep the pre-minibatch rng chain bit-for-bit."""
    if minibatch is not None:
        return jax.random.split(rng, 4)
    return tuple(jax.random.split(rng, 3)) + (None,)


def make_spec(space, cfg, loss_fn, *, edge=None, rho_scale=None, reg=None,
              selector=None, delay_model=None, track_x=False,
              backend=None, mesh=None, minibatch=None,
              autotune=None) -> ConsensusSpec:
    """Build a ConsensusSpec from an ADMMConfig plus problem structure.

    ``backend`` (jnp | pallas | auto) overrides ``cfg.backend`` and is
    resolved onto the space — the one switch that swaps the epoch's
    elementwise hot path between the jnp composition and the fused
    Pallas kernels.

    ``mesh`` (a jax Mesh, or a preset name for
    ``repro.launch.mesh.resolve_mesh``) overrides ``cfg.mesh`` and is
    resolved onto the space — when set, ``asybadmm_epoch`` runs the
    SPMD-sharded implementation (core/sharded.py) over it.

    ``autotune`` (off | cached | sweep) overrides ``cfg.autotune`` and
    selects the kernel-tile source (kernels/autotune.py). "sweep" runs
    the deterministic tile sweep for this spec's shapes here — eagerly,
    never inside a trace — persists the winners, then dispatches like
    "cached"."""
    from ..kernels.autotune import resolve_autotune
    resolved = resolve_backend(
        backend if backend is not None else getattr(cfg, "backend", "auto"))
    from ..launch.mesh import resolve_mesh           # no cycle: mesh.py is leaf
    resolved_mesh = resolve_mesh(
        mesh if mesh is not None else getattr(cfg, "mesh", None))
    resolved_tune = resolve_autotune(
        autotune if autotune is not None else getattr(cfg, "autotune", "off"))
    if dataclasses.is_dataclass(space):
        updates = {}
        if getattr(space, "backend", None) != resolved:
            updates["backend"] = resolved
        if getattr(space, "mesh", None) is not resolved_mesh \
                and resolved_mesh is not None:
            updates["mesh"] = resolved_mesh
        if getattr(space, "autotune", None) != resolved_tune \
                and hasattr(space, "autotune"):
            updates["autotune"] = resolved_tune
        if updates:
            space = dataclasses.replace(space, **updates)
    if getattr(space, "mesh", None) is not None:
        from .sharded import validate_space_mesh
        validate_space_mesh(space)
    if resolved_tune == "sweep" and getattr(space, "autotune", None) == "sweep":
        if getattr(space, "backend", "jnp") == "pallas":
            from ..kernels.autotune import sweep_for_space
            sweep_for_space(space.num_workers, space.num_blocks,
                            space.packer.block_dim,
                            mesh=getattr(space, "mesh", None))
        # sweep happens once, here; dispatch reads the cached winners
        space = dataclasses.replace(space, autotune="cached")
    N, M = space.num_workers, space.num_blocks
    if edge is None:
        edge = jnp.ones((N, M), bool)
    else:
        edge = jnp.asarray(edge, bool)
    if rho_scale is None:
        rho_vec = jnp.full((N,), cfg.rho)
    else:
        rho_vec = cfg.rho * jnp.asarray(rho_scale)
    if reg is None:
        reg = make_prox(cfg.l1_coef, cfg.clip)
    sel_arg = selector if selector is not None else cfg.block_selection
    if sel_arg == "zipf":
        # honor the config's exponent — the registry entry carries the
        # default a=1.1 only
        sel = make_zipf_selector(getattr(cfg, "zipf_a", 1.1))
    else:
        sel = resolve_block_selector(sel_arg)
    if delay_model is None:
        delay_model = UniformDelay(cfg.max_delay)
    if minibatch is None:
        minibatch = getattr(cfg, "minibatch", None)
    if minibatch is not None:
        if not 0.0 < minibatch <= 1.0:
            raise ValueError(f"minibatch fraction must be in (0, 1]; "
                             f"got {minibatch}")
        if minibatch == 1.0:
            minibatch = None               # full batch — keep the 3-way split
    return ConsensusSpec(space=space, loss_fn=loss_fn, edge=edge,
                         rho_vec=rho_vec, reg=reg, gamma=cfg.gamma,
                         block_fraction=cfg.block_fraction, selector=sel,
                         delay_model=delay_model, track_x=track_x,
                         seed=cfg.seed, minibatch=minibatch)


def init_consensus_state(spec: ConsensusSpec, z0=None) -> ConsensusState:
    """Algorithm 1 lines 1-2 in either space. ``z0`` is in user
    representation (flat vector / params pytree; flat mode defaults to 0)."""
    space = spec.space
    z0r = space.init_repr(z0)
    state = ConsensusState(
        z_hist=space.init_history(z0r, spec.delay_model.depth),
        y=space.zeros_workers(z0r),                       # Alg. 1 line 2
        # w init: w = rho_i * x + y with x = z0, y = 0  ->  rho_i * z0
        w_cache=space.workers_scaled(z0r, spec.rho_vec),
        x=space.broadcast_workers(z0r) if spec.track_x else (),  # line 1
        t=jnp.zeros((), jnp.int32),
        rng=jax.random.PRNGKey(spec.seed),
    )
    mesh = getattr(space, "mesh", None)
    if isinstance(mesh, jax.sharding.Mesh):
        # place every state tensor on its NamedSharding up front so the
        # first sharded epoch starts from the right layout (an
        # AbstractMesh — shape-only analysis — has no devices to put to)
        from .sharded import consensus_state_shardings
        state = jax.device_put(state, consensus_state_shardings(spec, state))
    return state


# Divergence watchdog (debug): when enabled, every epoch checks the
# freshly committed z table for NaN/Inf and halts with the offending
# round + block ids (FloatingPointError from the host callback) instead
# of silently training on garbage. Off by default — the check syncs a
# device->host copy per epoch. The PS runtime has its own per-commit
# flavor (``PSRuntime(check_finite=True)``).
_EPOCH_CHECK_FINITE = False


def set_epoch_check_finite(enabled: bool) -> bool:
    """Toggle the epoch-level NaN/Inf watchdog; returns the previous
    setting (so tests/callers can restore it)."""
    global _EPOCH_CHECK_FINITE
    prev = _EPOCH_CHECK_FINITE
    _EPOCH_CHECK_FINITE = bool(enabled)
    return prev


def _raise_nonfinite(t, bad_blocks) -> None:
    bad = np.asarray(bad_blocks)
    if bad.any():
        blocks = np.nonzero(bad)[0].tolist()
        raise FloatingPointError(
            f"asybadmm_epoch divergence watchdog: the round-{int(t)} z "
            f"update produced NaN/Inf in block(s) {blocks} — the run is "
            f"training on garbage. Check rho / gamma / step sizes; "
            f"disable with set_epoch_check_finite(False).")


def asybadmm_epoch(spec: ConsensusSpec, state: ConsensusState, data
                   ) -> Tuple[ConsensusState, Dict[str, jax.Array]]:
    """One epoch of Algorithm 1 across all workers + servers — THE single
    implementation both the flat driver and the pytree trainer use.

    With a mesh on the space, the same epoch runs SPMD (shard_map over
    (data..., model); see core/sharded.py) — the z trajectory is pinned
    equal to this single-device path by tests/test_spmd_parity.py."""
    space = spec.space
    if getattr(space, "mesh", None) is not None:
        from .sharded import sharded_epoch
        return sharded_epoch(spec, state, data)
    N, M = spec.edge.shape
    rng, r_delay, r_sel, r_batch = epoch_keys(state.rng, spec.minibatch)

    # --- each worker pulls (possibly stale) z~ per block (Assumption 3) ---
    with stage("asybadmm.gather"):
        delays = sample_delay_model(spec.delay_model, r_delay, N, M, state.t)
        z_tilde = space.gather(state.z_hist, delays)

    # --- local gradients at z~ (eq. 5 linearization point), optionally on
    #     a fresh per-worker minibatch (incremental workers, Hong 2014) ---
    with stage("asybadmm.grad"):
        losses, g = space.worker_grads(spec.loss_fn, z_tilde, data,
                                       minibatch=spec.minibatch, rng=r_batch)

    # --- block selection (Alg. 1 line 4) via the shared policy registry ---
    with stage("asybadmm.select"):
        ctx = SelectorContext(rng=r_sel, edge=spec.edge, t=state.t,
                              block_fraction=spec.block_fraction,
                              grad_sqnorm=lambda: space.grad_sqnorm(g))
        sel = spec.selector(ctx)

        # --- partial participation (elastic/chaos replay): absent workers
        #     contribute no edge updates this round — their y/w_cache/x
        #     rows and server-cache contributions stay frozen, matching
        #     what a crashed worker leaves behind on the block servers ---
        pmask = participation_mask_for(spec.delay_model, state.t)
        if pmask is not None:
            sel = sel & pmask

    # --- worker update (11)(12)(9) + the sel-masked merges, one fused
    #     pass over the worker bundles on the pallas backend ---
    with stage("asybadmm.worker_update"):
        y, w_cache, x = space.worker_select_update(
            g, state.y, z_tilde, state.w_cache, state.x, sel, spec.rho_vec,
            spec.track_x)

    # --- server update (13): fresh w for pushers, stale cache otherwise;
    #     pallas fuses the edge-masked reduce into the prox grid ---
    with stage("asybadmm.server_update"):
        rho_sum = jnp.sum(jnp.where(spec.edge, spec.rho_vec[:, None], 0.0),
                          axis=0)                                   # (M,)
        z_new = space.server_consensus_update(
            space.current(state.z_hist), w_cache, spec.edge, rho_sum,
            spec.gamma, spec.reg)

    if _EPOCH_CHECK_FINITE:
        bad = ~jnp.all(jnp.isfinite(z_new.reshape(z_new.shape[0], -1)),
                       axis=1)
        jax.debug.callback(_raise_nonfinite, state.t, bad)

    info = {"loss": jnp.mean(losses),
            "selected_fraction": jnp.mean(sel.astype(jnp.float32))}
    with stage("asybadmm.push"):
        z_hist = space.push(state.z_hist, z_new)
    return ConsensusState(z_hist=z_hist, y=y, w_cache=w_cache, x=x,
                          t=state.t + 1, rng=rng), info


def consensus_residual(spec: ConsensusSpec, state: ConsensusState) -> jax.Array:
    """Cross-worker dispersion of the w cache (0 at consensus) — the
    space-generic analogue of ``ADMMTrainer.consensus_residual``."""
    num = jnp.zeros((), jnp.float32)
    den = jnp.zeros((), jnp.float32)
    for leaf in spec.space.worker_leaves(state.w_cache):
        w32 = leaf.astype(jnp.float32)
        mean = jnp.mean(w32, axis=0, keepdims=True)
        num = num + jnp.sum(jnp.square(w32 - mean))
        den = den + jnp.sum(jnp.square(mean)) * leaf.shape[0]
    return jnp.sqrt(num / jnp.maximum(den, 1e-12))
