"""User-facing builder API for AsyBADMM — one surface over both spaces.

``ConsensusSession`` binds a :class:`~repro.core.space.ConsensusSpec`
(space + policies) to an :class:`~repro.configs.base.ADMMConfig` and
exposes init/step/run. Build one with:

* ``ConsensusSession.flat(...)``   — flat-vector consensus (the paper's
  sparse workloads; fixed per-worker data, optional support/edge set);
* ``ConsensusSession.pytree(...)`` — params-pytree consensus training
  (streaming per-worker batches).

Both modes honor every ``ADMMConfig`` policy — ``block_selection``
(random | cyclic | gauss_southwell, or any callable registered with
``register_block_selector``), heterogeneous ``rho_scale``, bounded-delay
models, and general-form edge sets.

    from repro.api import ConsensusSession, solve

    sess = ConsensusSession.flat(loss_fn, (X, y), dim=512, cfg=cfg,
                                 support=support)
    state, history = sess.run(600, eval_every=100)
    z = sess.z(state)

    # or, one call:
    z, history = solve(loss_fn, (X, y), dim=512, num_epochs=600, cfg=cfg)

See API.md for the migration table from the pre-`VariableSpace` APIs.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from .configs.base import ADMMConfig
from .core.blocks import TreeBlocks, make_block_layout, make_tree_blocks
from .core.consensus import ConsensusProblem, make_problem
from .core.metrics import kkt_violations, stationarity
from .core.space import (ConsensusSpec, ConsensusState, TreeSpace,
                         asybadmm_epoch, consensus_residual,
                         init_consensus_state, make_spec)
from .obs.profile import install_compile_marker


@dataclasses.dataclass(frozen=True)
class ConsensusSession:
    """A configured AsyBADMM run: spec + config (+ fixed data, flat mode).

    spec    : the generic step spec (space, edge, rho_vec, policies);
    cfg     : the ADMMConfig the spec was built from;
    data    : fixed per-worker data (flat mode); ``step`` falls back to
              it when no batch is passed;
    z0      : default initial consensus value in user representation
              (params pytree in pytree mode);
    problem : the flat-mode ConsensusProblem (None in pytree mode) —
              kept so the stationarity/KKT metrics stay available.
    """
    spec: ConsensusSpec
    cfg: ADMMConfig
    data: Any = None
    z0: Any = None
    problem: Optional[ConsensusProblem] = None

    def __post_init__(self):
        # a compile inside a traced window shows on the trace, named
        install_compile_marker()

    # ------------------------------------------------------------------
    # builders
    # ------------------------------------------------------------------
    @staticmethod
    def flat(loss_fn: Callable, data: Any, dim: int,
             cfg: Optional[ADMMConfig] = None, *,
             support: Optional[np.ndarray] = None,
             edge: Optional[Any] = None,
             rho_scale: Optional[Any] = None,
             l1_coef: Optional[float] = None,
             clip: Optional[float] = None,
             l2_coef: float = 0.0,
             selector=None, delay_model=None,
             backend: Optional[str] = None,
             mesh: Any = None,
             autotune: Optional[str] = None) -> "ConsensusSession":
        """Flat-vector consensus over ``dim`` coordinates split into
        ``cfg.num_blocks`` blocks. Regularizer terms default to the
        config's (``cfg.l1_coef`` / ``cfg.clip``); kwargs override.
        ``backend`` (jnp | pallas | auto) overrides ``cfg.backend`` —
        the fused-Pallas vs pure-jnp hot-path switch. ``mesh`` (a jax
        Mesh or a ``launch.mesh.resolve_mesh`` preset name) overrides
        ``cfg.mesh`` — when set, every epoch runs SPMD with workers
        sharded over the ``data`` axes and block servers over ``model``
        (see API.md's support matrix)."""
        cfg = cfg if cfg is not None else ADMMConfig()
        problem = make_problem(
            loss_fn, data, dim=dim, num_blocks=cfg.num_blocks,
            support=support, edge=edge,
            l1_coef=cfg.l1_coef if l1_coef is None else l1_coef,
            clip=cfg.clip if clip is None else clip,
            l2_coef=l2_coef, rho_scale=rho_scale)
        spec = problem.spec(cfg, selector=selector, delay_model=delay_model,
                            backend=backend, mesh=mesh, autotune=autotune)
        if isinstance(spec.space.mesh, jax.sharding.Mesh):
            # the fixed data goes to the devices whose workers read it,
            # once — not resharded from one device every epoch
            from .core.sharded import consensus_data_shardings
            problem = dataclasses.replace(problem, data=jax.device_put(
                problem.data, consensus_data_shardings(spec, problem.data)))
        return ConsensusSession(spec=spec, cfg=cfg, data=problem.data,
                                problem=problem)

    @staticmethod
    def pytree(loss_fn: Callable, params: Any, cfg: Optional[ADMMConfig],
               num_workers: int, *,
               blocks: Optional[TreeBlocks] = None,
               edge: Optional[Any] = None,
               rho_scale: Optional[Any] = None,
               selector=None, delay_model=None,
               backend: Optional[str] = None,
               mesh: Any = None,
               autotune: Optional[str] = None) -> "ConsensusSession":
        """Params-pytree consensus: leaves are balanced into
        ``cfg.num_blocks`` logical blocks (or pass explicit ``blocks``);
        per-worker batches stream in through ``step``/``run``.
        ``backend`` (jnp | pallas | auto) overrides ``cfg.backend``;
        ``mesh`` overrides ``cfg.mesh`` (SPMD epoch: workers over the
        ``data`` axes, packed block servers over ``model`` — pytree
        mode shards z natively since the BlockLayout lowering; see
        API.md's support matrix)."""
        cfg = cfg if cfg is not None else ADMMConfig()
        if blocks is None:
            blocks = make_tree_blocks(params, cfg.num_blocks)
        space = TreeSpace(blocks=blocks, num_workers=num_workers,
                          layout=make_block_layout(params, blocks))
        spec = make_spec(space, cfg, loss_fn, edge=edge, rho_scale=rho_scale,
                         selector=selector, delay_model=delay_model,
                         track_x=False, backend=backend, mesh=mesh,
                         autotune=autotune)
        return ConsensusSession(spec=spec, cfg=cfg, z0=params)

    # ------------------------------------------------------------------
    # driving
    # ------------------------------------------------------------------
    def init(self, z0: Any = None) -> ConsensusState:
        return init_consensus_state(
            self.spec, z0 if z0 is not None else self.z0)

    def step(self, state: ConsensusState, batch: Any = None
             ) -> Tuple[ConsensusState, Dict]:
        """One epoch of Algorithm 1. ``batch`` defaults to the session's
        fixed data (flat mode)."""
        data = batch if batch is not None else self.data
        return asybadmm_epoch(self.spec, state, data)

    def step_fn(self):
        """Jitted (state, batch) -> (state, info); its programs are named
        ``asybadmm_step`` in HLO and traces."""
        spec = self.spec

        def asybadmm_step(state, batch):
            return asybadmm_epoch(spec, state, batch)
        return jax.jit(asybadmm_step)

    def run_ps(self, num_rounds: int, z0: Any = None, *,
               discipline: str = "lockfree",
               timing: Any = None,
               batches: Optional[Callable[[int], Any]] = None,
               compute: str = "real",
               seed: Optional[int] = None,
               record_z: bool = True,
               faults: Any = None,
               transport: Any = None,
               check_finite: bool = False,
               checkpoint_every: Optional[int] = None,
               checkpoint_dir: Optional[str] = None,
               resume_from: Optional[str] = None,
               telemetry: Any = None,
               metrics_every: Optional[int] = None):
        """Drive ``num_rounds`` rounds under the event-driven Parameter
        Server runtime (``repro.ps``) instead of the vectorized epoch:
        per-block ``lockfree`` servers (or the ``locked`` full-vector
        baseline), workers running the real jitted space ops, bounded
        staleness enforced by stalling (Assumption 3's T comes from the
        session's delay model), and every pull recorded into a
        :class:`~repro.ps.trace.DelayTrace`.

        ``timing`` is a :class:`~repro.ps.timing.CostProfile` (service
        times; defaults to unit worker cost). ``compute="timing"``
        skips the numerics for pure coordination studies;
        ``record_z=False`` keeps only the live staleness window of
        committed versions (long-training memory mode — ``z_final``
        still returned, ``z_versions`` not). Returns a
        :class:`~repro.ps.runtime.PSRunResult` (``z_final`` /
        ``z_versions`` in user representation) — replay its trace
        through the fast epoch with
        ``delay_model=result.to_delay_model()``.

        ``faults`` is a :class:`~repro.ps.chaos.FaultPlan` (or a path
        to its JSON) injecting worker crash/rejoin, joins/leaves,
        slowdowns and server commit spikes — the run stays
        deterministic and its trace (staleness + participation) still
        replays through the epoch; see API.md's elastic-PS section.

        ``transport`` is a :class:`~repro.ps.timing.Transport`
        (unreliable network: drop/dup/reorder probabilities +
        ack/retry/backoff) — convenience for setting ``timing.net``
        when no other cost tuning is needed; with every knob at zero it
        is inert (byte-identical to no transport). ``check_finite=True``
        arms the divergence watchdog: the run halts with a
        ``FloatingPointError`` naming the round/block the moment a
        committed z goes NaN/Inf. See API.md's transport-reliability
        section.

        Durability (``repro.ps.recovery``; API.md's "Durability &
        recovery"): ``checkpoint_every=E`` writes an atomic,
        crash-consistent snapshot of the whole runtime into
        ``checkpoint_dir`` every E rounds; ``resume_from=`` (a snapshot
        prefix or the checkpoint directory for its latest) restores one
        and continues mid-stream, with results identical to the
        uninterrupted run — and a ``server_crash`` fault event makes a
        block server lose its volatile state and rebuild it from its
        write-ahead commit log with zero committed folds lost.

        Observability (``repro.obs``; API.md's "Observability"):
        ``telemetry=`` turns the deterministic telemetry layer on —
        pass ``True`` (span tracing only), a ``.jsonl`` path /
        ``"stdout"`` / a callable (per-round record stream), or a
        :class:`~repro.obs.Telemetry` for full control (span tracer +
        sink + Chrome-trace path). Telemetry records in virtual
        sim-time only and never perturbs the schedule: the run's z, fold
        logs and makespan are bitwise identical to ``telemetry=None``.
        ``metrics_every=k`` emits every k-th round's record (plus the
        final round)."""
        import dataclasses as _dc

        from .ps import PSRuntime
        from .ps.chaos import FaultPlan
        from .ps.timing import CostProfile
        if isinstance(faults, (str, bytes)) or hasattr(faults, "__fspath__"):
            faults = FaultPlan.load(faults)
        if transport is not None:
            if timing is not None and timing.net is not None:
                raise ValueError(
                    "pass the Transport either as transport= or as "
                    "timing.net, not both")
            timing = _dc.replace(timing if timing is not None
                                 else CostProfile(), net=transport)
        rt = PSRuntime(self.spec, data=self.data, batches=batches,
                       discipline=discipline, timing=timing,
                       compute=compute, seed=seed, record_z=record_z,
                       faults=faults, check_finite=check_finite,
                       telemetry=telemetry, metrics_every=metrics_every)
        return rt.run(num_rounds, z0=z0 if z0 is not None else self.z0,
                      checkpoint_every=checkpoint_every,
                      checkpoint_dir=checkpoint_dir,
                      resume_from=resume_from)

    def run(self, num_epochs: int, z0: Any = None, *,
            batches: Optional[Callable[[int], Any]] = None,
            eval_every: int = 0,
            eval_fn: Optional[Callable] = None
            ) -> Tuple[ConsensusState, List[Dict]]:
        """Drive ``num_epochs`` epochs. ``batches(t)`` supplies the epoch-t
        per-worker batch (defaults to the fixed data). Eval records carry
        ``loss`` (+ ``objective`` in flat mode) and ``eval_fn(session,
        state)`` extras."""
        state = self.init(z0)
        step = self.step_fn()
        hist: List[Dict] = []
        for t in range(num_epochs):
            data = batches(t) if batches is not None else self.data
            state, info = step(state, data)
            if eval_every and (t + 1) % eval_every == 0:
                rec = {"epoch": t + 1, "loss": float(info["loss"])}
                if self.problem is not None:
                    rec["objective"] = float(
                        self.problem.objective(self.z(state)))
                if eval_fn is not None:
                    rec.update(eval_fn(self, state))
                hist.append(rec)
        return state, hist

    # ------------------------------------------------------------------
    # inspection
    # ------------------------------------------------------------------
    def z(self, state: ConsensusState) -> Any:
        """Newest consensus value in user representation (flat vector /
        params pytree)."""
        space = self.spec.space
        return space.to_user(space.current(state.z_hist))

    def objective(self, state: ConsensusState) -> float:
        if self.problem is None:
            raise ValueError("objective() needs flat mode (fixed data); "
                             "use step()'s info['loss'] in pytree mode")
        return float(self.problem.objective(self.z(state)))

    def consensus_residual(self, state: ConsensusState) -> float:
        """Cross-worker w-cache dispersion (0 at consensus), both modes."""
        return float(consensus_residual(self.spec, state))

    def stationarity(self, state: ConsensusState) -> Dict:
        if self.problem is None:
            raise ValueError("stationarity metrics need flat mode")
        # per-worker rho_i, so heterogeneous rho_scale runs are scored
        # against the Lagrangian they actually optimized
        return stationarity(self.problem, state, self.spec.rho_vec)

    def kkt_violations(self, state: ConsensusState) -> Dict:
        if self.problem is None:
            raise ValueError("KKT metrics need flat mode")
        return kkt_violations(self.problem, state, self.spec.rho_vec)


def solve(loss_fn: Callable, data: Any, dim: int, num_epochs: int = 500,
          cfg: Optional[ADMMConfig] = None, *, eval_every: int = 0,
          z0: Optional[jax.Array] = None, **flat_kwargs
          ) -> Tuple[jax.Array, List[Dict]]:
    """One-call flat solve: build a session, run it, return (z, history).

    ``flat_kwargs`` forward to :meth:`ConsensusSession.flat`
    (support/edge/rho_scale/l1_coef/clip/...).
    """
    sess = ConsensusSession.flat(loss_fn, data, dim, cfg, **flat_kwargs)
    state, hist = sess.run(num_epochs, z0=z0,
                           eval_every=eval_every or num_epochs)
    return sess.z(state), hist
