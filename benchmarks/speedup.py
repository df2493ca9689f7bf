"""Paper Table 1 analogue: speedup of p workers performing k iterations.

We cannot rent 36 EC2 cores, so we reproduce the quantity Table 1
actually measures — the scalability of the *coordination scheme* —
with the event-driven Parameter Server runtime (``repro.ps``). This
module is now a thin client of that subsystem: the lock domains, push
queues, bounded-staleness stalls and makespan accounting all live in
``repro.ps``; here we only

* measure the real per-event costs — one worker iteration and one
  block-server commit of the REAL jitted ``VariableSpace`` hot path
  (``repro.ps.timing.measure_costs``; the hand-rolled loss_fn /
  server_update measurement this file used to carry is gone);
* feed them to the scheduler as service times (lognormal jitter, the
  EC2 stragglers Assumption 3 exists for) and sweep workers x
  {lockfree, locked} through ONE code path (``PSRuntime`` in
  timing-only mode);
* report ``T_k(p)`` = makespan until k total iterations commit,
  work-shared by p workers, and ``Speedup_p = T_k(1) / T_k(p)``.

``--smoke`` (CI, via scripts/ci.sh) additionally runs a DETERMINISTIC
locked-vs-lockfree comparison at 8 workers — constant service times in
a coordination-bound regime (worker compute pinned to 4 block-serve
units, M=16, so the full-vector lock's M-serial commit dominates) —
and gates the lockfree/locked makespan ratio against
``min_lockfree_speedup_x8`` in benchmarks/kernels_baseline.json.

``--scenario`` runs the elastic-PS chaos studies instead of Table 1:

* ``churn``      — 8-worker REAL-compute run under per-push commits
  with a deterministic crash+rejoin plan (``FaultPlan.churn``):
  replays the chaos trace through the vectorized epoch (single device,
  and the SPMD (data=4, model=2) mesh when 8 devices are up) and gates
  rounds-to-tolerance chaos/fault-free vs ``max_churn_rounds_ratio``;
* ``lossy``      — 8-worker REAL-compute run over an unreliable
  transport (5% drop / 2% dup / 10% reorder, ack+retry reliability):
  gates rounds-to-tolerance lossy/reliable vs
  ``max_lossy_rounds_ratio`` and replay parity of the lossy trace;
* ``skew``       — timing-only zipf vs uniform block selection: hot
  head blocks pile onto few lock domains (queue-occupancy spread,
  gated vs ``min_skew_occupancy_ratio``);
* ``heavy_tail`` — Pareto worker compute (the EC2 straggler tail):
  stall-time concentration under lockfree vs per_push commits (gated
  vs ``min_heavy_tail_stall``).

All scenarios print the per-worker stall-time and per-domain queue
occupancy histograms from ``PSRunResult.metrics["histograms"]``.

CSV columns: name, us_per_call (simulated makespan), derived (speedup).
"""
import argparse
import json
import pathlib

import numpy as np

from repro.api import ConsensusSession
from repro.configs.base import ADMMConfig
from repro.data import make_sparse_logreg
from repro.ps import (ConstantService, CostProfile, FaultPlan,
                      LognormalService, ParetoService, PSRuntime,
                      measure_costs)

K_ITERS = 320
WORKERS = [1, 4, 8, 16, 32]
M_BLOCKS = 16
GATE_WORKERS = 8
GATE_ROUNDS = 12
BASELINE = pathlib.Path(__file__).parent / "kernels_baseline.json"
CHURN_DIM = M_BLOCKS * 16


def build_session(num_workers: int, dim: int = 2048, samples: int = 64,
                  seed: int = 0, *, block_selection: str = "random",
                  zipf_a: float = 1.1, delay_model=None,
                  mesh=None) -> ConsensusSession:
    """The paper's sparse-logreg workload (eq. 22) on the unified API."""
    import jax.numpy as jnp

    data = make_sparse_logreg(num_workers=num_workers,
                              samples_per_worker=samples, dim=dim,
                              density=0.1, seed=seed)

    def loss_fn(z, d):
        X, y = d
        return jnp.mean(jnp.log1p(jnp.exp(-y * (X @ z))))

    cfg = ADMMConfig(rho=2.0, gamma=0.1, max_delay=2, block_fraction=0.5,
                     num_blocks=M_BLOCKS, l1_coef=1e-3, clip=1e4, seed=seed,
                     block_selection=block_selection, zipf_a=zipf_a)
    return ConsensusSession.flat(
        loss_fn, (jnp.asarray(data.X), jnp.asarray(data.y)), dim=dim,
        cfg=cfg, delay_model=delay_model, mesh=mesh)


def measured_costs(dim: int = 2048, samples: int = 64) -> dict:
    """Real measured costs of one worker iteration and one z-block
    commit, timed on the unified jitted hot path."""
    sess = build_session(1, dim=dim, samples=samples)
    return measure_costs(sess.spec, sess.data)


def makespan(p: int, k_total: int, timing: CostProfile,
             discipline: str) -> float:
    """Event-driven makespan until k_total iterations commit, the work
    shared by p workers (ceil-split like the paper's fixed-k runs)."""
    rounds = -(-k_total // p)
    sess = build_session(p, dim=M_BLOCKS * 16, samples=4)
    rt = PSRuntime(sess.spec, discipline=discipline, timing=timing,
                   compute="timing")
    return rt.run(rounds).makespan


def table1(emit, costs: dict, workers=WORKERS, k_iters=K_ITERS,
           jitter: float = 0.3) -> None:
    for discipline in ("lockfree", "locked"):
        timing = CostProfile(
            t_worker=LognormalService(costs["t_worker"], jitter),
            t_server_block=LognormalService(costs["t_server_block"],
                                            jitter / 2))
        base = makespan(1, k_iters, timing, discipline)
        for p in workers:
            tk = base if p == 1 else makespan(p, k_iters, timing, discipline)
            emit(f"table1_{discipline}_p{p},{tk*1e6:.0f},"
                 f"speedup={base / tk:.2f}")


def smoke_gate(emit, costs: dict) -> bool:
    """Deterministic coordination-bound comparison at 8 workers:
    constant service, worker compute = 4 block-serve units. The only
    difference between the two runs is the lock discipline, so the
    makespan ratio isolates exactly the paper's §1 claim (block-wise
    servers beat the full-vector lock). Gated vs the baseline."""
    ts = costs["t_server_block"]
    timing = CostProfile(t_worker=ConstantService(4.0 * ts),
                         t_server_block=ConstantService(ts))
    spans = {d: makespan(GATE_WORKERS, GATE_WORKERS * GATE_ROUNDS, timing, d)
             for d in ("lockfree", "locked")}
    ratio = spans["locked"] / spans["lockfree"]
    min_ratio = json.loads(BASELINE.read_text())["min_lockfree_speedup_x8"]
    ok = ratio >= min_ratio
    emit(f"speedup_gate_lockfree_x{GATE_WORKERS},"
         f"{spans['lockfree']*1e6:.0f},ratio={ratio:.2f}")
    emit(f"speedup_gate_locked_x{GATE_WORKERS},"
         f"{spans['locked']*1e6:.0f},min_ratio={min_ratio}")
    if not ok:
        emit(f"speedup_gate_FAILED,0,locked/lockfree ratio {ratio:.2f} < "
             f"{min_ratio}")
    return ok


# ---------------------------------------------------------------------------
# elastic-PS chaos scenarios (--scenario churn | skew | heavy_tail)
# ---------------------------------------------------------------------------

def _emit_hist(emit, name: str, hist: dict) -> None:
    """One histogram as a CSV row: total count, then edge:count bins."""
    bins = "|".join(f"{hist['edges'][i]:.3g}:{c}"
                    for i, c in enumerate(hist["counts"]))
    emit(f"{name},{sum(hist['counts'])},bins={bins}")


def _rounds_to_tolerance(losses, tol: float):
    for t, loss in enumerate(losses):
        if np.isfinite(loss) and loss <= tol:
            return t + 1
    return None


def _replay_max_err(res, sess) -> float:
    """Max |z_replay - z_runtime| over all rounds, replaying ``res``'s
    trace through ``sess``'s vectorized epoch."""
    state = sess.init()
    step = sess.step_fn()
    err = 0.0
    for t in range(res.num_rounds):
        state, _ = step(state, sess.data)
        err = max(err, float(np.max(np.abs(
            np.asarray(res.z_versions[t + 1]) - np.asarray(sess.z(state))))))
    return err


def churn_scenario(emit, smoke: bool = False) -> bool:
    """Crash+rejoin at 8 workers, per-push commits, REAL numerics:
    deterministic plan, replay-parity through the epoch (single device
    + SPMD when 8 devices are up), and a rounds-to-tolerance gate —
    chaos must converge within ``max_churn_rounds_ratio`` x the
    fault-free round count (benchmarks/kernels_baseline.json)."""
    import jax

    R = 16 if smoke else 24
    timing = CostProfile(t_worker=ConstantService(1.0),
                         t_server_block=ConstantService(0.25))
    plan = FaultPlan.churn(GATE_WORKERS, seed=0, crashes=2,
                           window=(2.0, 8.0), down=(2.0, 5.0))
    sess = build_session(GATE_WORKERS, dim=CHURN_DIM, samples=4)
    ff = sess.run_ps(R, discipline="per_push", timing=timing)
    ch = sess.run_ps(R, discipline="per_push", timing=timing, faults=plan)

    # rounds-to-tolerance: the loss level the fault-free run reaches at
    # 60% of its rounds; chaos must get there within max_ratio x as many
    tol = ff.losses[int(0.6 * R) - 1]
    r_ff = _rounds_to_tolerance(ff.losses, tol)
    r_ch = _rounds_to_tolerance(ch.losses, tol)
    ratio = float("inf") if r_ch is None else r_ch / r_ff
    max_ratio = json.loads(BASELINE.read_text())["max_churn_rounds_ratio"]

    emit(f"churn_faultfree_makespan,{ff.makespan*1e6:.0f},"
         f"rounds_to_tol={r_ff}")
    emit(f"churn_chaos_makespan,{ch.makespan*1e6:.0f},"
         f"rounds_to_tol={r_ch}")
    emit(f"churn_rounds_ratio,{ratio:.3f},max={max_ratio}"
         f"|crashes={ch.metrics['crashes']}|rejoins={ch.metrics['rejoins']}")
    _emit_hist(emit, "churn_worker_stall_hist",
               ch.metrics["histograms"]["worker_stall_time"])
    _emit_hist(emit, "churn_server_occupancy_hist",
               ch.metrics["histograms"]["server_occupancy"])

    # replay parity: the chaos trace (staleness + participation) must
    # reproduce the runtime's z trajectory through the fast epoch
    dm = ch.to_delay_model()
    err1 = _replay_max_err(ch, build_session(GATE_WORKERS, dim=CHURN_DIM,
                                             samples=4, delay_model=dm))
    emit(f"churn_replay_err_1dev,{err1:.2e},tol=1e-05")
    ok = err1 <= 1e-5
    if jax.device_count() >= 8:
        from repro.launch.mesh import make_test_mesh
        err8 = _replay_max_err(
            ch, build_session(GATE_WORKERS, dim=CHURN_DIM, samples=4,
                              delay_model=dm, mesh=make_test_mesh(8)))
        emit(f"churn_replay_err_spmd,{err8:.2e},mesh=data4xmodel2")
        ok = ok and err8 <= 1e-5
    else:
        emit("churn_replay_err_spmd,skipped,need 8 devices "
             "(XLA_FLAGS=--xla_force_host_platform_device_count=8)")
    if ratio > max_ratio:
        emit(f"churn_gate_FAILED,0,rounds ratio {ratio:.3f} > {max_ratio}")
    if not ok:
        emit("churn_gate_FAILED,0,replay parity error above 1e-5")
    return ok and ratio <= max_ratio


def lossy_scenario(emit, smoke: bool = False) -> bool:
    """Unreliable transport at 8 workers, REAL numerics: 5% drop, 2%
    duplication, 10% reorder on every worker<->server link, with the
    runtime's ack/retry/backoff reliability layer on. Gates
    rounds-to-tolerance lossy/reliable vs ``max_lossy_rounds_ratio``
    (benchmarks/kernels_baseline.json) and replay parity of the lossy
    trace through the vectorized epoch (single device + SPMD when 8
    devices are up)."""
    import jax

    from repro.ps import Transport

    R = 16 if smoke else 24
    tw, ts = ConstantService(1.0), ConstantService(0.25)
    tr = Transport(0.0, 0.0, drop_rate=0.05, dup_rate=0.02,
                   reorder_rate=0.1, ack_timeout=0.5)
    sess = build_session(GATE_WORKERS, dim=CHURN_DIM, samples=4)
    rel = sess.run_ps(R, timing=CostProfile(t_worker=tw, t_server_block=ts))
    lo = sess.run_ps(R, timing=CostProfile(t_worker=tw, t_server_block=ts,
                                           net=tr))

    tol = rel.losses[int(0.6 * R) - 1]
    r_rel = _rounds_to_tolerance(rel.losses, tol)
    r_lo = _rounds_to_tolerance(lo.losses, tol)
    ratio = float("inf") if r_lo is None else r_lo / r_rel
    max_ratio = json.loads(BASELINE.read_text())["max_lossy_rounds_ratio"]

    t = lo.metrics["transport"]
    emit(f"lossy_reliable_makespan,{rel.makespan*1e6:.0f},"
         f"rounds_to_tol={r_rel}")
    emit(f"lossy_transport_makespan,{lo.makespan*1e6:.0f},"
         f"rounds_to_tol={r_lo}")
    emit(f"lossy_rounds_ratio,{ratio:.3f},max={max_ratio}"
         f"|delivery_rate={t['delivery_rate']:.3f}"
         f"|drops={t['drops']}|dups={t['dups']}|reorders={t['reorders']}"
         f"|retransmits={t['retransmits']}|dups_dropped={t['dups_dropped']}"
         f"|timeout_fallbacks={t['timeout_fallbacks']}")

    dm = lo.to_delay_model()
    err1 = _replay_max_err(lo, build_session(GATE_WORKERS, dim=CHURN_DIM,
                                             samples=4, delay_model=dm))
    emit(f"lossy_replay_err_1dev,{err1:.2e},tol=1e-05")
    ok = err1 <= 1e-5
    if jax.device_count() >= 8:
        from repro.launch.mesh import make_test_mesh
        err8 = _replay_max_err(
            lo, build_session(GATE_WORKERS, dim=CHURN_DIM, samples=4,
                              delay_model=dm, mesh=make_test_mesh(8)))
        emit(f"lossy_replay_err_spmd,{err8:.2e},mesh=data4xmodel2")
        ok = ok and err8 <= 1e-5
    else:
        emit("lossy_replay_err_spmd,skipped,need 8 devices "
             "(XLA_FLAGS=--xla_force_host_platform_device_count=8)")
    if ratio > max_ratio:
        emit(f"lossy_gate_FAILED,0,rounds ratio {ratio:.3f} > {max_ratio}")
    if not ok:
        emit("lossy_gate_FAILED,0,replay parity error above 1e-5")
    return ok and ratio <= max_ratio


def server_crash_scenario(emit, smoke: bool = False) -> bool:
    """Block-server crash + WAL-replay recovery at 8 real-compute
    workers: a deterministic ``server_crash`` plan drops two lock
    domains' in-memory state mid-run; each rebuilds from its write-ahead
    commit log. Gates (benchmarks/kernels_baseline.json):

    * **zero lost folds** — every domain's committed fold log matches
      the crash-free run's per-round multiset exactly (hard-fail);
    * **rounds-to-tolerance** — the crash run must reach the crash-free
      tolerance within ``max_server_crash_rounds_ratio`` x its rounds
      (recovery costs sim time, never committed progress);
    * **replay parity** — the crash run's trace replays through the
      vectorized epoch within 1e-5 (single-device + SPMD when 8
      devices are up)."""
    import jax

    R = 16 if smoke else 24
    timing = CostProfile(t_worker=ConstantService(1.0),
                         t_server_block=ConstantService(0.25))
    plan = FaultPlan.of(FaultPlan.server_crash(2, at=3.0, down=2.5),
                        FaultPlan.server_crash(9, at=6.0, down=3.0))
    sess = build_session(GATE_WORKERS, dim=CHURN_DIM, samples=4)
    rt_ff = PSRuntime(sess.spec, data=sess.data, timing=timing)
    ff = rt_ff.run(R)
    rt_cr = PSRuntime(sess.spec, data=sess.data, timing=timing,
                      faults=plan)
    cr = rt_cr.run(R)

    # zero lost folds: per-domain, per-round fold MULTISETS must match
    # the crash-free run (in-round order may differ across a recovery)
    lost = 0
    for d_ff, d_cr in zip(rt_ff.domains, rt_cr.domains):
        per_round_ff = {}
        for (t, i, j) in d_ff.fold_log:
            per_round_ff.setdefault(t, []).append((i, j))
        per_round_cr = {}
        for (t, i, j) in d_cr.fold_log:
            per_round_cr.setdefault(t, []).append((i, j))
        for t in set(per_round_ff) | set(per_round_cr):
            if sorted(per_round_ff.get(t, [])) \
                    != sorted(per_round_cr.get(t, [])):
                lost += 1
    # read the durability instruments straight off the run's metrics
    # registry (the same instruments PSRunResult.metrics is built from)
    m = rt_cr.registry.collect(["server_recoveries", "wal"])
    emit(f"server_crash_folds,{sum(len(d.fold_log) for d in rt_cr.domains)},"
         f"mismatched_rounds={lost}"
         f"|recoveries={m['server_recoveries']}"
         f"|wal_commits={m['wal']['commits']}"
         f"|wal_replays={m['wal']['replays']}")
    ok = lost == 0 and m["server_recoveries"] == 2 \
        and ff.metrics.get("server_recoveries", 0) == 0

    tol = ff.losses[int(0.6 * R) - 1]
    r_ff = _rounds_to_tolerance(ff.losses, tol)
    r_cr = _rounds_to_tolerance(cr.losses, tol)
    ratio = float("inf") if r_cr is None else r_cr / r_ff
    max_ratio = json.loads(BASELINE.read_text())[
        "max_server_crash_rounds_ratio"]
    emit(f"server_crash_faultfree_makespan,{ff.makespan*1e6:.0f},"
         f"rounds_to_tol={r_ff}")
    emit(f"server_crash_chaos_makespan,{cr.makespan*1e6:.0f},"
         f"rounds_to_tol={r_cr}")
    emit(f"server_crash_rounds_ratio,{ratio:.3f},max={max_ratio}")

    dm = cr.to_delay_model()
    err1 = _replay_max_err(cr, build_session(GATE_WORKERS, dim=CHURN_DIM,
                                             samples=4, delay_model=dm))
    emit(f"server_crash_replay_err_1dev,{err1:.2e},tol=1e-05")
    ok = ok and err1 <= 1e-5
    if jax.device_count() >= 8:
        from repro.launch.mesh import make_test_mesh
        err8 = _replay_max_err(
            cr, build_session(GATE_WORKERS, dim=CHURN_DIM, samples=4,
                              delay_model=dm, mesh=make_test_mesh(8)))
        emit(f"server_crash_replay_err_spmd,{err8:.2e},mesh=data4xmodel2")
        ok = ok and err8 <= 1e-5
    else:
        emit("server_crash_replay_err_spmd,skipped,need 8 devices "
             "(XLA_FLAGS=--xla_force_host_platform_device_count=8)")
    if lost:
        emit(f"server_crash_gate_FAILED,0,{lost} rounds lost/extra folds "
             f"after WAL replay")
    if ratio > max_ratio:
        emit(f"server_crash_gate_FAILED,0,rounds ratio {ratio:.3f} > "
             f"{max_ratio}")
    if not ok:
        emit("server_crash_gate_FAILED,0,replay parity or recovery "
             "count off")
    return ok and ratio <= max_ratio


def skew_scenario(emit, smoke: bool = False) -> bool:
    """Timing-only: zipf(a=1.5) vs uniform block selection at 8 workers
    under per-push commits (commit work paid per push, so a domain's
    busy time follows its push count). Skewed selection piles pushes
    onto the head blocks' lock domains — visible as queue-occupancy
    spread across the 16 per-block servers. Gated: the zipf run's
    occupancy spread (busiest/mean domain busy fraction) must exceed
    the uniform run's by ``min_skew_occupancy_ratio``."""
    R = 12 if smoke else 40
    timing = CostProfile(t_worker=ConstantService(1.0),
                         t_server_block=ConstantService(0.25),
                         t_push=0.05)
    spread = {}
    for selection in ("random", "zipf"):
        sess = build_session(GATE_WORKERS, dim=CHURN_DIM, samples=4,
                             block_selection=selection, zipf_a=1.5)
        rt = PSRuntime(sess.spec, discipline="per_push", timing=timing,
                       compute="timing")
        res = rt.run(R)
        # named-subset read off the run's metrics registry
        m = rt.registry.collect(["server_busy_frac", "histograms"])
        bf = m["server_busy_frac"]
        spread[selection] = max(bf) / (sum(bf) / len(bf))
        emit(f"skew_{selection}_makespan,{res.makespan*1e6:.0f},"
             f"busy_max={max(bf):.3f}|busy_min={min(bf):.3f}"
             f"|spread={spread[selection]:.3f}")
        _emit_hist(emit, f"skew_{selection}_occupancy_hist",
                   m["histograms"]["server_occupancy"])
    min_ratio = json.loads(BASELINE.read_text())["min_skew_occupancy_ratio"]
    ratio = spread["zipf"] / spread["random"]
    emit(f"skew_spread_ratio,{ratio:.3f},min={min_ratio}")
    if ratio < min_ratio:
        emit(f"skew_gate_FAILED,0,zipf/random occupancy spread "
             f"{ratio:.3f} < {min_ratio}")
        return False
    return True


def heavy_tail_scenario(emit, smoke: bool = False) -> bool:
    """Timing-only: Pareto(alpha=1.1) worker compute — Assumption 3's
    straggler tail — under round-buffered vs per-push commits. Stall
    time concentrates on the workers behind the straggler. Gated: the
    straggler tail must actually bite (lockfree stall time >=
    ``min_heavy_tail_stall``) while every served read stays within the
    enforced staleness bound."""
    R = 12 if smoke else 40
    timing = CostProfile(t_worker=ParetoService(1.0, alpha=1.1),
                         t_server_block=ConstantService(0.25))
    stalls = {}
    ok = True
    for disc in ("lockfree", "per_push"):
        sess = build_session(GATE_WORKERS, dim=CHURN_DIM, samples=4)
        rt = PSRuntime(sess.spec, discipline=disc, timing=timing,
                       compute="timing")
        res = rt.run(R)
        m = rt.registry.collect(["stall_time", "max_served_tau", "bound",
                                 "histograms"])
        stalls[disc] = m["stall_time"]
        ok = ok and m["max_served_tau"] <= m["bound"]
        emit(f"heavy_tail_{disc}_makespan,{res.makespan*1e6:.0f},"
             f"stall_time={m['stall_time']:.2f}"
             f"|max_served_tau={m['max_served_tau']}")
        _emit_hist(emit, f"heavy_tail_{disc}_stall_hist",
                   m["histograms"]["worker_stall_time"])
    min_stall = json.loads(BASELINE.read_text())["min_heavy_tail_stall"]
    emit(f"heavy_tail_lockfree_stall,{stalls['lockfree']:.2f},"
         f"min={min_stall}")
    if not ok:
        emit("heavy_tail_gate_FAILED,0,served tau above the bound")
        return False
    if stalls["lockfree"] < min_stall:
        emit(f"heavy_tail_gate_FAILED,0,lockfree stall time "
             f"{stalls['lockfree']:.2f} < {min_stall} — straggler tail "
             f"not biting; timing model regressed?")
        return False
    return True


SCENARIOS = {"churn": churn_scenario, "lossy": lossy_scenario,
             "server_crash": server_crash_scenario,
             "skew": skew_scenario, "heavy_tail": heavy_tail_scenario}


def main(emit=print, smoke: bool = False) -> None:
    costs = measured_costs()
    emit(f"speedup_measured_costs,{costs['t_worker']*1e6:.1f},"
         f"t_serve_block_us={costs['t_server_block']*1e6:.1f}")
    if smoke:
        if not smoke_gate(emit, costs):
            raise SystemExit(1)
        table1(emit, costs, workers=[1, GATE_WORKERS], k_iters=64)
    else:
        table1(emit, costs)


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="CI mode: deterministic locked-vs-lockfree gate "
                         "at 8 workers + a reduced Table-1 sweep (or a "
                         "reduced chaos scenario with --scenario)")
    ap.add_argument("--scenario", default=None, choices=sorted(SCENARIOS),
                    help="elastic-PS chaos study instead of Table 1: "
                         "churn (crash+rejoin, replay parity + "
                         "rounds-to-tolerance gate), lossy (unreliable "
                         "transport: drop/dup/reorder + ack/retry, "
                         "rounds-to-tolerance + replay gates), "
                         "server_crash (block-server crash + WAL-replay "
                         "recovery: zero-lost-folds, rounds-to-tolerance "
                         "+ replay gates), skew "
                         "(zipf block selection), heavy_tail (Pareto "
                         "stragglers)")
    args = ap.parse_args()
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    if args.scenario is not None:
        if not SCENARIOS[args.scenario](print, smoke=args.smoke):
            raise SystemExit(1)
    else:
        main(smoke=args.smoke)
