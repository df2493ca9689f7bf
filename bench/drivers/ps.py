"""Driver of the parameter-server cell: ``repro.ps.PSRuntime`` with real
compute, the ``lockfree`` discipline, the default ``CostProfile`` and no
faults — what ``ConsensusSession.run_ps`` builds.

The runtime is built once, with telemetry streaming one record per
completed round to the benchmark's sink (``metrics_every=1``). Set-up
runs its first ``check_epochs`` rounds from the seed (compiling every
jitted op of its engine) and keeps their losses and the committed z. The
window runs the same runtime object again from z0, so its engine's
compiled ops are reused, and the sink closes the window at the first
round completed ``--seconds`` after the start: the round time is the
time to that completion over the rounds completed. The check replays
the set-up rounds in the plain reference with the staleness the run
recorded, and compares each round's loss, and the committed z and the
workers' w after the last.
"""
from __future__ import annotations

import dataclasses
import time
from typing import Any, Optional

import jax
import numpy as np

import check
import gen
import problem
import reference

RUN = "bench_ps_run"
ROUND = "bench_ps_round"
MAX_ROUNDS = 10_000          # far past any window; the sink ends the run


class _WindowClosed(Exception):
    pass


class _Rounds:
    """The per-round sink: times each completed round and closes the
    window; between rounds the host span ``bench_ps_round`` is open."""

    def __init__(self):
        self.deadline: Optional[float] = None
        self.t0 = self.t_last = 0.0
        self.done = 0
        self._span = None

    def start(self, seconds: Optional[float]) -> None:
        self.t0 = self.t_last = time.perf_counter()
        self.deadline = None if seconds is None else self.t0 + seconds
        self.done = 0
        self._open()

    def _open(self):
        self._span = jax.profiler.TraceAnnotation(ROUND)
        self._span.__enter__()

    def close(self):
        if self._span is not None:
            self._span.__exit__(None, None, None)
            self._span = None

    def __call__(self, record: dict) -> None:
        self.close()
        self.t_last = time.perf_counter()
        self.done += 1
        if self.deadline is not None and self.t_last >= self.deadline:
            raise _WindowClosed
        self._open()


@dataclasses.dataclass
class Run:
    cell: dict
    runtime: Any
    rounds: _Rounds
    rows: Any
    params: reference.Params
    admm_seed: int
    checked: check.Trajectory
    delays: np.ndarray
    devices: list


def setup(cell: dict, seed: int) -> Run:
    from repro.obs import Telemetry
    from repro.ps import PSRuntime
    cfg, traffic = cell["config"], cell["traffic"]
    s = gen.shape_of(cfg)
    mb = traffic.get("minibatch")
    _, admm_seed = gen.seed_words(seed)
    admm = problem.admm_config(cfg, admm_seed, minibatch=mb)
    rows = gen.make_rows(cfg, seed)
    sess = problem.session(cfg, rows, admm, None)
    sink = _Rounds()
    rt = PSRuntime(sess.spec, data=sess.data,
                   discipline=traffic["discipline"], record_z=False,
                   telemetry=Telemetry(spans=False, sink=sink),
                   metrics_every=1)
    n = cell["check_epochs"]
    sink.start(None)
    res = rt.run(n)
    sink.close()
    z = np.asarray(res.z_final, np.float32)
    checked = [(None, loss, None) for loss in res.losses[:-1]]
    checked.append((z, res.losses[-1], np.asarray(rt.w)))
    return Run(cell=cell, runtime=rt, rounds=sink, rows=sess.data,
               params=reference.params_of(cfg, mb, s["rows"],
                                          dim=s["features"]),
               admm_seed=admm_seed, checked=checked,
               delays=np.asarray(res.trace.delays[:n]),
               devices=[jax.devices()[0]])


def window(run: Run, seconds: float) -> dict:
    """Rounds of one ``PSRuntime.run`` until ``seconds`` have passed."""
    sink = run.rounds
    with jax.profiler.TraceAnnotation(RUN):
        sink.start(seconds)
        try:
            run.runtime.run(MAX_ROUNDS)
        except _WindowClosed:
            pass
        finally:
            sink.close()
    if sink.done == 0:
        raise RuntimeError("no PS round completed in the window")
    elapsed = sink.t_last - sink.t0
    return {"units": sink.done, "seconds": elapsed,
            "ps_round_ms": 1000.0 * elapsed / sink.done}


def kernel_shapes(run: Run) -> dict:
    return {}


def hlo_text(run: Run) -> str:
    return ""


def drop_program(run: Run) -> None:
    run.runtime = None
    jax.clear_caches()


def reference_run(run: Run, dtype) -> check.Trajectory:
    return reference.run(run.params, run.admm_seed, run.rows,
                         len(run.checked), dtype=dtype, delays=run.delays)
