"""Driver of the consensus cells: ``ConsensusSession.step_fn`` epochs.

Set-up makes the rows on the chips that hold them, builds the session
and its jitted step, and drives that step from the seed through its
first ``check_epochs`` epochs (the first compiles), keeping z, the
loss and the workers' w of each. The window then goes on from that state with the same
step: epochs dispatched back to back, at most ``in_flight`` of them not
yet complete, each completion timed by ``block_until_ready`` on the
epoch's counter. After the window the program's state is dropped and the
plain reference repeats the first epochs from the same seed.
"""
from __future__ import annotations

import collections
import dataclasses
import time
from typing import Any

import jax
import numpy as np

import check
import gen
import problem
import reference

DISPATCH = "bench_epoch_dispatch"
WAIT = "bench_epoch_wait"


@dataclasses.dataclass
class Run:
    cell: dict
    sess: Any
    step: Any
    state: Any
    rows: Any
    params: reference.Params
    admm_seed: int
    checked: check.Trajectory
    selected_fraction: float
    devices: list


def setup(cell: dict, seed: int) -> Run:
    cfg, traffic = cell["config"], cell["traffic"]
    s = gen.shape_of(cfg)
    mb = traffic.get("minibatch")
    _, admm_seed = gen.seed_words(seed)
    admm = problem.admm_config(cfg, admm_seed, minibatch=mb)
    mesh = problem.make_mesh(cfg)
    shapes = (jax.ShapeDtypeStruct((s["num_workers"], s["rows"], s["nnz"]),
                                   np.int32),
              jax.ShapeDtypeStruct((s["num_workers"], s["rows"], s["nnz"]),
                                   np.float32),
              jax.ShapeDtypeStruct((s["num_workers"], s["rows"]),
                                   np.float32))
    placed = problem.data_shardings(cfg, admm, mesh, shapes)
    rows = gen.make_rows(cfg, seed, sharding=placed)
    sess = problem.session(cfg, rows, admm, mesh)
    step = sess.step_fn()
    state = sess.init()
    checked, info = [], None
    for _ in range(cell["check_epochs"]):
        state, info = step(state, sess.data)
        checked.append((np.asarray(sess.z(state), np.float32),
                        float(info["loss"]), np.asarray(state.w_cache)))
    devices = (list(mesh.devices.flat) if mesh is not None
               else [jax.devices()[0]])
    return Run(cell=cell, sess=sess, step=step, state=state,
               rows=sess.data,
               params=reference.params_of(cfg, mb, s["rows"],
                                          dim=s["features"]),
               admm_seed=admm_seed, checked=checked,
               selected_fraction=float(info["selected_fraction"]),
               devices=devices)


def window(run: Run, seconds: float) -> dict:
    """Epochs back to back for ``seconds``; the time is from the start
    to the completion of the last epoch, over the epochs completed."""
    limit = run.cell["traffic"]["in_flight"]
    step, state, data = run.step, run.state, run.sess.data
    run.state = None              # each state is freed once it is used
    pending = collections.deque()
    done = 0
    t0 = time.perf_counter()
    t_last = t0
    while t_last - t0 < seconds:
        with jax.profiler.TraceAnnotation(DISPATCH):
            state, _ = step(state, data)
        pending.append(state.t)
        if len(pending) >= limit:
            with jax.profiler.TraceAnnotation(WAIT):
                pending.popleft().block_until_ready()
            done += 1
            t_last = time.perf_counter()
    with jax.profiler.TraceAnnotation(WAIT):
        while pending:
            pending.popleft().block_until_ready()
            done += 1
    t_last = time.perf_counter()
    run.state = state
    return {"units": done, "seconds": t_last - t0,
            "epoch_ms": 1000.0 * (t_last - t0) / done}


def kernel_shapes(run: Run) -> dict:
    """What the byte counts of the kernels need (per chip)."""
    space = run.sess.spec.space
    mesh = getattr(space, "mesh", None)
    n, m = space.num_workers, space.num_blocks
    data = model = 1
    if mesh is not None:
        data, model = mesh.shape["data"], mesh.shape["model"]
    return {"num_workers": n // data, "num_blocks": m // model,
            "block_dim": space.packer.block_dim,
            "edges": int(np.asarray(run.sess.spec.edge).sum()) // (data * model),
            "selected_fraction": run.selected_fraction,
            "track_x": bool(run.sess.spec.track_x), "sharded": mesh is not None}


def drop_program(run: Run) -> None:
    """Free the program's state before the reference runs."""
    run.state = run.step = None
    jax.clear_caches()


def hlo_text(run: Run) -> str:
    """The compiled step's HLO, whose instruction names the trace shows."""
    return run.step.lower(run.state, run.sess.data).compile().as_text()


def reference_run(run: Run, dtype) -> check.Trajectory:
    mesh = run.sess.spec.space.mesh if len(run.devices) > 1 else None
    return reference.run(run.params, run.admm_seed, run.rows,
                         len(run.checked), dtype=dtype, mesh=mesh)
