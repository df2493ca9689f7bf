"""Names on the profiler's clock (repro.obs.profile): the epoch's device
scopes in the compiled program, the PS runtime's host spans and the
compile marker in a profiler trace, and the registry's refusals.

* A tiny flat epoch and a tiny 4-device sharded epoch (a child process
  with ``--xla_force_host_platform_device_count=4``) compile to HLO whose
  ``op_name``s carry every ``DEVICE_SCOPES`` name that applies to them,
  with the benchmark loss's own scope nested under ``asybadmm.grad``.
* A tiny ``PSRuntime`` run traced by ``jax.profiler`` on the CPU writes
  every ``HOST_SPANS`` name onto ``/host:CPU``, inside the window span
  the benchmark opens; a fresh jit there leaves an ``asybadmm.compile``
  marker carrying its name.
"""
import functools
import glob
import json
import os
import pathlib
import re
import subprocess
import sys
import tempfile

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.api import ConsensusSession
from repro.configs.base import ADMMConfig
from repro.obs import (DEVICE_SCOPES, HOST_SPANS, Telemetry, host_span,
                       stage)
from repro.ps import PSRuntime

ROOT = pathlib.Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT / "bench"))

from problem import GRAD_SCOPE, sparse_logreg_loss  # noqa: E402

WINDOW = "bench_window"            # bench/tracing.py's window span
N, S, R, DIM, M = 4, 16, 3, 500, 4     # blocks of 125 padded to 128
FLAT_SCOPES = sorted(DEVICE_SCOPES - {"asybadmm.exchange"})
SHARDED_SCOPES = sorted(DEVICE_SCOPES)


def _rows(n=N):
    r = np.random.RandomState(3)
    return (jnp.asarray(r.randint(0, DIM, (n, S, R)), jnp.int32),
            jnp.asarray(r.rand(n, S, R), jnp.float32),
            jnp.asarray(np.sign(r.randn(n, S)), jnp.float32))


def _session(mesh=None, n=N):
    cfg = ADMMConfig(rho=10.0, gamma=0.01, max_delay=2, num_blocks=M,
                     block_fraction=0.5, block_selection="random",
                     l1_coef=1e-3, clip=1e4, backend="jnp", minibatch=0.5,
                     seed=5)
    return ConsensusSession.flat(sparse_logreg_loss, _rows(n), dim=DIM,
                                 cfg=cfg, mesh=mesh)


def _op_names(hlo: str) -> list:
    return re.findall(r'op_name="([^"]*)"', hlo)


@functools.lru_cache(maxsize=None)
def _flat_op_names() -> tuple:
    sess = _session()
    step = sess.step_fn()
    return tuple(_op_names(step.lower(sess.init(), sess.data)
                           .compile().as_text()))


_SHARDED = """
import json, re, sys
sys.path[:0] = {paths!r}
import test_profile_names as t
from repro.launch.mesh import make_test_mesh
sess = t._session(mesh=make_test_mesh(devices=4, model=2), n=8)
step = sess.step_fn()
print(json.dumps(t._op_names(step.lower(sess.init(), sess.data)
                              .compile().as_text())))
"""


@functools.lru_cache(maxsize=None)
def _sharded_op_names() -> tuple:
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    code = _SHARDED.format(paths=[str(ROOT / "src"), str(ROOT / "tests")])
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    return tuple(json.loads(out.stdout.strip().splitlines()[-1]))


@pytest.mark.parametrize("scope", FLAT_SCOPES)
def test_flat_epoch_carries_scope(scope):
    assert any(scope in n for n in _flat_op_names()), scope


@pytest.mark.parametrize("scope", SHARDED_SCOPES)
def test_sharded_epoch_carries_scope(scope):
    assert any(scope in n for n in _sharded_op_names()), scope


@pytest.mark.parametrize("which", ["flat", "sharded"])
def test_loss_scope_sits_under_grad(which):
    names = _flat_op_names() if which == "flat" else _sharded_op_names()
    loss = [n for n in names if GRAD_SCOPE in n]
    assert loss
    for n in loss:
        assert "asybadmm.grad" in n.split(GRAD_SCOPE)[0], n


# ---------------------------------------------------------------------------
# host spans and the compile marker in a profiler trace
# ---------------------------------------------------------------------------

@functools.lru_cache(maxsize=None)
def _traced_ps_run() -> dict:
    """name -> [(start, end, stats)] on /host:CPU, plus the window."""
    from jax._src.profiler import ProfileData
    sess = _session()
    records = []
    rt = PSRuntime(sess.spec, data=sess.data, record_z=False,
                   telemetry=Telemetry(spans=False, sink=records.append),
                   metrics_every=1)
    tmp = tempfile.mkdtemp(prefix="profile_names_")
    jax.profiler.start_trace(tmp)
    try:
        with jax.profiler.TraceAnnotation(WINDOW):
            rt.run(3)
            fresh = jax.jit(lambda x: x * 7.0 - 1.0)   # compiles here
            fresh(jnp.ones(5)).block_until_ready()
    finally:
        jax.profiler.stop_trace()
    path, = glob.glob(os.path.join(tmp, "**", "*.xplane.pb"),
                      recursive=True)
    host = ProfileData.from_file(path).find_plane_with_name("/host:CPU")
    found = {}
    for line in host.lines:
        for ev in line.events:
            stats = dict(ev.stats) if ev.name == "asybadmm.compile" else {}
            found.setdefault(ev.name, []).append(
                (ev.start_ns, ev.start_ns + ev.duration_ns, stats))
    assert len(records) == 3
    return found


@pytest.mark.parametrize("name", sorted(HOST_SPANS))
def test_ps_run_writes_host_span_inside_window(name):
    found = _traced_ps_run()
    (w0, w1, _), = found[WINDOW]
    assert found.get(name), f"no {name!r} span on /host:CPU"
    for s, e, _ in found[name]:
        assert w0 <= s <= e <= w1, name


def test_compile_marker_names_the_fresh_jit():
    marks = _traced_ps_run()["asybadmm.compile"]
    assert any("<lambda>" in str(stats.get("fun_name", ""))
               and float(stats["seconds"]) > 0 for _, _, stats in marks)


def test_host_syncs_are_counted_per_worker_round():
    """Each worker round reads its loss once; each round's selection is
    read once (the selector is gradient-free)."""
    found = _traced_ps_run()
    assert len(found["ps.sync.loss"]) == 3 * N
    assert len(found["ps.sync.select"]) == 3


@pytest.mark.parametrize("make,bad", [
    (stage, "asybadmm.unknown"), (stage, "ps.sync.loss"),
    (host_span, "ps.worker.unknown"), (host_span, "asybadmm.gather"),
])
def test_unregistered_names_are_refused(make, bad):
    with pytest.raises(ValueError, match="registered"):
        make(bad)
