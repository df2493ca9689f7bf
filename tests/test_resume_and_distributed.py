"""Checkpoint-resume of ADMM training and SPMD execution of the flat
AsyBADMM driver on an 8-host-device mesh (subprocess — device count must
be forced before jax init) — plus the PS runtime's mid-stream resume
determinism property: under ARBITRARY snapshot cadences and worker-crash
schedules, a run resumed from any snapshot finishes with exactly the
fold log and final z of the uninterrupted run."""
import os
import subprocess
import sys
import tempfile

import jax
import jax.numpy as jnp
import numpy as np

from repro.checkpoint import restore, save
from repro.configs import get_smoke
from repro.configs.base import ADMMConfig
from repro.data import TokenPipeline
from repro.models import build_model
from repro.training import ADMMTrainer

SRC = os.path.join(os.path.dirname(__file__), "..", "src")


def test_admm_state_checkpoint_resume(tmp_path):
    """Training 10 steps straight == training 5, checkpointing the FULL
    ADMM state (z ring, duals, w cache, rng), restoring, training 5."""
    cfg = get_smoke("qwen3-1.7b")
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    pipe = TokenPipeline(vocab_size=cfg.vocab_size, seq_len=17,
                         global_batch=8, seed=0)
    tr = ADMMTrainer(loss_fn=model.loss,
                     admm=ADMMConfig(rho=5.0, gamma=0.05, max_delay=1,
                                     block_fraction=0.5, num_blocks=4),
                     num_workers=4)
    step = jax.jit(tr.train_step)

    straight = tr.init(params)
    for i in range(10):
        straight, _ = step(straight, pipe.batch(i, num_workers=4))

    half = tr.init(params)
    for i in range(5):
        half, _ = step(half, pipe.batch(i, num_workers=4))
    path = str(tmp_path / "admm_ckpt")
    save(path, half._asdict(), step=5)
    resumed_dict = restore(path, half._asdict())
    resumed = type(half)(**resumed_dict)
    for i in range(5, 10):
        resumed, _ = step(resumed, pipe.batch(i, num_workers=4))

    for a, b in zip(jax.tree.leaves(straight.params),
                    jax.tree.leaves(resumed.params)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-6, atol=1e-6)


def test_flat_driver_runs_spmd():
    """The paper's Algorithm 1 driver executes under jit on a 4-device
    (2 data x 2 model) host mesh with the worker axis sharded — the
    result matches the single-device run bit-for-bit semantics."""
    code = """
import os
os.environ['XLA_FLAGS'] = '--xla_force_host_platform_device_count=4'
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P
from repro.configs.base import ADMMConfig
from repro.core import init_state, make_problem, make_step_fn, run
from repro.data import make_sparse_logreg
from repro.launch.mesh import make_test_mesh

data = make_sparse_logreg(num_workers=4, samples_per_worker=32, dim=64,
                          density=0.2, seed=0)
def loss_fn(z, d):
    X, y = d
    return jnp.mean(jnp.log1p(jnp.exp(-y * (X @ z))))
prob = make_problem(loss_fn, (jnp.asarray(data.X), jnp.asarray(data.y)),
                    dim=64, num_blocks=8, support=data.support, l1_coef=1e-3)
cfg = ADMMConfig(rho=2.0, gamma=0.1, max_delay=1, block_fraction=0.5,
                 num_blocks=8)

# single device reference
state_ref, hist_ref = run(prob, cfg, 30, eval_every=30)

# SPMD: worker axis over 'data', blocks over 'model'
mesh = make_test_mesh(devices=4, model=2)
with mesh:
    state = init_state(prob, cfg)
    shard = lambda a, spec: jax.device_put(a, NamedSharding(mesh, spec))
    state = state._replace(
        y=shard(state.y, P('data', 'model', None)),
        w_cache=shard(state.w_cache, P('data', 'model', None)),
        x=shard(state.x, P('data', 'model', None)),
        z_hist=shard(state.z_hist, P(None, 'model', None)))
    step = make_step_fn(prob, cfg)
    for _ in range(30):
        state = step(state)
    z = prob.blocks.from_blocks(state.z_hist[0])
    obj = float(prob.objective(z))
print('REF', hist_ref[-1]['objective'], 'SPMD', obj)
assert abs(obj - hist_ref[-1]['objective']) < 1e-3, (obj, hist_ref)
print('SPMD_OK')
"""
    env = dict(os.environ, PYTHONPATH=SRC, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    r = subprocess.run([sys.executable, "-c", code], env=env,
                       capture_output=True, text=True, timeout=600)
    assert r.returncode == 0, r.stderr[-2000:]
    assert "SPMD_OK" in r.stdout


# ---------------------------------------------------------------------------
# PS runtime mid-stream resume: determinism property (hypothesis)
# ---------------------------------------------------------------------------

_PS_N, _PS_M, _PS_DBLK = 3, 4, 5
_PS_ROUNDS = 8


def _ps_session():
    from repro.api import ConsensusSession
    rs = np.random.RandomState(11)
    centers = jnp.asarray(rs.randn(_PS_N, _PS_M * _PS_DBLK)
                          .astype(np.float32))
    cfg = ADMMConfig(rho=2.0, gamma=0.1, max_delay=2, block_fraction=0.5,
                     num_blocks=_PS_M, block_selection="random",
                     l1_coef=1e-3, clip=0.8, seed=0)
    loss = lambda z, c: 0.5 * jnp.sum(jnp.square(z - c))
    return ConsensusSession.flat(loss, centers, dim=_PS_M * _PS_DBLK,
                                 cfg=cfg)


def _ps_runtime(faults):
    from repro.ps import ConstantService, CostProfile, PSRuntime
    sess = _ps_session()
    timing = CostProfile(t_worker=ConstantService(1.0),
                         t_server_block=ConstantService(0.25))
    return PSRuntime(sess.spec, data=sess.data, timing=timing,
                     faults=faults)


def _resume_roundtrip(every, crashes, pick):
    """One property example: run with checkpointing + worker-crash
    chaos uninterrupted, then resume from one of its snapshots;
    return both (runtime, result) pairs and the chosen snapshot."""
    from repro.ps import FaultPlan
    plan = FaultPlan.of(*[FaultPlan.crash(w, at, down)
                          for (w, at, down) in crashes]) \
        if crashes else None
    with tempfile.TemporaryDirectory() as td:
        rt_full = _ps_runtime(plan)
        full = rt_full.run(_PS_ROUNDS, checkpoint_every=every,
                           checkpoint_dir=td)
        snaps = full.metrics["snapshots"]
        assert snaps, "cadence <= rounds/2 must produce a snapshot"
        snap = snaps[pick % len(snaps)]
        rt_res = _ps_runtime(plan)
        res = rt_res.run(_PS_ROUNDS, resume_from=snap)
    return rt_full, full, rt_res, res, snap


def _assert_resume_identical(rt_full, full, rt_res, res, snap):
    for d_full, d_res in zip(rt_full.domains, rt_res.domains):
        assert d_full.fold_log == d_res.fold_log, \
            f"fold log diverged after resume from {snap}"
    np.testing.assert_array_equal(np.asarray(full.z_final),
                                  np.asarray(res.z_final),
                                  err_msg=f"final z diverged after "
                                          f"resume from {snap}")
    np.testing.assert_array_equal(full.trace.delays, res.trace.delays)
    assert full.losses == res.losses
    assert full.makespan == res.makespan


try:
    import hypothesis  # noqa: F401
    from hypothesis import given, settings, strategies as st

    _crash_st = st.lists(
        st.tuples(st.integers(0, _PS_N - 1),          # worker
                  st.floats(0.5, 7.5),                # crash time
                  st.floats(0.5, 4.0)),               # downtime
        max_size=2,
        unique_by=lambda c: c[0])                     # one crash/worker

    @given(every=st.integers(1, _PS_ROUNDS // 2), crashes=_crash_st,
           pick=st.integers(0, 7))
    @settings(max_examples=10, deadline=None)
    def test_resume_determinism_property(every, crashes, pick):
        """For ARBITRARY snapshot cadences and worker-crash schedules,
        a run resumed from ANY of its crash-consistent snapshots
        finishes with exactly the uninterrupted run's committed fold
        log, final z, staleness trace, losses, and makespan — the
        snapshot captures the complete runtime state and the resumed
        tail re-derives every event identically."""
        _assert_resume_identical(*_resume_roundtrip(every, crashes, pick))
except ImportError:                                   # pragma: no cover
    pass


def test_resume_determinism_fixed_schedule():
    """One deterministic cell of the property (runs even without
    hypothesis): cadence 2, a mid-run worker crash, resume from the
    second snapshot."""
    _assert_resume_identical(
        *_resume_roundtrip(2, [(1, 2.5, 1.5)], pick=1))
