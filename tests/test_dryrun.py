"""Dry-run / sharding integration tests.

The production-mesh lowerings need 512 host devices, which must be
forced *before* jax initializes — so these tests run dryrun machinery
in a subprocess (smoke tests elsewhere must keep seeing 1 device).
"""
import json
import os
import subprocess
import sys

import pytest

SRC = os.path.join(os.path.dirname(__file__), "..", "src")


def _run(code: str, timeout=600):
    env = dict(os.environ, PYTHONPATH=SRC, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    return subprocess.run([sys.executable, "-c", code], env=env,
                          capture_output=True, text=True, timeout=timeout)


def test_single_device_default():
    """No global XLA_FLAGS leakage: default jax sees 1 CPU device."""
    r = _run("import jax; print(jax.device_count())")
    assert r.returncode == 0, r.stderr
    assert r.stdout.strip() == "1"


def test_mesh_construction():
    r = _run(
        "import os\n"
        "os.environ['XLA_FLAGS'] = '--xla_force_host_platform_device_count=512'\n"
        "from repro.launch.mesh import make_production_mesh\n"
        "m1 = make_production_mesh()\n"
        "m2 = make_production_mesh(multi_pod=True)\n"
        "print(dict(m1.shape), dict(m2.shape))\n")
    assert r.returncode == 0, r.stderr
    assert "{'data': 16, 'model': 16}" in r.stdout
    assert "{'pod': 2, 'data': 16, 'model': 16}" in r.stdout


@pytest.mark.parametrize("arch,shape", [
    ("granite-moe-1b-a400m", "train_4k"),     # MoE ADMM train
    ("mamba2-370m", "long_500k"),             # SSM sub-quadratic decode
    ("qwen3-1.7b", "prefill_32k"),            # dense prefill
])
def test_dryrun_lowers_and_compiles(arch, shape):
    code = (
        "from repro.launch.dryrun import run_one\n"
        f"row = run_one({arch!r}, {shape!r}, 'pod')\n"
        "import json; print('RESULT ' + json.dumps({k: row[k] for k in "
        "('status', 'bottleneck', 'flops_per_device')}))\n")
    r = _run(code)
    assert r.returncode == 0, r.stderr[-2000:]
    line = [l for l in r.stdout.splitlines() if l.startswith("RESULT ")][0]
    res = json.loads(line[len("RESULT "):])
    assert res["status"] == "ok"
    assert res["flops_per_device"] > 0


def test_dryrun_sharded_epoch_lowers_and_compiles():
    """--variant sharded_epoch: the SPMD-sharded asybadmm_epoch itself
    (shard_map, packed TreeSpace block servers over `model`) lowers and
    compiles at production shape — the ConsensusSession runtime path,
    not just the GSPMD trainer step."""
    code = (
        "from repro.launch.dryrun import run_one\n"
        "row = run_one('qwen3-1.7b', 'train_4k', 'pod', 'sharded_epoch')\n"
        "import json; print('RESULT ' + json.dumps({k: row[k] for k in "
        "('status', 'bottleneck', 'flops_per_device')}))\n")
    r = _run(code)
    assert r.returncode == 0, r.stderr[-2000:]
    line = [l for l in r.stdout.splitlines() if l.startswith("RESULT ")][0]
    res = json.loads(line[len("RESULT "):])
    assert res["status"] == "ok"
    assert res["flops_per_device"] > 0


def test_dryrun_multipod_lowers():
    code = (
        "from repro.launch.dryrun import run_one\n"
        "row = run_one('qwen3-1.7b', 'decode_32k', 'multipod')\n"
        "print('STATUS', row['status'], row.get('error', ''))\n")
    r = _run(code)
    assert r.returncode == 0, r.stderr[-2000:]
    assert "STATUS ok" in r.stdout


def test_long500k_skips_full_attention():
    from repro.launch.dryrun import skip_reason
    assert skip_reason("qwen1.5-32b", "long_500k") is not None
    assert skip_reason("mamba2-370m", "long_500k") is None
    assert skip_reason("mixtral-8x7b", "long_500k") is None  # SWA
    assert skip_reason("zamba2-1.2b", "long_500k") is None   # hybrid
    assert skip_reason("qwen1.5-32b", "train_4k") is None


def test_hlo_collective_parser():
    from repro.analysis.hlo import collective_bytes
    hlo = """
  %ar = f32[1024,16]{1,0} all-reduce(%x), replica_groups={}
  %ag.1 = bf16[512]{0} all-gather(%y), dimensions={0}
  %rs = (f32[8,8]{1,0}, f32[8,8]{1,0}) reduce-scatter(%a, %b)
  %cp = u32[4]{0} collective-permute(%z)
"""
    cb = collective_bytes(hlo)
    assert cb["all-reduce"] == 1024 * 16 * 4
    assert cb["all-gather"] == 512 * 2
    assert cb["reduce-scatter"] == 2 * 64 * 4
    assert cb["collective-permute"] == 16
    assert cb["total"] == sum(v for k, v in cb.items() if k != "total")
