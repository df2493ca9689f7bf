"""CPU rehearsal of the harness, and compiles at full size for a v5e.

* Each cell runs end to end at a tiny size (tiny.py) on the CPU, with
  the Pallas kernels interpreted, and prints a line of the contract's
  shape; the four-chip cell runs in a child process with four CPU
  devices.
* ``run.py`` refuses a machine without a TPU, and a directory that holds
  only the benchmark (no program), printing no result.
* The steps of ``kdda.minibatch`` (one chip) and ``kddb.full4`` (a 2x2
  mesh) compile at full size for a described v5e; the compiled memory is
  printed, and KDDb's one-chip step is shown not to fit one chip.

    JAX_PLATFORMS=cpu python -m pytest -q -s bench/tests/test_rehearsal.py
"""
from __future__ import annotations

import dataclasses
import json
import os
import pathlib
import shutil
import subprocess
import sys

import numpy as np
import pytest

HERE = pathlib.Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))

import tiny  # noqa: E402  (puts bench/ and src/ on the path)

import gen  # noqa: E402
import problem  # noqa: E402
import run as harness  # noqa: E402

ROOT = HERE.parents[1]
V5E_HBM = 16e9
KEYS = ["correct", "attempted", "failed", "metrics", "device", "checks"]


def _shape_ok(line: dict, cell: str, chips: int) -> None:
    assert list(line) == KEYS                 # the checks come last
    assert line["correct"] is True, line["checks"]
    assert line["attempted"] >= 1 and line["failed"] == 0
    want = {m["name"] for m in harness.load_cell(cell)["end_to_end"]}
    assert set(line["metrics"]) == want
    for m in line["metrics"].values():
        assert m["value"] > 0 and m["unit"]
    assert line["device"]["count"] == chips
    for v in line["checks"].values():
        assert set(v) == {"value", "limit"}


@pytest.mark.parametrize("cell", ["kdda.minibatch", "kdda.full", "kdda.ps"])
def test_one_chip_cell_line(cell):
    _shape_ok(tiny.measure(cell), cell, 1)


def test_four_chip_cell_line():
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    out = subprocess.run([sys.executable, str(HERE / "tiny.py"),
                          "kddb.full4"], env=env, capture_output=True,
                         text=True, timeout=900)
    assert out.returncode == 0, out.stderr[-3000:]
    _shape_ok(json.loads(out.stdout.strip().splitlines()[-1]),
              "kddb.full4", 4)


def test_per_layer_metric_without_workloads_goes_to_its_moves_cells(
        monkeypatch):
    """A per-layer metric that a later PR adds without ``workloads`` is
    read in every cell that reports the end-to-end metric it moves."""
    orig = harness.load_json

    def load_json(path):
        d = orig(path)
        if path.name == "BENCHMARK.json":
            d["per_layer"].append({"name": "added_ms", "moves": "ps_round_ms"})
        return d
    monkeypatch.setattr(harness, "load_json", load_json)
    has = {c: "added_ms" in {m["name"] for m in
                             harness.load_cell(c)["per_layer"]}
           for c in ("kdda.ps", "kdda.minibatch", "kddb.full4")}
    assert has == {"kdda.ps": True, "kdda.minibatch": False,
                   "kddb.full4": False}


def _bench(cwd, *args):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "kdda.minibatch",
         "--seed", "1", "--seconds", "1", "--trace", "0", *args], cwd=cwd,
        env=env, capture_output=True, text=True, timeout=300)


def test_refuses_without_tpu():
    out = _bench(ROOT)
    assert out.returncode != 0
    assert "no TPU" in out.stderr
    assert out.stdout.strip() == ""


def test_refuses_without_the_program(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    out = _bench(tmp_path)
    assert out.returncode != 0
    assert out.stdout.strip() == ""


# --- compiles at full size for a described v5e (nothing runs) ----------

@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:       # no TPU compiler, or the library is held
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture
def native_kernels(monkeypatch):
    """Kernels compile with Mosaic, as on a chip."""
    import jax
    from repro.kernels import tiling
    monkeypatch.setattr(tiling, "default_interpret", lambda: False)
    jax.clear_caches()
    yield
    jax.clear_caches()


def _compiled_step(name: str, devices, mesh_shape=None):
    """The cell's jitted epoch at full size, compiled for ``devices``."""
    import jax
    from jax.sharding import (AxisType, Mesh, NamedSharding,
                              PartitionSpec as P, SingleDeviceSharding)
    from repro.core.consensus import make_problem
    from repro.core.sharded import (consensus_data_specs,
                                    consensus_state_specs)
    from repro.core.space import asybadmm_epoch, init_consensus_state
    cell = harness.load_cell(name)
    cfg = dict(cell["config"], admm=dict(cell["config"]["admm"],
                                         backend="pallas"))
    s = gen.shape_of(cfg)
    n, m, r = s["num_workers"], s["rows"], s["nnz"]
    data = (jax.ShapeDtypeStruct((n, m, r), np.int32),
            jax.ShapeDtypeStruct((n, m, r), np.float32),
            jax.ShapeDtypeStruct((n, m), np.float32))
    admm = problem.admm_config(cfg, 1, minibatch=cell["traffic"]["minibatch"])
    spec = make_problem(problem.sparse_logreg_loss, data, dim=cfg["features"],
                        num_blocks=cfg["num_blocks"]).spec(admm)
    state = jax.eval_shape(lambda: init_consensus_state(spec))
    if mesh_shape is None:
        one = SingleDeviceSharding(devices[0])
        put = lambda tree, _: jax.tree.map(       # noqa: E731
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one),
            tree)
        specs = (None, None)
    else:
        mesh = Mesh(np.asarray(devices[:4]).reshape(mesh_shape),
                    ("data", "model"), axis_types=(AxisType.Auto,) * 2)
        spec = dataclasses.replace(
            spec, space=dataclasses.replace(spec.space, mesh=mesh))
        put = lambda tree, specs: jax.tree.map(   # noqa: E731
            lambda a, p: jax.ShapeDtypeStruct(
                a.shape, a.dtype, sharding=NamedSharding(mesh, p)),
            tree, specs, is_leaf=lambda v: isinstance(v, P))
        specs = (consensus_state_specs(spec, state),
                 consensus_data_specs(spec, data))
    return jax.jit(lambda st, d: asybadmm_epoch(spec, st, d)).lower(
        put(state, specs[0]), put(data, specs[1])).compile()


def _bytes(c) -> int:
    m = c.memory_analysis()
    return (m.argument_size_in_bytes + m.output_size_in_bytes
            + m.temp_size_in_bytes - m.alias_size_in_bytes)


def test_kdda_minibatch_step_fits_one_chip(topo, native_kernels):
    c = _compiled_step("kdda.minibatch", topo.devices)
    assert c.as_text().count("tpu_custom_call") >= 2
    print(f"\nkdda.minibatch step, one v5e: {c.memory_analysis()}")
    assert _bytes(c) < V5E_HBM


def test_kddb_step_sharded_on_2x2(topo, native_kernels):
    c = _compiled_step("kddb.full4", topo.devices, (2, 2))
    text = c.as_text()
    assert "tpu_custom_call" in text
    assert "all-reduce" in text and "all-to-all" in text
    print(f"\nkddb.full4 step, per chip of a 2x2 v5e: "
          f"{c.memory_analysis()}")
    assert _bytes(c) < V5E_HBM


def test_kddb_step_does_not_fit_one_chip(topo, native_kernels):
    """On one chip the compiler itself refuses KDDb's step: it needs more
    HBM than a v5e has."""
    from jax.errors import JaxRuntimeError
    with pytest.raises(JaxRuntimeError, match="RESOURCE_EXHAUSTED") as e:
        _compiled_step("kddb.full4", topo.devices)
    print("\nkddb step on one v5e: "
          + " ".join(str(e.value).split("\n")[0].split()[:40]))
