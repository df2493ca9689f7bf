"""The comparison that decides ``correct`` fails where it must.

At a size a test run holds (tiny.py): the control, the plain reference
computed in bfloat16 and put in the program's place, reads over the
cell's limits; and each fault planted under the timed path (faults.py)
turns a whole run's ``correct`` false. A sound run is correct. The
four-device cell runs in a child process with four CPU devices.

    JAX_PLATFORMS=cpu python -m pytest -q bench/tests
"""
from __future__ import annotations

import importlib
import json
import os
import pathlib
import subprocess
import sys

import jax.numpy as jnp
import pytest

HERE = pathlib.Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))

import tiny  # noqa: E402  (puts bench/ and src/ on the path)
import faults  # noqa: E402

import check  # noqa: E402

ONE_CHIP = ["kdda.minibatch", "kdda.full", "kdda.ps"]
FOUR_CHIP = ["kddb.full4"]


def child(cell: str, fault: str = "") -> dict:
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    out = subprocess.run([sys.executable, str(HERE / "tiny.py"), cell]
                         + ([fault] if fault else []), env=env,
                         capture_output=True, text=True, timeout=900)
    assert out.returncode == 0, out.stderr[-3000:]
    return json.loads(out.stdout.strip().splitlines()[-1])


@pytest.mark.parametrize("cell", ONE_CHIP)
def test_control_reads_over_the_limits(cell):
    c = tiny.tiny_cell(cell)
    driver = importlib.import_module(f"drivers.{c['traffic']['driver']}")
    run = driver.setup(c, tiny.SEED)
    driver.drop_program(run)
    ref = driver.reference_run(run, jnp.float32)
    control = driver.reference_run(run, jnp.bfloat16)
    assert not check.judge(check.gaps(control, ref), c["limits"])["correct"]
    assert check.judge(check.gaps(run.checked, ref), c["limits"])["correct"]


@pytest.mark.parametrize("fault", ["state_unchanged", "half_batch",
                                   "answer_altered"])
@pytest.mark.parametrize("cell", ONE_CHIP)
def test_fault_is_not_correct(cell, fault, monkeypatch):
    faults.FAULTS[fault](monkeypatch.setattr)
    line = tiny.measure(cell)
    assert line["correct"] is False, line["checks"]
    assert line["failed"] >= 1


@pytest.mark.parametrize("fault", ["", "state_unchanged", "half_batch",
                                   "no_exchange", "no_model_exchange",
                                   "answer_altered"])
def test_four_device_fault_is_not_correct(fault):
    line = child(FOUR_CHIP[0], fault)
    assert line["correct"] is (fault == ""), line["checks"]


@pytest.mark.parametrize("cell,fault", [
    *[(c, "half_batch") for c in ONE_CHIP],
    ("kddb.full4", "no_exchange"), ("kddb.full4", "no_model_exchange")])
def test_reference_fault_reads_over_the_limits(cell, fault, monkeypatch):
    """The faults that bench/tools/calibrate.py plants in the reference
    at a cell's own size read over the limits at a tiny size too; the
    mesh's faults are planted in a reference that runs on one device."""
    c = tiny.tiny_cell(cell)
    driver = importlib.import_module(f"drivers.{c['traffic']['driver']}")
    run = driver.setup(dict(c, config=dict(c["config"], mesh=None)),
                       tiny.SEED)
    driver.drop_program(run)
    ref = driver.reference_run(run, jnp.float32)
    faults.REFERENCE_FAULTS[fault](c["config"], monkeypatch.setattr)
    bad = driver.reference_run(run, jnp.float32)
    assert not check.judge(check.gaps(bad, ref), c["limits"])["correct"]
    unchanged = check.gaps(faults.unchanged(ref), ref)
    assert unchanged["w_gap"] == unchanged["z_gap"] == 1.0
