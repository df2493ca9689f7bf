"""chip_smoke.py off the chip: it refuses the CPU, and its phases run
end to end at a tiny table with the kernels interpreted."""
import os
import pathlib
import subprocess
import sys

import pytest

ROOT = pathlib.Path(__file__).resolve().parents[1]


def test_chip_smoke_refuses_cpu():
    env = dict(os.environ, PYTHONPATH=str(ROOT / "src"), JAX_PLATFORMS="cpu")
    r = subprocess.run([sys.executable, str(ROOT / "chip_smoke.py")], env=env,
                       capture_output=True, text=True, timeout=300)
    assert r.returncode != 0
    assert '"ok"' not in r.stdout
    assert "no TPU" in r.stderr


@pytest.fixture
def smoke(monkeypatch):
    """chip_smoke with ``backend="auto"`` steered to the interpreted
    kernels and no native-kernel requirement (this is the CPU)."""
    monkeypatch.syspath_prepend(str(ROOT))
    import chip_smoke
    import repro.core.space as space
    resolve = space.resolve_backend
    monkeypatch.setattr(space, "resolve_backend",
                        lambda b=None: resolve("pallas" if b in (None, "auto")
                                               else b))
    monkeypatch.setattr(chip_smoke, "NATIVE_KERNEL", "")
    return chip_smoke


def test_chip_smoke_phases_tiny(smoke, capsys):
    import jax.numpy as jnp
    N, M, dblk = 8, 8, 128
    raw = smoke.make_data(0, n=N, dim=M * dblk)
    cfg = smoke.admm_config(0, num_blocks=M)
    data = (jnp.asarray(raw.X), jnp.asarray(raw.y))
    sess = smoke.phase_epoch(data, raw.support, M * dblk, cfg, epochs=3)
    smoke.phase_ps(sess, data, raw.support, M * dblk, cfg, rounds=2)
    out = capsys.readouterr().out
    assert "pallas vs jnp: max |dz|" in out
    assert "ps: replay bitwise equal: True" in out
