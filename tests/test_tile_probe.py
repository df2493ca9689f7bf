"""benchmarks/tile_probe.py off the chip: it offers only tiles Mosaic
accepts, refuses the CPU, and its timing loop runs at a tiny shape with
the kernels interpreted."""
import pathlib

import pytest

ROOT = pathlib.Path(__file__).resolve().parents[1]


@pytest.fixture
def tile_probe(monkeypatch):
    monkeypatch.syspath_prepend(str(ROOT / "benchmarks"))
    import tile_probe
    return tile_probe


@pytest.mark.parametrize("M,d,blk_ds,want", [
    (64, 315904, (512,), [(8, 512), (16, 512), (32, 512), (64, 512)]),
    (12, 256, (128, 256, 384), [(12, 128), (12, 256)]),
    (1, 256, (128,), [(1, 128)]),
])
def test_chip_tiles(tile_probe, M, d, blk_ds, want):
    assert tile_probe.chip_tiles(M, d, blk_ds) == want


def test_tile_probe_refuses_cpu(tile_probe, capsys):
    assert tile_probe.main(["--shape", "2", "8", "128", "--reps", "1"]) == 1
    assert "no TPU" in capsys.readouterr().err


def test_tile_probe_rows_tiny(tile_probe):
    rows = tile_probe.probe(2, 16, 256, blk_ds=(128,), reps=1,
                            interpret=True)
    assert [r[:3] for r in rows] == [
        ("worker", 8, 128), ("server", 8, 128),
        ("worker", 16, 128), ("server", 16, 128),
        ("worker_jnp", 0, 0), ("server_jnp", 0, 0)]
    assert all(ms > 0 for *_, ms in rows)
