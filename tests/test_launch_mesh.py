"""launch/mesh.py helpers — shape math, presets, and the eager
validation that replaced the silent ``devices // model`` reshape.

Helper functions only need ``axis_names`` / ``shape``, so they are
exercised against ``AbstractMesh`` (no forced host devices); the
device-count error paths are exercised against this container's real
single CPU device.
"""
import jax
import pytest
from jax.sharding import AbstractMesh

from repro.launch.mesh import (MESH_PRESETS, data_axes, make_production_mesh,
                               make_test_mesh, model_axis_size, num_workers,
                               resolve_mesh)


def _amesh(*shape_tuple):
    names, sizes = zip(*shape_tuple)
    return AbstractMesh(sizes, names)


def test_helpers_single_pod():
    m = _amesh(("data", 16), ("model", 16))
    assert data_axes(m) == ("data",)
    assert num_workers(m) == 16
    assert model_axis_size(m) == 16


def test_helpers_multi_pod():
    m = _amesh(("pod", 2), ("data", 16), ("model", 16))
    assert data_axes(m) == ("pod", "data")
    assert num_workers(m) == 32             # workers span pod x data
    assert model_axis_size(m) == 16


def test_helpers_no_model_axis():
    m = _amesh(("data", 8),)
    assert data_axes(m) == ("data",)
    assert num_workers(m) == 8
    assert model_axis_size(m) == 1          # missing axis = unsharded blocks


def test_test_mesh_shape():
    m = _amesh(("data", 4), ("model", 2))   # what make_test_mesh(8) builds
    assert num_workers(m) * model_axis_size(m) == 8


def test_make_test_mesh_rejects_non_divisible():
    with pytest.raises(ValueError, match="devices=6 does not divide"):
        make_test_mesh(6, model=4)
    with pytest.raises(ValueError, match="does not divide"):
        make_test_mesh(7)                   # default model=2
    with pytest.raises(ValueError, match="must be >= 1"):
        make_test_mesh(8, model=0)


def test_make_test_mesh_reports_missing_devices():
    """With too few host devices the error must name the XLA_FLAGS fix,
    not die in jax.make_mesh."""
    if jax.device_count() >= 512:
        pytest.skip("container already forces many host devices")
    with pytest.raises(RuntimeError, match="xla_force_host_platform"):
        make_test_mesh(512)


def test_make_production_mesh_reports_missing_devices():
    if jax.device_count() >= 256:
        pytest.skip("container already forces many host devices")
    with pytest.raises(RuntimeError, match="need 256 devices"):
        make_production_mesh()
    with pytest.raises(RuntimeError, match="need 512 devices"):
        make_production_mesh(multi_pod=True)


def test_resolve_mesh():
    assert resolve_mesh(None) is None
    assert resolve_mesh("none") is None
    m = _amesh(("data", 4), ("model", 2))
    assert resolve_mesh(m) is m             # pass-through for built meshes
    with pytest.raises(ValueError, match="unknown mesh"):
        resolve_mesh("v5e")
    assert set(MESH_PRESETS) == {"none", "test", "pod", "multipod"}
