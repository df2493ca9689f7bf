"""Production mesh construction + mesh-shape helpers.

Defined as functions (never module-level constants) so importing this
module never touches jax device state — smoke tests must keep seeing the
single real CPU device; only dryrun.py forces 512 host devices.

Axis convention (consumed by the SPMD epoch in ``core/sharded.py``):
``data`` (+ optional outer ``pod``) shards the *worker* axis of the
consensus state — each worker's duals/w-cache live with its data shard —
and ``model`` shards the *block-server* axis (FlatSpace blocks; the
dryrun's tensor-parallel param dims in pytree mode).

Production target: TPU v5e, 256 chips/pod (16x16), optionally 2 pods.
  single pod : (data=16, model=16)            axes ("data", "model")
  multi pod  : (pod=2, data=16, model=16)     axes ("pod", "data", "model")
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def _auto_mesh(shape, axes, devices):
    """``jax.make_mesh`` with Auto axis types: the epoch and the dryrun
    place data with explicit ``NamedSharding``s and ``shard_map``, and
    leave every other propagation to the compiler (JAX's default
    Explicit axes reject the implicit gathers that relies on)."""
    return jax.make_mesh(shape, axes, (AxisType.Auto,) * len(axes),
                         devices=devices)


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    import numpy as np
    n = int(np.prod(shape))
    devs = jax.devices()
    if len(devs) < n:
        raise RuntimeError(
            f"need {n} devices (set XLA_FLAGS=--xla_force_host_platform_"
            f"device_count=512 before importing jax); have {len(devs)}")
    return _auto_mesh(shape, axes, devs[:n])


def make_test_mesh(devices: int = 8, model: int = 2):
    """Small (data, model) mesh over the first ``devices`` devices: the
    chips of one TPU host (``chip_smoke.py --chips 4``) or forced host
    CPU devices in integration tests.

    ``devices`` must split evenly into ``model`` columns and the process
    must see at least ``devices`` devices — both are validated eagerly
    so a bad count fails with an actionable message instead of an
    opaque reshape error."""
    if model <= 0 or devices <= 0:
        raise ValueError(f"devices={devices} and model={model} must be >= 1")
    if devices % model != 0:
        raise ValueError(
            f"make_test_mesh: devices={devices} does not divide into "
            f"model={model} columns (devices % model == {devices % model}); "
            f"pick devices as a multiple of the model axis")
    devs = jax.devices()
    if len(devs) < devices:
        raise RuntimeError(
            f"make_test_mesh: need {devices} devices but jax sees "
            f"{len(devs)} ({devs[0].platform}); run on a host with that "
            f"many chips, or on the CPU set XLA_FLAGS="
            f"--xla_force_host_platform_device_count={devices} before "
            f"importing jax")
    return _auto_mesh((devices // model, model), ("data", "model"),
                      devs[:devices])


MESH_PRESETS = ("none", "test", "pod", "multipod")


def resolve_mesh(mesh):
    """Resolve an ``ADMMConfig.mesh`` / CLI value to a Mesh or None.

    Accepts None / "none" (single-device epoch), an already-built mesh
    (anything with ``axis_names`` — ``jax.sharding.Mesh`` or an
    ``AbstractMesh`` for shape-only analysis), or a preset name:
    ``test`` (8 host devices, data=4 x model=2), ``pod``, ``multipod``.
    """
    if mesh is None or mesh == "none":
        return None
    if hasattr(mesh, "axis_names"):
        return mesh
    if mesh == "test":
        return make_test_mesh()
    if mesh == "pod":
        return make_production_mesh()
    if mesh == "multipod":
        return make_production_mesh(multi_pod=True)
    raise ValueError(f"unknown mesh {mesh!r}; expected None, a jax Mesh, "
                     f"or one of {MESH_PRESETS}")


def data_axes(mesh) -> tuple:
    return tuple(a for a in mesh.axis_names if a in ("pod", "data"))


def num_workers(mesh) -> int:
    n = 1
    for a in data_axes(mesh):
        n *= mesh.shape[a]
    return n


def model_axis_size(mesh) -> int:
    return mesh.shape.get("model", 1)
