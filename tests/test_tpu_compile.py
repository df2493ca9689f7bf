"""Mosaic/XLA compiles for a described TPU v5e — no chip needed.

Interpret mode accepts tiles and operands the chip refuses, so every
other kernel test can pass while the chip rejects the kernel. These
tests compile the main path for a v5e described by
``jax.experimental.topologies`` (the TPU compiler ships with jaxlib):
the three epoch kernels at the ``kdda_like`` table, at the PS-commit
shape M=1 and at the smoke shape, the flash-attention kernel, the
pallas epoch, the SPMD epoch on a 2x2 mesh, and the jnp epoch's memory
fit on one chip. Nothing runs; a compile that passes is not a chip run.

The topology is described inside a module fixture, never at import:
only one process may load the TPU library, and xdist workers all
import every test file.
"""
import dataclasses
import math
import os
import re

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import (AxisType, Mesh, NamedSharding, PartitionSpec as P,
                          SingleDeviceSharding)

from repro.api import ConsensusSession
from repro.configs.base import ADMMConfig
from repro.core.blocks import make_flat_blocks
from repro.core.sharded import consensus_data_specs, consensus_state_specs
from repro.core.space import FlatSpace, asybadmm_epoch, init_consensus_state
from repro.kernels import tiling
from repro.kernels.admm_update import admm_worker_select_update_3d
from repro.kernels.flash_attention import flash_attention_bhsd
from repro.kernels.prox_update import prox_consensus_2d, server_prox_fused_2d

KDDA = (8, 64, 315904)               # benchmarks/kernels_bench.py kdda_like
SHAPES = {"kdda_like": KDDA, "ps_commit": (8, 1, 315904),
          "smoke": (4, 8, 256)}
NATIVE = "tpu_custom_call"
V5E_HBM = 16 * 2**30


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:       # no TPU compiler, or the library is held
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def native_kernels(monkeypatch):
    """Kernels that resolve ``interpret=None`` compile with Mosaic, as
    they do on a chip (this process's backend is the CPU)."""
    monkeypatch.setattr(tiling, "default_interpret", lambda: False)
    jax.clear_caches()
    yield
    jax.clear_caches()


def _sds(shape, sharding, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _compile(fn, *args):
    return jax.jit(fn).lower(*args).compile()


@pytest.mark.parametrize("shape", list(SHAPES), ids=list(SHAPES))
def test_worker_kernel_compiles(one_chip, shape):
    N, M, d = SHAPES[shape]
    t = _sds((N, M, d), one_chip)
    c = _compile(lambda *a: admm_worker_select_update_3d(*a, interpret=False),
                 t, t, t, t, _sds((N, M, 1), one_chip), _sds((N,), one_chip),
                 t)
    assert NATIVE in c.as_text()


@pytest.mark.parametrize("shape", list(SHAPES), ids=list(SHAPES))
def test_server_kernel_compiles(one_chip, shape):
    N, M, d = SHAPES[shape]
    c = _compile(
        lambda z, w, e, r: server_prox_fused_2d(z, w, e, r, 0.1, 1e-3, 1e4,
                                                interpret=False),
        _sds((M, d), one_chip), _sds((N, M, d), one_chip),
        _sds((N, M, 1), one_chip), _sds((M, 1), one_chip))
    assert NATIVE in c.as_text()


@pytest.mark.parametrize("shape", list(SHAPES), ids=list(SHAPES))
def test_prox_kernel_compiles(one_chip, shape):
    _, M, d = SHAPES[shape]
    c = _compile(
        lambda z, w, r: prox_consensus_2d(z, w, r, 0.1, 1e-3, 1e4,
                                          interpret=False),
        _sds((M, d), one_chip), _sds((M, d), one_chip),
        _sds((M, 1), one_chip))
    assert NATIVE in c.as_text()


def test_flash_attention_compiles(one_chip):
    q = _sds((2, 256, 128), one_chip)
    c = _compile(lambda q, k, v: flash_attention_bhsd(q, k, v,
                                                      interpret=False),
                 q, q, q)
    assert NATIVE in c.as_text()


def _logreg(z, d):
    X, y = d
    return jnp.mean(jnp.log1p(jnp.exp(-y * (X @ z))))


def _kdda_session(backend):
    N, M, d = KDDA
    cfg = ADMMConfig(rho=2.0, gamma=0.1, max_delay=1, block_fraction=0.5,
                     num_blocks=M, l1_coef=1e-3, clip=1e4)
    data = (jax.ShapeDtypeStruct((N, 4, M * d), jnp.float32),
            jax.ShapeDtypeStruct((N, 4), jnp.float32))
    return ConsensusSession.flat(_logreg, data, dim=M * d, cfg=cfg,
                                 backend=backend)


def _place(tree, sharding):
    return jax.tree.map(lambda s: _sds(s.shape, sharding, s.dtype), tree)


def _compile_epoch(sess, sharding):
    state = _place(jax.eval_shape(sess.init), sharding)
    return sess.step_fn().lower(state, _place(sess.data, sharding)).compile()


def test_jnp_kdda_epoch_fits_one_chip(one_chip):
    """The jnp flat epoch at the paper's table fits one v5e's HBM."""
    c = _compile_epoch(_kdda_session("jnp"), one_chip)
    m = c.memory_analysis()
    total = (m.argument_size_in_bytes + m.output_size_in_bytes
             + m.temp_size_in_bytes - m.alias_size_in_bytes)
    assert total < V5E_HBM, total


def test_pallas_kdda_epoch_compiles(one_chip, native_kernels):
    sess = _kdda_session("pallas")
    c = _compile_epoch(sess, one_chip)
    assert c.as_text().count(NATIVE) >= 2       # worker + server kernels
    m = c.memory_analysis()
    assert (m.argument_size_in_bytes + m.output_size_in_bytes
            + m.temp_size_in_bytes) < V5E_HBM


def test_sharded_kdda_epoch_compiles(topo, native_kernels):
    """The SPMD pallas epoch over (data=2, model=2) of a 2x2 host."""
    mesh = Mesh(np.asarray(topo.devices[:4]).reshape(2, 2),
                ("data", "model"), axis_types=(AxisType.Auto,) * 2)
    sess = _kdda_session("pallas")
    spec = dataclasses.replace(
        sess.spec, space=dataclasses.replace(sess.spec.space, mesh=mesh))
    state = jax.eval_shape(lambda: init_consensus_state(sess.spec))
    named = lambda specs, tree: jax.tree.map(
        lambda s, p: _sds(s.shape, NamedSharding(mesh, p), s.dtype), tree,
        specs, is_leaf=lambda v: isinstance(v, P))
    c = jax.jit(lambda s, d: asybadmm_epoch(spec, s, d)).lower(
        named(consensus_state_specs(spec, state), state),
        named(consensus_data_specs(spec, sess.data), sess.data)).compile()
    text = c.as_text()
    assert NATIVE in text
    assert "all-reduce" in text and "all-to-all" in text


KDDA_FEATURES, KDDA_NNZ = 20_216_830, 36        # bench kdda_l1logreg


def _sparse_logreg(z, rows):
    idx, val, y = rows
    return jnp.mean(jax.nn.softplus(-y * jnp.sum(val * z[idx], axis=-1)))


def _element_counts(hlo: str) -> set:
    return {math.prod(int(d) for d in dims.split(",") if d)
            for dims in re.findall(r"\b[a-z]+[0-9]*\[([0-9,]*)\]", hlo)}


@pytest.mark.parametrize("rows,temp_cap",
                         [(1_026, 0.5e9), (1_050_969, 2e9)],
                         ids=["minibatch", "full_batch"])
def test_worker_grads_holds_no_batched_flat_view(one_chip, rows, temp_cap):
    """``worker_grads`` at the KDDa table (N=8, M=64) differentiates each
    worker against its own (d,) vector: no array of N*d (or N*M*used_dim)
    elements, the batched view a vmap over workers writes out, and
    temporaries of one worker's vector and rows, not N of them."""
    N, M = 8, 64
    space = FlatSpace(blocks=make_flat_blocks(KDDA_FEATURES, M),
                      num_workers=N)
    b = space.blocks
    data = (_sds((N, rows, KDDA_NNZ), one_chip, jnp.int32),
            _sds((N, rows, KDDA_NNZ), one_chip), _sds((N, rows), one_chip))
    c = _compile(lambda zt, d: space.worker_grads(_sparse_logreg, zt, d),
                 _sds((N, M, b.block_dim), one_chip), data)
    counts = _element_counts(c.as_text())
    assert b.dim in counts                       # the per-worker vector
    assert not counts & {N * b.dim, N * b.logical_dim}
    temp = c.memory_analysis().temp_size_in_bytes
    assert temp < temp_cap, temp
