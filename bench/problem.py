"""The benchmark's own loss and the session that the cells drive.

The loss is user code, as the paper's users write it: the mean logistic
loss of one worker's padded-ELL rows (the smooth part of eq. 22), a
gather of z at the nonzero columns and a sum per row. It opens the named
scope ``GRAD_SCOPE``, so the trace reduction finds its forward ops and
their transposes (the scatter-add of the gradient) by name.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

GRAD_SCOPE = "bench_sparse_logreg"


def sparse_logreg_loss(z, rows):
    """Mean log(1 + exp(-y a.z)) over one worker's rows; ``rows`` is
    (idx (m, r), val (m, r), y (m,))."""
    idx, val, y = rows
    with jax.named_scope(GRAD_SCOPE):
        margin = jnp.sum(val * z[idx], axis=-1)
        return jnp.mean(jax.nn.softplus(-y * margin))


def admm_config(cfg: dict, admm_seed: int, minibatch=None):
    from repro.configs.base import ADMMConfig
    a = cfg["admm"]
    return ADMMConfig(rho=a["rho"], gamma=a["gamma"],
                      max_delay=a["max_delay"],
                      block_fraction=a["block_fraction"],
                      block_selection=a["block_selection"],
                      num_blocks=cfg["num_blocks"], l1_coef=a["l1_coef"],
                      clip=a["clip"], backend=a["backend"],
                      minibatch=minibatch, seed=admm_seed)


def make_mesh(cfg: dict):
    """The cell's (data, model) mesh, or None on one chip."""
    m = cfg.get("mesh")
    if not m:
        return None
    from repro.launch.mesh import make_test_mesh
    return make_test_mesh(devices=m["data"] * m["model"], model=m["model"])


def data_shardings(cfg: dict, admm, mesh, data_shapes):
    """Where the rows live: the placement the session gives its data
    (``core/sharded.py::consensus_data_shardings``), so the generator
    makes each chip's rows on that chip. None on one chip."""
    if mesh is None:
        return None
    from repro.core.consensus import make_problem
    from repro.core.sharded import consensus_data_shardings
    spec = make_problem(sparse_logreg_loss, data_shapes,
                        dim=cfg["features"], num_blocks=cfg["num_blocks"]
                        ).spec(admm, mesh=mesh)
    return consensus_data_shardings(spec, data_shapes)


def session(cfg: dict, data, admm, mesh):
    from repro.api import ConsensusSession
    return ConsensusSession.flat(sparse_logreg_loss, data,
                                 dim=cfg["features"], cfg=admm, mesh=mesh)
