"""Plain reference of AsyBADMM epochs (Algorithm 1) on the benchmark's
sparse logistic loss, written from the paper's equations in plain
``jax.numpy``. It imports nothing of the program.

Blocks are the paper's contiguous coordinate ranges: block j holds
coordinates ``[j*u, (j+1)*u)`` with ``u = ceil(dim / M)``; the state is
kept as (…, M, u) arrays, zero past ``dim``. One epoch, for the workers
i and blocks j:

* delays tau_ij ~ U{0..T}, and z~_i = z^{t - tau_ij} blockwise (the ring
  of the last T+1 consensus values, Assumption 3);
* a minibatch of each worker's rows (a uniform subset of fixed size),
  when the traffic asks for one;
* g_i = grad f_i(z~_i), f_i the mean logistic loss, by its formula:
  ``sum_r -y_r sigmoid(-y_r a_r.z) a_r / m``, scattered at the nonzeros;
* each worker updates a uniformly random ``block_fraction`` of its
  blocks: x = z~ - (g + y)/rho (11), y' = y + rho (x - z~) (12),
  w = rho x + y' (9); the other blocks keep their values;
* every block server: z' = prox((gamma z + sum_i w_ij) / mu), mu = gamma +
  sum_i rho, the prox of l1 then the box (13).

The random draws use the seed's key chain in the program's order (the
key splits into next, delay, selection[, minibatch] keys each epoch), so
the reference makes the same draws as the run it checks. ``dtype``
computes the whole epoch in another precision: the control.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np


@dataclasses.dataclass(frozen=True)
class Params:
    num_workers: int
    num_blocks: int
    dim: int
    rows: int                   # rows per worker
    rho: float
    gamma: float
    max_delay: int
    block_fraction: float
    l1_coef: float
    clip: float
    minibatch: Optional[float]  # fraction of rows per epoch, None = all

    @property
    def block_len(self) -> int:
        return -(-self.dim // self.num_blocks)

    @property
    def batch_rows(self) -> int:
        if self.minibatch is None:
            return self.rows
        return max(1, min(self.rows, int(round(self.minibatch * self.rows))))


def params_of(cfg: dict, minibatch: Optional[float], rows: int,
              dim: Optional[int] = None) -> Params:
    a = cfg["admm"]
    return Params(num_workers=cfg["num_workers"],
                  num_blocks=cfg["num_blocks"],
                  dim=cfg["features"] if dim is None else dim, rows=rows,
                  rho=a["rho"], gamma=a["gamma"], max_delay=a["max_delay"],
                  block_fraction=a["block_fraction"], l1_coef=a["l1_coef"],
                  clip=a["clip"], minibatch=minibatch)


class State(NamedTuple):
    z_hist: jax.Array           # (T+1, M, u), index 0 newest
    y: jax.Array                # (N, M, u)
    w: jax.Array                # (N, M, u)
    x: jax.Array                # (N, M, u)
    rng: jax.Array


def init(p: Params, admm_seed: int, dtype=jnp.float32) -> State:
    shape = (p.num_workers, p.num_blocks, p.block_len)
    return State(z_hist=jnp.zeros((p.max_delay + 1,) + shape[1:], dtype),
                 y=jnp.zeros(shape, dtype), w=jnp.zeros(shape, dtype),
                 x=jnp.zeros(shape, dtype),
                 rng=jax.random.PRNGKey(admm_seed))


def _draws(p: Params, rng, delays=None):
    if p.minibatch is not None:
        nxt, r_delay, r_sel, r_batch = jax.random.split(rng, 4)
    else:
        nxt, r_delay, r_sel = jax.random.split(rng, 3)
        r_batch = None
    N, M = p.num_workers, p.num_blocks
    if delays is not None:
        pass                  # staleness the run observed, not drawn
    elif p.max_delay > 0:
        delays = jax.random.randint(r_delay, (N, M), 0, p.max_delay + 1)
    else:
        delays = jnp.zeros((N, M), jnp.int32)
    if p.block_fraction >= 1.0:
        sel = jnp.ones((N, M), bool)
    else:
        k = max(1, int(round(p.block_fraction * M)))
        score = jax.random.gumbel(r_sel, (N, M))
        sel = score >= jax.lax.top_k(score, k)[0][:, -1:]
    rows = None
    if r_batch is not None:
        u = jax.random.uniform(r_batch, (N, p.rows))
        rows = jnp.argsort(u, axis=1)[:, :p.batch_rows]
    return nxt, delays, sel, rows


def _grad(p: Params, z_flat, idx, val, y, dtype):
    """Loss and gradient of one worker's mean logistic loss."""
    val = val.astype(dtype)
    margin = jnp.sum(val * z_flat[idx], axis=-1)
    t = -y.astype(dtype) * margin
    loss = jnp.mean(jnp.logaddexp(jnp.zeros((), dtype), t))
    coef = -y.astype(dtype) * jax.nn.sigmoid(t) / np.float32(idx.shape[0])
    g = jnp.zeros(z_flat.shape, dtype).at[idx].add(val * coef[:, None])
    return loss.astype(jnp.float32), g


def _grads(p: Params, z_t, rows, dtype, mesh):
    """Every worker's loss and gradient, one worker at a time on the chip
    that holds its rows (a mesh splits the workers over all its axes)."""
    def local(z, idx, val, y):
        return jax.lax.map(
            lambda a: _grad(p, *a, dtype=dtype), (z, idx, val, y))
    if mesh is not None:
        from jax.sharding import PartitionSpec as P
        spec = P(tuple(mesh.axis_names))
        local = jax.shard_map(local, mesh=mesh, in_specs=(spec,) * 4,
                              out_specs=(spec, spec))
    return local(z_t, *rows)


def _push(p: Params, w):
    """What every block server receives: the sum of its workers' w."""
    return jnp.sum(w, axis=0)


def epoch(p: Params, state: State, rows, delays=None, dtype=jnp.float32,
          mesh=None):
    """One epoch; returns (state, mean worker loss at z~). ``delays``
    (N, M) replaces the drawn staleness where a run recorded its own."""
    N, M, u = p.num_workers, p.num_blocks, p.block_len
    nxt, delays, sel, batch = _draws(p, state.rng, delays)
    if batch is not None:
        take = jnp.arange(N)[:, None]
        rows = tuple(a[take, batch] for a in rows)
    z_t = state.z_hist[delays, jnp.arange(M)[None, :]]         # (N, M, u)
    losses, g = _grads(p, z_t.reshape(N, M * u), rows, dtype, mesh)
    g = g.reshape(N, M, u)
    rho = jnp.asarray(p.rho, dtype)
    x_new = z_t - (g + state.y) / rho                             # (11)
    y_new = state.y + rho * (x_new - z_t)                         # (12)
    w_new = rho * x_new + y_new                                   # (9)
    keep = sel[:, :, None]
    y = jnp.where(keep, y_new, state.y)
    w = jnp.where(keep, w_new, state.w)
    x = jnp.where(keep, x_new, state.x)
    mu = jnp.asarray(p.gamma + N * p.rho, dtype)
    v = (jnp.asarray(p.gamma, dtype) * state.z_hist[0]
         + _push(p, w)) / mu                                      # (13)
    z = jnp.sign(v) * jnp.maximum(jnp.abs(v) - p.l1_coef / mu, 0)
    z = jnp.clip(z, -p.clip, p.clip).astype(dtype)
    z_hist = jnp.concatenate([z[None], state.z_hist[:-1]], axis=0)
    return State(z_hist=z_hist, y=y, w=w, x=x, rng=nxt), jnp.mean(losses)


def flat_z(p: Params, state: State) -> np.ndarray:
    """The newest consensus value as a float32 (dim,) host vector."""
    z = np.asarray(state.z_hist[0].astype(jnp.float32))
    return z.reshape(-1)[:p.dim]


def run(p: Params, admm_seed: int, rows, epochs: int, dtype=jnp.float32,
        mesh=None, delays=None):
    """[(z (dim,), loss, w (N, M, u))] after each of the first ``epochs``
    epochs, as float32 host arrays; ``delays`` (epochs, N, M), where
    given, is the staleness each epoch read."""
    step = jax.jit(functools.partial(epoch, p, dtype=dtype, mesh=mesh),
                   donate_argnums=(0,))
    state = init(p, admm_seed, dtype)
    if mesh is not None:
        from jax.sharding import NamedSharding, PartitionSpec as P
        workers = NamedSharding(mesh, P(tuple(mesh.axis_names)))
        state = state._replace(y=jax.device_put(state.y, workers),
                               w=jax.device_put(state.w, workers),
                               x=jax.device_put(state.x, workers))
    out = []
    with jax.default_matmul_precision("highest"):
        for t in range(epochs):
            state, loss = step(state, rows,
                               None if delays is None else delays[t])
            out.append((flat_z(p, state), float(loss),
                        np.asarray(state.w.astype(jnp.float32))))
    return out
