"""Sparse logistic-regression rows shaped like KDD Cup 2010, made on the
device from a seed.

Each worker holds ``samples / num_workers`` rows in padded-ELL form:
``idx`` (N, m, r) int32 column indices, sorted within a row, ``val``
(N, m, r) float32 values and labels ``y`` (N, m) in {-1, +1}. The
statistics follow ``data/sparse.py``: a ``locality`` share of each row's
columns from the worker's private band of ``features / N``, the rest
from a 1/(rank+1) power-law pool over all features, a planted weight
vector of density ``weight_density`` and label noise ``label_noise``.

Worker i's rows come from ``fold_in(key, i)`` alone, so the rows do not
depend on how the workers are laid out over chips: the generator runs
as one jitted call, with the output sharding of the cell, and every
chip makes the rows it holds.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

_EXACT_F32 = 1 << 24          # above this float32 integers step by 2


def seed_words(seed: int) -> tuple:
    """Two 31-bit words from any non-negative seed: the data key and the
    ADMM seed (``ADMMConfig.seed``), independent of each other."""
    words = np.random.SeedSequence(int(seed)).generate_state(2, np.uint32)
    return int(words[0] >> 1), int(words[1] >> 1)


def shape_of(cfg: dict) -> dict:
    n = cfg["num_workers"]
    return {"num_workers": n, "rows": cfg["samples"] // n,
            "nnz": cfg["nnz_per_row"], "features": cfg["features"]}


def _power_law(key, shape, features: int):
    """Column ranks with P(k) proportional to 1/(k+1), k < features: the
    inverse CDF of the density 1/x on [1, features+1), plus one random
    low bit where float32 cannot tell neighbouring integers apart."""
    ku, kb = jax.random.split(key)
    u = jax.random.uniform(ku, shape)
    x = jnp.exp(u * np.float32(np.log(features + 1.0)))
    k = jnp.floor(x).astype(jnp.int32) - 1
    low = jax.random.bernoulli(kb, 0.5, shape).astype(jnp.int32)
    k = jnp.where(k >= _EXACT_F32, (k & ~1) + low, k)
    return jnp.clip(k, 0, features - 1)


def _worker_rows(key, w_true, i, *, rows: int, nnz: int, features: int,
                 num_workers: int, locality: float, label_noise: float):
    k = jax.random.fold_in(key, i)
    k_local, k_shared, k_val, k_noise, k_label = jax.random.split(k, 5)
    n_local = int(locality * nnz)
    band = features // num_workers
    local = jax.random.randint(k_local, (rows, n_local), i * band,
                               (i + 1) * band, jnp.int32)
    shared = _power_law(k_shared, (rows, nnz - n_local), features)
    idx = jnp.sort(jnp.concatenate([local, shared], axis=1), axis=1)
    val = jax.random.normal(k_val, (rows, nnz), jnp.float32)
    logits = (jnp.sum(val * w_true[idx], axis=1)
              + label_noise * jax.random.normal(k_noise, (rows,)))
    y = jnp.where(jax.random.uniform(k_label, (rows,))
                  < jax.nn.sigmoid(logits), 1.0, -1.0).astype(jnp.float32)
    return idx, val, y


def make_rows(cfg: dict, seed: int, sharding=None):
    """(idx, val, y) for the configuration ``cfg`` from ``seed``, made in
    one jitted call; ``sharding`` (a pytree of three shardings, or None)
    places the result."""
    s = shape_of(cfg)
    g = cfg["generator"]
    key = jax.random.PRNGKey(seed_words(seed)[0])

    def make(key):
        k_mask, k_w, k_rows = jax.random.split(key, 3)
        f = s["features"]
        w_true = jnp.where(
            jax.random.uniform(k_mask, (f,)) < g["weight_density"],
            jax.random.normal(k_w, (f,)), 0.0)
        per_worker = functools.partial(
            _worker_rows, k_rows, w_true, rows=s["rows"], nnz=s["nnz"],
            features=f, num_workers=s["num_workers"],
            locality=g["locality"], label_noise=g["label_noise"])
        return jax.vmap(per_worker)(jnp.arange(s["num_workers"]))

    return jax.jit(make, out_shardings=sharding)(key)
