"""Paper Fig. 2 analogue: AsyBADMM convergence on sparse logistic
regression (synthetic KDDa-like data), sync vs async at several delay
bounds, plus the stationarity metric P (Theorem 1.3).

CSV columns: name, us_per_call (per-epoch wall time), derived
(final objective | final P).
"""
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.api import ConsensusSession
from repro.configs.base import ADMMConfig
from repro.data import make_sparse_logreg

EPOCHS = 600
EVAL_EVERY = 100


def build_session(cfg, num_workers=8, dim=512, samples=64, seed=0):
    data = make_sparse_logreg(num_workers=num_workers,
                              samples_per_worker=samples, dim=dim,
                              density=0.1, seed=seed)

    def loss_fn(z, d):
        X, y = d
        return jnp.mean(jnp.log1p(jnp.exp(-y * (X @ z))))

    return ConsensusSession.flat(
        loss_fn, (jnp.asarray(data.X), jnp.asarray(data.y)), dim=dim,
        cfg=cfg, support=data.support, l1_coef=1e-3, clip=1e4)


def run_one(sess, epochs=EPOCHS):
    state = sess.init()
    step = sess.step_fn()
    state, _ = step(state, sess.data)        # compile
    jax.block_until_ready(state.z_hist)
    t0 = time.perf_counter()
    trace = []
    for t in range(epochs):
        state, _ = step(state, sess.data)
        if (t + 1) % EVAL_EVERY == 0:
            trace.append(sess.objective(state))
    jax.block_until_ready(state.z_hist)
    dt = (time.perf_counter() - t0) / epochs
    P = float(sess.stationarity(state)["P"])
    return dt * 1e6, trace, P


def main(emit=print):
    variants = [
        ("fig2_sync_D0", ADMMConfig(rho=2.0, gamma=0.0, max_delay=0,
                                    block_fraction=1.0, num_blocks=16)),
        ("fig2_async_D2", ADMMConfig(rho=2.0, gamma=0.1, max_delay=2,
                                     block_fraction=0.5, num_blocks=16, seed=1)),
        ("fig2_async_D4", ADMMConfig(rho=2.0, gamma=0.1, max_delay=4,
                                     block_fraction=0.5, num_blocks=16, seed=2)),
        ("fig2_async_D8", ADMMConfig(rho=2.0, gamma=0.2, max_delay=8,
                                     block_fraction=0.5, num_blocks=16, seed=3)),
        ("fig2_fullvec_async", ADMMConfig(rho=2.0, gamma=0.1, max_delay=2,
                                          block_fraction=1.0, num_blocks=1,
                                          seed=4)),
    ]
    for name, cfg in variants:
        us, trace, P = run_one(build_session(cfg))
        emit(f"{name},{us:.1f},obj={trace[-1]:.4f};P={P:.3e};"
             f"trace={'|'.join(f'{x:.3f}' for x in trace)}")


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    main()
