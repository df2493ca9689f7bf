"""Paper §5 reproduction: sparse L1 logistic regression (eq. 22) on
synthetic KDDa-like data — sync vs async vs full-vector, with the fused
Pallas gradient kernel cross-checked against autodiff.

    PYTHONPATH=src python examples/sparse_logreg_admm.py [--dim 1024]
"""
import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.api import ConsensusSession
from repro.configs.base import ADMMConfig
from repro.data import make_sparse_logreg
from repro.kernels import ops, ref


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--dim", type=int, default=1024)
    ap.add_argument("--workers", type=int, default=8)
    ap.add_argument("--samples", type=int, default=96)
    ap.add_argument("--epochs", type=int, default=600)
    args = ap.parse_args()

    data = make_sparse_logreg(num_workers=args.workers,
                              samples_per_worker=args.samples,
                              dim=args.dim, density=0.08, seed=0)

    def loss_fn(z, d):
        X, y = d
        return jnp.mean(jnp.log1p(jnp.exp(-y * (X @ z))))

    def session_for(cfg: ADMMConfig) -> ConsensusSession:
        return ConsensusSession.flat(
            loss_fn, (jnp.asarray(data.X), jnp.asarray(data.y)),
            dim=args.dim, cfg=cfg, support=data.support,
            l1_coef=1e-3, clip=1e4)

    # --- kernel cross-check: fused Pallas gradient == autodiff gradient ---
    X0, y0 = jnp.asarray(data.X[0]), jnp.asarray(data.y[0])
    w = jnp.zeros(args.dim)
    g_kernel = ops.logreg_grad(X0, y0, w)
    g_auto = jax.grad(lambda z: loss_fn(z, (X0, y0)))(w)
    print(f"pallas logreg_grad vs autodiff: max|Δ| = "
          f"{float(jnp.max(jnp.abs(g_kernel - g_auto))):.2e}")

    variants = {
        "sync (block, D=0)": ADMMConfig(rho=2.0, gamma=0.0, max_delay=0,
                                        block_fraction=1.0, num_blocks=16),
        "AsyBADMM (D=2, 50% blocks)": ADMMConfig(rho=2.0, gamma=0.1,
                                                 max_delay=2,
                                                 block_fraction=0.5,
                                                 num_blocks=16, seed=1),
        "full-vector async (M=1)": ADMMConfig(rho=2.0, gamma=0.1,
                                              max_delay=2,
                                              block_fraction=1.0,
                                              num_blocks=1, seed=2),
    }
    print(f"\n{'variant':30s} {'epochs':>6s} {'objective':>10s} "
          f"{'P':>10s} {'s/epoch':>8s}")
    for name, cfg in variants.items():
        sess = session_for(cfg)
        t0 = time.time()
        state, hist = sess.run(args.epochs, eval_every=args.epochs)
        dt = (time.time() - t0) / args.epochs
        P = float(sess.stationarity(state)["P"])
        print(f"{name:30s} {args.epochs:6d} {hist[-1]['objective']:10.4f} "
              f"{P:10.2e} {dt:8.4f}")


if __name__ == "__main__":
    main()
