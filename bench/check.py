"""The comparison that decides ``correct``.

Three numbers, over the first epochs of the run, each against the plain
reference from the same seed:

* ``w_gap``: for every (worker, block) pair, the norm of w_run - w_ref
  over the block, relative to the reference's norm of that pair or the
  median moved pair's, whichever is larger; the worst pair of the epochs
  whose w the run kept. w = rho z~ - 2 g - y_old on the pairs a worker
  updates (eqs. 9, 11, 12), so this sees every block's gradient, the
  minibatch draw, the selection, the worker kernel, the delayed gather
  and, on a mesh, the exchange of z~ and gradients over the model axis;
* ``z_gap``: the largest |z_run - z_ref| over all coordinates and the
  epochs whose z the run kept, relative to the largest |z_ref| of that
  epoch — the committed consensus value: the server kernel and, on a
  mesh, the sum of w over the data axes. In the first epochs the l1 prox
  leaves z nonzero mostly in the power-law head, which lies in block 0;
* ``loss_gap``: the largest |loss_run - loss_ref| / |loss_ref| of the
  mean worker loss at the delayed z~ — the gradient's forward pass.

Each has the limit that the cell's file gives; the numbers and limits
are printed with every run.
"""
from __future__ import annotations

import sys
from typing import Dict, List, Optional, Tuple

import numpy as np

# (z (dim,) or None, loss, w (N, M, >= u) or None) after each epoch
Trajectory = List[Tuple[Optional[np.ndarray], float, Optional[np.ndarray]]]


def _norms(rows) -> np.ndarray:
    """(N, M) norms over the last axis, one worker's (M, u) float32 rows
    at a time, summed in float32: within a few tenths of a percent,
    ample for a gap that its limit holds with room of 8 or more."""
    return np.stack([np.sqrt(np.einsum("ij,ij->i", r, r))
                     for r in rows]).astype(np.float64)


def w_pair_gap(w: np.ndarray, w_ref: np.ndarray) -> float:
    """The worst (worker, block) pair's |w - w_ref| over max(|w_ref| of
    the pair, the median |w_ref| of the pairs the reference moved). The
    run's blocks may carry lane padding past the reference's length."""
    u = w_ref.shape[-1]
    diff = _norms(w[i, :, :u] - w_ref[i] for i in range(w_ref.shape[0]))
    ref = _norms(w_ref)
    moved = ref[ref > 0]
    floor = float(np.median(moved)) if moved.size else 0.0
    scale = np.maximum(np.maximum(ref, floor), np.finfo(np.float32).tiny)
    return float(np.max(diff / scale))


def gaps(run: Trajectory, ref: Trajectory) -> Dict[str, float]:
    """The three numbers; a NaN or infinity anywhere reads as infinity."""
    if len(run) != len(ref):
        raise ValueError(f"{len(run)} epochs to compare with {len(ref)}")
    w_gaps, z_gaps, loss_gaps = [0.0], [0.0], [0.0]
    for (z, loss, w), (z_ref, loss_ref, w_ref) in zip(run, ref):
        loss_gaps.append(abs(loss - loss_ref) / abs(loss_ref))
        if w is not None:                 # the run kept w this epoch
            w_gaps.append(w_pair_gap(w, w_ref))
        if z is not None:                 # the run kept z this epoch
            diff = np.abs(z.astype(np.float64) - z_ref)
            scale = max(float(np.max(np.abs(z_ref))),
                        np.finfo(np.float32).tiny)
            z_gaps.append(float(np.max(diff)) / scale)
    worst = lambda v: max(v) if np.all(np.isfinite(v)) else float("inf")
    return {"w_gap": worst(w_gaps), "z_gap": worst(z_gaps),
            "loss_gap": worst(loss_gaps)}


def judge(numbers: Dict[str, float], limits: Dict[str, float]) -> dict:
    """{name: {"value", "limit"}} for every limited number, and whether
    all are within their limits (a NaN is not)."""
    out = {k: {"value": numbers[k], "limit": limits[k]} for k in limits}
    ok = all(v["value"] <= v["limit"] for v in out.values())
    return {"checks": out, "correct": bool(ok),
            "failed": sum(not v["value"] <= v["limit"]
                          for v in out.values())}


def report(checks: dict) -> None:
    """The numbers beside their limits, as the last lines on stderr."""
    for k, v in checks.items():
        print(f"check {k} {v['value']!r} limit {v['limit']!r}",
              file=sys.stderr, flush=True)
