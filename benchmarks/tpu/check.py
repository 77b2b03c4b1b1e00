"""The comparisons that decide ``correct``: what the timed path produced
against the plain reference. Each number has its own limit, kept per
cell in ``limits/<cell>.json`` with the readings it was set from
(PERF.md)."""
from __future__ import annotations

import numpy as np

# leaves whose reference gradient is under this share of the median
# leaf's are moved by round-off alone and left out of the update check
NOUGHT = 1e-3


def _norms(tree) -> list:
    import jax
    return [float(np.linalg.norm(np.asarray(x, np.float64)))
            for x in jax.tree_util.tree_leaves(tree)]


def worst_leaf_gap(prog_tree, ref_tree, keep=None) -> float:
    """max over leaves of |norm_prog - norm_ref| / max(norm_ref, median
    leaf norm_ref): the gap between the two norms, not the norm of the
    difference."""
    p, r = _norms(prog_tree), _norms(ref_tree)
    keep = keep or [True] * len(r)
    med = float(np.median([x for x, k in zip(r, keep) if k]))
    gaps = [abs(a - b) / max(b, med, 1e-30)
            for a, b, k in zip(p, r, keep) if k]
    return max(gaps)


def train_numbers(prog: dict, ref: dict) -> dict:
    """``prog``/``ref``: ``losses`` (the first three steps), ``grad1`` (the
    first gradient as the optimizer got it), ``p0``/``p3`` (params before
    step 1 and after step 3); ``ref`` also ``raw_grad1`` (before weight
    decay), which picks the leaves the update check leaves out."""
    lp, lr = np.asarray(prog["losses"], np.float64), np.asarray(ref["losses"], np.float64)
    loss_gap = float(np.max(np.abs(lp - lr) / np.abs(lr)))
    raw = _norms(ref["raw_grad1"])
    med = float(np.median(raw))
    keep = [x >= NOUGHT * med for x in raw]
    import jax
    delta = lambda t: jax.tree_util.tree_map(  # noqa: E731
        lambda a, b: np.asarray(a, np.float64) - np.asarray(b, np.float64),
        t["p3"], t["p0"])
    return {"loss_gap": loss_gap,
            "grad_gap": worst_leaf_gap(prog["grad1"], ref["grad1"]),
            "update_gap": worst_leaf_gap(delta(prog), delta(ref), keep),
            "leaves_left_out": float(len(keep) - sum(keep))}


def serve_numbers(served: np.ndarray, ref: np.ndarray,
                  missing: int) -> dict:
    """Per answer, the largest logit gap over the row's reference scale
    (its largest |logit|, or the median row's where that is larger)."""
    served = np.asarray(served, np.float64)
    ref = np.asarray(ref, np.float64)
    scale = np.abs(ref).max(axis=1)
    scale = np.maximum(scale, np.median(scale))
    gap = np.abs(served - ref).max(axis=1) / scale
    return {"logit_gap": float(gap.max()) if len(gap) else 0.0,
            "missing_answers": float(missing)}


def judge(numbers: dict, limits: dict) -> tuple:
    """(correct, checks): every number that has a limit, beside it. A
    number that is not finite fails."""
    checks, ok = {}, True
    for name, limit in limits["limits"].items():
        v = numbers[name]
        checks[name] = {"value": v, "limit": limit}
        ok = ok and bool(np.isfinite(v)) and v <= limit
    return ok, checks
