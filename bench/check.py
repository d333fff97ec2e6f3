"""The numbers that decide ``correct``, and their comparison with limits.

Training compares, against the reference's first steps from the same
weights and rows: each step's loss; the gradient of the first step as
the optimizer got it (worked out from its first moment after one step
and the global norm the step reports), leaf by leaf; and the change of
the parameters after the checked steps, leaf by leaf. A leaf is one
layer's slice of a stacked weight, or an unstacked weight. A leaf's gap
is |norm(program) - norm(reference)| over the larger of the reference's
norm of that leaf and of the median leaf; the number is the worst leaf.
Leaves whose reference gradient is under a thousandth of the median
leaf's move by round-off alone and are left out of the change.

Serving compares, at each position that produced a served token, how
far that token's reference logit lies below the reference's best logit
there: the widest such gap, and the mean gap over all served tokens.
"""

from __future__ import annotations

import numpy as np

import jax
import jax.numpy as jnp

QUIET_GRAD = 1e-3


def _norms(tree):
    out = []
    for path, leaf in jax.tree_util.tree_flatten_with_path(tree)[0]:
        x = leaf.astype(jnp.float32)
        stacked = str(getattr(path[0], "key", "")) == "blocks"
        axes = tuple(range(1, x.ndim)) if stacked else None
        out.append(jnp.sqrt(jnp.sum(x * x, axis=axes)).reshape(-1))
    return out


_norms_jit = jax.jit(_norms)


def leaf_norms(tree) -> dict[str, float]:
    """Norm of each leaf (each layer of a stacked leaf apart)."""
    paths = [jax.tree_util.keystr(p)
             for p, _ in jax.tree_util.tree_flatten_with_path(tree)[0]]
    out = {}
    for name, n in zip(paths, _norms_jit(tree)):
        n = np.asarray(n, np.float64)
        if n.size == 1:
            out[name] = float(n[0])
        else:
            out.update({f"{name}[{i}]": float(v) for i, v in enumerate(n)})
    return out


def worst_leaf(prog: dict, ref: dict, names=None) -> tuple[float, str]:
    names = list(ref) if names is None else names
    med = float(np.median([ref[n] for n in ref]))
    gaps = {n: abs(prog[n] - ref[n]) / max(ref[n], med, 1e-30)
            for n in names}
    worst = max(gaps, key=gaps.get)
    return gaps[worst], worst


def train_numbers(prog: dict, ref: dict) -> dict:
    """prog/ref: losses (list), grad (leaf norms of the first gradient),
    change (leaf norms)."""
    lp, lr = np.asarray(prog["losses"]), np.asarray(ref["losses"])
    med = float(np.median(list(ref["grad"].values())))
    moving = [n for n, g in ref["grad"].items() if g >= QUIET_GRAD * med]
    grad_gap, grad_leaf = worst_leaf(prog["grad"], ref["grad"])
    change_gap, change_leaf = worst_leaf(prog["change"], ref["change"],
                                         moving)
    rel = np.abs(lp - lr) / np.abs(lr)
    return {"loss_gap_step1": float(rel[0]),
            "grad_gap": grad_gap, "change_gap": change_gap,
            "_loss_gap_steps": float(np.max(rel)),
            "_grad_gap_median": _median_gap(prog["grad"], ref["grad"]),
            "_change_gap_median": _median_gap(prog["change"], ref["change"],
                                              moving),
            "_grad_leaf": grad_leaf, "_change_leaf": change_leaf,
            "_quiet_leaves": len(ref["grad"]) - len(moving)}


def _median_gap(prog: dict, ref: dict, names=None) -> float:
    names = list(ref) if names is None else names
    med = float(np.median([ref[n] for n in ref]))
    return float(np.median([abs(prog[n] - ref[n]) / max(ref[n], med, 1e-30)
                            for n in names]))


def token_gaps(ref_logits: np.ndarray, tokens: np.ndarray) -> np.ndarray:
    """How far below the reference's best logit each served token's
    reference logit lies. ref_logits (..., V) f32 at the positions that
    produced ``tokens``."""
    got = np.take_along_axis(ref_logits, tokens[..., None], -1)[..., 0]
    return ref_logits.max(-1) - got


def serve_numbers(gaps: np.ndarray) -> dict:
    return {"logit_gap": float(np.max(gaps)),
            "gap_mean": float(np.mean(gaps)),
            "_gap_p90": float(np.percentile(gaps, 90)),
            "_mismatch_share": float(np.mean(gaps > 0))}


def judge(numbers: dict, limits: dict) -> tuple[bool, dict]:
    """Every limited number at or under its limit; returns (ok, checks)."""
    checks = {k: (numbers[k], limits[k]) for k in limits}
    ok = all(np.isfinite(v) and v <= lim for v, lim in checks.values())
    return ok, checks
