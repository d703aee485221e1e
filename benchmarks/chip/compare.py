"""The comparison that decides ``correct``: numbers read from the
program and from the plain reference, and the gap between them.

A "leaf" is one parameter tensor of one layer: leaves stacked over the
layers of a segment are split along their first axis. Norms of leaves
are compared, not the norm of their difference, because two correct
runs in different precisions round differently element by element but
agree on how large each tensor is.

    gap(leaf) = |norm_program - norm_reference|
                / max(norm_reference, median over leaves of norm_reference)

The median floor keeps a leaf whose reference norm is all but zero
from turning rounding into a large ratio.
"""
from __future__ import annotations

from typing import Any, Mapping, Sequence

import jax
import jax.numpy as jnp
import numpy as np

#: leaves whose first-step reference gradient is below this share of
#: the median leaf's move under Adam by round-off alone (a bias that
#: softmax cancels, say), so their change is not compared
STILL_LEAF = 1e-3


def leaf_norms(tree: Any) -> jax.Array:
    """Float32 norm of every leaf, stacked leaves split by layer."""
    out = []
    for path, leaf in jax.tree_util.tree_flatten_with_path(tree)[0]:
        x = leaf.astype(jnp.float32)
        if "segments" in jax.tree_util.keystr(path):
            out.append(jnp.sqrt(jnp.sum(x * x, axis=tuple(range(1, x.ndim)))))
        else:
            out.append(jnp.sqrt(jnp.sum(x * x))[None])
    return jnp.concatenate(out)


def leaf_gap(program: Sequence[float], reference: Sequence[float],
             keep: np.ndarray | None = None) -> float:
    """Worst leaf gap (over ``keep`` when given); infinite where the
    program's norms are not finite."""
    p = np.asarray(program, np.float64)
    r = np.asarray(reference, np.float64)
    if not np.all(np.isfinite(p)):
        return float("inf")
    gaps = np.abs(p - r) / np.maximum(r, np.median(r))
    if keep is not None:
        gaps = np.where(keep, gaps, 0.0)
    return float(np.max(gaps))


def moving(reference_grad: Sequence[float]) -> np.ndarray:
    """Leaves whose reference gradient moves them: at least STILL_LEAF of
    the median leaf's."""
    g = np.asarray(reference_grad, np.float64)
    return g >= STILL_LEAF * np.median(g)


def train_readings(program: Mapping[str, Any],
                   reference: Mapping[str, Any]) -> dict[str, float]:
    """The three numbers a train cell compares.

    ``loss_gap``: the largest relative gap of a step's loss.
    ``grad_gap``: the worst leaf of the first, unclipped gradient.
    ``update_gap``: the worst moving leaf of the parameters' change
    after the checked steps.
    """
    lp = np.asarray(program["losses"], np.float64)
    lr = np.asarray(reference["losses"], np.float64)
    loss_gap = (float(np.max(np.abs(lp - lr) / np.abs(lr)))
                if np.all(np.isfinite(lp)) else float("inf"))
    grad_gap = leaf_gap(program["grad_leaves"], reference["grad_leaves"])
    update_gap = leaf_gap(program["update_leaves"],
                          reference["update_leaves"],
                          moving(reference["grad_leaves"]))
    return {"loss_gap": loss_gap, "grad_gap": grad_gap,
            "update_gap": update_gap}


def judge(readings: Mapping[str, float],
          limits: Mapping[str, float]) -> tuple[bool, dict[str, dict]]:
    """``correct`` and each limited number beside its limit. A number
    that is missing or not finite fails; a reading with no limit is not
    compared."""
    checks, ok = {}, True
    for name, limit in limits.items():
        value = readings.get(name)
        good = value is not None and np.isfinite(value) and value <= limit
        ok = ok and bool(good)
        checks[name] = {"value": value, "limit": limit}
    return ok, checks
