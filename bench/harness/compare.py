"""The numbers `correct` compares, and the verdict against their limits.

A change is compared by its norm, leaf by leaf: the gap between the norm
of the program's change of a leaf and the norm of the reference's change
of that leaf, over the reference's norm of that leaf or of the median
leaf, whichever is larger (some leaves barely move). Leaves whose
reference gradient is under a thousandth of the median leaf's move by
round-off alone and are left out.
"""
from __future__ import annotations

from typing import Dict, Optional

import jax
import numpy as np

GRAD_FLOOR = 1e-3


def norms(tree) -> np.ndarray:
    """Per leaf, its norm in float64."""
    return np.array([np.linalg.norm(np.asarray(x, np.float64))
                     for x in jax.tree_util.tree_leaves(tree)])


def change_gaps(prog, ref, base, grad_norms=None) -> np.ndarray:
    """Per leaf, |‖prog-base‖ - ‖ref-base‖| / max(‖ref-base‖, median leaf's
    ‖ref-base‖); NaN for a leaf left out by `grad_norms`, the per-leaf
    norms of the reference's gradient. One leaf at a time in float64."""
    leaves = jax.tree_util.tree_leaves
    dp, dr = [], []
    for p, r, b in zip(leaves(prog), leaves(ref), leaves(base)):
        b = np.asarray(b, np.float64)
        dp.append(np.linalg.norm(np.asarray(p, np.float64) - b))
        dr.append(np.linalg.norm(np.asarray(r, np.float64) - b))
    dp, dr = np.array(dp), np.array(dr)
    keep = np.ones(len(dr), bool)
    if grad_norms is not None:
        gn = np.asarray(grad_norms)
        keep = gn >= GRAD_FLOOR * np.median(gn)
    scale = np.maximum(dr, np.median(dr[keep]))
    return np.where(keep, np.abs(dp - dr) / scale, np.nan)


def change_gap(prog, ref, base, grad_norms=None) -> float:
    """The worst leaf's gap."""
    return float(np.nanmax(change_gaps(prog, ref, base, grad_norms)))


def finite(tree) -> bool:
    return all(np.isfinite(x).all() for x in jax.tree_util.tree_leaves(tree))


def verdict(numbers: Dict[str, Optional[float]], limits: Dict) -> Dict:
    """{name: {"value", "limit"}} and whether every number is within its
    limit. A number that is missing or not finite fails."""
    out, ok = {}, True
    for name, lim in limits.items():
        v = numbers.get(name)
        good = v is not None and np.isfinite(v) and v <= lim
        ok = ok and bool(good)
        out[name] = {"value": v, "limit": lim}
    return {"correct": ok, "compared": out}
