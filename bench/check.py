"""The comparison that decides ``correct`` for a training cell.

Both sides give the same readings of the first ``check_steps`` steps
(see :func:`bench.reference.train.run`): each step's loss, the first
gradient as the optimizer got it (clipped), the change of every weight
over those steps, and the sampled counts. The numbers compared:

* ``loss``: the largest relative gap of a step's loss;
* ``grad``: the worst weight leaf's gap between the two first-gradient
  norms, over the larger of the reference leaf's norm and the median
  leaf's norm;
* ``update``: the same for the norm of the change over the steps;
* ``sampled_v``, ``sampled_e``: the largest difference in the deepest
  layer's vertex count and in the edges sampled over all layers (exact).

Leaves whose reference first gradient is under a thousandth of the
median leaf's are left out of ``grad`` and ``update``: Adam moves such
a leaf by round-off alone. A cell's ``limits/<cell>.json`` lists the
numbers it compares, each with its limit; a number with no upper
reading to set a limit from is not listed, and not compared.
"""
from __future__ import annotations

import math
from typing import Dict

import jax
import numpy as np

NAMES = ("loss", "grad", "update", "sampled_v", "sampled_e")
_QUIET = 1e-3


def _norms(tree) -> np.ndarray:
    return np.array([float(np.linalg.norm(np.asarray(x, np.float64)))
                     for x in jax.tree.leaves(tree)])


def _leaf_gap(prog, ref, keep) -> float:
    p, r = _norms(prog)[keep], _norms(ref)[keep]
    scale = np.maximum(r, np.median(r))
    return float(np.max(np.abs(p - r) / scale))


def numbers(prog: Dict, ref: Dict) -> Dict[str, float]:
    g_ref = _norms(ref["first_grad"])
    keep = g_ref >= _QUIET * np.median(g_ref)
    losses = zip(prog["losses"], ref["losses"])
    return {
        "loss": max(abs(a - b) / abs(b) for a, b in losses),
        "grad": _leaf_gap(prog["first_grad"], ref["first_grad"], keep),
        "update": _leaf_gap(prog["delta"], ref["delta"], keep),
        "sampled_v": float(max(abs(a[0] - b[0]) for a, b in
                               zip(prog["counts"], ref["counts"]))),
        "sampled_e": float(max(abs(a[1] - b[1]) for a, b in
                               zip(prog["counts"], ref["counts"]))),
    }


def verdict(nums: Dict[str, float], limits: Dict[str, float]):
    """(correct, {name: {"value", "limit"}}): every number that has a
    limit finite and at or under it."""
    table = {n: {"value": nums[n], "limit": limits[n]} for n in NAMES
             if n in limits}
    ok = all(math.isfinite(v["value"]) and v["value"] <= v["limit"]
             for v in table.values())
    return ok, table
