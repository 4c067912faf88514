"""The numbers that decide ``correct``, each held against its limit.

Training: the relative gap of each of the first three losses; per leaf, the
gap between the program's and the reference's norm of the first gradient and
of the parameters' change over three steps, each against the reference's
norm of that leaf or of the median leaf, whichever is larger, each taken by
the worst leaf.  Leaves whose reference gradient is under a thousandth of the
median leaf's move by round-off alone and are left out of the change.  The
median leaf's change gap is reported beside it (PERF.md, section 2).
"""
from __future__ import annotations

import statistics

import jax.numpy as jnp
import numpy as np

QUIET_SHARE = 1e-3


def norms(tree: dict) -> dict:
    """Host float norm of each named device array."""
    return {k: float(jnp.linalg.norm(jnp.asarray(v, jnp.float32).reshape(-1)))
            for k, v in tree.items()}


def diff(after: dict, before: dict) -> dict:
    return {k: after[k].astype(jnp.float32) - before[k].astype(jnp.float32) for k in before}


def leaf_gaps(prog: dict, ref: dict, keys) -> dict:
    """Per leaf, the gap between the two norms against the reference's norm
    of that leaf or of the median leaf, whichever is larger."""
    keys = list(keys)
    med = statistics.median(ref[k] for k in keys)
    return {k: abs(prog[k] - ref[k]) / max(ref[k], med) for k in keys}


def train_numbers(prog: dict, ref: dict) -> dict:
    """``prog``/``ref``: {"loss": [3 floats], "grad": {leaf: norm},
    "change": {leaf: norm}}; ``ref`` also has "grad_all", the reference's
    gradient norm of every leaf that can change (for the quiet-leaf rule)."""
    loss_gap = max(abs(p - r) / abs(r) for p, r in zip(prog["loss"], ref["loss"]))
    grad = leaf_gaps(prog["grad"], ref["grad"], ref["grad"])
    med = statistics.median(ref["grad_all"].values())
    moving = [k for k in ref["change"] if ref["grad_all"][k] >= QUIET_SHARE * med]
    change = leaf_gaps(prog["change"], ref["change"], moving)
    worst = max(change, key=change.get)
    return {"loss_gap": loss_gap, "grad_gap": max(grad.values()),
            "change_gap": change[worst],
            "_grad_leaf": max(grad, key=grad.get), "_change_leaf": worst,
            "_change_median": statistics.median(change.values()),
            "_quiet_leaves": sorted(set(ref["change"]) - set(moving))}


def held(numbers: dict, limits: dict) -> dict:
    """{name: {"value", "limit"}} for every limited number."""
    return {k: {"value": numbers[k], "limit": limits[k]} for k in limits}


def all_within(checks: dict) -> bool:
    return all(np.isfinite(c["value"]) and c["value"] <= c["limit"] for c in checks.values())

