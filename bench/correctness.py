"""How ``correct`` is decided for a training cell.

The program and the plain reference each train the first steps from the
same seeded weights on the same batches.  Five numbers compare them:

* ``loss_gap``: the largest relative gap between the two losses of a step,
  over the compared steps;
* ``grad1_gap``: by the worst leaf, the gap between the norms of the first
  gradient as the optimizer received it (the program's is read back from
  Adam's first moment after one step, ``m / (1 - b1)``);
* ``grad1_median_gap``: the same gaps' median over the leaves the first
  step sent.  The worst leaf is nearly always the attention output
  projection, whose gradient is the most sensitive to the bfloat16
  activations, and it swings from seed to seed; the median leaf is steady,
  and it is the number that a float8 computation of the same steps fails;
* ``change_gap``: by the worst leaf, the gap between the norms of each
  leaf's change over the compared steps;
* ``resid_gap``: by the worst leaf, the gap between the norms of the
  error-feedback residual after the compared steps.  Adam's first updates
  are nearly sign(g) times a constant, so the change hardly depends on how
  the residual scaled what was sent; the residual itself does.

A leaf's gap is measured against the reference's norm of that leaf or of
the median leaf, whichever is larger; the median is over the leaves whose
reference norm is not zero.  Leaves whose raw first gradient in the
reference is under ``GRAD_FLOOR`` of the median leaf's (a key bias under
softmax) move under Adam by round-off alone and are left out of
``change_gap``.
"""
from __future__ import annotations

import math
import statistics
import sys

import jax
import jax.numpy as jnp

GRAD_FLOOR = 1e-3


@jax.jit
def _norms(xs):
    return [jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32)))) for x in xs]


@jax.jit
def _diff_norms(xs, ys):
    return [jnp.sqrt(jnp.sum(jnp.square((x - y).astype(jnp.float32))))
            for x, y in zip(xs, ys)]


def _keyed(flat, norms) -> dict:
    return {jax.tree_util.keystr(p): float(n) for (p, _), n in zip(flat, norms)}


def leaf_norms(tree) -> dict:
    """The norm of each leaf, keyed by its path."""
    flat = jax.tree_util.tree_leaves_with_path(tree)
    return _keyed(flat, _norms([x for _, x in flat]))


def change_norms(tree, start) -> dict:
    """The norm of each leaf's change from ``start``, keyed by its path:
    one reduction that returns the norms alone, so no tree of differences
    is held beside the two."""
    if jax.tree.structure(tree) != jax.tree.structure(start):
        raise ValueError("the two trees differ in structure")
    flat = jax.tree_util.tree_leaves_with_path(tree)
    return _keyed(flat, _diff_norms([x for _, x in flat],
                                    jax.tree.leaves(start)))


def leaf_gaps(prog: dict, ref: dict, keys) -> dict:
    """Each leaf's gap between the two norms, against the reference's norm
    of that leaf or of the median leaf, whichever is larger."""
    keys = list(keys)
    nonzero = [ref[k] for k in keys if ref[k] > 0]
    med = statistics.median(nonzero) if nonzero else 0.0
    out = {}
    for k in keys:
        den = max(ref[k], med)
        diff = abs(prog[k] - ref[k])
        gap = diff / den if den > 0 else (0.0 if diff == 0 else math.inf)
        out[k] = gap if not math.isnan(gap) else math.inf
    return out


def worst_leaf(prog: dict, ref: dict, keys) -> float:
    return max(leaf_gaps(prog, ref, keys).values(), default=0.0)


def numbers(prog: dict, ref: dict) -> dict:
    losses = [abs(a - b) / abs(b) for a, b in zip(prog["losses"],
                                                    ref["losses"])]
    grads = ref["raw_grad"]
    floor = GRAD_FLOOR * statistics.median(grads.values())
    moving = [k for k, g in grads.items() if g >= floor]
    grad1 = leaf_gaps(prog["grad1"], ref["grad1"], ref["grad1"])
    sent = [gap for k, gap in grad1.items() if ref["grad1"][k] > 0]
    return {
        "loss_gap": max(x if not math.isnan(x) else math.inf for x in losses),
        "grad1_gap": max(grad1.values()),
        "grad1_median_gap": statistics.median(sent) if sent else math.inf,
        "change_gap": worst_leaf(prog["change"], ref["change"], moving),
        "resid_gap": worst_leaf(prog["resid"], ref["resid"], ref["resid"]),
    }


def judge(nums: dict, limits: dict) -> tuple[bool, dict]:
    """``(correct, {name: {"value", "limit"}})``.  A reading that is not
    finite is reported as the largest float, so that the line stays JSON."""
    checks = {k: {"value": nums[k] if math.isfinite(nums[k])
                  else sys.float_info.max, "limit": limits[k]}
              for k in limits}
    ok = all(c["value"] <= c["limit"] for c in checks.values())
    return ok, checks
