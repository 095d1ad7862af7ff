"""The comparison that decides `correct`.

The program's first `steps` dispatches of `rounds` rounds each (the
window's own call on the window's own trainer, in set-up) are followed
by the plain reference from the same weights, data and keys. Three
numbers are compared, each against its limit in `limits/<cell>.json`:

- loss_gap: the largest relative gap of any round's discriminator or
  generator objective, |program - reference| / |reference|;
- grad_gap: per leaf, the gap between the norms of the change after the
  first dispatch (SGD: the learning rate times the summed gradients the
  optimizer applied), |n_program - n_reference|, over the larger of the
  reference's norm of that leaf and of the median leaf; the worst leaf;
- change_gap: the same after the last dispatch.

Leaves whose first change in the reference is under a thousandth of the
median leaf's are left out of both norm gaps: there the change is
round-off (none is in a DCGAN today; the rule is by value, not by name).
"""
from __future__ import annotations

import math

import jax
import numpy as np

NUMBERS = ("loss_gap", "grad_gap", "change_gap")
TINY_LEAF = 1e-3


def change_norms(after, before) -> dict:
    """{leaf path: ||after - before||} in float64."""
    flat_a = jax.tree_util.tree_flatten_with_path(after)[0]
    flat_b = jax.tree_util.tree_leaves(before)
    return {jax.tree_util.keystr(p): float(np.linalg.norm(
        np.asarray(a, np.float64) - np.asarray(b, np.float64)))
        for (p, a), b in zip(flat_a, flat_b)}


def norm_gap(program: dict, reference: dict, keep) -> float:
    ref = np.array([reference[k] for k in keep])
    prog = np.array([program[k] for k in keep])
    floor = np.maximum(ref, np.median(ref))
    return float(np.max(np.abs(prog - ref) / floor))


def readings(params0, program: dict, reference: dict, prog_objectives,
             ref_objectives, first: int, last: int) -> dict:
    """program/reference: {rounds: params after that many rounds} for
    `first` and `last`; *_objectives: (rounds, 2)."""
    p1, r1 = (change_norms(program[first], params0),
              change_norms(reference[first], params0))
    p3, r3 = (change_norms(program[last], params0),
              change_norms(reference[last], params0))
    median = np.median(list(r1.values()))
    keep = [k for k, v in r1.items() if v >= TINY_LEAF * median]
    po = np.asarray(prog_objectives, np.float64)[:last]
    ro = np.asarray(ref_objectives, np.float64)[:last]
    loss = np.abs(po - ro) / np.maximum(np.abs(ro), 1e-12)
    return {
        "loss_gap": float(np.max(loss)) if np.all(np.isfinite(po))
        else math.inf,
        "grad_gap": norm_gap(p1, r1, keep),
        "change_gap": norm_gap(p3, r3, keep),
    }


def verdict(values: dict, limits: dict):
    """(every number finite and within its limit, {name: [value, limit]})."""
    table = {n: [values[n], limits[n]] for n in NUMBERS}
    ok = all(math.isfinite(v) and v <= lim for v, lim in table.values())
    return ok, table
