"""The numbers that decide ``correct``, from the program's first rounds and
the plain reference's.

Both runs start from the seed and follow the same rounds on the same
batches. Per parameter leaf, the norm of the first round's update (the
step the server applies) and of the change after all the checked rounds
are compared as the gap between the program's norm and the reference's.
Leaves whose first update the reference puts under a thousandth of the
median leaf's move by round-off alone: they are left out by that rule.
These numbers hold for every family:

- ``update_gap``, ``change_gap``: the worst leaf, its gap taken over the
  larger of the reference's norm of that leaf and of the median leaf;
- ``update_rms``: the root mean square over the leaves of the first
  update's gap over the reference's norm, steadier from seed to seed
  than the worst leaf (PERF.md, "How correct is decided").

The eval numbers are the family's: ``eval_numbers`` of
``bench/reference/<family>.py`` compares the program's eval with the
reference's, and its numbers are merged in.
"""
from __future__ import annotations

import jax
import numpy as np

NEGLIGIBLE = 1e-3


def _leaf_norms(a, b) -> np.ndarray:
    return np.array([
        np.linalg.norm(np.asarray(x, np.float64).ravel()
                       - np.asarray(y, np.float64).ravel())
        for x, y in zip(jax.tree_util.tree_leaves(a),
                        jax.tree_util.tree_leaves(b))])


def _norm_gap(prog: np.ndarray, ref: np.ndarray, keep: np.ndarray) -> float:
    floor = np.median(ref[keep])
    return float(np.max(np.abs(prog[keep] - ref[keep])
                        / np.maximum(ref[keep], floor)))


def _rel(prog, ref):
    return np.abs(prog - ref) / ref


def compare(prog_thetas: list, prog_eval: list, ref: dict,
            family) -> tuple:
    """prog_thetas: the program's global before the first checked round
    and after each; prog_eval: its eval after each, as the family's
    ``program_eval`` reads it. ``ref`` is what the family's
    ``run_rounds`` returns, ``family`` its module. Returns the compared
    numbers, how many leaves were left out, and the per-leaf readings
    they were taken from."""
    r_thetas = ref["thetas"]
    shapes = [np.shape(x) for x in jax.tree_util.tree_leaves(prog_thetas[0])]
    ref_shapes = [np.shape(x) for x in jax.tree_util.tree_leaves(r_thetas[0])]
    if shapes != ref_shapes:
        raise ValueError(f"program and reference parameter shapes differ: "
                         f"{shapes} vs {ref_shapes}")
    r_first = _leaf_norms(r_thetas[1], r_thetas[0])
    keep = r_first >= NEGLIGIBLE * np.median(r_first)
    p_first = _leaf_norms(prog_thetas[1], prog_thetas[0])
    p_change = _leaf_norms(prog_thetas[-1], prog_thetas[0])
    r_change = _leaf_norms(r_thetas[-1], r_thetas[0])
    out = {
        "update_gap": _norm_gap(p_first, r_first, keep),
        "update_rms": float(np.sqrt(np.mean(
            _rel(p_first, r_first)[keep] ** 2))),
        "change_gap": _norm_gap(p_change, r_change, keep),
        **family.eval_numbers(prog_eval, ref),
    }
    # per leaf: reference and program norms of the first update, then of
    # the change
    detail = {"leaves": [[float(a), float(b), float(c), float(d)]
                         for a, b, c, d in zip(r_first, p_first, r_change,
                                               p_change)]}
    return out, int((~keep).sum()), detail
