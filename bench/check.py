"""The numbers that decide ``correct``, from the program's first rounds and
the plain reference's.

Both runs start from the seed and follow the same rounds on the same
batches. Per parameter leaf, the norm of the first round's update (the
step the server applies) and of the change after all the checked rounds
are compared as the gap between the program's norm and the reference's.
Leaves whose first update the reference puts under a thousandth of the
median leaf's move by round-off alone: they are left out by that rule.

- ``update_gap``, ``change_gap``: the worst leaf, its gap taken over the
  larger of the reference's norm of that leaf and of the median leaf;
- ``update_rms``: the root mean square over the leaves of the first
  update's gap over the reference's norm, steadier from seed to seed
  than the worst leaf (PERF.md, "How correct is decided");
- ``eval_moved``: the share of the test set whose predicted class the
  program's eval engine puts elsewhere than the reference does, at
  least: half the summed absolute gap of the two confusion matrices over
  the test set's size, after the first round (later rounds carry the
  rounding of more local steps, PERF.md).
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


def compare(prog_thetas: list, prog_confusion: list, ref: dict) -> tuple:
    """prog_thetas: the program's global before the first checked round
    and after each; prog_confusion: its eval counts after each. ``ref``
    is what ``bench/reference/<family>.run_rounds`` returns. Returns the
    compared numbers, how many leaves were left out, and the per-leaf
    readings they were taken from."""
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
    p_conf = np.asarray(prog_confusion[0], np.float64)
    r_conf = ref["confusion"][0]
    out = {
        "update_gap": _norm_gap(p_first, r_first, keep),
        "update_rms": float(np.sqrt(np.mean(
            _rel(p_first, r_first)[keep] ** 2))),
        "change_gap": _norm_gap(p_change, r_change, keep),
        "eval_moved": float(np.abs(p_conf - r_conf).sum() / 2
                            / r_conf.sum()),
    }
    # per leaf: reference and program norms of the first update, then of
    # the change
    detail = {"leaves": [[float(a), float(b), float(c), float(d)]
                         for a, b, c, d in zip(r_first, p_first, r_change,
                                               p_change)]}
    return out, int((~keep).sum()), detail
