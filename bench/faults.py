"""Faults planted under the timed path, and the lower-precision control.

The benchmark's own runs never plant one. ``bench/controls.py`` reads
each on the chip at a cell's size, and ``bench/tests`` sees each turn
``correct`` false at a test size:

- ``control``: the plain reference in the program's place, at
  ``CONTROL_PRECISION``, the precision below the float32 at ``highest``
  that the configuration states (``bench/run.py`` swaps it in);
- ``frozen``: the round returns the global it was given;
- ``half_batch``: the local loss is the mean over the first half of each
  batch only;
- ``dropped``: the round's first client's update is left out of the
  fusion, one client's answer lost where it is fused (in a round of
  several cohort tiles, in the first tile only);
- ``no_exchange``: fusion sees only the clients on the first chip, as if
  the all-reduce between chips were left out (in every tile);
- ``wrong_answer``: the family's eval-side fault, an answer altered where
  it is produced (``plant_wrong_answer`` of
  ``bench/reference/<family>.py``).
"""
from __future__ import annotations

import numpy as np

FAULTS = ("control", "frozen", "half_batch", "dropped", "no_exchange",
          "wrong_answer")
CONTROL_PRECISION = "high"


def plant_task(fault: str | None, task, family) -> None:
    """Plant a fault of the task's functions; ``family`` is the cell's
    ``bench/reference/<family>.py``."""
    if fault == "half_batch":
        loss_fn = task.loss_fn

        def half(params, batch):
            return loss_fn(params, {k: v[:v.shape[0] // 2]
                                    for k, v in batch.items()})
        task.loss_fn = half
    elif fault == "wrong_answer":
        family.plant_wrong_answer(task)


def weights(fault: str | None, w, chips: int, first: bool = True):
    """The fusion weights the faulty tile uses instead of ``w``; ``first``
    says whether it is the first tile of its round."""
    if fault == "dropped" and first:
        w = np.array(w, np.float64)
        w[0] = 0.0
    elif fault == "no_exchange":
        w = np.array(w, np.float64)
        w[len(w) // chips:] = 0.0
    return w


def output(fault: str | None, global_in, global_out):
    """The new global the faulty round returns."""
    return global_in if fault == "frozen" else global_out
