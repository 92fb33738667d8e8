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
- ``dropped``: the first client's update is left out of the fusion, one
  client's answer lost where it is fused;
- ``no_exchange``: fusion sees only the clients on the first chip, as if
  the all-reduce between chips were left out;
- ``wrong_answer``: the eval engine's predicted class moves to the next
  class where it is produced.
"""
from __future__ import annotations

import numpy as np

FAULTS = ("control", "frozen", "half_batch", "dropped", "no_exchange",
          "wrong_answer")
CONTROL_PRECISION = "high"


def plant_task(fault: str | None, task) -> None:
    if fault == "half_batch":
        loss_fn = task.loss_fn

        def half(params, batch):
            return loss_fn(params, {k: v[:v.shape[0] // 2]
                                    for k, v in batch.items()})
        task.loss_fn = half
    elif fault == "wrong_answer":
        predict_fn = task.predict_fn

        def shifted(params, batch):
            pred, gold, w = predict_fn(params, batch)
            return (pred + 1) % task.n_classes, gold, w
        task.predict_fn = shifted


def weights(fault: str | None, w, chips: int):
    """The fusion weights the faulty round uses instead of ``w``."""
    if fault not in ("dropped", "no_exchange"):
        return w
    w = np.array(w, np.float64)
    if fault == "dropped":
        w[0] = 0.0
    else:
        w[len(w) // chips:] = 0.0
    return w


def output(fault: str | None, global_in, global_out):
    """The new global the faulty round returns."""
    return global_in if fault == "frozen" else global_out
