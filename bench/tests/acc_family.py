"""A second model family for the tests, written into a copy of the
benchmark as ``bench/reference/cnn_acc.py``: the VGG maths of
``bench/reference/cnn.py``, with the program's eval read as its pooled
accuracy after each round (``history["acc"]``) and compared as
``acc_gap``, an eval number of its own."""
from __future__ import annotations

import numpy as np

from bench.reference import cnn

batch_shapes = cnn.batch_shapes
test_set = cnn.test_set


def run_rounds(*args, **kwargs) -> dict:
    ref = cnn.run_rounds(*args, **kwargs)
    ref["acc"] = [float(np.trace(c) / c.sum()) for c in ref["confusion"]]
    return ref


def program_eval(history, rounds: int) -> list:
    return [float(a) for a in history["acc"][:rounds]]


def eval_numbers(prog_eval: list, ref: dict) -> dict:
    """``acc_gap``: the gap between the program's and the reference's
    accuracy after the first round."""
    return {"acc_gap": abs(prog_eval[0] - ref["acc"][0])}


def plant_wrong_answer(task) -> None:
    """Every predicted class moves off the gold one."""
    predict_fn = task.predict_fn

    def off_gold(params, batch):
        pred, gold, w = predict_fn(params, batch)
        return (gold + 1) % task.n_classes, gold, w
    task.predict_fn = off_gold
