"""A configuration of a second model family, added to a copy of the
benchmark as new files only (``bench/tests/acc_family.py`` as its
``bench/reference/<family>.py``, its work module, config and limits): the
harness takes it unchanged. A sound run is correct; the family's own
``wrong_answer`` fails the eval number its limits file names."""
from __future__ import annotations

import pytest

from bench import cells, run
from bench.tests import tiny

SEED = 2**31 + 37      # past 32 signed bits, as a benchmark seed may be


@pytest.fixture(scope="module")
def cell(tmp_path_factory):
    root = tiny.make_root(str(tmp_path_factory.mktemp("family")))
    return cells.resolve("tiny_acc.silo", root=root)


def test_second_family_sound_run_is_correct(cell):
    assert cell.family == "cnn_acc"
    result = run.run_cell(cell, SEED, 0.2)
    assert result["correct"], result["checks"]
    assert "acc_gap" in result["checks"]
    assert "eval_moved" not in result["checks"]


def test_second_family_wrong_answer_fails_its_eval_number(cell):
    result = run.run_cell(cell, SEED, 0.2, fault="wrong_answer")
    assert not result["correct"]
    failing = {k for k, c in result["checks"].items()
               if c["value"] > c["limit"]}
    assert failing == {"acc_gap"}
