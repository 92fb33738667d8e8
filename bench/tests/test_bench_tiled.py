"""Rounds that run as several cohort tiles, at a test size past the look
for a chip: every client of a population of four (or three, the second
tile padded) through an engine two wide. A sound run is correct and counts
every participant's samples and no padded slot's; the control and each
planted fault turn ``correct`` false."""
from __future__ import annotations

import numpy as np
import pytest

from bench import cells, run
from bench.tests import tiny

SEED = 2**31 + 23      # past 32 signed bits, as a benchmark seed may be


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return tiny.make_root(str(tmp_path_factory.mktemp("tiled")))


def _failing(result):
    return {k for k, c in result["checks"].items()
            if c["value"] > c["limit"]}


@pytest.mark.parametrize("workload,participants", [
    ("tiny_vgg.tiled", 4), ("tiny_vgg.padded", 3)])
def test_tiled_run_is_correct_and_counts_every_participant(
        root, workload, participants):
    cell = cells.resolve(workload, root=root)
    result = run.run_cell(cell, SEED, 0.2, keep_detail=True)
    assert result["correct"], result["checks"]
    w = result["detail"]["window"]
    assert w["rounds"] == result["attempted"] >= 1
    # each client's 2 steps of 8 samples, the padded slot left out
    assert w["participants"] == [participants] * w["rounds"]
    assert w["samples"] == w["rounds"] * participants * 2 * 8
    assert result["metrics"]["samples_per_s"]["value"] == \
        pytest.approx(w["samples"] / w["seconds"])


@pytest.mark.parametrize("fault,caught_by", [
    ("control", {"update_gap", "update_rms", "change_gap"}),
    ("frozen", {"update_gap", "update_rms", "change_gap"}),
    ("half_batch", {"update_gap", "update_rms", "change_gap"}),
    ("dropped", {"update_gap", "update_rms", "change_gap"}),
    ("wrong_answer", {"eval_moved"})])
def test_tiled_control_and_faults_are_not_correct(root, fault, caught_by):
    cell = cells.resolve("tiny_vgg.tiled", root=root)
    result = run.run_cell(cell, SEED, 0.2, fault=fault)
    assert not result["correct"]
    assert _failing(result) & caught_by


def test_checked_rounds_leave_out_the_padded_slots():
    """Three participants through an engine two wide: the second tile's
    slot past the third participant repeats the tile's first client and is
    left out; each participant keeps its own shard size as weight."""
    parts = [np.arange(0, 10), np.arange(10, 13), np.arange(13, 20)]
    steps, batch = 2, 4
    # slot order: clients 0, 1 | 2, then client 2 again as the pad
    calls = [np.full(batch, p[1]) for p in (parts[0], parts[1], parts[2],
                                            parts[2]) for _ in range(steps)]
    rounds = run.checked_rounds([calls], parts, steps, [3])
    assert len(rounds) == 1
    assert rounds[0]["sels"].shape == (3, steps, batch)
    assert rounds[0]["sels"][:, 0, 0].tolist() == [1, 11, 14]
    assert rounds[0]["weights"].tolist() == [10.0, 3.0, 7.0]
