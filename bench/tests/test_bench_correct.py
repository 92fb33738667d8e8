"""The correctness check at a test size, past the look for a chip: a sound
run is correct, and the lower-precision control and each planted fault
turn ``correct`` false."""
from __future__ import annotations

import json
import os
import subprocess
import sys

import pytest

from bench import cells, run
from bench.tests import tiny

SEED = 2**31 + 11      # past 32 signed bits, as a benchmark seed may be


@pytest.fixture(scope="module")
def cell(tmp_path_factory):
    root = tiny.make_root(str(tmp_path_factory.mktemp("one_chip")))
    return cells.resolve("tiny_vgg.silo", root=root)


def _failing(result):
    return {k for k, c in result["checks"].items()
            if c["value"] > c["limit"]}


def test_sound_run_is_correct(cell):
    result = run.run_cell(cell, SEED, 0.2)
    assert result["correct"], result["checks"]
    assert result["attempted"] >= 1 and result["failed"] == 0
    assert list(result)[-1] == "checks"
    assert set(result["metrics"]) == {m["name"] for m in cell.end_to_end}
    assert result["metrics"]["samples_per_s"]["value"] > 0


@pytest.mark.parametrize("fault,caught_by", [
    ("control", {"update_gap", "update_rms", "change_gap"}),
    ("frozen", {"update_gap", "update_rms", "change_gap"}),
    ("half_batch", {"update_gap", "update_rms", "change_gap"}),
    ("dropped", {"update_gap", "update_rms", "change_gap"}),
    ("wrong_answer", {"eval_moved"})])
def test_control_and_faults_are_not_correct(cell, fault, caught_by):
    result = run.run_cell(cell, SEED, 0.2, fault=fault)
    assert not result["correct"]
    assert _failing(result) & caught_by


def test_compare_on_known_trees():
    """Per-leaf norm gaps by hand; a leaf the reference leaves still is
    left out; the CNN family's ``eval_moved`` reads the first round only
    and is merged in."""
    import numpy as np

    from bench import check
    from bench.reference import cnn

    def tree(a, b, still):
        return {"a": np.full(4, a), "b": np.full(4, b),
                "still": np.full(4, still)}
    ref = {"thetas": [tree(0, 0, 0), tree(1, 2, 1e-9), tree(2, 4, 0)],
           "confusion": [np.eye(2) * 50, np.eye(2) * 50]}
    prog = [tree(0, 0, 0), tree(1.1, 2, 5e-9), tree(2, 4, 0)]
    conf = [np.array([[45.0, 5.0], [0.0, 50.0]]), np.zeros((2, 2))]
    numbers, left_out, _ = check.compare(prog, conf, ref, cnn)
    assert left_out == 1
    # leaf a: |2.2 - 2| / max(2, median 3) ; leaf b matches
    assert numbers["update_gap"] == pytest.approx(0.2 / 3)
    assert numbers["update_rms"] == pytest.approx(np.sqrt(0.1 ** 2 / 2))
    assert numbers["change_gap"] == 0.0
    assert cnn.eval_numbers(conf, ref) == {
        "eval_moved": pytest.approx(5 / 100)}
    assert numbers["eval_moved"] == cnn.eval_numbers(conf, ref)["eval_moved"]


def test_exchange_left_out_is_not_correct(tmp_path):
    """The cohort sharded over four (virtual CPU) devices, with fusion
    seeing only the first device's clients."""
    root = tiny.make_root(str(tmp_path), chips=4)
    paths = [cells.ROOT, os.path.join(cells.ROOT, "src")]
    code = (
        "import json, sys\n"
        f"sys.path[:0] = {paths!r}\n"
        "from bench import cells, run\n"
        f"cell = cells.resolve('tiny_vgg.silo', root={root!r})\n"
        f"r = run.run_cell(cell, {SEED}, 0.2, fault='no_exchange')\n"
        "print(json.dumps({'correct': r['correct'], 'count': "
        "r['device']['count'], 'checks': r['checks']}))\n")
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, env=env, timeout=900)
    assert out.returncode == 0, out.stderr[-4000:]
    result = json.loads(out.stdout.strip().splitlines()[-1])
    assert result["count"] == 4
    assert not result["correct"]
    assert _failing(result) & {"update_gap", "update_rms", "change_gap"}
