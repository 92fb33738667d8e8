"""Every cell of BENCHMARK.json resolves to files the launcher accepts; a
new cell is added with new files only; without a TPU the benchmark exits
non-zero and prints no result."""
from __future__ import annotations

import os
import subprocess
import sys

import pytest

from bench import cells
from bench.tests import tiny

BENCH = cells.load_benchmark()
WORKLOADS = [w["name"] for w in BENCH["workloads"]]
# the numbers every family compares; the rest of a limits file names the
# family's own eval numbers (``eval_numbers`` of its reference module)
GENERIC = {"update_gap", "update_rms", "change_gap", "window_compiles",
           "failed_rounds"}
HOOKS = ("run_rounds", "batch_shapes", "test_set", "program_eval",
         "eval_numbers", "plant_wrong_answer")


@pytest.mark.parametrize("workload", WORKLOADS)
def test_cell_resolves_to_an_argv_the_launcher_accepts(workload):
    from repro.launch import train
    cell = cells.resolve(workload)
    args = train.parse_args(cell.argv + ["--seed", "2147483659"])
    e = cell.traffic["expect"]
    assert args.nodes == e["population"]
    assert (args.cohort_size or args.nodes) == e["cohort"]
    assert args.local_epochs * args.steps_per_epoch == e["steps"]
    assert args.batch == e["batch"]
    assert args.train_size == cell.config["train_size"]
    assert args.train_size // 4 == cell.config["test_size"]
    assert cell.config["matmul_precision"] in ("highest", "high",
                                               "default")
    assert cell.traffic["chips"] == cell.chips
    assert {"window_compiles", "failed_rounds"} <= set(cell.limits)
    assert set(cell.limits) & {"update_gap", "update_rms", "change_gap"}
    assert set(cell.limits) - GENERIC, "no eval number is compared"
    reference = cells.family_module(cell, "reference")
    for hook in HOOKS:
        assert callable(getattr(reference, hook)), hook
    assert cells.family_module(cell, "work").train_flops_per_sample
    names = {m["name"] for m in cell.end_to_end}
    assert {"setup_s", "samples_per_s", "peak_hbm_gib"} <= names
    assert cell.per_layer
    for m in cell.per_layer:
        assert callable(cells.metric_reader(cell, m["name"]))


def test_benchmark_json_keys_and_names():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    for c in BENCH["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert c["file"].startswith("bench/")
    for m in BENCH["end_to_end"] + BENCH["per_layer"]:
        assert m["better"] in ("lower", "higher")
    for m in BENCH["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
    fours = sum(w["chips"] == 4 for w in BENCH["workloads"])
    assert fours <= max(1, len(BENCH["workloads"]) // 2)


def test_a_later_cell_is_new_files_and_entries(tmp_path):
    root = tiny.make_root(str(tmp_path))
    cell = cells.resolve("tiny_vgg.silo", root=root)
    assert cell.config["name"] == "tiny_vgg"
    assert cell.traffic["expect"]["population"] == 4
    assert cells.resolve("tiny_vgg.tiled", root=root).traffic["expect"][
        "cohort"] == 2
    assert cells.resolve("tiny_acc.silo", root=root).family == "cnn_acc"
    # the cells already there resolve unchanged beside it
    for w in WORKLOADS:
        assert cells.resolve(w, root=root).argv == cells.resolve(w).argv
    with pytest.raises(KeyError):
        cells.resolve("no_such.cell", root=root)


def test_unknown_device_has_no_peaks():
    assert cells.peaks("TPU v5 lite")["bf16_flops_per_s"] == 197e12
    with pytest.raises(KeyError):
        cells.peaks("cpu")


def test_run_without_a_tpu_exits_nonzero_and_prints_no_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run(
        [sys.executable, os.path.join(cells.BENCH_DIR, "run.py"),
         "--workload", WORKLOADS[0], "--seed", "0", "--seconds", "10",
         "--trace", "0"],
        capture_output=True, text=True, env=env, timeout=300)
    assert out.returncode != 0
    assert out.stdout.strip() == ""
    assert "TPU" in out.stderr
