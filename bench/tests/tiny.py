"""Test-size cells, written into a copy of the benchmark beside the real
cells as a later PR adds one, new files and entries only: the reduced
VGG9 (G=5) with 4 clients, 2 steps of 8 images, in one round program
(``tiny_vgg.silo``) or as two cohort tiles of two (``tiny_vgg.tiled``),
and as a model of a second family (``tiny_acc.silo``,
``bench/tests/acc_family.py``)."""
from __future__ import annotations

import json
import os
import shutil

from bench import cells

def config(chips: int) -> dict:
    train = 400 * chips
    return {
        "name": "tiny_vgg",
        "family": "cnn",
        "argv": ["--mode", "fl", "--arch", "vgg9", "--reduced", "--method",
                 "fed2", "--train-size", str(train)],
        "model": {"plan": [["c", 20], ["p"], ["c", 40], ["p"], ["c", 40],
                           ["p"]],
                  "fc_dims": [80], "n_classes": 10, "fed2_groups": 5,
                  "decouple": 3, "norm": "gn", "input_hw": 32,
                  "input_channels": 3},
        "matmul_precision": "highest",
        "train_size": train,
        "test_size": train // 4,
        "local_sgd": {"lr": 0.01, "momentum": 0.9},
        "reduced": [],
    }


def traffic(chips: int) -> dict:
    nodes = 4 * chips
    return {"chips": chips,
            "argv": ["--nodes", str(nodes), "--classes-per-node", "5",
                     "--steps-per-epoch", "2", "--batch", "8"],
            "expect": {"population": nodes, "cohort": nodes, "steps": 2,
                       "batch": 8, "image_shape": [32, 32, 3]}}


def tiled(nodes: int) -> dict:
    """Every client every round through an engine two wide: two tiles, the
    second padded where ``nodes`` is odd."""
    return {"chips": 1,
            "argv": ["--nodes", str(nodes), "--classes-per-node", "5",
                     "--sampler", "full", "--cohort-size", "2",
                     "--steps-per-epoch", "2", "--batch", "8"],
            "expect": {"population": nodes, "cohort": 2, "steps": 2,
                       "batch": 8, "image_shape": [32, 32, 3]}}


# the test cell's limits, between CPU readings of sound runs (at most
# 2.3e-6) and of the control (at least 4e-5)
LIMITS = {"update_gap": 1e-5, "update_rms": 1e-5, "change_gap": 1e-5,
          "eval_moved": 0.01, "window_compiles": 0, "failed_rounds": 0}
# the padded cell's change after three rounds, on 9 seeds on the CPU, read
# 2.4e-7 to 1.3e-5 (3 ulps of one GroupNorm scale near 1, whose change is
# small), the control 9.9e-4 and up
PADDED_LIMITS = dict(LIMITS, change_gap=1e-4)
# the second family names its own eval number in place of eval_moved
ACC_LIMITS = {**{k: v for k, v in LIMITS.items() if k != "eval_moved"},
              "acc_gap": 0.01}


def make_root(dest: str, chips: int = 1) -> str:
    """A copy of the benchmark under ``dest`` whose BENCHMARK.json also
    holds the cells ``tiny_vgg.silo`` and ``tiny_acc.silo`` (on ``chips``
    chips), ``tiny_vgg.tiled`` (4 clients) and ``tiny_vgg.padded`` (3
    clients, one chip), added as a later PR adds one: new files and new
    entries only."""
    shutil.copytree(cells.BENCH_DIR, os.path.join(dest, "bench"),
                    ignore=shutil.ignore_patterns(".traces", "__pycache__"))
    # the second family's modules, from the test's own and the CNN's work
    modules = {"bench/reference/cnn_acc.py": "tests/acc_family.py",
               "bench/work/cnn_acc.py": "work/cnn.py"}
    bench = cells.load_benchmark()
    for name in ("tiny_vgg", "tiny_acc"):
        bench["configs"].append({"name": name, "source": "test",
                                 "file": f"bench/configs/{name}.json",
                                 "reduced": [], "why": "test size"})
    for name, conf, traf, n in (
            ("tiny_vgg.silo", "tiny_vgg", "tiny_silo", chips),
            ("tiny_vgg.tiled", "tiny_vgg", "tiny_tiled", 1),
            ("tiny_vgg.padded", "tiny_vgg", "tiny_padded", 1),
            ("tiny_acc.silo", "tiny_acc", "tiny_silo", chips)):
        bench["workloads"].append({"name": name, "config": conf,
                                   "traffic": traf, "chips": n,
                                   "why": "test size"})
    files = {"BENCHMARK.json": bench,
             "bench/configs/tiny_vgg.json": config(chips),
             "bench/configs/tiny_acc.json": dict(
                 config(chips), name="tiny_acc", family="cnn_acc"),
             "bench/traffic/tiny_silo.json": traffic(chips),
             "bench/traffic/tiny_tiled.json": tiled(4),
             "bench/traffic/tiny_padded.json": tiled(3),
             "bench/limits/tiny_vgg.silo.json": LIMITS,
             "bench/limits/tiny_vgg.tiled.json": LIMITS,
             "bench/limits/tiny_vgg.padded.json": PADDED_LIMITS,
             "bench/limits/tiny_acc.silo.json": ACC_LIMITS}
    for rel in [*modules, *files]:
        path = os.path.join(dest, rel)
        if rel != "BENCHMARK.json" and os.path.exists(path):
            raise FileExistsError(f"{rel} is a new file of the test")
    for rel, src in modules.items():
        shutil.copy(os.path.join(cells.BENCH_DIR, src), os.path.join(dest, rel))
    for rel, obj in files.items():
        with open(os.path.join(dest, rel), "w") as f:
            json.dump(obj, f)
    return dest
