"""A test-size cell, written into a copy of the benchmark beside the real
cells: the reduced VGG9 (G=5) with 4 clients, 2 steps of 8 images."""
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


# the test cell's limits, between CPU readings of sound runs (at most
# 2.3e-6) and of the control (at least 4e-5)
LIMITS = {"update_gap": 1e-5, "update_rms": 1e-5, "change_gap": 1e-5,
          "eval_moved": 0.01, "window_compiles": 0, "failed_rounds": 0}


def make_root(dest: str, chips: int = 1) -> str:
    """A copy of the benchmark under ``dest`` whose BENCHMARK.json also
    holds the cell ``tiny_vgg.silo`` (on ``chips`` chips), added as a
    later PR adds one: new files and new entries only."""
    shutil.copytree(cells.BENCH_DIR, os.path.join(dest, "bench"),
                    ignore=shutil.ignore_patterns(".traces", "__pycache__"))
    bench = cells.load_benchmark()
    bench["configs"].append({"name": "tiny_vgg", "source": "test",
                             "file": "bench/configs/tiny_vgg.json",
                             "reduced": [], "why": "test size"})
    bench["workloads"].append({"name": "tiny_vgg.silo", "config": "tiny_vgg",
                               "traffic": "tiny_silo", "chips": chips,
                               "why": "test size"})
    files = {"BENCHMARK.json": bench,
             "bench/configs/tiny_vgg.json": config(chips),
             "bench/traffic/tiny_silo.json": traffic(chips),
             "bench/limits/tiny_vgg.silo.json": LIMITS}
    for rel, obj in files.items():
        with open(os.path.join(dest, rel), "w") as f:
            json.dump(obj, f)
    return dest
