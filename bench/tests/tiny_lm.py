"""A test-size cell of the language-model family, written into a copy of
the benchmark beside the real cells as a later PR adds one, new files and
entries only: the reduced DeepSeek-V2-Lite (one dense and two MoE layers,
4 of 16 experts held, a quarter of the vocabulary) over 4 silos, each a
cohort tile of one, 2 steps of one 32-token sequence (``tiny_lm.silo``)."""
from __future__ import annotations

import json
import os
import shutil

from bench import cells

MODEL = {"n_layers": 3, "first_dense": 1, "d_model": 64, "n_heads": 4,
         "kv_lora": 32, "qk_nope": 16, "qk_rope": 8, "v_head": 16,
         "rope_theta": 10000.0,
         "yarn": {"factor": 40, "original_max_position": 4096,
                  "beta_fast": 32, "beta_slow": 1, "mscale": 0.707,
                  "mscale_all_dim": 0.707},
         "dense_ff": 128, "expert_ff": 32, "router_experts": 16,
         "held": [0, 4], "top_k": 4, "shared_ff": 64, "vocab": 128,
         "rms_eps": 1e-6, "aux_weight": 0.0, "seq": 32}

CONFIG = {"name": "tiny_lm", "family": "lm",
          "argv": ["--mode", "fl", "--arch", "deepseek-v2-lite", "--reduced",
                   "--layers", "3", "--chips-per-layer", "4", "--method",
                   "fed2", "--train-size", "16"],
          "model": MODEL, "matmul_precision": "highest", "train_size": 16,
          "test_size": 4, "local_sgd": {"lr": 0.01, "momentum": 0.9},
          "reduced": []}

TRAFFIC = {"chips": 1,
           "argv": ["--nodes", "4", "--cohort-size", "1", "--sampler",
                    "full", "--dirichlet", "0.5", "--steps-per-epoch", "2",
                    "--batch", "1", "--seq", "32"],
           "expect": {"population": 4, "cohort": 1, "steps": 2, "batch": 1,
                      "seq": 32}}

# between CPU readings on 5 seeds of sound runs (update_gap 6.6e-7 to
# 2.4e-6, update_rms 2.1e-7 to 1.3e-6, eval_loss_gap at most 9.1e-8) and of
# the control at high (update_gap 4.6e-6 and up, update_rms 4.1e-6 and up);
# change_gap does not separate them here (3 rounds of 2 steps)
LIMITS = {"update_gap": 3e-6, "update_rms": 2.5e-6, "change_gap": 1e-4,
          "eval_loss_gap": 1e-5, "window_compiles": 0, "failed_rounds": 0}


def make_root(dest: str) -> str:
    """A copy of the benchmark under ``dest`` whose BENCHMARK.json also
    holds the cell ``tiny_lm.silo``."""
    shutil.copytree(cells.BENCH_DIR, os.path.join(dest, "bench"),
                    ignore=shutil.ignore_patterns(".traces", "__pycache__"))
    bench = cells.load_benchmark()
    bench["configs"].append({"name": "tiny_lm", "source": "test",
                             "file": "bench/configs/tiny_lm.json",
                             "reduced": [], "why": "test size"})
    bench["workloads"].append({"name": "tiny_lm.silo", "config": "tiny_lm",
                               "traffic": "tiny_lm_silo", "chips": 1,
                               "why": "test size"})
    files = {"BENCHMARK.json": bench,
             "bench/configs/tiny_lm.json": CONFIG,
             "bench/traffic/tiny_lm_silo.json": TRAFFIC,
             "bench/limits/tiny_lm.silo.json": LIMITS}
    for rel, obj in files.items():
        path = os.path.join(dest, rel)
        if rel != "BENCHMARK.json" and os.path.exists(path):
            raise FileExistsError(f"{rel} is a new file of the test")
        with open(path, "w") as f:
            json.dump(obj, f)
    return dest
