"""Work counted from shapes, and the reference's parameter tree against
the program's."""
from __future__ import annotations

import json
import os

import jax
import numpy as np
import pytest

from bench import cells
from bench.reference import cnn as ref
from bench.work import cnn as work

MODELS = {
    "vgg9": json.load(open(os.path.join(
        cells.BENCH_DIR, "configs", "vgg9_fed2.json")))["model"],
    # VGG-16 (arXiv:1409.1556) with the CIFAR-100 FC 512-512 head, G=8
    "vgg16": {"plan": [["c", 64], ["c", 64], ["p"], ["c", 128], ["c", 128],
                       ["p"], ["c", 256], ["c", 256], ["c", 256], ["p"],
                       ["c", 512], ["c", 512], ["c", 512], ["p"],
                       ["c", 512], ["c", 512], ["c", 512], ["p"]],
              "fc_dims": [512, 512], "n_classes": 100, "fed2_groups": 8,
              "decouple": 6, "norm": "gn", "input_hw": 32,
              "input_channels": 3},
}


@pytest.mark.parametrize("name,params,gflop", [
    ("vgg9", 521_616, 0.304), ("vgg16", 8_603_304, 1.73)])
def test_params_and_training_flops_per_sample(name, params, gflop):
    model = MODELS[name]
    assert work.param_count(model) == params
    assert work.train_flops_per_sample(model) == \
        3 * work.forward_flops_per_sample(model)
    assert work.train_flops_per_sample(model) / 1e9 == \
        pytest.approx(gflop, rel=5e-3)


def test_paired_fusion_bytes_and_flops_per_call():
    # the largest VGG16 leaf, 8 clients: read 8 copies and the weights,
    # write one
    m = 2_359_296
    assert max(work.leaf_sizes(MODELS["vgg16"])) == m
    assert work.paired_fusion_bytes(m, 8) == 4 * (8 * m + 8 + m)
    assert work.paired_fusion_flops(m, 8) == 16 * m


@pytest.mark.parametrize("name,arch", [("vgg9", "vgg9"),
                                       ("vgg16", "vgg16")])
def test_reference_tree_matches_the_program(name, arch):
    import importlib
    model = MODELS[name]
    from repro.fl.runtime import cnn_task
    cfg = importlib.import_module(f"repro.configs.{arch}").full(
        fed2_groups=model["fed2_groups"])
    prog = jax.eval_shape(cnn_task(cfg).init_fn, jax.random.PRNGKey(0))
    mine = jax.eval_shape(lambda: ref.init(0, model))
    assert (jax.tree_util.tree_structure(prog)
            == jax.tree_util.tree_structure(mine))
    assert ([x.shape for x in jax.tree_util.tree_leaves(prog)]
            == [x.shape for x in jax.tree_util.tree_leaves(mine)])
    assert sorted(int(np.prod(x.shape)) for x in jax.tree_util.tree_leaves(
        mine)) == sorted(work.leaf_sizes(model))
