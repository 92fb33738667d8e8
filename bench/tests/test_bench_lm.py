"""The language-model family: the DeepSeek-V2-Lite cell's files agree
with the program's model and with the published config, and a test-size
cell of the family (``bench/tests/tiny_lm.py``), four silos each a cohort
tile of one, is correct where it is sound and not where the control or a
fault stands in."""
from __future__ import annotations

import json
import os

import jax
import pytest

from bench import cells, run
from bench.reference import lm as reference
from bench.tests import tiny_lm
from bench.work import lm as work

SEED = 2**31 + 41      # past 32 signed bits, as a benchmark seed may be
CELL = "dsv2lite_fed2.silo"


def _share_config(argv):
    from repro.configs import deepseek_v2_lite
    from repro.configs.common import chip_share
    from repro.launch import train
    args = train.parse_args(argv + ["--seed", "0"])
    return chip_share(deepseek_v2_lite.full(), layers=args.layers,
                      chips=args.chips_per_layer)


def test_cell_states_the_share_of_the_published_model():
    cell = cells.resolve(CELL)
    c, m = cell.config, cell.config["model"]
    # the catalog's keys; reduced names exactly depth, experts and vocab
    assert set(c["reduced"]) == {"num_hidden_layers", "n_routed_experts",
                                 "vocab_size"}
    for k in c["reduced"]:
        assert c[k] < c["published"][k]
    assert (c["num_hidden_layers"], c["n_routed_experts"],
            c["vocab_size"]) == (m["n_layers"], m["held"][1], m["vocab"])
    assert (c["hidden_size"], c["num_attention_heads"], c["kv_lora_rank"],
            c["qk_nope_head_dim"], c["qk_rope_head_dim"],
            c["v_head_dim"]) == (m["d_model"], m["n_heads"], m["kv_lora"],
                                 m["qk_nope"], m["qk_rope"], m["v_head"])
    assert c["q_lora_rank"] is None
    assert (c["intermediate_size"], c["moe_intermediate_size"],
            c["num_experts_per_tok"], c["n_shared_experts"]
            * c["moe_intermediate_size"]) == (
        m["dense_ff"], m["expert_ff"], m["top_k"], m["shared_ff"])
    y = c["rope_scaling"]
    assert (y["factor"], y["original_max_position_embeddings"],
            y["mscale_all_dim"]) == (m["yarn"]["factor"],
                                     m["yarn"]["original_max_position"],
                                     m["yarn"]["mscale_all_dim"])
    assert m["router_experts"] == c["published"]["n_routed_experts"]
    assert m["seq"] == cell.traffic["expect"]["seq"]
    # the program builds the same share from the cell's flags
    cfg = _share_config(cell.argv)
    assert (cfg.n_layers, cfg.vocab, cfg.moe.n_experts, cfg.moe.held,
            cfg.mla.q_lora) == (m["n_layers"], m["vocab"],
                                m["router_experts"], tuple(m["held"]), None)


def test_work_counts_the_programs_parameters():
    from repro.models.transformer import init_params
    cell = cells.resolve(CELL)
    shapes = jax.eval_shape(lambda k: init_params(k, _share_config(
        cell.argv)), jax.random.PRNGKey(0))
    n = sum(x.size for x in jax.tree_util.tree_leaves(shapes))
    assert n == work.param_count(cell.config["model"]) == \
        cell.config["params"]
    assert work.train_flops_per_sample(cell.config["model"]) / 1e9 == \
        pytest.approx(cell.config["train_gflop_per_sample"], rel=1e-6)


def test_reference_yarn_matches_the_published_ranges():
    m = cells.resolve(CELL).config["model"]
    inv = reference.yarn_inv_freq(m)
    extra = 1.0 / 10000.0 ** (2 * jax.numpy.arange(32) / 64)
    # dims below the correction range keep their frequency, dims past it
    # are slowed by the factor (range 10 to 23 for beta 32 and 1)
    assert jax.numpy.allclose(inv[:11], extra[:11], rtol=1e-6)
    assert jax.numpy.allclose(inv[23:], extra[23:] / 40, rtol=1e-6)
    assert reference.softmax_scale(m) == pytest.approx(
        192 ** -0.5 * (0.1 * 0.707 * 3.6888794541139363 + 1) ** 2)


@pytest.fixture(scope="module")
def tiny(tmp_path_factory):
    root = tiny_lm.make_root(str(tmp_path_factory.mktemp("lm")))
    return cells.resolve("tiny_lm.silo", root=root)


def _failing(result):
    return {k for k, c in result["checks"].items()
            if not c["value"] <= c["limit"]}


def test_tiny_lm_sound_run_is_correct(tiny):
    assert tiny.family == "lm"
    result = run.run_cell(tiny, SEED, 0.1, keep_detail=True)
    assert result["correct"], result["checks"]
    w = result["detail"]["window"]
    # four silos, each its own tile, 2 steps of one sequence
    assert w["participants"] == [4] * w["rounds"]
    assert w["samples"] == w["rounds"] * 4 * 2


@pytest.mark.parametrize("fault,caught_by", [
    ("control", {"update_gap", "update_rms"}),
    ("wrong_answer", {"eval_loss_gap"})])
def test_tiny_lm_control_and_wrong_answer_are_not_correct(tiny, fault,
                                                          caught_by):
    result = run.run_cell(tiny, SEED, 0.1, fault=fault)
    assert not result["correct"]
    assert _failing(result) & caught_by


def test_tiny_lm_files_are_new(tmp_path):
    root = tiny_lm.make_root(str(tmp_path))
    assert os.path.exists(os.path.join(root, "bench", "configs",
                                       "tiny_lm.json"))
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        names = [w["name"] for w in json.load(f)["workloads"]]
    assert names[-1] == "tiny_lm.silo" and CELL in names


def test_model_scopes_count_the_grouped_products_under_moe():
    """The model's scopes from op name stacks, clipped to the window; the
    TPU's grouped-product custom calls, whose stack is their own name,
    count under ``moe`` and ``experts``."""
    from bench import model_scopes
    ops = [["%fusion.1", 0, 10], ["%ragged-dot-none", 10, 5],
           ["%fusion.2", 20, 10], ["%fusion.3", 35, 10]]
    stacks = ["jit(tile_fn)/local/mla/dot_general", "ragged-dot-none",
              "jit(tile_fn)/local/transpose(jvp(moe))/shared/mul",
              "jit(counts)/while/head/reduce"]
    data = {"devices": {"0": {"ops": ops, "op_scope": [0, 1, 2, 3],
                              "stacks": stacks}}}
    got = model_scopes._reduce(data, 0, 40)
    assert got == {"mla": 10, "moe": 15, "experts": 5, "shared": 10,
                   "head": 5}
