"""DeepSeek-V2-Lite at a small size (the configuration's ``reduced()``,
seeded random weights): latent attention without a query low-rank path
and with YaRN, the expert layer told which experts it holds, one chip's
share built from the launcher's flags, and Fed2's groups over the held
experts."""
from __future__ import annotations

import dataclasses
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import deepseek_v2_236b, deepseek_v2_lite
from repro.configs.common import chip_share
from repro.models import attention as attn
from repro.models import moe as moe_lib
from repro.models.layers import YaRN, rope_freqs, yarn_softmax_mscale
from repro.models.transformer import init_params

CFG = deepseek_v2_lite.reduced()


def _yarn_inv_freq(dim, theta, low, high, factor):
    extra = 1.0 / theta ** (np.arange(0, dim, 2) / dim)
    keep = 1.0 - np.clip((np.arange(dim // 2) - low) / (high - low), 0, 1)
    return extra / factor * (1 - keep) + extra * keep


def test_yarn_frequencies_and_scale_follow_the_published_rope():
    # 64 rope dims over 4,096 positions: correction range 10 to 23
    inv = np.asarray(rope_freqs(64, 10000.0, yarn=YaRN()))
    np.testing.assert_allclose(inv, _yarn_inv_freq(64, 1e4, 10, 23, 40),
                               rtol=1e-6)
    m = 0.1 * 0.707 * math.log(40) + 1
    assert yarn_softmax_mscale(YaRN()) == pytest.approx(m * m)
    assert deepseek_v2_lite.full().mla.softmax_scale == pytest.approx(
        192 ** -0.5 * m * m)
    with pytest.raises(ValueError):
        YaRN(mscale=1.0)


def test_mla_without_q_lora_matches_a_plain_forward():
    cfg = CFG.mla
    assert cfg.q_lora is None
    p = attn.mla_init(jax.random.PRNGKey(0), cfg)
    assert "wq" in p and "wq_a" not in p
    b, s, h = 2, 40, cfg.n_heads
    x = jax.random.normal(jax.random.PRNGKey(1), (b, s, cfg.d_model))
    with jax.default_matmul_precision("highest"):
        got = attn.mla_apply(p, x, cfg, q_chunk=16, kv_chunk=8)
    w = {k: np.asarray(v["w"], np.float64) for k, v in p.items()
         if "w" in v}
    xn = np.asarray(x, np.float64)
    nope, rope, vd = cfg.qk_nope_dim, cfg.qk_rope_dim, cfg.v_head_dim
    inv = _yarn_inv_freq(rope, cfg.rope_theta, 1, 3, 40)  # 8 rope dims
    ang = np.arange(s)[:, None] * inv
    cos, sin = np.cos(ang)[:, None], np.sin(ang)[:, None]

    def rot(t):
        t1, t2 = t[..., :rope // 2], t[..., rope // 2:]
        return np.concatenate([t1 * cos - t2 * sin, t2 * cos + t1 * sin], -1)
    q = (xn @ w["wq"]).reshape(b, s, h, nope + rope)
    q = np.concatenate([q[..., :nope], rot(q[..., nope:])], -1)
    kv = xn @ w["wkv_a"]
    c = kv[..., :cfg.kv_lora]
    c = c / np.sqrt(np.mean(c * c, -1, keepdims=True) + 1e-6)
    k_rope = rot(kv[..., None, cfg.kv_lora:])
    k = np.concatenate([(c @ w["wk_b"]).reshape(b, s, h, nope),
                        np.broadcast_to(k_rope, (b, s, h, rope))], -1)
    v = (c @ w["wv_b"]).reshape(b, s, h, vd)
    sc = np.einsum("bqhd,bkhd->bhqk", q, k) * cfg.softmax_scale
    sc = np.where(np.tril(np.ones((s, s), bool)), sc, -np.inf)
    pr = np.exp(sc - sc.max(-1, keepdims=True))
    pr /= pr.sum(-1, keepdims=True)
    o = np.einsum("bhqk,bkhd->bqhd", pr, v).reshape(b, s, h * vd)
    np.testing.assert_allclose(np.asarray(got), o @ w["wo"], rtol=2e-4,
                               atol=2e-5)


def _layer(cfg, seed=0):
    p = moe_lib.moe_init(jax.random.PRNGKey(seed), cfg)
    x = jax.random.normal(jax.random.PRNGKey(seed + 1), (2, 24, cfg.d_model))
    return p, x


def _shared(p, x):
    sp = p["shared"]
    return (jax.nn.silu(x @ sp["w_gate"]["w"]) * (x @ sp["w_up"]["w"])) \
        @ sp["w_down"]["w"]


@pytest.mark.parametrize("shares", [8, 4])
def test_held_expert_shares_sum_to_the_uncut_layer(shares):
    """Each share routes over all experts and computes its own experts'
    part; the parts of disjoint shares, with the shared expert that every
    chip computes alike counted once, sum to the uncut layer."""
    cfg = CFG.moe
    p, x = _layer(cfg)
    with jax.default_matmul_precision("highest"):
        whole, _ = moe_lib.moe_apply_dense_reference(p, x, cfg)
        shared = _shared(p, x)
        n = cfg.n_experts // shares
        total = shared
        for j in range(shares):
            part = dict(p, **{k: p[k][j * n:(j + 1) * n]
                              for k in ("w_gate", "w_up", "w_down")})
            y, _ = moe_lib.moe_apply(
                part, x, dataclasses.replace(cfg, held=(j * n, n)))
            total = total + (y - shared)
    np.testing.assert_allclose(np.asarray(total), np.asarray(whole),
                               rtol=1e-4, atol=1e-5)


def test_held_layer_drops_no_token():
    """Routing every token to the same experts would overflow any
    capacity; the held layer computes every pair."""
    cfg = dataclasses.replace(CFG.moe, held=(0, 16))
    p, x = _layer(cfg)
    p = dict(p, router={"w": jnp.zeros_like(p["router"]["w"])
                        .at[:, :4].set(1.0)})
    with jax.default_matmul_precision("highest"):
        got, _ = moe_lib.moe_apply(p, x, cfg)
        want, _ = moe_lib.moe_apply_dense_reference(p, x, cfg)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-4, atol=1e-5)


def test_chip_share_cuts_depth_experts_and_vocabulary_only():
    full = deepseek_v2_lite.full()
    share = chip_share(full, layers=5, chips=8)
    assert (share.n_layers, share.vocab, share.moe.n_experts,
            share.moe.held, share.moe.top_k) == (5, 12800, 64, (0, 8), 6)
    assert (share.d_model, share.mla, share.moe.d_ff_expert,
            share.moe.d_ff_shared, share.moe_dense_ff) == (
        full.d_model, full.mla, 1408, 2816, 10944)
    with pytest.raises(ValueError):
        chip_share(full, layers=1, chips=8)    # no layer after the dense
    with pytest.raises(ValueError):
        chip_share(full, layers=5, chips=3)    # 64 experts do not divide


def test_build_fl_run_builds_the_lm_run_from_argv():
    from repro.fl import runtime
    from repro.launch import train
    args = train.parse_args([
        "--mode", "fl", "--arch", "deepseek-v2-lite", "--reduced",
        "--layers", "3", "--chips-per-layer", "4", "--nodes", "4",
        "--cohort-size", "1", "--dirichlet", "0.5", "--batch", "1",
        "--seq", "16", "--train-size", "12", "--seed", "3"])
    task, fl, parts, get_batch, test_batches = train.build_fl_run(args)
    assert not task.batched_clients and task.n_classes is None
    assert (fl.population, fl.cohort_size, fl.eval_batch) == (4, 1, 1)
    assert sum(len(q) for q in parts) == 12
    b = get_batch(np.array([0, 5]))
    assert {k: v.shape for k, v in b.items()} == {
        "tokens": (2, 16), "labels": (2, 16), "mask": (2, 16)}
    np.testing.assert_array_equal(b["tokens"][:, 1:], b["labels"][:, :-1])
    assert b["tokens"].max() < 512 // 4       # the vocabulary slice
    assert test_batches[0]["tokens"].shape == (3, 16)   # train_size // 4
    params = jax.eval_shape(task.init_fn, jax.random.PRNGKey(0))
    assert params["blocks"]["ffn"]["w_up"].shape[:2] == (2, 4)
    assert params["blocks"]["ffn"]["router"]["w"].shape[-1] == 16
    assert params["unembed"]["w"].shape == (64, 128)
    assert isinstance(task, runtime.FLTask)


def test_lm_group_axes_pair_the_held_experts():
    from repro.core import fusion
    cfg = chip_share(CFG, layers=3, chips=4)
    params = jax.eval_shape(lambda k: init_params(k, cfg),
                            jax.random.PRNGKey(0))
    axes = fusion.lm_group_axes(params, cfg)
    for k in ("w_gate", "w_up", "w_down"):
        assert axes["blocks"]["ffn"][k] == fusion.GroupAxis(1, 4)
    assert axes["blocks"]["ffn"]["router"]["w"] is None
    assert axes["unembed"]["w"] is None      # the head stays shared


def test_deepseek_236b_states_its_mla_and_yarn():
    cfg = deepseek_v2_236b.full()
    assert cfg.mla.q_lora == 1536 and cfg.mla.kv_lora == 512
    assert cfg.mla.rope_scaling == YaRN()
    assert deepseek_v2_236b.reduced().mla.q_lora == 1536


def _unwritten_past_groups(monkeypatch):
    """``jax.lax.ragged_dot`` as a TPU leaves it: rows past the last group
    hold whatever was there (NaN here), in the product and in the
    gradient of its left operand."""
    real = jax.lax.ragged_dot

    def spoil(y, sizes):
        past = jnp.arange(y.shape[0]) >= jnp.sum(sizes)
        return jnp.where(past[:, None], jnp.nan, y)

    @jax.custom_vjp
    def ragged(x, w, sizes):
        return spoil(real(x, w, sizes), sizes)

    def fwd(x, w, sizes):
        return ragged(x, w, sizes), (x, w, sizes)

    def bwd(res, ct):
        x, w, sizes = res
        _, vjp = jax.vjp(lambda a, b: real(a, b, sizes), x, w)
        dx, dw = vjp(ct)
        return spoil(dx, sizes), dw, None

    ragged.defvjp(fwd, bwd)
    monkeypatch.setattr(jax.lax, "ragged_dot",
                        lambda x, w, sizes, **kw: ragged(x, w, sizes))


def test_held_layer_ignores_rows_past_the_groups(monkeypatch):
    """Pairs routed to experts held elsewhere sit past the grouped
    products' last group, where a TPU leaves the rows unwritten: neither
    the layer's output nor any gradient reads them."""
    cfg = dataclasses.replace(CFG.moe, held=(4, 4))
    p, x = _layer(cfg, seed=3)

    def loss(p, x):
        y, _ = moe_lib.moe_apply(p, x, cfg)
        return jnp.sum(jnp.sin(y))
    with jax.default_matmul_precision("highest"):
        want = jax.value_and_grad(loss, argnums=(0, 1))(p, x)
        _unwritten_past_groups(monkeypatch)
        got = jax.value_and_grad(loss, argnums=(0, 1))(p, x)
    for a, b in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(want)):
        assert bool(jnp.all(jnp.isfinite(a)))
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-5, atol=1e-6)


def test_held_expert_model_refuses_a_cohort_wider_than_one():
    """A held-expert model has no batched form on the TPU, so the engine
    takes it one client per tile and says so for a wider cohort."""
    from repro.fl.engine import make_round_engine
    from repro.launch import train

    args = train.parse_args([
        "--mode", "fl", "--arch", "deepseek-v2-lite", "--reduced",
        "--layers", "3", "--chips-per-layer", "4", "--nodes", "2",
        "--cohort-size", "2", "--dirichlet", "0.5",
        "--steps-per-epoch", "1", "--batch", "1", "--seq", "16",
        "--train-size", "8", "--rounds", "1", "--seed", "5"])
    task, fl, *_ = train.build_fl_run(args)
    params = jax.eval_shape(task.init_fn, jax.random.PRNGKey(0))
    with pytest.raises(ValueError, match="cohort_size 2"):
        make_round_engine(task, fl, params)
