"""Plain reference of one chip's share of a DeepSeek-V2 language model and
its federated rounds.

Written from the published description (arXiv:2405.04434; the model's
``config.json`` and ``modeling_deepseek.py``) in straightforward
``jax.numpy`` at float32, every product at ``highest`` precision; it
imports nothing of the program. At ``precision="high"`` every product is
instead the sum of three bf16 passes (``cnn._passes``), as a TPU computes
float32 at ``high``: the lower-precision control. The model, per layer:

- RMSNorm (eps from the config), then multi-head latent attention with no
  query low-rank path: q = x Wq split into 128 "nope" and 64 rope dims a
  head; the latent [c_kv, k_rope] = x Wkv_a, c_kv RMS-normed, k_nope =
  c_kv Wk_b, v = c_kv Wv_b; the rope dims rotated by YaRN frequencies
  (factor 40 over 4,096 positions, correction range from beta 32 and 1)
  in the half-split layout (upstream first interleaves them: a fixed
  permutation of the rope columns of Wq and Wkv_a, which random weights
  do not see); causal softmax at ``192**-0.5 * m**2`` with m = 0.1 *
  mscale_all_dim * ln(factor) + 1; output through Wo;
- RMSNorm, then the first ``first_dense`` layers a dense SwiGLU FFN, the
  others the expert layer: a softmax over all ``router_experts`` router
  logits, the top-``top_k`` with no renormalisation, and of those only the
  experts this chip holds (``held``: first index and count), each held
  expert computed on every token and combined with its routing weight
  (the absent experts' part is left out, as on the chip); plus the shared
  SwiGLU expert on every token; the load-balance term weighed by
  ``aux_weight``;
- a final RMSNorm and the untied head over the vocabulary slice; the loss
  is the mean next-token cross-entropy over the mask.

Each layer is rematerialised in the backward pass, and attention is taken
in blocks of ``Q_BLOCK`` queries over all keys, so that it fits the chip.

Initialisation follows the program's recipe from the seed: the key split
into 8 (embedding, MoE layers, ..., dense layers at 5, head at 6), each
layer's key split as the program's layer splits it, weights N(0,
1/fan_in), norm scales one. The parameter tree has the program's keys, so
that its leaves compare in the same order.

A federated round, as in ``cnn.py``: every client starts from the global,
runs momentum SGD on its batches, and the server takes the shard-size-
weighted mean, in blocks of the engine's width as a tiled round takes it.
The eval is the test set's mean next-token loss after each round
(``eval_loss``), which compares logits where an argmax would flip on
rounding.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

from bench.reference.cnn import PRECISIONS, _passes

Q_BLOCK = 512


# -- initialisation -------------------------------------------------------

def _normal(key, shape, fan_in):
    return jax.random.normal(key, shape, jnp.float32) * float(
        1.0 / np.sqrt(fan_in))


def _dense(key, d_in, d_out):
    return {"w": _normal(key, (d_in, d_out), d_in)}


def _attn_init(key, m):
    ks = jax.random.split(key, 7)
    d, h = m["d_model"], m["n_heads"]
    qk = m["qk_nope"] + m["qk_rope"]
    return {"wq": _dense(ks[1], d, h * qk),
            "wkv_a": _dense(ks[2], d, m["kv_lora"] + m["qk_rope"]),
            "kv_a_norm": {"scale": jnp.ones((m["kv_lora"],))},
            "wk_b": _dense(ks[3], m["kv_lora"], h * m["qk_nope"]),
            "wv_b": _dense(ks[4], m["kv_lora"], h * m["v_head"]),
            "wo": _dense(ks[5], h * m["v_head"], d)}


def _swiglu_init(key, d, f):
    ks = jax.random.split(key, 3)
    return {"w_gate": _dense(ks[0], d, f), "w_up": _dense(ks[1], d, f),
            "w_down": _dense(ks[2], f, d)}


def _dense_layer_init(key, m):
    ks = jax.random.split(key, 2)
    d = m["d_model"]
    return {"ln1": {"scale": jnp.ones((d,))}, "attn": _attn_init(ks[0], m),
            "ln2": {"scale": jnp.ones((d,))},
            "ffn": _swiglu_init(ks[1], d, m["dense_ff"])}


def _moe_layer_init(key, m):
    ks = jax.random.split(key, 4)
    d, f, e = m["d_model"], m["expert_ff"], m["held"][1]
    kf = jax.random.split(ks[1], 5)
    ffn = {"router": _dense(kf[0], d, m["router_experts"]),
           "w_gate": _normal(kf[1], (e, d, f), d),
           "w_up": _normal(kf[2], (e, d, f), d),
           "w_down": _normal(kf[3], (e, f, d), f),
           "shared": _swiglu_init(kf[4], d, m["shared_ff"])}
    return {"ln1": {"scale": jnp.ones((d,))}, "attn": _attn_init(ks[0], m),
            "ln2": {"scale": jnp.ones((d,))}, "ffn": ffn}


def _stack(init, key, n, m):
    return jax.vmap(lambda k: init(k, m))(jax.random.split(key, n))


def init(seed: int, model: dict) -> dict:
    ks = jax.random.split(jax.random.PRNGKey(seed), 8)
    d, v = model["d_model"], model["vocab"]
    n_dense = model["first_dense"]
    return {"embed": {"table": _normal(ks[0], (v, d), d)},
            "pre_blocks": _stack(_dense_layer_init, ks[5], n_dense, model),
            "blocks": _stack(_moe_layer_init, ks[1],
                             model["n_layers"] - n_dense, model),
            "final_norm": {"scale": jnp.ones((d,))},
            "unembed": _dense(ks[6], d, v)}


# -- forward --------------------------------------------------------------

def yarn_inv_freq(model: dict) -> np.ndarray:
    """modeling_deepseek.py's DeepseekV2YarnRotaryEmbedding frequencies."""
    dim, base, y = model["qk_rope"], model["rope_theta"], model["yarn"]
    extra = 1.0 / (base ** (np.arange(0, dim, 2, dtype=np.float32) / dim))
    inter = extra / y["factor"]

    def corr(rot):
        return (dim * math.log(y["original_max_position"]
                               / (rot * 2 * math.pi)) / (2 * math.log(base)))
    low = max(math.floor(corr(y["beta_fast"])), 0)
    high = min(math.ceil(corr(y["beta_slow"])), dim - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(dim // 2, dtype=np.float32) - low)
                   / (high - low), 0, 1)
    mask = 1.0 - ramp
    return (inter * (1 - mask) + extra * mask).astype(np.float32)


def softmax_scale(model: dict) -> float:
    y = model["yarn"]
    m = 0.1 * y["mscale_all_dim"] * math.log(y["factor"]) + 1.0
    return (model["qk_nope"] + model["qk_rope"]) ** -0.5 * m * m


def _rms(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True)
                             + eps) * scale


def _rope(x, pos, inv):
    ang = pos[:, None].astype(jnp.float32) * inv          # (S, d/2)
    cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    half = x.shape[-1] // 2
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _forward(params, model, tokens, precision):
    """tokens (B, S) -> (hidden after the final norm (B, S, d), aux)."""
    def ein(spec, a, b):
        return _passes(lambda p: functools.partial(jnp.einsum, spec,
                                                   precision=p),
                       precision)(a, b)

    eps = model["rms_eps"]
    h_n, nope, rope = model["n_heads"], model["qk_nope"], model["qk_rope"]
    vd, lora = model["v_head"], model["kv_lora"]
    inv = jnp.asarray(yarn_inv_freq(model))
    scale = softmax_scale(model)
    b, s = tokens.shape
    pos = jnp.arange(s)

    def attention(p, x):
        q = ein("bsd,de->bse", x, p["wq"]["w"]).reshape(b, s, h_n,
                                                         nope + rope)
        q = jnp.concatenate([q[..., :nope], _rope(q[..., nope:], pos, inv)],
                            -1)
        kv = ein("bsd,de->bse", x, p["wkv_a"]["w"])
        c = _rms(kv[..., :lora], p["kv_a_norm"]["scale"], eps)
        k_rope = _rope(kv[..., None, lora:], pos, inv)
        k = jnp.concatenate([
            ein("bsl,le->bse", c, p["wk_b"]["w"]).reshape(b, s, h_n, nope),
            jnp.broadcast_to(k_rope, (b, s, h_n, rope))], -1)
        v = ein("bsl,le->bse", c, p["wv_b"]["w"]).reshape(b, s, h_n, vd)

        width = min(Q_BLOCK, s)

        @jax.checkpoint
        def block(q0):
            qb = jax.lax.dynamic_slice_in_dim(q, q0, width, axis=1)
            sc = ein("bqhd,bkhd->bhqk", qb, k) * scale
            causal = (q0 + jnp.arange(width))[:, None] >= pos[None, :]
            sc = jnp.where(causal, sc, -jnp.inf)
            return ein("bhqk,bkhd->bqhd", jax.nn.softmax(sc, axis=-1), v)
        o = jax.lax.map(block, jnp.arange(0, s, width))   # (blocks, b, ...)
        o = jnp.moveaxis(o, 0, 1).reshape(b, s, h_n * vd)
        return ein("bse,ed->bsd", o, p["wo"]["w"])

    def swiglu(p, x):
        g = ein("bsd,df->bsf", x, p["w_gate"]["w"])
        u = ein("bsd,df->bsf", x, p["w_up"]["w"])
        return ein("bsf,fd->bsd", jax.nn.silu(g) * u, p["w_down"]["w"])

    def experts(p, x):
        first, count = model["held"]
        logits = ein("bsd,de->bse", x, p["router"]["w"])
        probs = jax.nn.softmax(logits, axis=-1)
        w, ids = jax.lax.top_k(probs, model["top_k"])
        g = ein("bsd,edf->ebsf", x, p["w_gate"])
        u = ein("bsd,edf->ebsf", x, p["w_up"])
        out = ein("ebsf,efd->ebsd", jax.nn.silu(g) * u, p["w_down"])
        # each held expert's routing weight, 0 where it is not in the top-k
        held = jnp.einsum("bsk,bske->bse", w,
                          jax.nn.one_hot(ids - first, count),
                          precision=jax.lax.Precision.HIGHEST)
        y = ein("bse,ebsd->bsd", held, out)
        e = model["router_experts"]
        top1 = jnp.mean(jax.nn.one_hot(ids[..., 0], e).reshape(-1, e), 0)
        aux = e * jnp.sum(top1 * jnp.mean(probs.reshape(-1, e), 0))
        return y + swiglu(p["shared"], x), aux

    def layer(p, x, moe):
        x = x + attention(p["attn"], _rms(x, p["ln1"]["scale"], eps))
        h = _rms(x, p["ln2"]["scale"], eps)
        if moe:
            y, aux = experts(p["ffn"], h)
        else:
            y, aux = swiglu(p["ffn"], h), jnp.zeros(())
        return x + y, aux

    x = params["embed"]["table"][tokens]
    aux = jnp.zeros(())
    for key, moe in (("pre_blocks", False), ("blocks", True)):
        def body(carry, p, moe=moe):
            x, a = carry
            x, a1 = jax.checkpoint(functools.partial(layer, moe=moe))(p, x)
            return (x, a + a1), None
        (x, aux), _ = jax.lax.scan(body, (x, aux), params[key])
    return _rms(x, params["final_norm"]["scale"], eps), aux


def _nll(params, model, batch, precision):
    """Each position's next-token loss (B, S) and the load-balance term."""
    h, aux = _forward(params, model, batch["tokens"], precision)
    logits = _passes(lambda p: functools.partial(
        jnp.einsum, "bsd,dv->bsv", precision=p), precision)(
        h, params["unembed"]["w"])
    gold = jnp.take_along_axis(logits, batch["labels"][..., None], -1)[..., 0]
    return jax.nn.logsumexp(logits, axis=-1) - gold, aux


def loss(params, model, batch, precision="highest"):
    nll, aux = _nll(params, model, batch, precision)
    m = batch["mask"]
    return (jnp.sum(nll * m) / jnp.maximum(jnp.sum(m), 1.0)
            + model["aux_weight"] * aux)


def _local(model, lr, momentum, precision):
    """(global, batches with leaves (S, B, ...)) -> the client's params
    after S momentum-SGD steps from the global."""
    def client(theta, batches):
        def step(carry, batch):
            p, v = carry
            grad = jax.grad(loss)(p, model, batch, precision)
            v = jax.tree_util.tree_map(lambda v_, g_: momentum * v_ + g_,
                                       v, grad)
            p = jax.tree_util.tree_map(lambda p_, v_: p_ - lr * v_, p, v)
            return (p, v), None
        v0 = jax.tree_util.tree_map(jnp.zeros_like, theta)
        (p, _), _ = jax.lax.scan(step, (theta, v0), batches)
        return p
    return client


KEYS = ("tokens", "labels", "mask")


def run_rounds(model: dict, seed: int, rounds: list, test: tuple, fetch, *,
               lr: float, momentum: float, devices=None,
               block: int | None = None,
               precision: str = "highest") -> dict:
    """Federated rounds from the seed's initialisation (``bench/cells.py``
    states the contract). Clients train one after another on the first of
    ``devices``; a round's mean is taken as a round of tiles ``block``
    wide takes it: each block's mean by its own weights, scaled by the
    block's weight and summed in order, over the round's weight.

    Returns ``thetas`` (host arrays: the global before the first round and
    after each) and ``eval_loss``, the test set's mean next-token loss of
    each round's new global."""
    if precision not in PRECISIONS:
        raise ValueError(f"precision {precision!r} not in {PRECISIONS}")
    dev = (devices or jax.local_devices())[0]
    with jax.default_device(dev):     # eagerly, as the program initialises
        theta = init(seed, model)
    local = jax.jit(_local(model, lr, momentum, precision))
    # a product and a sum in programs of their own, each over its first
    # operand's buffers: rounded as the program's eager product and
    # in-place sum round them (one program would fuse them into a
    # multiply-add), with no model-sized copy beyond the running sum
    scale = jax.jit(lambda t, w: jax.tree_util.tree_map(lambda l: l * w, t),
                    donate_argnums=0)
    add = jax.jit(lambda a, b: jax.tree_util.tree_map(jnp.add, a, b),
                  donate_argnums=0)
    nll = jax.jit(lambda p, b: _nll(p, model, b, precision)[0])

    thetas = [jax.tree_util.tree_map(np.asarray, theta)]
    evals = []
    for rnd in rounds:
        sels = np.asarray(rnd["sels"])
        w_all = np.asarray(rnd["weights"], np.float64)
        n = len(w_all)
        width = block or n
        acc, total = None, 0.0
        for c0 in range(0, n, width):
            real = range(c0, min(n, c0 + width))
            s_b = float(w_all[list(real)].sum())
            mean = None
            for c in real:
                got = fetch(sels[c].ravel())
                p = local(theta, {k: jnp.asarray(np.asarray(got[k]).reshape(
                    sels[c].shape + np.shape(got[k])[1:])) for k in KEYS})
                if len(real) > 1:       # a one-client block's mean is it
                    p = scale(p, np.float32(w_all[c] / s_b))
                mean = p if mean is None else add(mean, p)
                del p
            mean = scale(mean, np.float32(s_b))
            acc = mean if acc is None else add(acc, mean)
            del mean
            total += s_b
        del theta
        theta = jax.tree_util.tree_map(lambda a: a / np.float32(total), acc)
        del acc
        thetas.append(jax.tree_util.tree_map(np.asarray, theta))
        tot, cnt = 0.0, 0.0
        for i in range(len(test[0])):
            one = {k: jnp.asarray(a[i:i + 1]) for k, a in zip(KEYS, test)}
            tot += float(jnp.sum(nll(theta, one) * one["mask"]))
            cnt += float(jnp.sum(one["mask"]))
        evals.append(tot / max(cnt, 1.0))
    return {"thetas": thetas, "eval_loss": evals}


# -- the family's hooks for the harness ----------------------------------

def batch_shapes(batch) -> dict:
    """The traffic file's ``expect`` entries that one batch fixes."""
    return {"seq": int(np.shape(batch["tokens"])[1])}


def test_set(test_batches) -> tuple:
    """(tokens, labels, mask) of the eval set as host arrays."""
    return tuple(np.concatenate([np.asarray(b[k]) for b in test_batches])
                 for k in KEYS)


def program_eval(history, rounds: int) -> list:
    """The program's mean test loss after each of the first ``rounds``
    rounds; ``run_rounds`` records its own under the same key."""
    return [float(x) for x in history["eval_loss"][:rounds]]


def eval_numbers(prog_eval: list, ref: dict) -> dict:
    """``eval_loss_gap``: the gap between the program's and the
    reference's mean test loss after the first round, over the
    reference's."""
    r = ref["eval_loss"][0]
    return {"eval_loss_gap": abs(prog_eval[0] - r) / r}


def plant_wrong_answer(task) -> None:
    """The eval scores each position against the label one position
    back: every gold token moved where the eval reads it."""
    predict_fn = task.predict_fn

    def shifted(params, batch):
        return predict_fn(params, dict(batch, labels=jnp.roll(
            batch["labels"], 1, axis=-1)))
    task.predict_fn = shifted
