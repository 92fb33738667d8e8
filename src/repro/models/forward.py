"""Forward / loss / cache-init / single-token decode for every family.

Public API:
  forward(params, cfg, tokens, embeds=None)   -> (hidden, aux_loss)
  lm_loss(params, cfg, batch)                 -> scalar CE (+ MoE aux)
  init_cache(cfg, batch, max_len)             -> decode cache pytree
  decode_step(params, cfg, cache, tokens, pos)-> (logits, new_cache)
"""
from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp

from repro.models import attention as attn
from repro.models import ssm as ssm_lib
from repro.models import transformer as tfm
from repro.models.layers import embed_apply
from repro.models.transformer import (ModelConfig, _gelu_ffn_apply,
                                      _norm_apply, block_apply, block_decode,
                                      chunked_ce_loss, unembed_apply)


# ---------------------------------------------------------------------------
# Forward (train / prefill)
# ---------------------------------------------------------------------------


def _scan_blocks(params_stack, x, apply_one, remat: bool):
    fn = jax.checkpoint(apply_one) if remat else apply_one

    def body(carry, layer_params):
        h, aux = carry
        h, a = fn(layer_params, h)
        return (h, aux + a), None

    (x, aux), _ = jax.lax.scan(body, (x, jnp.zeros((), jnp.float32)),
                               params_stack)
    return x, aux


def forward(params, cfg: ModelConfig, tokens, *, embeds=None, positions=None):
    """tokens: (B, S_text) int32; embeds: modality-frontend output
    (encdec: (B, frames, d) encoder input; vlm: (B, n_patches, d) prepended).
    ``positions=None`` is ``0..S_total-1``: attention then computes only
    the key blocks its queries see (``attention.chunked_attention``).
    Returns (hidden (B, S_total, d), aux_loss)."""
    if cfg.family == "encdec":
        return _forward_encdec(params, cfg, tokens, embeds)

    x = embed_apply(params["embed"], tokens).astype(cfg.dtype)
    if cfg.family == "vlm":
        assert embeds is not None
        x = jnp.concatenate([embeds.astype(cfg.dtype), x], axis=1)
    aux_total = jnp.zeros((), jnp.float32)
    if cfg.family == "hybrid":
        x, aux_total = _forward_hybrid(params, cfg, x, positions)
    else:
        kind = tfm._default_kind(cfg)
        if "pre_blocks" in params:
            dcfg = dataclasses.replace(cfg, d_ff=cfg.moe_dense_ff)
            apply_pre = functools.partial(_apply_pre_block, cfg=dcfg,
                                          positions=positions,
                                          mla=cfg.mla is not None)
            x, a = _scan_blocks(params["pre_blocks"], x, apply_pre,
                                cfg.remat_blocks)
            aux_total += a
        apply_dense = functools.partial(
            lambda p, h, **kw: block_apply(p, h, **kw), cfg=cfg, kind=kind,
            positions=positions)
        x, a = _scan_blocks(params["blocks"], x, apply_dense, cfg.remat_blocks)
        aux_total += a
        if "gblocks" in params:
            apply_g = functools.partial(
                lambda p, h, **kw: block_apply(p, h, **kw), cfg=cfg,
                kind=kind if kind != "attn_ffn" else None, grouped=True,
                positions=positions)
            x, a = _scan_blocks(params["gblocks"], x, apply_g,
                                cfg.remat_blocks)
            aux_total += a
    x = _norm_apply(cfg, params["final_norm"], x)
    return x, aux_total


def _apply_pre_block(p, x, *, cfg, positions, mla):
    aux = jnp.zeros((), jnp.float32)
    h = _norm_apply(cfg, p["ln1"], x)
    if mla:
        a = attn.mla_apply(p["attn"], h, cfg.mla, positions=positions,
                           q_chunk=cfg.attn_q_chunk, kv_chunk=cfg.attn_kv_chunk)
    else:
        a = attn.gqa_apply(p["attn"], h, cfg.attn_cfg, positions=positions,
                           q_chunk=cfg.attn_q_chunk, kv_chunk=cfg.attn_kv_chunk)
    x = x + a
    x = x + tfm.ffn_apply(p["ffn"], _norm_apply(cfg, p["ln2"], x), cfg)
    return x, aux


def _forward_hybrid(params, cfg: ModelConfig, x, positions):
    """zamba2: scan over super-blocks = (attn_every ssm blocks + shared attn)."""
    k = cfg.hybrid_attn_every
    nb = cfg.n_layers
    assert nb % k == 0
    n_super = nb // k
    stacked = jax.tree_util.tree_map(
        lambda a: a.reshape((n_super, k) + a.shape[1:]), params["blocks"])
    shared = params["shared_attn"]

    ssm_apply = functools.partial(
        lambda p, h, **kw: block_apply(p, h, **kw), cfg=cfg, kind="ssm")
    ssm_fn = jax.checkpoint(ssm_apply) if cfg.remat_blocks else ssm_apply

    def shared_apply(h):
        hh = _norm_apply(cfg, shared["ln1"], h)
        a = attn.gqa_apply(shared["attn"], hh, cfg.attn_cfg,
                           positions=positions, q_chunk=cfg.attn_q_chunk,
                           kv_chunk=cfg.attn_kv_chunk)
        h = h + a
        return h + tfm.ffn_apply(shared["ffn"],
                                 _norm_apply(cfg, shared["ln2"], h), cfg)

    shared_fn = jax.checkpoint(shared_apply) if cfg.remat_blocks \
        else shared_apply

    def super_body(carry, super_params):
        h = carry

        def inner(c, lp):
            c, _ = ssm_fn(lp, c)
            return c, None

        h, _ = jax.lax.scan(inner, h, super_params)
        h = shared_fn(h)
        return h, None

    x, _ = jax.lax.scan(super_body, x, stacked)
    return x, jnp.zeros((), jnp.float32)


def _forward_encdec(params, cfg: ModelConfig, tokens, frames):
    """whisper: frames (B, enc_frames, d) stubbed conv-frontend output."""
    ecfg = dataclasses.replace(cfg, norm="layernorm", act="gelu", window=None,
                               use_rope=False)
    x = frames.astype(cfg.dtype) + params["enc_pos"]["table"][None]
    enc_pos = jnp.arange(cfg.enc_frames)

    def enc_apply(p, h):
        hh = _norm_apply(ecfg, p["ln1"], h)
        acfg = dataclasses.replace(ecfg.attn_cfg, causal=False)
        h = h + attn.gqa_apply(p["attn"], hh, acfg, positions=enc_pos,
                               q_chunk=ecfg.attn_q_chunk,
                               kv_chunk=ecfg.attn_kv_chunk)
        h = h + _gelu_ffn_apply(p["ffn"], _norm_apply(ecfg, p["ln2"], h))
        return h, jnp.zeros((), jnp.float32)

    enc_out, _ = _scan_blocks(params["enc_blocks"], x, enc_apply,
                              cfg.remat_blocks)
    enc_out = _norm_apply(ecfg, params["enc_norm"], enc_out)

    s = tokens.shape[1]
    positions = jnp.arange(s)
    y = embed_apply(params["embed"], tokens).astype(cfg.dtype)
    y = y + jnp.take(params["dec_pos"]["table"],
                     jnp.minimum(positions, cfg.dec_pos_size - 1), axis=0)[None]

    def dec_apply(p, h, grouped=False):
        hh = _norm_apply(ecfg, p["ln1"], h)
        h = h + attn.gqa_apply(p["attn"], hh, ecfg.attn_cfg,
                               positions=positions, q_chunk=ecfg.attn_q_chunk,
                               kv_chunk=ecfg.attn_kv_chunk)
        hh = _norm_apply(ecfg, p["ln_x"], h)
        xcfg = dataclasses.replace(ecfg.attn_cfg, causal=False)
        kv = attn.cross_kv(p["xattn"], enc_out, xcfg)
        h = h + attn.gqa_apply(p["xattn"], hh, xcfg, positions=positions,
                               kv=kv, kv_positions=enc_pos,
                               q_chunk=ecfg.attn_q_chunk,
                               kv_chunk=ecfg.attn_kv_chunk)
        h = h + _gelu_ffn_apply(p["ffn"], _norm_apply(ecfg, p["ln2"], h),
                                grouped=grouped)
        return h, jnp.zeros((), jnp.float32)

    y, _ = _scan_blocks(params["blocks"], y, dec_apply, cfg.remat_blocks)
    if "gblocks" in params:
        y, _ = _scan_blocks(params["gblocks"], y,
                            functools.partial(dec_apply, grouped=True),
                            cfg.remat_blocks)
    y = _norm_apply(ecfg, params["final_norm"], y)
    return y, jnp.zeros((), jnp.float32)


# ---------------------------------------------------------------------------
# Loss
# ---------------------------------------------------------------------------


def lm_loss(params, cfg: ModelConfig, batch):
    """batch: {"tokens": (B,S), "labels": (B,S), "mask": (B,S),
    optional "embeds": frontend stub output}. The MoE load-balance term
    is weighed by the config's ``aux_loss_weight``."""
    h, aux = forward(params, cfg, batch["tokens"],
                     embeds=batch.get("embeds"))
    if cfg.family == "vlm":  # loss only on text positions
        h = h[:, cfg.n_patches:]
    loss = chunked_ce_loss(params, h, batch["labels"], batch["mask"], cfg)
    return loss + cfg.aux_loss_weight * aux


# ---------------------------------------------------------------------------
# Decode
# ---------------------------------------------------------------------------


def _block_cache_init(cfg: ModelConfig, kind: str, batch: int, max_len: int):
    if kind == "ssm":
        return ssm_lib.mamba2_cache_init(cfg.ssm, batch, cfg.dtype)
    if kind == "mla_moe" or kind == "mla_dense":
        return attn.mla_cache_init(cfg.mla, batch, max_len, cfg.dtype)
    return attn.gqa_cache_init(cfg.attn_cfg, batch, max_len, cfg.dtype)


def _stacked_cache(n, one):
    return jax.tree_util.tree_map(
        lambda a: jnp.broadcast_to(a[None], (n,) + a.shape), one)


def init_cache(cfg: ModelConfig, batch: int, max_len: int):
    """Decode cache for `serve_step`. max_len = context window to serve."""
    if cfg.family == "encdec":
        ecfg = dataclasses.replace(cfg, norm="layernorm", use_rope=False)
        self_c = attn.gqa_cache_init(ecfg.attn_cfg, batch,
                                     min(max_len, cfg.dec_pos_size), cfg.dtype)
        cross = {
            "k": jnp.zeros((batch, cfg.enc_frames, cfg.n_kv_heads,
                            cfg.head_dim), cfg.dtype),
            "v": jnp.zeros((batch, cfg.enc_frames, cfg.n_kv_heads,
                            cfg.head_dim), cfg.dtype),
        }
        one = {"self": self_c, "cross": cross}
        cache = {"blocks": _stacked_cache(cfg.n_dense_blocks, one)}
        if cfg.fed2_decouple:
            cache["gblocks"] = _stacked_cache(cfg.fed2_decouple, one)
        return cache

    if cfg.family == "hybrid":
        one = ssm_lib.mamba2_cache_init(cfg.ssm, batch, cfg.dtype)
        n_super = cfg.n_layers // cfg.hybrid_attn_every
        # shared attention block: per-application KV ring buffer (SWA-style
        # window keeps long_500k tractable; full window if short context)
        acfg = dataclasses.replace(
            cfg.attn_cfg, window=min(max_len, 4096))
        shared_one = attn.gqa_cache_init(acfg, batch, max_len, cfg.dtype)
        return {"blocks": _stacked_cache(cfg.n_layers, one),
                "shared": _stacked_cache(n_super, shared_one)}

    kind = tfm._default_kind(cfg)
    cache = {}
    if "moe" == cfg.family and cfg.moe_first_dense:
        pk = "mla_dense" if cfg.mla else "attn_ffn"
        cache["pre_blocks"] = _stacked_cache(
            cfg.moe_first_dense, _block_cache_init(cfg, pk, batch, max_len))
    cache["blocks"] = _stacked_cache(
        cfg.n_dense_blocks - (cfg.moe_first_dense or 0),
        _block_cache_init(cfg, kind, batch, max_len))
    if cfg.fed2_decouple:
        cache["gblocks"] = _stacked_cache(
            cfg.fed2_decouple, _block_cache_init(cfg, kind, batch, max_len))
    return cache


def _scan_decode(params_stack, caches, x, step_one):
    def body(carry, inp):
        lp, lc = inp
        h = carry
        h, nc = step_one(lp, h, lc)
        return h, nc

    x, new_caches = jax.lax.scan(body, x, (params_stack, caches))
    return x, new_caches


def decode_step(params, cfg: ModelConfig, cache, tokens, pos):
    """One-token decode. tokens: (B, 1); pos: scalar int32 absolute position.
    Returns (logits (B, 1, vocab), new_cache)."""
    if cfg.family == "encdec":
        return _decode_encdec(params, cfg, cache, tokens, pos)

    x = embed_apply(params["embed"], tokens).astype(cfg.dtype)
    new_cache = {}
    if cfg.family == "hybrid":
        x, new_cache = _decode_hybrid(params, cfg, cache, x, pos)
    else:
        kind = tfm._default_kind(cfg)
        if "pre_blocks" in params:
            dcfg = dataclasses.replace(cfg, d_ff=cfg.moe_dense_ff)
            x, nc = _scan_decode(
                params["pre_blocks"], cache["pre_blocks"], x,
                lambda p, h, c: _pre_block_decode(p, h, c, dcfg, pos))
            new_cache["pre_blocks"] = nc
        x, nc = _scan_decode(
            params["blocks"], cache["blocks"], x,
            lambda p, h, c: block_decode(p, h, c, cfg, pos=pos, kind=kind))
        new_cache["blocks"] = nc
        if "gblocks" in params:
            x, nc = _scan_decode(
                params["gblocks"], cache["gblocks"], x,
                lambda p, h, c: block_decode(
                    p, h, c, cfg, pos=pos,
                    kind=kind if kind != "attn_ffn" else None, grouped=True))
            new_cache["gblocks"] = nc
        x = _norm_apply(cfg, params["final_norm"], x)

    table = params["embed"]["table"] if cfg.tie_embeddings else None
    logits = unembed_apply(params.get("unembed"), x, cfg, table)
    return logits, new_cache


def _pre_block_decode(p, x, c, dcfg, pos):
    if dcfg.mla:
        h = _norm_apply(dcfg, p["ln1"], x)
        a, c = attn.mla_decode(p["attn"], h, c, dcfg.mla, pos=pos)
        x = x + a
        x = x + tfm.ffn_apply(p["ffn"], _norm_apply(dcfg, p["ln2"], x), dcfg)
        return x, c
    return block_decode(p, x, c, dcfg, pos=pos, kind="attn_ffn")


def _decode_hybrid(params, cfg: ModelConfig, cache, x, pos):
    k = cfg.hybrid_attn_every
    n_super = cfg.n_layers // k
    stacked = jax.tree_util.tree_map(
        lambda a: a.reshape((n_super, k) + a.shape[1:]), params["blocks"])
    ssm_caches = jax.tree_util.tree_map(
        lambda a: a.reshape((n_super, k) + a.shape[1:]), cache["blocks"])
    shared = params["shared_attn"]
    acfg = dataclasses.replace(cfg.attn_cfg,
                               window=cache["shared"]["k"].shape[2])

    def super_body(carry, inp):
        h = carry
        sp, sc, shc = inp

        def inner(c2, inp2):
            lp, lc = inp2
            h2, nc2 = block_decode(lp, c2, lc, cfg, pos=pos, kind="ssm")
            return h2, nc2

        h, new_ssm = jax.lax.scan(inner, h, (sp, sc))
        hh = _norm_apply(cfg, shared["ln1"], h)
        a, new_shared = attn.gqa_decode(shared["attn"], hh, shc, acfg, pos=pos)
        h = h + a
        h = h + tfm.ffn_apply(shared["ffn"], _norm_apply(cfg, shared["ln2"], h),
                              cfg)
        return h, (new_ssm, new_shared)

    x, (new_ssm, new_shared) = jax.lax.scan(
        super_body, x, (stacked, ssm_caches, cache["shared"]))
    new_ssm = jax.tree_util.tree_map(
        lambda a: a.reshape((cfg.n_layers,) + a.shape[2:]), new_ssm)
    x = _norm_apply(cfg, params["final_norm"], x)
    return x, {"blocks": new_ssm, "shared": new_shared}


def encdec_prefill_cache(params, cfg: ModelConfig, cache, frames):
    """Run the encoder once and fill the decoder's cross-attention KV cache
    (whisper serving step 0). frames: (B, enc_frames, d) stub output."""
    ecfg = dataclasses.replace(cfg, norm="layernorm", act="gelu",
                               use_rope=False)
    x = frames.astype(cfg.dtype) + params["enc_pos"]["table"][None]
    enc_pos = jnp.arange(cfg.enc_frames)

    def enc_apply(p, h):
        hh = _norm_apply(ecfg, p["ln1"], h)
        acfg = dataclasses.replace(ecfg.attn_cfg, causal=False)
        h = h + attn.gqa_apply(p["attn"], hh, acfg, positions=enc_pos)
        h = h + _gelu_ffn_apply(p["ffn"], _norm_apply(ecfg, p["ln2"], h))
        return h, jnp.zeros((), jnp.float32)

    enc_out, _ = _scan_blocks(params["enc_blocks"], x, enc_apply, False)
    enc_out = _norm_apply(ecfg, params["enc_norm"], enc_out)
    xcfg = dataclasses.replace(ecfg.attn_cfg, causal=False)

    def fill(block_params, block_cache):
        k, v = attn.cross_kv(block_params["xattn"], enc_out, xcfg)
        return {**block_cache, "cross": {"k": k, "v": v}}

    new_cache = dict(cache)
    for key in ("blocks", "gblocks"):
        if key in cache:
            new_cache[key] = jax.vmap(fill)(params[key], cache[key])
    return new_cache


def _decode_encdec(params, cfg: ModelConfig, cache, tokens, pos):
    ecfg = dataclasses.replace(cfg, norm="layernorm", act="gelu",
                               use_rope=False)
    x = embed_apply(params["embed"], tokens).astype(cfg.dtype)
    x = x + jnp.take(params["dec_pos"]["table"],
                     jnp.minimum(jnp.reshape(pos, (1,)), cfg.dec_pos_size - 1),
                     axis=0)[None, 0]

    def step(p, h, c, grouped=False):
        hh = _norm_apply(ecfg, p["ln1"], h)
        a, new_self = attn.gqa_decode(p["attn"], hh, c["self"], ecfg.attn_cfg,
                                      pos=pos)
        h = h + a
        # cross attention over the precomputed encoder KV
        hh = _norm_apply(ecfg, p["ln_x"], h)
        b = h.shape[0]
        hq, hkv, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
        from repro.models.layers import dense_apply as _da
        q = _da(p["xattn"]["wq"], hh).reshape(b, hkv, hq // hkv, hd)
        s = jnp.einsum("bgrd,bsgd->bgrs", q, c["cross"]["k"]) / jnp.sqrt(
            jnp.asarray(hd, jnp.float32)).astype(h.dtype)
        w = jax.nn.softmax(s.astype(jnp.float32), axis=-1)
        o = jnp.einsum("bgrs,bsgd->bgrd", w.astype(h.dtype), c["cross"]["v"])
        h = h + _da(p["xattn"]["wo"], o.reshape(b, 1, hq * hd))
        h = h + _gelu_ffn_apply(p["ffn"], _norm_apply(ecfg, p["ln2"], h),
                                grouped=grouped)
        return h, {"self": new_self, "cross": c["cross"]}

    new_cache = {}
    x, nc = _scan_decode(params["blocks"], cache["blocks"], x, step)
    new_cache["blocks"] = nc
    if "gblocks" in params:
        x, nc = _scan_decode(params["gblocks"], cache["gblocks"], x,
                             lambda p, h, c: step(p, h, c, grouped=True))
        new_cache["gblocks"] = nc
    x = _norm_apply(ecfg, params["final_norm"], x)
    table = params["embed"]["table"] if cfg.tie_embeddings else None
    logits = unembed_apply(params.get("unembed"), x, cfg, table)
    return logits, new_cache
