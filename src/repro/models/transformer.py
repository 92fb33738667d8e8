"""Unified model assembly for all assigned architectures.

One ``ModelConfig`` drives six families:
  dense   — llama3.2-1b, qwen2-7b, h2o-danube-1.8b (SWA), stablelm-12b
  moe     — mixtral-8x22b (SWA), deepseek-v2-236b (MLA + shared experts)
  ssm     — mamba2-1.3b
  hybrid  — zamba2-2.7b (mamba2 stack + shared attention block every k layers)
  encdec  — whisper-base (stubbed conv frontend -> encoder + causal decoder)
  vlm     — internvl2-2b (stubbed ViT -> patch embeds prepended to tokens)

Fed2 structure adaptation (DESIGN.md §3): when ``fed2_groups > 0`` the last
``fed2_decouple`` blocks use block-diagonal (grouped) FFNs and the unembedding
becomes block-diagonal over vocab clusters — the transformer analog of the
paper's group convolution + decoupled logit layers. Lower blocks stay dense
("shared layers", Eq. 18).

Parameters are stacked over layers and applied with lax.scan so lowered HLO
size is depth-independent. The LM loss is a sequence-chunked, rematerialized
cross-entropy so (B, S, V) logits are never alive at once.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from repro.models import attention as attn
from repro.models import moe as moe_lib
from repro.models import ssm as ssm_lib
from repro.models.layers import (dense_apply, dense_init,
                                 embed_init, gelu, grouped_dense_apply,
                                 grouped_dense_init, layernorm_apply,
                                 layernorm_init, rmsnorm_apply, rmsnorm_init,
                                 silu)
from repro.models.module import stack_init


@dataclasses.dataclass(frozen=True)
class ModelConfig:
    arch_id: str
    family: str                     # dense | moe | ssm | hybrid | encdec | vlm
    n_layers: int
    d_model: int
    vocab: int
    n_heads: int = 0
    n_kv_heads: int = 0
    head_dim: int = 0
    d_ff: int = 0
    norm: str = "rmsnorm"           # rmsnorm | layernorm
    act: str = "swiglu"             # swiglu | gelu
    rope_theta: float = 10000.0
    rotary_pct: float = 1.0
    qkv_bias: bool = False
    qk_norm: bool = False
    window: int | None = None       # sliding-window attention
    use_rope: bool = True           # whisper decoder uses learned abs pos
    max_position: int = 1 << 19
    # latent attention (deepseek-v2): its sizes and rope scaling; None
    # for every other attention
    mla: attn.MLAConfig | None = None
    # moe
    moe: moe_lib.MoEConfig | None = None
    aux_loss_weight: float = 0.01   # load-balance term in lm_loss
    moe_first_dense: int = 0        # deepseek-v2: first layer dense FFN
    moe_dense_ff: int = 0
    # ssm / hybrid
    ssm: ssm_lib.SSMConfig | None = None
    hybrid_attn_every: int = 0      # zamba2: shared attn block every k layers
    # encdec
    enc_layers: int = 0
    enc_frames: int = 0
    enc_d_ff: int = 0
    dec_pos_size: int = 32768       # learned decoder pos table (encdec)
    # vlm
    n_patches: int = 0
    tie_embeddings: bool = False
    # fed2 structure adaptation
    fed2_groups: int = 0
    fed2_decouple: int = 0
    # numerics / lowering
    dtype: Any = jnp.float32
    loss_chunk: int = 512
    attn_q_chunk: int = 512
    attn_kv_chunk: int = 1024
    remat_blocks: bool = True

    def __post_init__(self):
        if self.n_heads and not self.head_dim:
            object.__setattr__(self, "head_dim", self.d_model // self.n_heads)

    @property
    def attn_cfg(self) -> attn.AttnConfig:
        return attn.AttnConfig(
            d_model=self.d_model, n_heads=self.n_heads,
            n_kv_heads=self.n_kv_heads, head_dim=self.head_dim,
            rope_theta=self.rope_theta,
            rotary_pct=self.rotary_pct if self.use_rope else 0.0,
            qkv_bias=self.qkv_bias, qk_norm=self.qk_norm, window=self.window)

    @property
    def n_dense_blocks(self) -> int:
        return self.n_layers - self.fed2_decouple

    @property
    def padded_vocab(self) -> int:
        """Vocab rounded up so (a) Fed2 groups divide it and (b) it shards
        evenly over the mesh model axis (unit 128, MaxText-style)."""
        import math
        g = max(self.fed2_groups, 1)
        unit = 128 * g // math.gcd(128, g)
        return -(-self.vocab // unit) * unit

    @property
    def is_subquadratic(self) -> bool:
        return self.family in ("ssm", "hybrid") or self.window is not None


# ---------------------------------------------------------------------------
# Norm helpers
# ---------------------------------------------------------------------------


def _norm_init(cfg, d=None, dtype=None):
    d = d or cfg.d_model
    dtype = dtype or cfg.dtype
    return (rmsnorm_init if cfg.norm == "rmsnorm" else layernorm_init)(d, dtype)


def _norm_apply(cfg, p, x):
    return (rmsnorm_apply if cfg.norm == "rmsnorm" else layernorm_apply)(p, x)


def _act(cfg, g, u):
    return (silu(g) if cfg.act == "swiglu" else gelu(g)) * u


# ---------------------------------------------------------------------------
# FFN (dense + grouped)
# ---------------------------------------------------------------------------


def ffn_init(key, cfg: ModelConfig, d_ff=None, dtype=None):
    d_ff = d_ff or cfg.d_ff
    dtype = dtype or cfg.dtype
    ks = jax.random.split(key, 3)
    return {"w_gate": dense_init(ks[0], cfg.d_model, d_ff, dtype=dtype),
            "w_up": dense_init(ks[1], cfg.d_model, d_ff, dtype=dtype),
            "w_down": dense_init(ks[2], d_ff, cfg.d_model, dtype=dtype)}


def ffn_apply(p, x, cfg: ModelConfig):
    with jax.named_scope("ffn"):
        return dense_apply(p["w_down"],
                           _act(cfg, dense_apply(p["w_gate"], x),
                                dense_apply(p["w_up"], x)))


def gffn_init(key, cfg: ModelConfig, dtype=None):
    """Block-diagonal SwiGLU FFN: Fed2 feature isolation for transformers."""
    g = cfg.fed2_groups
    dtype = dtype or cfg.dtype
    ks = jax.random.split(key, 3)
    return {"w_gate": grouped_dense_init(ks[0], g, cfg.d_model, cfg.d_ff,
                                         dtype=dtype),
            "w_up": grouped_dense_init(ks[1], g, cfg.d_model, cfg.d_ff,
                                       dtype=dtype),
            "w_down": grouped_dense_init(ks[2], g, cfg.d_ff, cfg.d_model,
                                         dtype=dtype)}


def gffn_apply(p, x, cfg: ModelConfig):
    return grouped_dense_apply(
        p["w_down"], _act(cfg, grouped_dense_apply(p["w_gate"], x),
                          grouped_dense_apply(p["w_up"], x)))


# ---------------------------------------------------------------------------
# Decoder blocks
# ---------------------------------------------------------------------------


def block_init(key, cfg: ModelConfig, *, grouped: bool = False,
               kind: str | None = None):
    """kind: 'attn_ffn' (default dense), 'moe', 'ssm', 'mla_moe'."""
    kind = kind or _default_kind(cfg)
    ks = jax.random.split(key, 4)
    p = {"ln1": _norm_init(cfg)}
    if kind == "ssm":
        p["mixer"] = ssm_lib.mamba2_init(ks[0], cfg.ssm, cfg.dtype)
        return p
    if kind == "mla_moe":
        p["attn"] = attn.mla_init(ks[0], cfg.mla, cfg.dtype)
    else:
        p["attn"] = attn.gqa_init(ks[0], cfg.attn_cfg, cfg.dtype)
    p["ln2"] = _norm_init(cfg)
    if kind in ("moe", "mla_moe"):
        p["ffn"] = moe_lib.moe_init(ks[1], cfg.moe, cfg.dtype)
    elif grouped:
        p["ffn"] = gffn_init(ks[1], cfg)
    else:
        p["ffn"] = ffn_init(ks[1], cfg)
    return p


def _default_kind(cfg: ModelConfig) -> str:
    if cfg.family == "ssm":
        return "ssm"
    if cfg.family == "moe":
        return "mla_moe" if cfg.mla else "moe"
    return "attn_ffn"


def block_apply(p, x, cfg: ModelConfig, *, grouped: bool = False,
                kind: str | None = None, positions=None):
    kind = kind or _default_kind(cfg)
    aux = jnp.zeros((), jnp.float32)
    if kind == "ssm":
        return x + ssm_lib.mamba2_apply(p["mixer"], _norm_apply(cfg, p["ln1"], x),
                                        cfg.ssm), aux
    h = _norm_apply(cfg, p["ln1"], x)
    if kind == "mla_moe":
        a = attn.mla_apply(p["attn"], h, cfg.mla, positions=positions,
                           q_chunk=cfg.attn_q_chunk, kv_chunk=cfg.attn_kv_chunk)
    else:
        a = attn.gqa_apply(p["attn"], h, cfg.attn_cfg, positions=positions,
                           q_chunk=cfg.attn_q_chunk, kv_chunk=cfg.attn_kv_chunk)
    x = x + a
    h = _norm_apply(cfg, p["ln2"], x)
    if kind in ("moe", "mla_moe"):
        y, aux = moe_lib.moe_apply(p["ffn"], h, cfg.moe)
    elif grouped:
        y = gffn_apply(p["ffn"], h, cfg)
    else:
        y = ffn_apply(p["ffn"], h, cfg)
    return x + y, aux


def block_decode(p, x, cache, cfg: ModelConfig, *, pos, kind=None,
                 grouped=False):
    kind = kind or _default_kind(cfg)
    if kind == "ssm":
        y, cache = ssm_lib.mamba2_decode(p["mixer"],
                                         _norm_apply(cfg, p["ln1"], x),
                                         cache, cfg.ssm)
        return x + y, cache
    h = _norm_apply(cfg, p["ln1"], x)
    if kind == "mla_moe":
        a, cache = attn.mla_decode(p["attn"], h, cache, cfg.mla, pos=pos)
    else:
        a, cache = attn.gqa_decode(p["attn"], h, cache, cfg.attn_cfg, pos=pos)
    x = x + a
    h = _norm_apply(cfg, p["ln2"], x)
    if kind in ("moe", "mla_moe"):
        y, _ = moe_lib.moe_apply(p["ffn"], h, cfg.moe)
    elif grouped:
        y = gffn_apply(p["ffn"], h, cfg)
    else:
        y = ffn_apply(p["ffn"], h, cfg)
    return x + y, cache


# ---------------------------------------------------------------------------
# Unembedding + chunked CE loss
# ---------------------------------------------------------------------------


def unembed_init(key, cfg: ModelConfig):
    if cfg.fed2_groups > 0:
        return grouped_dense_init(key, cfg.fed2_groups, cfg.d_model,
                                  cfg.padded_vocab, dtype=cfg.dtype)
    return dense_init(key, cfg.d_model, cfg.padded_vocab, dtype=cfg.dtype)


def unembed_apply(p, h, cfg: ModelConfig, embed_table=None):
    if cfg.tie_embeddings:
        logits = jnp.einsum("...d,vd->...v", h, embed_table)
    elif cfg.fed2_groups > 0:
        logits = grouped_dense_apply(p, h)
    else:
        logits = dense_apply(p, h)
    return logits[..., :cfg.vocab]


def chunked_ce_loss(params, h, labels, mask, cfg: ModelConfig):
    """Sequence-chunked softmax CE; chunk bodies rematerialized so full
    (B, S, V) logits never exist. h: (B, S, d); labels, mask: (B, S)."""
    b, s, d = h.shape
    ck = min(cfg.loss_chunk, s)
    nc = -(-s // ck)
    pad = nc * ck - s
    hp = jnp.pad(h, ((0, 0), (0, pad), (0, 0)))
    lp = jnp.pad(labels, ((0, 0), (0, pad)))
    mp = jnp.pad(mask, ((0, 0), (0, pad)))
    hs = hp.reshape(b, nc, ck, d).transpose(1, 0, 2, 3)
    ls = lp.reshape(b, nc, ck).transpose(1, 0, 2)
    ms = mp.reshape(b, nc, ck).transpose(1, 0, 2)
    table = params.get("embed", {}).get("table") if cfg.tie_embeddings else None

    @jax.checkpoint
    def chunk_loss(hc, lc, mc):
        with jax.named_scope("head"):
            logits = unembed_apply(params["unembed"] if not cfg.tie_embeddings
                                   else None, hc, cfg, table
                                   ).astype(jnp.float32)
            lse = jax.nn.logsumexp(logits, axis=-1)
            gold = jnp.take_along_axis(logits, lc[..., None], axis=-1)[..., 0]
            return jnp.sum((lse - gold) * mc), jnp.sum(mc)

    def body(acc, inp):
        l, n = chunk_loss(*inp)
        return (acc[0] + l, acc[1] + n), None

    (tot, cnt), _ = jax.lax.scan(body, (jnp.zeros(()), jnp.zeros(())),
                                 (hs, ls, ms.astype(jnp.float32)))
    return tot / jnp.maximum(cnt, 1.0)


# ---------------------------------------------------------------------------
# Full model init
# ---------------------------------------------------------------------------


def init_params(key, cfg: ModelConfig):
    ks = jax.random.split(key, 8)
    params = {"embed": embed_init(ks[0], cfg.padded_vocab, cfg.d_model,
                                  cfg.dtype)}

    if cfg.family == "encdec":
        ecfg = dataclasses.replace(
            cfg, norm="layernorm", act="gelu", window=None, use_rope=False)
        enc_block = functools.partial(_encdec_enc_block_init, cfg=ecfg)
        params["enc_blocks"] = stack_init(enc_block, ks[1], cfg.enc_layers)
        params["enc_norm"] = _norm_init(ecfg)
        params["enc_pos"] = _sinusoid_pos(cfg.enc_frames, cfg.d_model,
                                          cfg.dtype)
        params["dec_pos"] = {"table": 0.02 * jax.random.normal(
            ks[2], (cfg.dec_pos_size, cfg.d_model), cfg.dtype)}
        dec_block = functools.partial(_encdec_dec_block_init, cfg=ecfg)
        params["blocks"] = stack_init(dec_block, ks[3], cfg.n_dense_blocks)
        if cfg.fed2_decouple:
            gblock = functools.partial(_encdec_dec_block_init, cfg=ecfg,
                                       grouped=True)
            params["gblocks"] = stack_init(gblock, ks[4], cfg.fed2_decouple)
        params["final_norm"] = _norm_init(ecfg)
    elif cfg.family == "hybrid":
        nb = cfg.n_layers
        params["blocks"] = stack_init(
            functools.partial(block_init, cfg=cfg, kind="ssm"), ks[1], nb)
        params["shared_attn"] = _hybrid_shared_block_init(ks[2], cfg)
        params["final_norm"] = _norm_init(cfg)
    else:
        kind = _default_kind(cfg)
        n_dense = cfg.n_dense_blocks
        if cfg.family == "moe" and cfg.moe_first_dense:
            dcfg = dataclasses.replace(cfg, d_ff=cfg.moe_dense_ff)
            params["pre_blocks"] = stack_init(
                functools.partial(block_init, cfg=dcfg,
                                  kind="attn_ffn" if not cfg.mla else None),
                ks[5], cfg.moe_first_dense)
            if cfg.mla:  # deepseek dense layer still uses MLA attention
                params["pre_blocks"] = stack_init(
                    functools.partial(_mla_dense_block_init, cfg=dcfg),
                    ks[5], cfg.moe_first_dense)
            n_dense -= cfg.moe_first_dense
        params["blocks"] = stack_init(
            functools.partial(block_init, cfg=cfg, kind=kind), ks[1], n_dense)
        if cfg.fed2_decouple:
            params["gblocks"] = stack_init(
                functools.partial(block_init, cfg=cfg, grouped=True,
                                  kind=kind if kind != "attn_ffn" else None),
                ks[2], cfg.fed2_decouple)
        params["final_norm"] = _norm_init(cfg)

    if not cfg.tie_embeddings:
        params["unembed"] = unembed_init(ks[6], cfg)
    return params


def _sinusoid_pos(length, d, dtype):
    pos = np.arange(length)[:, None]
    dim = np.arange(0, d, 2)[None, :]
    ang = pos / np.power(10000.0, dim / d)
    table = np.zeros((length, d), np.float32)
    table[:, 0::2] = np.sin(ang)
    table[:, 1::2] = np.cos(ang)
    return {"table": jnp.asarray(table, dtype)}


def _encdec_enc_block_init(key, cfg):
    ks = jax.random.split(key, 2)
    acfg = dataclasses.replace(cfg.attn_cfg, causal=False, rotary_pct=0.0)
    return {"ln1": _norm_init(cfg), "attn": attn.gqa_init(ks[0], acfg, cfg.dtype),
            "ln2": _norm_init(cfg),
            "ffn": _gelu_ffn_init(ks[1], cfg)}


def _gelu_ffn_init(key, cfg, grouped=False):
    ks = jax.random.split(key, 2)
    if grouped:
        return {"w_up": grouped_dense_init(ks[0], cfg.fed2_groups, cfg.d_model,
                                           cfg.d_ff, bias=True, dtype=cfg.dtype),
                "w_down": grouped_dense_init(ks[1], cfg.fed2_groups, cfg.d_ff,
                                             cfg.d_model, bias=True,
                                             dtype=cfg.dtype)}
    return {"w_up": dense_init(ks[0], cfg.d_model, cfg.d_ff, bias=True,
                               dtype=cfg.dtype),
            "w_down": dense_init(ks[1], cfg.d_ff, cfg.d_model, bias=True,
                                 dtype=cfg.dtype)}


def _gelu_ffn_apply(p, x, grouped=False):
    ap = grouped_dense_apply if grouped else dense_apply
    return ap(p["w_down"], gelu(ap(p["w_up"], x)))


def _encdec_dec_block_init(key, cfg, grouped=False):
    ks = jax.random.split(key, 3)
    return {"ln1": _norm_init(cfg),
            "attn": attn.gqa_init(ks[0], cfg.attn_cfg, cfg.dtype),
            "ln_x": _norm_init(cfg),
            "xattn": attn.gqa_init(ks[1], dataclasses.replace(
                cfg.attn_cfg, causal=False), cfg.dtype),
            "ln2": _norm_init(cfg),
            "ffn": _gelu_ffn_init(ks[2], cfg, grouped=grouped)}


def _mla_dense_block_init(key, cfg):
    ks = jax.random.split(key, 2)
    return {"ln1": _norm_init(cfg),
            "attn": attn.mla_init(ks[0], cfg.mla, cfg.dtype),
            "ln2": _norm_init(cfg), "ffn": ffn_init(ks[1], cfg)}


def _hybrid_shared_block_init(key, cfg):
    ks = jax.random.split(key, 2)
    return {"ln1": _norm_init(cfg),
            "attn": attn.gqa_init(ks[0], cfg.attn_cfg, cfg.dtype),
            "ln2": _norm_init(cfg), "ffn": ffn_init(ks[1], cfg)}
