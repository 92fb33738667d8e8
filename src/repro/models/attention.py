"""Attention blocks: GQA (with optional sliding window / QK-norm / partial
rotary), and DeepSeek-style MLA (multi-head latent attention).

Prefill/train uses a flash-style chunked attention (lax.scan over query and
key/value chunks with an online softmax) so lowered HLO never materializes a
full (B, H, S, S) score tensor — this is what keeps the 32k dry-run within
per-device memory on the production mesh.

Decode paths consume a KV cache:
  - full attention: cache (B, S_max, kv_heads, head_dim), scalar write pos
  - sliding window: ring buffer of size `window`
  - MLA: compressed latent cache (B, S_max, kv_lora + rope_dim) — the whole
    point of MLA — with weight-absorbed score/output computation.
"""
from __future__ import annotations

import dataclasses
import itertools

import jax
import jax.numpy as jnp
import numpy as np

from repro.models.layers import (YaRN, apply_rope, dense_apply, dense_init,
                                 rmsnorm_apply, rmsnorm_init, rope_freqs,
                                 yarn_softmax_mscale)

NEG_INF = -1e30


# ---------------------------------------------------------------------------
# Chunked (flash-style) attention core
# ---------------------------------------------------------------------------


def _attend_block(q, k, v, bias):
    """q:(B,Hq,Tq,D) k,v:(B,Hkv,Tk,D) bias:(1|B,1,Tq,Tk) -> partial softmax."""
    b, hq, tq, d = q.shape
    hkv = k.shape[1]
    rep = hq // hkv
    qg = q.reshape(b, hkv, rep, tq, d)
    s = jnp.einsum("bgrtd,bgkd->bgrtk", qg, k).astype(jnp.float32)
    s = s * (1.0 / np.sqrt(d)) + bias[:, :, None]
    m = jnp.max(s, axis=-1, keepdims=True)
    m = jnp.maximum(m, NEG_INF)  # all-masked rows stay finite
    p = jnp.exp(s - m)
    l = jnp.sum(p, axis=-1, keepdims=True)
    o = jnp.einsum("bgrtk,bgkd->bgrtd", p.astype(v.dtype), v)
    return o.reshape(b, hq, tq, d), m.reshape(b, hq, tq, 1), l.reshape(b, hq, tq, 1)


def attention_schedule(sq: int, skv: int, *, causal: bool,
                       window: int | None = None, q_chunk: int = 512,
                       kv_chunk: int = 1024) -> tuple[tuple[int, int], ...]:
    """The key chunks each query chunk of ``chunked_attention`` scans when
    the positions are the canonical ``0..S-1``: one ``(lo, hi)`` range
    (``hi`` exclusive) per query chunk. A chunk outside it holds no
    position the chunk's queries can see: past the last query under
    ``causal``, before the first query's window under ``window``."""
    q_chunk, kv_chunk = min(q_chunk, sq), min(kv_chunk, skv)
    nq, nk = -(-sq // q_chunk), -(-skv // kv_chunk)
    spans = []
    for i in range(nq):
        first, last = i * q_chunk, min((i + 1) * q_chunk, sq) - 1
        hi = min(last // kv_chunk + 1, nk) if causal else nk
        lo = max(first - window + 1, 0) // kv_chunk if window else 0
        spans.append((lo, hi))
    return tuple(spans)


def attention_blocks(sq: int, skv: int, *, causal: bool,
                     window: int | None = None, q_chunk: int = 512,
                     kv_chunk: int = 1024) -> tuple[int, int]:
    """(live, total): the (query chunk, key chunk) blocks that
    ``chunked_attention`` computes at canonical positions, and all of
    them."""
    spans = attention_schedule(sq, skv, causal=causal, window=window,
                               q_chunk=q_chunk, kv_chunk=kv_chunk)
    nk = -(-skv // min(kv_chunk, skv))
    return sum(hi - lo for lo, hi in spans), len(spans) * nk


def chunked_attention(q, k, v, *, q_positions=None, kv_positions=None,
                      causal: bool, window: int | None = None,
                      q_chunk: int = 512, kv_chunk: int = 1024,
                      kv_valid_len=None):
    """Online-softmax attention.

    q: (B, S_q, Hq, D); k, v: (B, S_kv, Hkv, D); positions: (S_q,), (S_kv,)
    Returns (B, S_q, Hq, D).

    Positions left as None are the canonical ``0..S-1``. Then each query
    chunk scans only the key chunks ``attention_schedule`` gives it (the
    causal triangle, or a window's band); the chunks it skips are wholly
    masked, and merging one adds exactly nothing. Explicit positions scan
    every key chunk and leave the skipping to the mask.
    """
    from repro.models.module import BATCH, maybe_shard
    # keep heads sharded over "model" through the chunking reshapes — GSPMD
    # loses the propagation and replicates (B,S,H,D) copies otherwise
    q = maybe_shard(q, BATCH, None, "model", None)
    k = maybe_shard(k, BATCH, None, "model", None)
    v = maybe_shard(v, BATCH, None, "model", None)
    b, sq, hq, d = q.shape
    skv = k.shape[1]
    q_chunk = min(q_chunk, sq)
    kv_chunk = min(kv_chunk, skv)
    nq = -(-sq // q_chunk)
    nk = -(-skv // kv_chunk)
    if q_positions is None and kv_positions is None:
        spans = attention_schedule(sq, skv, causal=causal, window=window,
                                   q_chunk=q_chunk, kv_chunk=kv_chunk)
    else:
        spans = ((0, nk),) * nq
    if q_positions is None:
        q_positions = jnp.arange(sq)
    if kv_positions is None:
        kv_positions = jnp.arange(skv)
    # pad to multiples
    def pad_to(x, n, axis):
        pad = n - x.shape[axis]
        if pad == 0:
            return x
        cfg = [(0, 0)] * x.ndim
        cfg[axis] = (0, pad)
        return jnp.pad(x, cfg)

    qp = pad_to(q, nq * q_chunk, 1).transpose(0, 2, 1, 3)  # (B,Hq,Sq,D)
    kp = pad_to(k, nk * kv_chunk, 1).transpose(0, 2, 1, 3)
    vp = pad_to(v, nk * kv_chunk, 1).transpose(0, 2, 1, 3)
    qpos = pad_to(q_positions, nq * q_chunk, 0).reshape(nq, q_chunk)
    kpos = pad_to(kv_positions, nk * kv_chunk, 0).reshape(nk, kv_chunk)
    kvalid = jnp.arange(nk * kv_chunk) < (skv if kv_valid_len is None
                                          else kv_valid_len)
    kvalid = kvalid.reshape(nk, kv_chunk)

    qs = qp.reshape(b, hq, nq, q_chunk, d).transpose(2, 0, 1, 3, 4)
    ks = kp.reshape(b, -1, nk, kv_chunk, d).transpose(2, 0, 1, 3, 4)
    vs = vp.reshape(b, -1, nk, kv_chunk, d).transpose(2, 0, 1, 3, 4)

    def q_body(lo, hi):
        """The scan over query chunks whose span is key chunks lo..hi-1."""
        def body(_, q_in):
            qc, qpos_c = q_in  # (B,Hq,Tq,D), (Tq,)

            def kv_body(state, j):
                o_acc, m_acc, l_acc = state
                kc, vc, kpos_c, kval_c = ks[j], vs[j], kpos[j], kvalid[j]
                mask = kval_c[None, :]
                if causal:
                    mask = mask & (kpos_c[None, :] <= qpos_c[:, None])
                if window is not None:
                    mask = mask & (kpos_c[None, :] > qpos_c[:, None] - window)
                bias = jnp.where(mask, 0.0, NEG_INF)[None, None].astype(
                    jnp.float32)
                o, m, l = _attend_block(qc, kc, vc, bias)
                m_new = jnp.maximum(m_acc, m)
                c_old = jnp.exp(m_acc - m_new)
                c_new = jnp.exp(m - m_new)
                o_acc = (o_acc * c_old.astype(o_acc.dtype)
                         + o * c_new.astype(o.dtype))
                l_acc = l_acc * c_old + l * c_new
                return (o_acc, m_new, l_acc), None

            o0 = jnp.zeros(qc.shape, jnp.float32)
            m0 = jnp.full(qc.shape[:-1] + (1,), NEG_INF, jnp.float32)
            l0 = jnp.zeros(qc.shape[:-1] + (1,), jnp.float32)
            # checkpoint the kv step: otherwise backward stacks the
            # (B,H,Tq,Tk) softmax residuals across ALL kv chunks
            # (flash-attention memory blowup — the whole point of chunking
            # would be lost). The step indexes key chunk j of the whole
            # stacks, so the backward adds each chunk's gradient into one
            # buffer in place.
            (o, m, l), _ = jax.lax.scan(jax.checkpoint(kv_body), (o0, m0, l0),
                                        jnp.arange(lo, hi))
            o = o / jnp.maximum(l, 1e-30)
            return None, o.astype(q.dtype)
        return body

    # one scan per run of query chunks that share a span (spans only grow)
    outs, i = [], 0
    for span, run in itertools.groupby(spans):
        n = len(list(run))
        _, o = jax.lax.scan(q_body(*span), None, (qs[i:i + n], qpos[i:i + n]))
        outs.append(o)
        i += n
    out = jnp.concatenate(outs).transpose(1, 2, 0, 3, 4).reshape(b, hq, nq * q_chunk, d)
    return out[:, :, :sq].transpose(0, 2, 1, 3)


# ---------------------------------------------------------------------------
# GQA block
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class AttnConfig:
    d_model: int
    n_heads: int
    n_kv_heads: int
    head_dim: int
    rope_theta: float = 10000.0
    rotary_pct: float = 1.0          # stablelm: 0.25
    qkv_bias: bool = False           # qwen2
    qk_norm: bool = False            # stablelm-2 style per-head norm
    window: int | None = None        # SWA (mixtral / h2o-danube)
    causal: bool = True

    @property
    def rotary_dim(self):
        rd = int(self.head_dim * self.rotary_pct)
        return rd - rd % 2


def gqa_init(key, cfg: AttnConfig, dtype=jnp.float32):
    ks = jax.random.split(key, 4)
    d, hq, hkv, hd = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    p = {
        "wq": dense_init(ks[0], d, hq * hd, bias=cfg.qkv_bias, dtype=dtype),
        "wk": dense_init(ks[1], d, hkv * hd, bias=cfg.qkv_bias, dtype=dtype),
        "wv": dense_init(ks[2], d, hkv * hd, bias=cfg.qkv_bias, dtype=dtype),
        "wo": dense_init(ks[3], hq * hd, d, bias=False, dtype=dtype),
    }
    if cfg.qk_norm:
        p["q_norm"] = rmsnorm_init(hd, dtype)
        p["k_norm"] = rmsnorm_init(hd, dtype)
    return p


def _project_qkv(p, x, cfg: AttnConfig, positions):
    b, s, _ = x.shape
    q = dense_apply(p["wq"], x).reshape(b, s, cfg.n_heads, cfg.head_dim)
    k = dense_apply(p["wk"], x).reshape(b, s, cfg.n_kv_heads, cfg.head_dim)
    v = dense_apply(p["wv"], x).reshape(b, s, cfg.n_kv_heads, cfg.head_dim)
    if cfg.qk_norm:
        q = rmsnorm_apply(p["q_norm"], q)
        k = rmsnorm_apply(p["k_norm"], k)
    if cfg.rotary_dim > 0:
        inv = rope_freqs(cfg.head_dim, cfg.rope_theta, cfg.rotary_dim)
        pos_b = jnp.broadcast_to(positions[None, :], (b, s))
        q = apply_rope(q, pos_b, inv, rotary_dim=cfg.rotary_dim)
        k = apply_rope(k, pos_b, inv, rotary_dim=cfg.rotary_dim)
    return q, k, v


def gqa_apply(p, x, cfg: AttnConfig, *, positions=None, kv=None,
              kv_positions=None, q_chunk=512, kv_chunk=1024):
    """Full-sequence attention (train / prefill). Optional cross-attention via
    precomputed ``kv=(k, v)`` (whisper decoder). ``positions=None`` is
    ``0..S-1``, which lets ``chunked_attention`` skip the key chunks no
    query sees."""
    b, s, _ = x.shape
    if kv is None:
        q, k, v = _project_qkv(
            p, x, cfg, jnp.arange(s) if positions is None else positions)
        kv_positions = positions
        causal = cfg.causal
    else:
        q = dense_apply(p["wq"], x).reshape(b, s, cfg.n_heads, cfg.head_dim)
        if cfg.qk_norm:
            q = rmsnorm_apply(p["q_norm"], q)
        k, v = kv
        causal = False
    o = chunked_attention(q, k, v, q_positions=positions,
                          kv_positions=kv_positions, causal=causal,
                          window=cfg.window, q_chunk=q_chunk,
                          kv_chunk=kv_chunk)
    return dense_apply(p["wo"], o.reshape(b, s, cfg.n_heads * cfg.head_dim))


def cross_kv(p, enc_out, cfg: AttnConfig):
    """Precompute K/V from encoder output for cross-attention."""
    b, s, _ = enc_out.shape
    k = dense_apply(p["wk"], enc_out).reshape(b, s, cfg.n_kv_heads, cfg.head_dim)
    v = dense_apply(p["wv"], enc_out).reshape(b, s, cfg.n_kv_heads, cfg.head_dim)
    if cfg.qk_norm:
        k = rmsnorm_apply(p["k_norm"], k)
    return k, v


# --- decode -----------------------------------------------------------------


def gqa_cache_init(cfg: AttnConfig, batch: int, max_len: int, dtype):
    size = min(max_len, cfg.window) if cfg.window else max_len
    return {
        "k": jnp.zeros((batch, size, cfg.n_kv_heads, cfg.head_dim), dtype),
        "v": jnp.zeros((batch, size, cfg.n_kv_heads, cfg.head_dim), dtype),
        # absolute positions held at each slot (-1 = empty)
        "slot_pos": jnp.full((size,), -1, jnp.int32),
    }


def gqa_decode(p, x, cache, cfg: AttnConfig, *, pos):
    """One-token decode. x: (B, 1, d); pos: scalar int32 absolute position."""
    b = x.shape[0]
    positions = jnp.reshape(pos, (1,))
    q, k, v = _project_qkv(p, x, cfg, positions)
    from repro.models.module import BATCH, maybe_shard
    size = cache["k"].shape[1]
    slot = jnp.mod(pos, size) if cfg.window else pos
    ck = jax.lax.dynamic_update_slice(cache["k"], k, (0, slot, 0, 0))
    cv = jax.lax.dynamic_update_slice(cache["v"], v, (0, slot, 0, 0))
    # pin the cache layout (batch, -, -, hd/model): without this, GSPMD
    # reshards the full multi-GiB cache around the DUS/einsum pair
    ck = maybe_shard(ck, BATCH, None, None, "model")
    cv = maybe_shard(cv, BATCH, None, None, "model")
    spos = jax.lax.dynamic_update_slice(cache["slot_pos"],
                                        jnp.reshape(pos, (1,)).astype(jnp.int32),
                                        (slot,))
    # one-token attention over the cache: (B, Hkv, rep, size)
    hq, hkv, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    rep = hq // hkv
    qg = q.reshape(b, hkv, rep, hd)
    s = jnp.einsum("bgrd,bsgd->bgrs", qg, ck).astype(jnp.float32)
    s = s * (1.0 / np.sqrt(hd))
    valid = (spos >= 0) & (spos <= pos)
    if cfg.window:
        valid = valid & (spos > pos - cfg.window)
    s = jnp.where(valid[None, None, None, :], s, NEG_INF)
    w = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bgrs,bsgd->bgrd", w.astype(cv.dtype), cv)
    o = o.reshape(b, 1, hq * hd)
    y = dense_apply(p["wo"], o)
    return y, {"k": ck, "v": cv, "slot_pos": spos}


# ---------------------------------------------------------------------------
# MLA (DeepSeek-V2 multi-head latent attention)
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class MLAConfig:
    """DeepSeek-V2's latent attention sizes. ``q_lora=None`` projects
    queries straight from the hidden state (DeepSeek-V2-Lite); an int
    routes them through a rank-``q_lora`` latent with its own RMSNorm.
    ``rope_scaling`` is the YaRN of the rope dims (None: plain rope).

    The rope dims use the half-split rotation of ``apply_rope``; upstream
    first interleaves them (``view(d/2, 2).transpose``), a fixed
    permutation of the rope columns of ``wq``/``wkv_a`` that random
    weights do not see."""
    d_model: int
    n_heads: int
    kv_lora: int = 512
    q_lora: int | None = 1536
    qk_nope_dim: int = 128
    qk_rope_dim: int = 64
    v_head_dim: int = 128
    rope_theta: float = 10000.0
    rope_scaling: YaRN | None = None

    @property
    def qk_head_dim(self):
        return self.qk_nope_dim + self.qk_rope_dim

    @property
    def softmax_scale(self) -> float:
        return self.qk_head_dim ** -0.5 * yarn_softmax_mscale(
            self.rope_scaling)

    def inv_freq(self):
        return rope_freqs(self.qk_rope_dim, self.rope_theta,
                          yarn=self.rope_scaling)


def mla_init(key, cfg: MLAConfig, dtype=jnp.float32):
    ks = jax.random.split(key, 7)
    h = cfg.n_heads
    if cfg.q_lora is None:
        q = {"wq": dense_init(ks[1], cfg.d_model, h * cfg.qk_head_dim,
                              dtype=dtype)}
    else:
        q = {"wq_a": dense_init(ks[0], cfg.d_model, cfg.q_lora, dtype=dtype),
             "q_a_norm": rmsnorm_init(cfg.q_lora, dtype),
             "wq_b": dense_init(ks[1], cfg.q_lora, h * cfg.qk_head_dim,
                                dtype=dtype)}
    return {
        **q,
        "wkv_a": dense_init(ks[2], cfg.d_model, cfg.kv_lora + cfg.qk_rope_dim,
                            dtype=dtype),
        "kv_a_norm": rmsnorm_init(cfg.kv_lora, dtype),
        "wk_b": dense_init(ks[3], cfg.kv_lora, h * cfg.qk_nope_dim, dtype=dtype),
        "wv_b": dense_init(ks[4], cfg.kv_lora, h * cfg.v_head_dim, dtype=dtype),
        "wo": dense_init(ks[5], h * cfg.v_head_dim, cfg.d_model, dtype=dtype),
    }


def _mla_q(p, x, cfg: MLAConfig, positions):
    b, s, _ = x.shape
    if cfg.q_lora is None:
        q = dense_apply(p["wq"], x)
    else:
        q = dense_apply(p["wq_b"],
                        rmsnorm_apply(p["q_a_norm"], dense_apply(p["wq_a"], x)))
    q = q.reshape(b, s, cfg.n_heads, cfg.qk_head_dim)
    q_nope, q_rope = q[..., :cfg.qk_nope_dim], q[..., cfg.qk_nope_dim:]
    pos_b = jnp.broadcast_to(positions[None, :], (b, s))
    q_rope = apply_rope(q_rope, pos_b, cfg.inv_freq())
    return q_nope, q_rope


def _mla_latent(p, x, cfg: MLAConfig, positions):
    b, s, _ = x.shape
    kv = dense_apply(p["wkv_a"], x)
    c_kv = rmsnorm_apply(p["kv_a_norm"], kv[..., :cfg.kv_lora])
    k_rope = kv[..., cfg.kv_lora:].reshape(b, s, 1, cfg.qk_rope_dim)
    pos_b = jnp.broadcast_to(positions[None, :], (b, s))
    k_rope = apply_rope(k_rope, pos_b, cfg.inv_freq())[:, :, 0]
    return c_kv, k_rope  # (B,S,kv_lora), (B,S,rope_dim)


def mla_apply(p, x, cfg: MLAConfig, *, positions=None, q_chunk=512,
              kv_chunk=1024):
    """Prefill/train path: expand latent to per-head K/V, chunked attention.
    The chunked kernel scales scores by ``qk_head_dim**-0.5``; YaRN's
    further ``mscale**2`` is folded into the queries. ``positions=None``
    is ``0..S-1`` and takes the causal block schedule."""
    with jax.named_scope("mla"):
        b, s, _ = x.shape
        rope_pos = jnp.arange(s) if positions is None else positions
        q_nope, q_rope = _mla_q(p, x, cfg, rope_pos)
        c_kv, k_rope = _mla_latent(p, x, cfg, rope_pos)
        h = cfg.n_heads
        k_nope = dense_apply(p["wk_b"], c_kv).reshape(b, s, h,
                                                      cfg.qk_nope_dim)
        v = dense_apply(p["wv_b"], c_kv).reshape(b, s, h, cfg.v_head_dim)
        q = jnp.concatenate([q_nope, q_rope], axis=-1)
        mscale = yarn_softmax_mscale(cfg.rope_scaling)
        if mscale != 1.0:
            q = q * mscale
        k = jnp.concatenate([k_nope,
                             jnp.broadcast_to(k_rope[:, :, None],
                                              (b, s, h, cfg.qk_rope_dim))],
                            axis=-1)
        # pad v to qk_head_dim so the chunked kernel can share shapes
        vpad = jnp.pad(v, ((0, 0), (0, 0), (0, 0),
                           (0, cfg.qk_head_dim - cfg.v_head_dim)))
        o = chunked_attention(q, k, vpad, q_positions=positions,
                              kv_positions=positions, causal=True,
                              q_chunk=q_chunk, kv_chunk=kv_chunk)
        o = o[..., :cfg.v_head_dim].reshape(b, s, h * cfg.v_head_dim)
        return dense_apply(p["wo"], o)


def mla_cache_init(cfg: MLAConfig, batch: int, max_len: int, dtype):
    return {
        "c_kv": jnp.zeros((batch, max_len, cfg.kv_lora), dtype),
        "k_rope": jnp.zeros((batch, max_len, cfg.qk_rope_dim), dtype),
        "slot_pos": jnp.full((max_len,), -1, jnp.int32),
    }


def mla_decode(p, x, cache, cfg: MLAConfig, *, pos):
    """Absorbed one-token decode over the compressed latent cache."""
    b = x.shape[0]
    positions = jnp.reshape(pos, (1,))
    q_nope, q_rope = _mla_q(p, x, cfg, positions)       # (B,1,H,*)
    c_kv, k_rope = _mla_latent(p, x, cfg, positions)    # (B,1,kv_lora)
    ck = jax.lax.dynamic_update_slice(cache["c_kv"], c_kv, (0, pos, 0))
    cr = jax.lax.dynamic_update_slice(cache["k_rope"], k_rope, (0, pos, 0))
    spos = jax.lax.dynamic_update_slice(cache["slot_pos"],
                                        jnp.reshape(pos, (1,)).astype(jnp.int32),
                                        (pos,))
    h = cfg.n_heads
    # absorb W_uk into q: q_lat (B,H,kv_lora)
    wk_b = p["wk_b"]["w"].reshape(cfg.kv_lora, h, cfg.qk_nope_dim)
    q_lat = jnp.einsum("bhd,lhd->bhl", q_nope[:, 0], wk_b)
    s_lat = jnp.einsum("bhl,bsl->bhs", q_lat, ck)
    s_rope = jnp.einsum("bhd,bsd->bhs", q_rope[:, 0], cr)
    s = (s_lat + s_rope).astype(jnp.float32) * cfg.softmax_scale
    valid = (spos >= 0) & (spos <= pos)
    s = jnp.where(valid[None, None, :], s, NEG_INF)
    w = jax.nn.softmax(s, axis=-1)
    o_lat = jnp.einsum("bhs,bsl->bhl", w.astype(ck.dtype), ck)  # (B,H,kv_lora)
    wv_b = p["wv_b"]["w"].reshape(cfg.kv_lora, h, cfg.v_head_dim)
    o = jnp.einsum("bhl,lhd->bhd", o_lat, wv_b).reshape(b, 1, h * cfg.v_head_dim)
    y = dense_apply(p["wo"], o)
    return y, {"c_kv": ck, "k_rope": cr, "slot_pos": spos}
