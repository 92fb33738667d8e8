"""Mixture-of-Experts FFN with sort-based capacity dispatch.

Dispatch builds an (E, C, d) buffer via scatter (tokens sorted by expert,
rank-within-expert slotting, overflow dropped) so compiled FLOPs track the
ACTIVE expert compute (top_k x capacity_factor), not E x dense — this is what
makes the roofline's MODEL_FLOPS/HLO_FLOPs ratio meaningful for MoE archs.
When experts are sharded over the mesh "model" axis, the scatter/gather pair
lowers to the expected all-to-all style collectives.

Supports Mixtral-style top-k softmax routing and DeepSeek-V2 style
(softmax -> top-k, plus always-on shared experts).

A layer told which experts it holds (``MoEConfig.held``, one chip's share
under expert parallelism) routes over all ``n_experts`` and computes, with
no token dropped, the part of the result its own experts give: every
(token, held expert) pair, as grouped products over the pairs sorted by
expert (``jax.lax.ragged_dot``), so the work follows the pairs routed
here. Shared experts run on every token. On one chip the exchange with
the other shares is absent: the partial result goes on to the next layer.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

from repro.models.layers import dense_apply, dense_init, silu
from repro.models.module import default_init


@dataclasses.dataclass(frozen=True)
class MoEConfig:
    d_model: int
    d_ff_expert: int
    n_experts: int
    top_k: int
    n_shared: int = 0            # deepseek-v2: 2 shared experts
    d_ff_shared: int = 0         # hidden dim of the fused shared expert
    capacity_factor: float = 1.25
    router_norm_topk: bool = True  # mixtral renormalizes over top-k
    # (first, count): the experts this layer holds, of the n_experts the
    # router scores; None holds them all (the capacity dispatch below)
    held: tuple[int, int] | None = None

    @property
    def n_held(self) -> int:
        return self.n_experts if self.held is None else self.held[1]


def moe_init(key, cfg: MoEConfig, dtype=jnp.float32):
    ks = jax.random.split(key, 5)
    e, d, f = cfg.n_held, cfg.d_model, cfg.d_ff_expert
    p = {
        "router": dense_init(ks[0], d, cfg.n_experts, dtype=dtype),
        # stacked expert SwiGLU weights
        "w_gate": default_init(ks[1], (e, d, f), fan_in=d, dtype=dtype),
        "w_up": default_init(ks[2], (e, d, f), fan_in=d, dtype=dtype),
        "w_down": default_init(ks[3], (e, f, d), fan_in=f, dtype=dtype),
    }
    if cfg.n_shared > 0:
        fs = cfg.d_ff_shared or cfg.n_shared * cfg.d_ff_expert
        kk = jax.random.split(ks[4], 3)
        p["shared"] = {
            "w_gate": dense_init(kk[0], d, fs, dtype=dtype),
            "w_up": dense_init(kk[1], d, fs, dtype=dtype),
            "w_down": dense_init(kk[2], fs, d, dtype=dtype),
        }
    return p


def route(router_logits, cfg: MoEConfig):
    """router_logits: (N, E) -> (weights (N,k), ids (N,k), aux metrics)."""
    probs = jax.nn.softmax(router_logits.astype(jnp.float32), axis=-1)
    weights, ids = jax.lax.top_k(probs, cfg.top_k)
    if cfg.router_norm_topk:
        weights = weights / jnp.sum(weights, axis=-1, keepdims=True)
    # Switch-style load-balance aux loss terms
    n, e = router_logits.shape
    frac_tokens = jnp.mean(
        jax.nn.one_hot(ids[:, 0], e, dtype=jnp.float32), axis=0)
    frac_probs = jnp.mean(probs, axis=0)
    aux_loss = e * jnp.sum(frac_tokens * frac_probs)
    return weights, ids, aux_loss


def moe_apply(p, x, cfg: MoEConfig, *, capacity: int | None = None,
              chunk_tokens: int = 32768):
    """x: (B, S, d) -> (y, aux_loss).

    Dispatch is microbatched: a lax.scan over token chunks bounds the
    (E, C, d) dispatch buffer to one chunk's capacity — without this, a
    256x4096 global batch on deepseek-v2 needs an 80 GiB buffer per copy
    and the train dry-run blows past HBM. A layer that holds a share of
    the experts (``cfg.held``) takes the drop-free path instead."""
    b, s, d = x.shape
    n = b * s
    if cfg.held is None and n > chunk_tokens and s > 1:
        nc = -(-n // chunk_tokens)
        pad = nc * chunk_tokens - n
        xf = jnp.pad(x.reshape(n, d), ((0, pad), (0, 0)))
        xs = xf.reshape(nc, chunk_tokens, 1, d)

        def body(acc, xc):
            y, a = moe_apply(p, xc.transpose(1, 0, 2), cfg,
                             capacity=capacity)
            return acc + a, y.transpose(1, 0, 2)

        aux, ys = jax.lax.scan(jax.checkpoint(body),
                               jnp.zeros((), jnp.float32), xs)
        y = ys.reshape(nc * chunk_tokens, d)[:n].reshape(b, s, d)
        return y, aux / nc
    with jax.named_scope("moe"):
        xf = x.reshape(n, d)
        with jax.named_scope("route"):
            weights, ids, aux = route(dense_apply(p["router"], xf), cfg)
        with jax.named_scope("experts"):
            if cfg.held is None:
                y = _capacity_experts(p, xf, weights, ids, cfg, capacity,
                                      decode=s == 1)
            else:
                y = _held_experts(p, xf, weights, ids, cfg)
        if "shared" in p:
            with jax.named_scope("shared"):
                sp = p["shared"]
                hs = (silu(dense_apply(sp["w_gate"], xf))
                      * dense_apply(sp["w_up"], xf))
                y = y + dense_apply(sp["w_down"], hs)
        return y.reshape(b, s, d), aux


def _capacity_experts(p, xf, weights, ids, cfg: MoEConfig, capacity,
                      decode: bool):
    """Every expert held: tokens past an expert's capacity are dropped."""
    n, d = xf.shape
    e, k = cfg.n_experts, cfg.top_k
    if capacity is None:
        if decode:  # drop-free (production serving semantics)
            capacity = n * k
        else:
            capacity = max(1, int(cfg.capacity_factor * k * n / e))

    flat_ids = ids.reshape(n * k)
    tok_idx = jnp.repeat(jnp.arange(n), k)
    flat_w = weights.reshape(n * k)

    order = jnp.argsort(flat_ids)  # stable
    sorted_e = flat_ids[order]
    counts = jnp.bincount(flat_ids, length=e)
    offsets = jnp.cumsum(counts) - counts
    rank = jnp.arange(n * k) - offsets[sorted_e]
    ok = rank < capacity
    slot = jnp.where(ok, rank, capacity)  # out-of-range rows dropped

    buf = jnp.zeros((e, capacity, d), xf.dtype)
    buf = buf.at[sorted_e, slot].set(xf[tok_idx[order]], mode="drop")

    # expert SwiGLU over the dispatch buffer
    g = jnp.einsum("ecd,edf->ecf", buf, p["w_gate"])
    u = jnp.einsum("ecd,edf->ecf", buf, p["w_up"])
    h = silu(g) * u
    out = jnp.einsum("ecf,efd->ecd", h, p["w_down"])

    y_sorted = out[sorted_e, slot]  # (n*k, d); dropped rows read garbage
    y_sorted = jnp.where(ok[:, None], y_sorted, 0.0)
    y = jnp.zeros((n, d), xf.dtype)
    return y.at[tok_idx[order]].add(
        y_sorted * flat_w[order][:, None].astype(xf.dtype))


def _held_experts(p, xf, weights, ids, cfg: MoEConfig):
    """The held experts' part of the layer for every (token, held expert)
    pair: the n*k routed pairs sorted by held expert, pairs routed to
    experts held elsewhere last, outside every group of the grouped
    products, and weighted by 0.

    A grouped product leaves its rows past the last group unwritten on a
    TPU (its transpose too), so every input and output of one is masked
    to those rows: no value of an unwritten row reaches the result or,
    through the backward pass, a gradient."""
    n, d = xf.shape
    k = cfg.top_k
    first, count = cfg.held
    local = ids.reshape(n * k) - first
    mine = (local >= 0) & (local < count)
    group = jnp.where(mine, local, count)
    order = jnp.argsort(group, stable=True)
    sizes = jnp.bincount(group, length=count + 1)[:count].astype(jnp.int32)
    rows = mine[order][:, None]

    def grouped(x, w):
        return jnp.where(rows, jax.lax.ragged_dot(x, w, sizes), 0.0)
    xs = jnp.where(rows, xf[order // k], 0.0)     # flat = token*k + slot
    h = jnp.where(rows, silu(grouped(xs, p["w_gate"]))
                  * grouped(xs, p["w_up"]), 0.0)
    out = grouped(h, p["w_down"])
    # back to (token, slot) order, then the routing-weighted sum
    out = out[jnp.argsort(order)].reshape(n, k, d)
    w = jnp.where(mine.reshape(n, k), weights, 0.0).astype(xf.dtype)
    return jnp.einsum("nk,nkd->nd", w, out)


def moe_apply_dense_reference(p, x, cfg: MoEConfig):
    """O(E) dense-compute reference (oracle for tests): every expert runs on
    every token, combine with top-k weights. Bit-exact modulo capacity drops."""
    b, s, d = x.shape
    xf = x.reshape(b * s, d)
    weights, ids, aux = route(dense_apply(p["router"], xf), cfg)
    g = jnp.einsum("nd,edf->enf", xf, p["w_gate"])
    u = jnp.einsum("nd,edf->enf", xf, p["w_up"])
    out = jnp.einsum("enf,efd->end", silu(g) * u, p["w_down"])  # (E,N,d)
    mask = jax.nn.one_hot(ids, cfg.n_experts, dtype=jnp.float32)  # (N,k,E)
    comb = jnp.einsum("nk,nke,end->nd", weights, mask,
                      out.astype(jnp.float32))
    y = comb.astype(x.dtype)
    if "shared" in p:
        sp = p["shared"]
        hs = silu(dense_apply(sp["w_gate"], xf)) * dense_apply(sp["w_up"], xf)
        y = y + dense_apply(sp["w_down"], hs)
    return y.reshape(b, s, d), aux
