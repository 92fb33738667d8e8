"""Expert-parallel MoE with explicit all-to-all dispatch (shard_map).

The einsum-dispatch in models/moe.py lets GSPMD choose collectives; this
module expresses the canonical expert-parallel schedule EXPLICITLY with
jax.lax collectives inside shard_map — the TPU-native mapping of the
GShard/DeepSpeed-MoE all-to-all pattern (DESIGN.md §5):

  per device (tokens sharded over the mesh axis `axis`, experts too):
    1. route local tokens; destination shard = expert_owner(e)
    2. scatter tokens into a (n_shards, cap, d) send buffer
    3. lax.all_to_all over `axis`  -> tokens for MY experts from every peer
    4. local expert FFN over a (E_local, C, d) buffer
    5. reverse all_to_all               -> expert outputs back to owners
    6. weighted combine into the local token stream

Requires n_experts % axis_size == 0. Numerics match
moe.moe_apply_dense_reference up to capacity drops (tests).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.models import moe as moe_lib
from repro.models.layers import dense_apply, silu


def _local_moe(p, xf, cfg, axis: str | None, capacity: int, nsh: int = 1):
    """Body run per shard. xf: (n_loc, d) local tokens. nsh: the static
    size of ``axis`` (shapes depend on it; mesh-known at trace time)."""
    n_loc, d = xf.shape
    e, k = cfg.n_experts, cfg.top_k
    e_loc = e // nsh

    weights, ids, aux = moe_lib.route(dense_apply(p["router"], xf), cfg)
    flat_ids = ids.reshape(n_loc * k)
    tok_idx = jnp.repeat(jnp.arange(n_loc), k)
    flat_w = weights.reshape(n_loc * k)

    # slot each (token, expert) pair into the send buffer for the expert's
    # owner shard: rank within destination shard, capped at `capacity`
    dest = flat_ids // e_loc                       # (n_loc*k,) in [0, nsh)
    order = jnp.argsort(dest)
    sdest = dest[order]
    counts = jnp.bincount(dest, length=nsh)
    offsets = jnp.cumsum(counts) - counts
    rank = jnp.arange(n_loc * k) - offsets[sdest]
    ok = rank < capacity
    slot = jnp.where(ok, rank, capacity)

    send = jnp.zeros((nsh, capacity, d), xf.dtype)
    send = send.at[sdest, slot].set(xf[tok_idx[order]], mode="drop")
    send_eid = jnp.full((nsh, capacity), -1, jnp.int32)
    send_eid = send_eid.at[sdest, slot].set(
        (flat_ids[order] % e_loc).astype(jnp.int32), mode="drop")

    if axis:
        recv = jax.lax.all_to_all(send, axis, 0, 0, tiled=False)
        recv_eid = jax.lax.all_to_all(send_eid, axis, 0, 0, tiled=False)
    else:
        recv, recv_eid = send, send_eid
    # recv: (nsh, capacity, d) token payloads for MY local experts
    re = recv.reshape(nsh * capacity, d)
    reid = recv_eid.reshape(nsh * capacity)

    # local expert weights: shard-local slice along the expert axis
    idx = jax.lax.axis_index(axis) if axis else 0
    wg = jax.lax.dynamic_slice_in_dim(p["w_gate"], idx * e_loc, e_loc, 0)
    wu = jax.lax.dynamic_slice_in_dim(p["w_up"], idx * e_loc, e_loc, 0)
    wd = jax.lax.dynamic_slice_in_dim(p["w_down"], idx * e_loc, e_loc, 0)

    # dispatch into per-local-expert buffer
    cap2 = nsh * capacity  # worst case: everything routes to one expert
    order2 = jnp.argsort(jnp.where(reid < 0, e_loc, reid))
    sid2 = reid[order2]
    counts2 = jnp.bincount(jnp.where(reid < 0, e_loc, reid),
                           length=e_loc + 1)[:e_loc]
    off2 = jnp.cumsum(counts2) - counts2
    rank2 = jnp.arange(cap2) - jnp.where(sid2 < e_loc, off2[
        jnp.clip(sid2, 0, e_loc - 1)], 0)
    ok2 = (sid2 >= 0) & (sid2 < e_loc) & (rank2 < cap2)
    slot2 = jnp.where(ok2, rank2, cap2)
    buf = jnp.zeros((e_loc, cap2, d), xf.dtype)
    buf = buf.at[jnp.clip(sid2, 0, e_loc - 1), slot2].set(
        re[order2], mode="drop")

    h = silu(jnp.einsum("ecd,edf->ecf", buf, wg)) * \
        jnp.einsum("ecd,edf->ecf", buf, wu)
    out = jnp.einsum("ecf,efd->ecd", h, wd)

    # un-dispatch back to (nsh, capacity, d) then reverse all_to_all
    back = jnp.zeros((cap2, d), xf.dtype)
    taken = out[jnp.clip(sid2, 0, e_loc - 1), slot2]
    taken = jnp.where(ok2[:, None], taken, 0.0)
    back = back.at[order2].set(taken)
    back = back.reshape(nsh, capacity, d)
    if axis:
        ret = jax.lax.all_to_all(back, axis, 0, 0, tiled=False)
    else:
        ret = back
    # combine: gather each pair's output from its send slot
    y_pair = ret[sdest, slot]
    y_pair = jnp.where(ok[:, None], y_pair, 0.0)
    y = jnp.zeros((n_loc, d), xf.dtype)
    y = y.at[tok_idx[order]].add(
        y_pair * flat_w[order][:, None].astype(xf.dtype))

    if "shared" in p:
        sp = p["shared"]
        hs = silu(dense_apply(sp["w_gate"], xf)) * dense_apply(sp["w_up"],
                                                               xf)
        y = y + dense_apply(sp["w_down"], hs)
    return y, aux


def moe_apply_ep(p, x, cfg, mesh, *, axis: str = "model",
                 capacity_factor: float | None = None):
    """shard_map expert-parallel MoE. x: (B, S, d) sharded over "data";
    experts sharded over ``axis``. Requires E % |axis| == 0."""
    b, s, d = x.shape
    nsh = mesh.shape[axis]
    assert cfg.n_experts % nsh == 0, (cfg.n_experts, nsh)
    dsh = mesh.shape.get("data", 1)
    n_loc = max(1, b // dsh) * s
    cf = capacity_factor or cfg.capacity_factor
    capacity = max(1, int(cf * cfg.top_k * n_loc / nsh))

    def body(p_loc, x_loc):
        bl, sl, _ = x_loc.shape
        y, aux = _local_moe(p_loc, x_loc.reshape(bl * sl, d), cfg,
                            axis if nsh > 1 else None, capacity, nsh=nsh)
        return y.reshape(bl, sl, d), aux

    pspecs = jax.tree_util.tree_map(lambda _: P(), p)  # replicated weights
    fn = jax.shard_map(body, mesh=mesh,
                       in_specs=(pspecs, P("data", None, None)),
                       out_specs=(P("data", None, None), P()),
                       check_vma=False)
    return fn(p, x)
