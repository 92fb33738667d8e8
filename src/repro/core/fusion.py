"""Federated model fusion.

- ``fedavg``: Eq. 1/18 coordinate-based (optionally sample-weighted) mean.
- ``paired_average``: Fed2's feature paired averaging (Eq. 19): group g of
  node i fuses with group g' of node j iff their logit signatures match.
  With the structural pre-alignment the permutation is the identity and the
  whole fusion is ONE masked mean — zero runtime matching cost, which is the
  paper's efficiency claim; the permutation argument expresses/tests the
  general semantics.
- ``fedprox_penalty``: FedProx (Li et al., MLSys'20) proximal term.
- FedMA-style matched averaging lives in core/matching.py.

All functions operate on *stacked* client params: every leaf has a leading
node axis N (clients are executed as a vmapped batch — DESIGN.md §5), so a
fusion is a tree_map of reductions and lowers to a single collective when the
node axis is sharded over the mesh "data" axis.

Fast path (DESIGN.md §5): ``fedavg`` and ``paired_average`` accept
``use_kernel=True`` to route the reduction through the Pallas
``paired_fusion_kernel`` — each leaf is raveled to (N, m) and streamed
through the kernel in one pass (per group block under presence weighting,
with that group's weight column). Every parameter is read exactly once
regardless of G, which makes the paper's efficiency claim literal: paired
averaging costs no more than FedAvg's coordinate mean. The tree_map
reduction below is the reference implementation (and the mesh-sharded
path, where it lowers to one all-reduce); tests assert both paths are
equal.
"""
from __future__ import annotations

import dataclasses
import os
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

PyTree = Any


def default_use_kernel() -> bool:
    """Kernel fusion default: on when Pallas compiles for real (a TPU
    backend), off on any other backend, where the tree_map reference is
    faster than an interpreted kernel. Shares
    ``kernels.ops.pallas_interpret()``'s platform rule, so the fuse-path
    default and the kernels' interpret/compile switch can never disagree.
    ``REPRO_FUSION_KERNEL=1``/``0`` overrides the default."""
    if os.environ.get("REPRO_FUSION_KERNEL"):
        return os.environ["REPRO_FUSION_KERNEL"] == "1"
    from repro.kernels.ops import pallas_interpret
    return not pallas_interpret()


@dataclasses.dataclass(frozen=True)
class GroupAxis:
    """Group partitioning of one param leaf: ``axis`` is split into
    ``n_groups`` contiguous blocks; block g belongs to structure group g."""
    axis: int
    n_groups: int


def fedavg(stacked: PyTree, weights=None, *, use_kernel: bool = False,
           bm: int = 1024, robust=None) -> PyTree:
    """Coordinate-based averaging (Eq. 1). stacked leaves: (N, ...).

    use_kernel=True: stream every leaf through the Pallas
    ``paired_fusion_kernel`` (one fused weighted-mean pass per leaf).
    robust: a reducing RobustRule (fl/robust.py, DESIGN.md §14) replaces
    the weighted-mean reduction per leaf (the sort-based statistic has no
    kernel fast path, so use_kernel is ignored)."""
    if robust is not None:
        n = jax.tree_util.tree_leaves(stacked)[0].shape[0]
        w = _norm_weights(weights, n)
        return jax.tree_util.tree_map(lambda p: robust.reduce(p, w),
                                      stacked)
    if use_kernel:
        n = jax.tree_util.tree_leaves(stacked)[0].shape[0]
        return _kernel_fuse(stacked, None, _norm_weights(weights, n), bm=bm)
    if weights is None:
        return jax.tree_util.tree_map(lambda p: jnp.mean(p, axis=0), stacked)
    w = jnp.asarray(weights, jnp.float32)
    w = w / jnp.sum(w)

    def wavg(p):
        wb = w.reshape((-1,) + (1,) * (p.ndim - 1)).astype(p.dtype)
        return jnp.sum(p * wb, axis=0)

    return jax.tree_util.tree_map(wavg, stacked)


def _norm_weights(weights, n) -> jnp.ndarray:
    if weights is None:
        return jnp.full((n,), 1.0 / n, jnp.float32)
    w = jnp.asarray(weights, jnp.float32)
    return w / jnp.sum(w)


def _kernel_fuse(stacked: PyTree, group_axes, w_shared, gw_norm=None, *,
                 bm: int = 1024) -> PyTree:
    """Per-leaf streaming fusion through ``kernels/paired_fusion.py``.

    Each leaf (each group block, under presence weighting) is raveled to
    (N, m) and streamed through one kernel pass with its weight vector:
    shared leaves use the sample weights, grouped leaf block g uses
    gw_norm[:, g] ((N, G), column-normalized). No concatenated temp is
    materialized — every parameter is read exactly once, i.e. FedAvg cost
    regardless of G (the paper's efficiency claim).

    group_axes: pytree of GroupAxis | None matching ``stacked``, or None
    (all leaves shared)."""
    from repro.kernels import ops as kops
    leaves, treedef = jax.tree_util.tree_flatten(stacked)
    gas = ([None] * len(leaves) if group_axes is None
           else treedef.flatten_up_to(group_axes))
    out = []
    for leaf, ga in zip(leaves, gas):
        if not isinstance(ga, GroupAxis) or gw_norm is None:
            out.append(kops.paired_fusion(leaf, w_shared, bm=bm))
            continue
        ax, g = ga.axis + 1, ga.n_groups   # +1: node axis
        blk = leaf.shape[ax] // g
        shp = leaf.shape[:ax] + (g, blk) + leaf.shape[ax + 1:]
        xg = leaf.reshape(shp)
        blocks = [
            kops.paired_fusion(
                jax.lax.index_in_dim(xg, gi, axis=ax, keepdims=False),
                gw_norm[:, gi], bm=bm)
            for gi in range(g)
        ]
        out.append(jnp.stack(blocks, axis=ax - 1).reshape(leaf.shape[1:]))
    return jax.tree_util.tree_unflatten(treedef, out)


def broadcast_global(global_params: PyTree, n: int) -> PyTree:
    """Replicate fused global params back to N clients (round start)."""
    return jax.tree_util.tree_map(
        lambda p: jnp.broadcast_to(p[None], (n,) + p.shape), global_params)


def _permute_groups(leaf, ga: GroupAxis, perm):
    """Reorder group blocks of one node's leaf along ga.axis by ``perm``."""
    ax, g = ga.axis, ga.n_groups
    size = leaf.shape[ax]
    assert size % g == 0, (leaf.shape, ga)
    blk = size // g
    shp = leaf.shape[:ax] + (g, blk) + leaf.shape[ax + 1:]
    x = leaf.reshape(shp)
    x = jnp.take(x, perm, axis=ax)
    return x.reshape(leaf.shape)


def paired_average(stacked: PyTree, group_axes: PyTree, perms=None,
                   weights=None, group_weights=None, *,
                   use_kernel: bool = False, bm: int = 1024,
                   robust=None) -> PyTree:
    """Feature paired averaging (Eq. 19).

    group_axes: pytree matching ``stacked`` with ``GroupAxis`` or ``None``
    per leaf (None = shared layer -> plain FedAvg, Eq. 18).
    perms: optional (N, G) int array; ``perms[n, g]`` = node n's local group
    index holding canonical logit signature g. Identity (or None) under the
    structural pre-alignment.
    group_weights: optional (N, G) per-node-per-group fusion weights — the
    paper's "only the groups that have the paired learning class are
    averaged" under non-IID: a node whose local data lacks all of group g's
    classes never trained g, so its copy is down-/zero-weighted. Columns
    that are all-zero fall back to uniform (no holder -> plain mean).
    use_kernel: route the reduction through the Pallas per-leaf streaming
    fast path (pairing permutations are applied as a cheap gather first;
    identity under the structural pre-alignment). The tree_map path below
    stays the reference/fallback.
    robust: a reducing RobustRule (fl/robust.py, DESIGN.md §14) replaces
    every reduction; grouped leaves under presence weighting reduce PER
    GROUP COLUMN with that column's weights (the rule renormalizes the
    column internally, so trimmed mass renormalizes within each group —
    alignment preserved). No kernel fast path: use_kernel is ignored.
    """
    if robust is not None:
        use_kernel = False
    if perms is not None:
        perms = jnp.asarray(perms)
    gw = None
    if group_weights is not None:
        gw = jnp.asarray(group_weights, jnp.float32)
        col = jnp.sum(gw, axis=0, keepdims=True)
        gw = jnp.where(col > 0, gw, 1.0)
        gw = gw / jnp.sum(gw, axis=0, keepdims=True)  # (N, G)

    if use_kernel:
        if perms is not None:
            def align(leaf, ga):
                if ga is None:
                    return leaf
                return jax.vmap(
                    lambda one, p: _permute_groups(one, ga, p))(leaf, perms)
            stacked = jax.tree_util.tree_map(
                align, stacked, group_axes,
                is_leaf=lambda x: x is None or isinstance(x, GroupAxis))
        n = jax.tree_util.tree_leaves(stacked)[0].shape[0]
        return _kernel_fuse(stacked, group_axes, _norm_weights(weights, n),
                            gw, bm=bm)

    def fuse(leaf, ga):
        if ga is None or perms is None:
            stacked_leaf = leaf
        else:
            stacked_leaf = jax.vmap(
                lambda one, p: _permute_groups(one, ga, p))(leaf, perms)
        if ga is not None and gw is not None:
            ax, g = ga.axis + 1, ga.n_groups  # +1: node axis
            blk = stacked_leaf.shape[ax] // g
            shp = (stacked_leaf.shape[:ax] + (g, blk) +
                   stacked_leaf.shape[ax + 1:])
            xg = stacked_leaf.reshape(shp)
            if robust is not None:
                # per-group-column robust reduction: group gi fuses with
                # ITS presence column (already column-normalized above),
                # so the rule's internal renormalization stays within
                # the group — alignment preserved
                blocks = [
                    robust.reduce(
                        jax.lax.index_in_dim(xg, gi, axis=ax,
                                             keepdims=False),
                        gw[:, gi])
                    for gi in range(g)
                ]
                return jnp.stack(blocks, axis=ax - 1).reshape(
                    stacked_leaf.shape[1:])
            wshape = [1] * xg.ndim
            wshape[0], wshape[ax] = gw.shape[0], g
            wb = gw.reshape(wshape).astype(xg.dtype)
            return jnp.sum(xg * wb, axis=0).reshape(stacked_leaf.shape[1:])
        if robust is not None:
            n = stacked_leaf.shape[0]
            return robust.reduce(stacked_leaf, _norm_weights(weights, n))
        if weights is None:
            return jnp.mean(stacked_leaf, axis=0)
        w = jnp.asarray(weights, jnp.float32)
        w = (w / jnp.sum(w)).reshape((-1,) + (1,) * (leaf.ndim - 1))
        return jnp.sum(stacked_leaf * w.astype(leaf.dtype), axis=0)

    return jax.tree_util.tree_map(fuse, stacked, group_axes,
                                  is_leaf=lambda x: x is None or
                                  isinstance(x, GroupAxis))


def presence_group_weights(class_counts, spec) -> np.ndarray:
    """(N, C) per-node class sample counts -> (N, G) group fusion weights:
    node n's weight for group g = its sample count over g's classes."""
    counts = np.asarray(class_counts, np.float64)
    n = counts.shape[0]
    gw = np.zeros((n, spec.n_groups))
    for g in range(spec.n_groups):
        cls = list(spec.classes_per_group[g])
        gw[:, g] = counts[:, cls].sum(axis=1)
    return gw


def fedprox_penalty(params: PyTree, global_params: PyTree, mu: float):
    """(mu/2) * ||w - w_global||^2 — added to the local loss."""
    sq = jax.tree_util.tree_map(
        lambda p, g: jnp.sum(jnp.square(p.astype(jnp.float32) -
                                        g.astype(jnp.float32))),
        params, global_params)
    return 0.5 * mu * sum(jax.tree_util.tree_leaves(sq))


# ---------------------------------------------------------------------------
# Group-axis trees for our model families
# ---------------------------------------------------------------------------


def cnn_group_axes(params: PyTree, cfg) -> PyTree:
    """GroupAxis tree for models/cnn.py params."""
    from repro.models.cnn import layer_meta
    metas = layer_meta(cfg)
    conv_metas = [m for m in metas if m.kind in ("c", "dw")]
    fc_metas = [m for m in metas if m.kind in ("fc", "logits")]
    g = cfg.fed2_groups

    axes = {"convs": [], "fcs": []}
    for m, layer in zip(conv_metas, params["convs"]):
        la = {}
        grouped = g > 1 and m.groups > 1
        for k, v in layer.items():
            if not grouped:
                la[k] = jax.tree_util.tree_map(lambda _: None, v)
            elif k == "dw":  # depthwise: channel axis is last of w, b
                la[k] = {kk: GroupAxis(vv.ndim - 1, g)
                         for kk, vv in v.items()}
            elif k == "norm":
                la[k] = {kk: GroupAxis(0, g) for kk in v}
            else:  # conv w: (k,k,ci/g,co) -> out-channel axis; b: (co,)
                if isinstance(v, dict):
                    la[k] = {kk: GroupAxis(vv.ndim - 1, g)
                             for kk, vv in v.items()}
                else:
                    la[k] = GroupAxis(v.ndim - 1, g)
        # plain conv layer: params stored flat {"w","b",("norm")}
        axes["convs"].append(la)
    for m, fc in zip(fc_metas, params["fcs"]):
        if m.grouped_fc:
            axes["fcs"].append({k: GroupAxis(0, cfg.fed2_groups) for k in fc})
        else:
            axes["fcs"].append({k: None for k in fc})
    return axes


def lm_group_axes(params: PyTree, cfg) -> PyTree:
    """GroupAxis tree for transformer params: gblocks grouped_dense leaves
    and the block-diagonal unembedding carry leading-axis groups."""
    g = cfg.fed2_groups

    def shared(tree):
        return jax.tree_util.tree_map(lambda _: None, tree)

    axes = {k: shared(v) for k, v in params.items()
            if k not in ("gblocks", "unembed")}
    if cfg.family == "moe" and cfg.moe is not None:
        # experts are the structure groups: pair expert weights by
        # signature, the experts this chip holds (every client routes
        # through the same global router, so index i pairs with index i)
        e = cfg.moe.n_held

        def mark_moe(path, leaf):
            names = [str(p) for p in path]
            if any("ffn" in n for n in names) and \
                    any(n.endswith(k) for n in names
                        for k in ("w_gate']", "w_up']", "w_down']")) and \
                    "shared" not in "".join(names) and leaf.ndim == 4:
                return GroupAxis(1, e)  # stacked (L, E, d, f)
            return None

        axes["blocks"] = jax.tree_util.tree_map_with_path(
            mark_moe, params["blocks"])
    if "gblocks" in params:
        def mark(path, leaf):
            names = [str(p) for p in path]
            if any("ffn" in n for n in names) and leaf.ndim >= 3:
                # stacked (L, G, i, o) grouped_dense -> group axis 1
                return GroupAxis(1, g)
            return None
        axes["gblocks"] = jax.tree_util.tree_map_with_path(
            mark, params["gblocks"])
    if "unembed" in params:
        if g > 0 and params["unembed"]["w"].ndim == 3:
            axes["unembed"] = {k: GroupAxis(0, g)
                               for k in params["unembed"]}
        else:
            axes["unembed"] = shared(params["unembed"])
    return axes
