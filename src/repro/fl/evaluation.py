"""Sharded evaluation engine (DESIGN.md §10).

The seed runtime evaluated the global model with a host-side Python loop
over eval batches — one jit dispatch per batch, a host stack, and a mean
of per-batch accuracies. This module replaces that loop with ONE
jit-compiled program over the whole eval set:

    tiles <- stage(batches, tile=B)   # (T, B, ...) fixed-width batch
                                      # tiles + a (T, B) padding mask
    counts <- engine.run(params, tiles)   # device-resident

``stage`` concatenates the eval batches host-side, pads the tail tile
(mask 0) so every tile has identical width, and — given a mesh — places
the tile axis on the mesh "data" axis with the same placement machinery
as the round engine (fl/engine.py): tiles then evaluate data-parallel
and the count reduction lowers to one all-reduce. Padding semantics:
padded positions repeat sample 0 with weight 0, so they contribute to
FLOPs but never to counts; the tile count is additionally padded to a
multiple of the mesh "data" axis size.

The engine computes example-weighted counts, not per-batch means:

  - ``n_classes`` given: a (C, C) confusion-count matrix (rows = gold,
    cols = predicted), accuracy = trace/total, per-class and per-group
    accuracies fall out of the rows (``per_class_accuracy``,
    ``group_accuracy`` — group g via ``GroupSpec.logit_signature``).
  - ``n_classes=None`` (LM tasks, where classes = vocab): weighted
    (correct, total) sums only — no vocab^2 confusion is materialized —
    and, where ``predict_fn`` also returns each position's negative
    log-likelihood, its weighted sum as a third entry (``mean_loss``).

Everything stays device-resident until the caller materializes it — one
host sync per eval at most, none inside the FL round loop
(fl/runtime.py accumulates per-round count arrays and materializes after
the last round).

``host_loop_eval`` is the seed loop, kept as the verified reference:
tests/test_evaluation.py pins the engine against it (allclose on
accuracy, exact on confusion counts) and ``benchmarks/flbench.py
bench_eval`` measures the throughput win.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

PyTree = Any


# single-device tile-count threshold above which ``stage`` selects the
# per-tile host-dispatch path: the fused lax.map program WINS at a few
# wide tiles (1.6-1.7x at 8x512 in flbench_eval.json) but its sequential
# device loop LOSES at many small ones (0.70x at 32x128 — the ROADMAP
# eval gap), where per-dispatch overhead is cheaper than the loop's.
# Multi-device meshes ignore it (tiles evaluate in parallel there).
HOST_DISPATCH_TILES = 16


@dataclasses.dataclass(frozen=True)
class EvalTiles:
    """The staged eval set: every batch leaf stacked to (T, B, ...) plus
    the (T, B) padding mask. ``n_real`` is the true sample count (the
    mask's support). ``host_dispatch`` is the path selection made at
    staging time (single device, > HOST_DISPATCH_TILES tiles): the
    engine then dispatches one jitted per-tile program per tile and
    accumulates counts on device, instead of one fused lax.map program —
    identical counts (small-integer f32 sums are exact in any order),
    different dispatch economics."""
    batches: dict
    mask: jnp.ndarray
    n_real: int
    host_dispatch: bool = False

    @property
    def n_tiles(self) -> int:
        return int(self.mask.shape[0])

    @property
    def tile(self) -> int:
        return int(self.mask.shape[1])


def stage(batches: list, *, tile: int, mesh=None) -> EvalTiles:
    """Stack a list of batch dicts into fixed-width eval tiles.

    batches: list of dicts of per-example arrays (leading axis = example).
    tile: tile width B (``FLConfig.eval_batch``). The concatenated set is
    padded to a multiple of B — and, under a mesh, the tile count to a
    multiple of the "data" axis size — by repeating sample 0 at mask 0.
    """
    if not batches:
        raise ValueError("stage() needs at least one eval batch")
    cat = {k: np.concatenate([np.asarray(b[k]) for b in batches])
           for k in batches[0]}
    n_real = len(next(iter(cat.values())))
    n_tiles = -(-n_real // tile)
    if mesh is not None:
        dsize = (mesh.shape["data"] if "data" in mesh.axis_names else 1)
        n_tiles = -(-n_tiles // dsize) * dsize
    total = n_tiles * tile
    mask = np.zeros((total,), np.float32)
    mask[:n_real] = 1.0
    pad = total - n_real

    def to_tiles(x):
        if pad:
            x = np.concatenate([x, np.broadcast_to(x[:1],
                                                   (pad,) + x.shape[1:])])
        return x.reshape((n_tiles, tile) + x.shape[1:])

    tiles = {k: to_tiles(v) for k, v in cat.items()}
    mask = mask.reshape(n_tiles, tile)
    if mesh is not None:
        dsize = (mesh.shape["data"] if "data" in mesh.axis_names else 1)
        shard = lambda a: jax.device_put(  # noqa: E731
            a, NamedSharding(mesh, P("data", *([None] * (a.ndim - 1)))))
    else:
        dsize = 1
        shard = jnp.asarray
    host_dispatch = dsize == 1 and n_tiles > HOST_DISPATCH_TILES
    return EvalTiles(batches={k: shard(v) for k, v in tiles.items()},
                     mask=shard(mask), n_real=n_real,
                     host_dispatch=host_dispatch)


@dataclasses.dataclass(frozen=True)
class EvalEngine:
    """One jitted evaluation over staged tiles.

    ``run(params, tiles)`` returns device arrays (no host sync):
      confusion mode: (C, C) float32 confusion counts;
      counts mode:    (correct, total) float32 scalars.
    """
    run: Callable
    n_classes: int | None
    mesh: Any = None


def make_eval_engine(predict_fn: Callable, n_classes: int | None = None, *,
                     mesh=None) -> EvalEngine:
    """Build the engine for one task.

    predict_fn(params, batch) -> (pred, gold, weight[, nll]): per-position
    predictions, gold labels, and example weights — (B,) for classifiers,
    (B, L) for LMs (weight = the batch's own mask) — and, optionally in
    counts mode, each position's negative log-likelihood of its gold
    label. The staging pad mask multiplies into ``weight``, broadcasting
    over trailing axes.
    """

    def one_tile(params, batch, m):
        pred, gold, w, *nll = predict_fn(params, batch)
        w = (w.astype(jnp.float32) *
             m.reshape(m.shape + (1,) * (w.ndim - 1)))
        pred, gold, w = pred.ravel(), gold.ravel(), w.ravel()
        if n_classes is None:
            correct = jnp.sum((pred == gold) * w)
            sums = [correct, jnp.sum(w)]
            if nll:
                sums.append(jnp.sum(nll[0].astype(jnp.float32).ravel() * w))
            return jnp.stack(sums)
        # confusion as a one-hot contraction (C, B) @ (B, C): XLA lowers
        # this to one small matmul — measurably faster than a (B,)-long
        # scatter-add into the (C, C) matrix
        oh_gold = jax.nn.one_hot(gold, n_classes, dtype=jnp.float32) * \
            w[:, None]
        oh_pred = jax.nn.one_hot(pred, n_classes, dtype=jnp.float32)
        return oh_gold.T @ oh_pred

    data_size = 1 if mesh is None else int(mesh.shape.get("data", 1))

    def counts(params, batches, mask):
        if data_size > 1:
            # tile axis on "data": tiles evaluate device-parallel and the
            # count sum lowers to one all-reduce
            cons = lambda t: jax.lax.with_sharding_constraint(  # noqa: E731
                t, jax.tree_util.tree_map(
                    lambda l: NamedSharding(
                        mesh, P("data", *([None] * (l.ndim - 1)))), t))
            batches, mask = cons(batches), cons(mask)
            per_tile = jax.vmap(one_tile, in_axes=(None, 0, 0))(
                params, batches, mask)
        else:
            # one device (mesh-less or a 1-device mesh): sequential tiles
            # INSIDE one dispatch (lax.map) — per-tile activations stay
            # cache-sized like the seed loop and memory is bounded by one
            # tile, but the per-batch Python dispatch overhead is gone.
            # vmapping all tiles onto a single device would materialize
            # the whole eval set's activations at once.
            per_tile = jax.lax.map(
                lambda bm: one_tile(params, bm[0], bm[1]),
                (batches, mask))
        return jnp.sum(per_tile, axis=0)

    counts = jax.jit(counts)

    # host-dispatch path (stage() selects it at single-device + many
    # tiles): one jitted per-tile dispatch each, counts accumulated ON
    # DEVICE — the result is still a device array and the sums are
    # bit-identical to the fused path's (confusion/count entries are
    # small non-negative integers in float32, exact under any addition
    # order). Trades the lax.map sequential loop's overhead for cheap
    # per-dispatch overhead, which wins once tiles are many and small
    # (the ROADMAP "0.70x at eval_batch=128" gap).
    one_tile_jit = jax.jit(one_tile)
    accum = jax.jit(lambda a, b: a + b)

    def run(params, tiles: EvalTiles):
        if not (tiles.host_dispatch and data_size == 1):
            return counts(params, tiles.batches, tiles.mask)
        acc = None
        for t in range(tiles.n_tiles):
            batch = {k: v[t] for k, v in tiles.batches.items()}
            c = one_tile_jit(params, batch, tiles.mask[t])
            acc = c if acc is None else accum(acc, c)
        return acc

    return EvalEngine(run=run, n_classes=n_classes, mesh=mesh)


# ---------------------------------------------------------------------------
# Reading the counts (host-side, after materialization)
# ---------------------------------------------------------------------------


def accuracy(counts) -> float:
    """Global accuracy from an engine result (either mode)."""
    c = np.asarray(counts)
    if c.ndim == 1:              # (correct, total)
        return float(c[0] / max(c[1], 1.0))
    return float(np.trace(c) / max(c.sum(), 1.0))


def mean_loss(counts) -> float | None:
    """The weighted mean negative log-likelihood from a counts-mode
    result that carries one (``(correct, total, nll)``), else None."""
    c = np.asarray(counts)
    if c.ndim != 1 or c.shape[0] < 3:
        return None
    return float(c[2] / max(c[1], 1.0))


def per_class_accuracy(confusion) -> np.ndarray:
    """(C,) per-class accuracy: diag / row sum (classes with no eval
    samples report 0)."""
    c = np.asarray(confusion, np.float64)
    row = c.sum(axis=1)
    return np.where(row > 0, np.diag(c) / np.maximum(row, 1.0), 0.0)


def group_accuracy(confusion, spec) -> np.ndarray:
    """(G,) per-group accuracy under a core/grouping.py GroupSpec: group
    g's accuracy over the eval samples whose gold label is in g's logit
    signature (Eq. 19's pairing key)."""
    c = np.asarray(confusion, np.float64)
    out = np.zeros(spec.n_groups)
    for g in range(spec.n_groups):
        cls = sorted(spec.logit_signature(g))
        row = c[cls].sum()
        out[g] = c[cls, cls].sum() / row if row > 0 else 0.0
    return out


# ---------------------------------------------------------------------------
# The seed host loop — the verified reference
# ---------------------------------------------------------------------------


def host_loop_eval(eval_fn: Callable, params: PyTree, batches: list):
    """The pre-engine evaluation (fl/runtime.py seed): one jit dispatch
    per eval batch, mean of per-batch accuracies. Equals the engine's
    pooled accuracy when all batches have equal width; kept as the
    reference the engine is pinned against."""
    return jnp.mean(jnp.stack([eval_fn(params, tb) for tb in batches]))
