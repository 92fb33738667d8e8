"""Heterogeneous-capacity federation: feature-aligned sub-model tiers
(DESIGN.md §11).

Fed2's structure adaptation allocates features to explicit structure
groups (DESIGN.md §3); this module exploits that allocation to let
clients of different hardware capacity train different-WIDTH sub-models
of one global net — the width-scaled-client regime of *Heterogeneous
Federated Learning* (Yu et al., PAPERS.md) made principled by feature
alignment:

- A ``CapacityTier`` is a width fraction w ∈ (0, 1]. Every logical
  client is assigned a tier (``TierPlan.assignment``, carried by
  ``Population.tiers``).
- **Sub-model extraction** slices the global parameter tree per tier:
  shared (shallow) leaves by contiguous channel PREFIX, decoupled
  (grouped) leaves by WHOLE feature groups — a tier keeps the first
  K = w·G structure groups and never splits one, so every surviving
  group's ``GroupSpec.logit_signature`` pairing (Eq. 19) stays exact.
  Tier widths for grouped nets must therefore satisfy w·G ∈ ℕ.
- **One compiled tile per tier**: each tier gets its own fixed-shape
  ``RoundEngine`` (PR 3's ``run_tile`` machinery) at the tier's slot
  width; a round runs every tier's tile and combines them.
- **Overlap-aware fusion**: per-leaf coverage counts renormalize the
  weighted sum, so a parameter region is averaged only over the clients
  whose tier holds it; regions no sampled client holds keep the previous
  global value. Presence-weighted fed2 composes: a grouped leaf's
  coverage is tracked per group column (a tier simply has zero presence
  for the groups it dropped).

The nesting is strictly prefix-shaped (tier w ⊂ tier w' for w < w'), so
coverage per group g is the weight mass of the clients whose tier keeps
≥ g+1 groups. A width-1.0 single-tier plan is DEGENERATE: the runtime
routes it through the homogeneous engine unchanged (bit-identical for
every registered method — ``tests/test_capacity.py``).

Only methods whose fuse is affine in the weighted client mean support
tiers (``FedMethod.tier_fusion`` — the same eligibility as cohort
tiling, minus per-client state): fedavg, fedprox, fed2, fednova,
fedavgm, fedadam. scaffold (stateful server step) and fedma (host
matching is not defined across widths) refuse with a clear error.

Uplink economics: a width-w tier's sub-model scales both in- and
out-channels, so its per-round uplink is ≈ w² of the dense bytes — a
0.25-width tier uplinks ~1/16 (``benchmarks/flbench.py bench_tiers``).
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable

import jax
import jax.numpy as jnp
import numpy as np

PyTree = Any


# ---------------------------------------------------------------------------
# Tier spec & per-client assignment
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class CapacityTier:
    """One capacity class: a width fraction of the global model."""
    width: float

    @property
    def name(self) -> str:
        return f"w{round(self.width * 100):03d}"


def parse_tiers(spec) -> tuple:
    """Normalize a tier-mix spec to ``((width, count), ...)``.

    Accepts the CLI string form ``"1.0x2,0.5x2,0.25x2"`` (width x client
    count per tier) or an already-structured sequence of pairs. The
    result is sorted by descending width."""
    if isinstance(spec, str):
        mix = []
        for part in spec.split(","):
            part = part.strip()
            if not part:
                continue
            try:
                w, c = part.split("x")
                mix.append((float(w), int(c)))
            except ValueError:
                raise ValueError(
                    f"bad tier spec {part!r}; expected <width>x<count>, "
                    "e.g. 1.0x2,0.5x2,0.25x2") from None
    else:
        mix = [(float(w), int(c)) for w, c in spec]
    mix.sort(key=lambda wc: -wc[0])
    return tuple(mix)


def validate_mix(mix, population: int) -> None:
    """The structural checks FLConfig applies at construction."""
    if not mix:
        raise ValueError("tier mix must name at least one tier")
    widths = [w for w, _ in mix]
    if len(set(widths)) != len(widths):
        raise ValueError(f"duplicate tier widths in {mix}")
    for w, c in mix:
        if not (0.0 < w <= 1.0):
            raise ValueError(f"tier width {w} outside (0, 1]")
        if not isinstance(c, int) or c <= 0:
            raise ValueError(f"tier count {c!r} must be a positive int")
    if max(widths) != 1.0:
        raise ValueError(
            "a tier mix needs a width-1.0 tier: the fused global model is "
            f"full-width, and without full-width clients its deepest "
            f"channels would never train (got widths {widths})")
    total = sum(c for _, c in mix)
    if total != population:
        raise ValueError(
            f"tier counts sum to {total} but population is {population}; "
            "every logical client needs exactly one tier")


# THE eligibility check for tiered fusion now lives in fl/compat.py —
# the unified capability matrix (DESIGN.md §16); re-exported here so
# historical call sites keep working.
from repro.fl.compat import check_tier_support  # noqa: E402,F401


@dataclasses.dataclass(frozen=True)
class TierPlan:
    """A validated mix plus the per-client tier assignment.

    mix: ``((width, count), ...)`` descending by width.
    assignment: (population,) int array — client i trains tier
    ``assignment[i]`` (an index into ``mix``). The assignment is a
    seed-deterministic permutation so tier membership does not correlate
    with the data partition's client-id structure."""
    mix: tuple
    assignment: np.ndarray

    @classmethod
    def from_mix(cls, mix, population: int, *, seed: int = 0) -> "TierPlan":
        mix = parse_tiers(mix)
        validate_mix(mix, population)
        rng = np.random.default_rng(seed + 7331)   # its own stream: the
        # run's batch/sampler rng (cfg.seed) must stay untouched so the
        # trivial plan stays bit-identical to the homogeneous engine
        perm = rng.permutation(population)
        assignment = np.empty(population, np.int32)
        pos = 0
        for t, (_, count) in enumerate(mix):
            assignment[perm[pos:pos + count]] = t
            pos += count
        return cls(mix=mix, assignment=assignment)

    @property
    def tiers(self) -> tuple:
        return tuple(CapacityTier(w) for w, _ in self.mix)

    @property
    def trivial(self) -> bool:
        """Single tier at full width — semantically the homogeneous
        engine; the runtime routes it there (bit-identical)."""
        return len(self.mix) == 1 and self.mix[0][0] == 1.0

    def ids_of(self, tier_idx: int, ids=None) -> np.ndarray:
        """The client ids assigned to tier ``tier_idx`` (restricted to
        ``ids``, order-preserving, when given)."""
        if ids is None:
            return np.nonzero(self.assignment == tier_idx)[0]
        ids = np.asarray(ids)
        return ids[self.assignment[ids] == tier_idx]


# ---------------------------------------------------------------------------
# Sub-model extraction: per-leaf slice maps
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class LeafSlice:
    """How one tier leaf embeds into its full-model leaf.

    idx: per-FULL-axis int index vectors (``np.ix_`` open mesh) — full
    axes carry an arange, sliced axes the kept indices. Contiguous
    prefixes everywhere except the conv→fc flatten boundary of
    non-grouped nets, where kept rows interleave (row % C < C_tier).
    shape: the tier leaf's shape. It differs from the sliced shape only
    for a grouped-dense leaf whose tier keeps K=1 groups — the tier
    layer is then a plain dense and the group axis squeezes away.
    group_axis/block/kept: full-leaf group geometry when the leaf is
    group-sliced (kept WHOLE groups — the invariant tests pin).
    tier_grouped: the TIER's engine fuses this leaf per group (i.e. the
    tier keeps >1 group), so presence-weighted coverage is per column.
    """
    idx: tuple
    shape: tuple
    group_axis: int | None = None
    block: int = 0
    kept: int = 0
    tier_grouped: bool = False

    @property
    def sliced_shape(self) -> tuple:
        return tuple(len(i) for i in self.idx)

    def extract(self, leaf):
        return leaf[np.ix_(*self.idx)].reshape(self.shape)


def extract_params(global_params: PyTree, slices: PyTree) -> PyTree:
    """Slice a full parameter tree down to one tier's sub-model."""
    return jax.tree_util.tree_map(
        lambda s, l: s.extract(l), slices, global_params,
        is_leaf=lambda x: isinstance(x, LeafSlice))


def _tier_leaf_slice(fshape, tshape, ga, kept: int) -> LeafSlice:
    """The generic shape-driven rule: equal dims stay whole, narrowed
    dims keep a contiguous prefix. Group geometry is annotated from the
    full model's GroupAxis tree."""
    from repro.core.fusion import GroupAxis
    grouped = isinstance(ga, GroupAxis)
    if len(tshape) == len(fshape) - 1 and grouped and kept == 1:
        # grouped-dense at K=1: the tier layer is plain dense; keep
        # group 0's block and squeeze the group axis
        idx = (np.arange(1),) + tuple(
            np.arange(t) for t in tshape)
        for fa, ta in zip(fshape[1:], tshape):
            assert ta <= fa, (fshape, tshape)
        return LeafSlice(idx=idx, shape=tuple(tshape),
                         group_axis=0, block=1, kept=1,
                         tier_grouped=False)
    assert len(tshape) == len(fshape), (fshape, tshape)
    idx = tuple(np.arange(t) for t in tshape)
    for fa, ta in zip(fshape, tshape):
        assert ta <= fa, (fshape, tshape)
    if not grouped:
        return LeafSlice(idx=idx, shape=tuple(tshape))
    block = fshape[ga.axis] // ga.n_groups
    assert tshape[ga.axis] % block == 0, (fshape, tshape, ga)
    return LeafSlice(idx=idx, shape=tuple(tshape), group_axis=ga.axis,
                     block=block, kept=tshape[ga.axis] // block,
                     tier_grouped=kept > 1)


def cnn_tier_config(cfg, width: float):
    """The width-w sub-model's CNNConfig.

    Grouped nets (``fed2_groups = G > 0``): w·G must be an integer K —
    the tier keeps the first K whole structure groups, every channel
    count scales by exactly K/G, and the logit layer keeps the first K
    class clusters (``n_classes`` becomes K·(n_classes/G); contiguous
    GroupSpec makes those classes 0..K·per-1). Plain nets: channel
    counts round to ``max(1, round(w·c))`` and the classifier head keeps
    ALL classes (only hidden widths shrink)."""
    import dataclasses as dc

    g = cfg.fed2_groups
    if not (0.0 < width <= 1.0):
        raise ValueError(f"tier width {width} outside (0, 1]")
    if g:
        k = width * g
        kept = int(round(k))
        if abs(k - kept) > 1e-9 or kept < 1:
            raise ValueError(
                f"tier width {width} does not keep whole feature groups "
                f"at fed2_groups={g} (width*G = {k:g}); group-whole "
                "slicing needs width in " +
                "{" + ", ".join(f"{i}/{g}" for i in range(1, g + 1)) + "}")
        if cfg.n_classes % g:
            raise ValueError(
                f"capacity tiers need fed2_groups ({g}) to divide "
                f"n_classes ({cfg.n_classes}) so dropped groups drop "
                "whole class clusters")
        scale = lambda c: (cfg.round_ch(c) * kept) // g        # noqa: E731
        n_classes = (cfg.n_classes * kept) // g
        groups = kept
    else:
        scale = lambda c: max(1, int(round(c * width)))        # noqa: E731
        n_classes = cfg.n_classes
        groups = 0
    if width == 1.0:
        return cfg
    plan = tuple(
        s if s[0] == "p" else (s[0], scale(s[1])) + tuple(s[2:])
        for s in cfg.plan)
    return dc.replace(cfg, arch_id=f"{cfg.arch_id}-w{round(width*100):03d}",
                      plan=plan, fc_dims=tuple(scale(d) for d in cfg.fc_dims),
                      n_classes=n_classes, fed2_groups=groups)


@dataclasses.dataclass
class TierModel:
    """One tier's runnable sub-model: its task (tier-shaped init/loss),
    the per-leaf slice tree into the full model, and sizing."""
    tier: CapacityTier
    model_cfg: Any
    task: Any                 # FLTask over the tier sub-model
    slices: PyTree            # LeafSlice tree, full-model structure
    param_bytes: int          # per-client uplink per round
    n_classes_kept: int


def cnn_tier_model(model_cfg, width: float) -> TierModel:
    """Build the width-w sub-model of a CNN: config, slice tree, and an
    FLTask whose loss masks examples of dropped class clusters (a
    grouped tier that kept K of G groups only emits the first K
    clusters' logits)."""
    from repro.core import fusion as fusion_lib
    from repro.fl import runtime as runtime_lib
    from repro.models.cnn import apply_cnn, init_cnn, layer_meta

    tier_cfg = cnn_tier_config(model_cfg, width)
    key = jax.random.PRNGKey(0)
    fshapes = jax.eval_shape(lambda k: init_cnn(k, model_cfg), key)
    tshapes = jax.eval_shape(lambda k: init_cnn(k, tier_cfg), key)
    ga_tree = fusion_lib.cnn_group_axes(fshapes, model_cfg)
    kept = tier_cfg.fed2_groups if model_cfg.fed2_groups else 0

    def leaf_pairs(gtree, ftree, ttree):
        # the group-axis tree leads: its None leaves are pytree nodes in
        # the shape trees, so it must define the mapped structure
        return jax.tree_util.tree_map(
            lambda g, f, t: _tier_leaf_slice(f.shape, t.shape, g, kept),
            gtree, ftree, ttree,
            is_leaf=lambda x: x is None or not isinstance(
                x, (dict, list, tuple)))

    # grouped-dense-at-K=1 leaves drop an axis, which breaks plain
    # tree_map (structures differ); walk the fcs list layer by layer
    slices = {"convs": leaf_pairs(ga_tree["convs"], fshapes["convs"],
                                  tshapes["convs"])}
    fcs = []
    for flayer, tlayer, glayer in zip(fshapes["fcs"], tshapes["fcs"],
                                      ga_tree["fcs"]):
        fcs.append({k: _tier_leaf_slice(flayer[k].shape, tlayer[k].shape,
                                        glayer[k], kept)
                    for k in flayer})
    slices["fcs"] = fcs

    # conv→fc flatten boundary of NON-grouped nets: reshape(b, -1)
    # flattens (h, w, c) channels-fastest, so the kept input rows of the
    # first fc interleave — row r survives iff (r % C_full) < C_tier.
    # (Grouped nets flatten group-major, which makes the kept rows a
    # contiguous prefix; mobilenet mean-pools, so rows ARE channels.)
    fmetas = layer_meta(model_cfg)
    fc_metas = [m for m in fmetas if m.kind in ("fc", "logits")]
    if (not model_cfg.fed2_groups and not model_cfg.is_mobilenet
            and fc_metas):
        conv_metas = [m for m in fmetas if m.kind in ("c", "dw")]
        tmetas = layer_meta(tier_cfg)
        t_conv = [m for m in tmetas if m.kind in ("c", "dw")]
        c_full, c_tier = conv_metas[-1].c_out, t_conv[-1].c_out
        if c_tier < c_full:
            d_in = fc_metas[0].c_in
            rows = np.nonzero((np.arange(d_in) % c_full) < c_tier)[0]
            s0 = slices["fcs"][0]["w"]
            slices["fcs"][0]["w"] = dataclasses.replace(
                s0, idx=(rows,) + s0.idx[1:])

    # sanity: every slice reproduces the tier leaf's exact shape
    t_leaves = jax.tree_util.tree_leaves(tshapes)
    s_leaves = jax.tree_util.tree_leaves(
        slices, is_leaf=lambda x: isinstance(x, LeafSlice))
    assert len(t_leaves) == len(s_leaves), (len(t_leaves), len(s_leaves))
    for t, s in zip(t_leaves, s_leaves):
        assert s.shape == t.shape, (t.shape, s.shape)

    task = runtime_lib.cnn_task(tier_cfg)
    if model_cfg.fed2_groups and tier_cfg.n_classes < model_cfg.n_classes:
        ncls = tier_cfg.n_classes

        def masked_loss(p, b):
            logits = apply_cnn(p, tier_cfg, b["images"])
            logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
            mask = (b["labels"] < ncls).astype(jnp.float32)
            lab = jnp.minimum(b["labels"], ncls - 1)
            gold = jnp.take_along_axis(logp, lab[:, None], axis=-1)[:, 0]
            return -jnp.sum(mask * gold) / jnp.maximum(jnp.sum(mask), 1.0)

        task.loss_fn = masked_loss
    task.tier_fn = None          # no tiers-of-tiers
    pbytes = sum(int(np.prod(l.shape)) * l.dtype.itemsize
                 for l in jax.tree_util.tree_leaves(tshapes))
    return TierModel(tier=CapacityTier(width), model_cfg=tier_cfg,
                     task=task, slices=slices, param_bytes=pbytes,
                     n_classes_kept=(tier_cfg.n_classes
                                     if model_cfg.fed2_groups
                                     else model_cfg.n_classes))


# ---------------------------------------------------------------------------
# The tiered engine: one compiled tile per tier + overlap-aware combine
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class TierTile:
    tier: CapacityTier
    model: TierModel
    width: int                # fixed slot count of this tier's tile
    engine: Any               # RoundEngine at cohort_size=width
    extract_fn: Callable      # global tree -> tier tree (jitted)
    zeros: PyTree             # tier-shaped zero tree (absent-tier filler)


@dataclasses.dataclass
class TieredEngine:
    """Per-tier fixed-shape tiles over one full-width server.

    A tiered round (``run_tiered_round``) runs every tier's
    ``run_tile`` (local phase + within-tier fuse at the tier's shapes),
    then ``combine_fn`` embeds the tier means into full shape with
    per-leaf coverage renormalization, and ``full.finish_round``
    applies the method's server step once."""
    plan: TierPlan
    tiles: list
    full: Any                 # full-width RoundEngine (server/eval/init)
    method: Any
    combine_fn: Callable
    use_gw: bool              # presence-weighted grouped coverage

    def init_server_state(self, global_params):
        return self.full.init_server_state(global_params)

    def init_population_state(self, global_params, population):
        return self.full.init_population_state(global_params, population)

    @property
    def eval_fn(self):
        return self.full.eval_fn


def make_tiered_engine(task, cfg, params_like, plan: TierPlan, *,
                       mesh=None, use_kernel=None, method=None,
                       use_gw: bool = False) -> TieredEngine:
    """Build per-tier tile engines + the overlap-aware combine.

    task must carry ``tier_fn`` (the model family's sub-model builder —
    ``cnn_task`` wires ``capacity.cnn_tier_model``)."""
    import dataclasses as dc

    from repro.fl import methods as methods_lib
    from repro.fl.engine import make_round_engine

    meth = method if method is not None else methods_lib.get(cfg.method)
    check_tier_support(meth)
    if task.tier_fn is None:
        raise ValueError(
            "this task has no tier_fn: capacity tiers are defined for "
            "model families with a sub-model builder (cnn_task)")

    base_cfg = dc.replace(cfg, tiers=None)
    full = make_round_engine(task, base_cfg, params_like, mesh=mesh,
                             use_kernel=use_kernel, method=meth)
    tiles = []
    for t, (width, count) in enumerate(plan.mix):
        model = task.tier_fn(width)
        # one fixed-shape tile per tier, sized by the tier's client
        # count: every sampler fits (full participation sends exactly
        # count ids per tier; cohort-sized samplers send fewer, padded
        # at zero weight)
        slots = count
        tier_cfg = dc.replace(base_cfg, cohort_size=slots)
        tshapes = jax.eval_shape(model.task.init_fn, jax.random.PRNGKey(0))
        engine = make_round_engine(model.task, tier_cfg, tshapes,
                                   mesh=mesh, use_kernel=use_kernel,
                                   method=meth)
        slices = model.slices
        if width == 1.0:          # identity slices: skip the gather
            extract_fn = lambda gp: gp                     # noqa: E731
        else:
            extract_fn = jax.jit(
                lambda gp, s=slices: extract_params(gp, s))
        zeros = jax.tree_util.tree_map(
            lambda l: jnp.zeros(l.shape, l.dtype), tshapes)
        tiles.append(TierTile(tier=CapacityTier(width), model=model,
                              width=slots, engine=engine,
                              extract_fn=extract_fn, zeros=zeros))

    treedef = jax.tree_util.tree_structure(params_like)
    flat_slices = [treedef.flatten_up_to(tl.model.slices) for tl in tiles]

    def combine(global_params, means, weight_masses, group_masses):
        """means[t]: tier t's within-tile weighted mean (tier shapes);
        weight_masses[t]: Σ of tier t's participant weights (scalar);
        group_masses[t]: Σ of its (slots, K_t) presence columns, or a
        zero vector when presence weighting is off. Returns the fused
        full tree: acc/coverage where covered, the previous global
        value elsewhere."""
        gl = treedef.flatten_up_to(global_params)
        acc = [jnp.zeros(l.shape, jnp.float32) for l in gl]
        cov = [jnp.zeros(l.shape, jnp.float32) for l in gl]
        for t in range(len(tiles)):
            ml = treedef.flatten_up_to(means[t])
            w_t = weight_masses[t]
            for j, (m, s) in enumerate(zip(ml, flat_slices[t])):
                x = m.reshape(s.sliced_shape).astype(jnp.float32)
                if use_gw and s.tier_grouped:
                    # per-group coverage: column g's mass, repeated over
                    # its block along the group axis
                    mass = jnp.repeat(group_masses[t][:s.kept], s.block)
                    bshape = [1] * len(s.sliced_shape)
                    bshape[s.group_axis] = s.kept * s.block
                    scale = mass.reshape(bshape)
                else:
                    scale = w_t
                if s.sliced_shape == gl[j].shape:   # identity (w=1.0
                    # tier): plain adds, no gather/scatter on the hot path
                    acc[j] = acc[j] + x * scale
                    cov[j] = cov[j] + jnp.broadcast_to(scale,
                                                       s.sliced_shape)
                    continue
                ix = np.ix_(*s.idx)
                acc[j] = acc[j].at[ix].add(x * scale)
                cov[j] = cov[j].at[ix].add(
                    jnp.broadcast_to(scale, s.sliced_shape))
        fused = [
            jnp.where(c > 0, a / jnp.where(c > 0, c, 1.0),
                      g.astype(jnp.float32)).astype(g.dtype)
            for a, c, g in zip(acc, cov, gl)]
        return jax.tree_util.tree_unflatten(treedef, fused)

    return TieredEngine(plan=plan, tiles=tiles, full=full, method=meth,
                        combine_fn=jax.jit(combine), use_gw=use_gw)


def run_tiered_round(tiered: TieredEngine, pop, method, server_state,
                     global_params, ids, get_batch, n_steps, cfg, rng,
                     uniform_weights: bool = False):
    """One heterogeneous round: every tier's tile (local phase +
    within-tier fuse over its sampled clients, zero-weight padded to the
    tile width), the overlap-aware combine, one server step. Returns
    (server_state, new_global); mirrors ``runtime.run_sampled_round``."""
    from repro.fl.runtime import pad_tile_inputs

    ids = np.asarray(ids, np.int64)
    # Population.tiers carries the per-client tier ids (runtime assigns
    # it from the plan) and is the routing source of truth; fall back to
    # the plan for direct engine drives that skipped the population
    assignment = (pop.tiers if pop.tiers is not None
                  else tiered.plan.assignment)
    means, w_masses, g_masses = [], [], []
    for t, tile in enumerate(tiered.tiles):
        tids = ids[assignment[ids] == t]
        kept = tile.model.model_cfg.fed2_groups or 1
        if len(tids) == 0:
            means.append(tile.zeros)
            w_masses.append(jnp.float32(0.0))
            g_masses.append(jnp.zeros((kept,), jnp.float32))
            continue
        _, w, gw, batches = pad_tile_inputs(
            pop, tids, tile.width, get_batch, n_steps, cfg.batch_size,
            rng, uniform_weights=uniform_weights, gw_cols=kept)
        with jax.profiler.TraceAnnotation("fl.dispatch"):
            tier_global = tile.extract_fn(global_params)
            _, fuse_out = tile.engine.run_tile(
                (), server_state, tier_global, batches, weights=w,
                group_weights=gw if tiered.use_gw else None)
        means.append(fuse_out)
        w_masses.append(jnp.float32(w.sum()))
        g_masses.append(jnp.asarray(
            gw.sum(axis=0) if (tiered.use_gw and gw is not None)
            else np.zeros(kept), jnp.float32))
    with jax.profiler.TraceAnnotation("fl.dispatch"):
        fused = tiered.combine_fn(global_params, tuple(means),
                                  tuple(w_masses), tuple(g_masses))
        return tiered.full.finish_round(server_state, global_params, fused)


# ---------------------------------------------------------------------------
# Dry-run lowering of one tier tile (launch/fl_dryrun.py)
# ---------------------------------------------------------------------------


def lower_tier_tile(task, cfg, mesh, batch_elems: dict, *, width: float,
                    local_steps: int, use_kernel: bool | None = None):
    """Lower one tier's tile (local phase + within-tier fuse) on ``mesh``
    from ShapeDtypeStructs — the per-tier analog of
    ``engine.lower_round``. Returns (Lowered, TierModel)."""
    import dataclasses as dc

    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.fl.engine import _client_sharding, make_round_engine

    cfg = dc.replace(cfg, tiers=None, local_epochs=1,
                     steps_per_epoch=local_steps)
    model = task.tier_fn(width)
    n = cfg.cohort_size
    tshapes = jax.eval_shape(model.task.init_fn, jax.random.PRNGKey(0))
    engine = make_round_engine(model.task, cfg, tshapes, mesh=mesh,
                               use_kernel=use_kernel)

    def spec(l, sharding):
        return jax.ShapeDtypeStruct(l.shape, l.dtype, sharding=sharding)

    gspecs = jax.tree_util.tree_map(
        lambda l: spec(l, NamedSharding(mesh, P())), tshapes)
    bspecs = {
        name: jax.ShapeDtypeStruct(
            (n, local_steps) + tuple(shape), dtype,
            sharding=_client_sharding(mesh, 2 + len(shape)))
        for name, (shape, dtype) in batch_elems.items()
    }
    wspec = jax.ShapeDtypeStruct((n,), jnp.float32,
                                 sharding=NamedSharding(mesh, P()))
    with mesh:
        return engine.tile_fn.lower((), (), gspecs, bspecs, wspec,
                                    None, None), model
