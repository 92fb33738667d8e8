"""Federated learning runtime.

Thin host loop over the sharded round engine (fl/engine.py), with the
POPULATION decoupled from the engine width (DESIGN.md §9): a run has
``cfg.population`` logical clients (fl/population.py — shard indices,
sample weights, persistent per-client method state), of which a sampled
cohort of ``cfg.cohort_size`` slots trains each round. The per-round
flow:

    ids   <- sampler.sample(round, population, cohort_size)
    state <- population.gather(ids)            # rows -> cohort slots
    state, global <- engine.run_round(state, global, batches, w[ids])
    population.scatter(ids, state)             # slots -> rows

Clients in a cohort execute SIMULTANEOUSLY as a vmapped batch over
stacked params, and one jitted function runs the whole round —
broadcast, local SGD, fusion, server step (DESIGN.md §5). Pass ``mesh=``
to shard the cohort axis over the mesh "data" axis; leave it None for
single-host vmap. When a round's participant set exceeds the cohort
width (``sampler="full"`` with population > cohort_size), the round runs
as multiple engine tiles whose fusion contributions accumulate in a
running weighted sum — unbiased, because each tile's fuse is a weighted
mean renormalized over its participants (§9).

Methods come from the fl/methods.py registry (DESIGN.md §6) — see
``methods.available()`` for the full set; samplers from the
fl/population.py registry — see its ``available()``. Both
``FLConfig.method`` and ``FLConfig.sampler`` are validated against their
registries at construction. The paper's comparison class:

  fedavg   coordinate-based mean (Eq. 1), sample-weighted
  fedprox  fedavg + proximal local loss (mu/2 ||w - w_g||^2)
  fed2     feature paired averaging (Eq. 19) over the group-axis tree
  fedma    one-shot matched averaging (WLA baseline, core/matching.py)

plus the beyond-paper strategies proving the method API (scaffold,
fednova, fedavgm, fedadam — fl/methods.py docstrings).

The host never blocks on device values inside the round loop: batches are
staged ahead, eval results stay device-resident, and accuracies are
materialized once after the last round (or lazily when ``log`` is given).
Evaluation runs through the jitted tiled engine of fl/evaluation.py
(DESIGN.md §10) — one dispatch over the staged eval tiles per round
instead of the seed's per-batch host loop (kept as
``evaluation.host_loop_eval``, the reference the engine is pinned
against); tasks that carry ``n_classes`` additionally get per-round
confusion counts for free.
"""
from __future__ import annotations

import dataclasses
import time
from typing import Any, Callable

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import fusion as fusion_lib
from repro.core import matching as matching_lib
from repro.fl import evaluation as evaluation_lib
from repro.fl import methods as methods_lib
from repro.fl import population as population_lib
from repro.fl.engine import make_round_engine
from repro.fl.population import Population

PyTree = Any

SPANS = ("fl.round", "fl.sample", "fl.pack", "fl.load", "fl.stack",
         "fl.gather", "fl.scatter", "fl.dispatch", "fl.eval", "fl.wait",
         "fl.log", "fl.checkpoint", "fl.event")
"""The program's host spans: ``jax.profiler.TraceAnnotation`` events on
the host plane of the profiler's trace, on the same clock as the device
planes, so that every idle gap of the device can be set beside what the
host was doing. With no profiler running a span records nothing and its
stats are never formatted; the stats are values the code already holds.

- ``fl.round``: one iteration of the sync round loop, from sampling
  through ``log`` (a ``StepTraceAnnotation``, ``step_num`` = the round);
  stats ``participants``, ``tiles`` (engine tiles: participants over the
  cohort width, rounded up, or the capacity tiers), and where the task
  states them (``FLTask.attn_blocks``: a language model of a known
  sequence length) ``attn_blocks`` and ``attn_blocks_live``, the
  (query chunk, key chunk) blocks of one attention layer over one
  sequence and those of them it computes
  (``models.attention.attention_blocks``).
- ``fl.sample``: the sampler's draw of the round's (or async wave's) ids.
- ``fl.pack``: one engine tile's inputs (``pad_tile_inputs``: padding,
  weights, packed batches); stats ``clients``, ``steps``, ``batch``, and
  ``tokens`` where the batches carry a ``tokens`` leaf (the tile's
  token count: slots x steps x batch x sequence).
- ``fl.load``: one ``get_batch`` call inside ``fl.pack``.
- ``fl.stack``: the single ``jax.device_put`` of a tile's packed host
  buffers (``_pack_client_batches``; stat ``bytes``, the bytes put), or
  an async buffer's ``jnp.stack`` (no stats).
- ``fl.gather``, ``fl.scatter``: client state rows to cohort slots and
  back (``Population.gather``/``scatter``); stat ``clients``. The
  whole-population shortcut keeps state on the device and has neither.
- ``fl.dispatch``: a call into the compiled round programs
  (``run_round``, ``run_tile``, ``finish_round``, ``host_fuse``, the
  tiers' combine, the async ``local_fn``/``event_fn``).
- ``fl.eval``: the eval dispatch (``EvalEngine.run`` or
  ``host_loop_eval``); stat ``tiles``.
- ``fl.wait``: the host blocking on the round's eval result (only where
  ``log`` is given: the loop waits nowhere else).
- ``fl.log``: the ``log`` callback.
- ``fl.checkpoint``: ``save_fl_checkpoint``.
- ``fl.event``: one async fusion event (fl/async_engine.py), the buffer
  stack and dispatch through its eval and ``log``; stat ``buffer``.

Inside the compiled round (fl/engine.py, fl/async_engine.py) the
``jax.named_scope`` scopes ``local`` (broadcast and the vmapped local
phase), ``codec``, ``fuse`` (robust pre-step and the method's fuse) and
``server`` name the ops on the device, and the Pallas kernels are named
``paired_fusion`` and ``local_step``. Inside ``local`` the model's own
scopes name its layers (models/): ``mla`` (latent attention,
``attention.mla_apply``), ``moe`` (the expert layer, ``moe.moe_apply``)
with ``route``, ``experts`` and ``shared`` inside it, ``ffn`` (a dense
SwiGLU FFN) and ``head`` (the LM head and its loss)."""


@dataclasses.dataclass(frozen=True)
class FLConfig:
    population: int = 10        # logical clients (fl/population.py)
    cohort_size: int | None = None  # engine width; None -> population
    sampler: str = "full"       # any name in population.available()
    rounds: int = 20
    local_epochs: int = 1
    steps_per_epoch: int = 10
    batch_size: int = 32
    lr: float = 0.05
    momentum: float = 0.9
    method: str = "fed2"        # any name in methods.available()
    prox_mu: float = 0.01
    server_lr: float = 1.0      # server-step methods (fedavgm, fedadam)
    server_momentum: float = 0.9
    seed: int = 0
    eval_batch: int = 512
    # client-state storage (fl/statestore.py, DESIGN.md §13): "memory"
    # keeps the historical stacked (P, ...) host arrays (O(P) RAM);
    # "mmap" keeps the population on disk as chunk_size-row mmap shards
    # (O(cohort) RAM, incremental checkpoints).
    store: str = "memory"
    chunk_size: int = 1024
    # heterogeneous capacity (fl/capacity.py, DESIGN.md §11): per-tier
    # (width, client count) pairs — "1.0x2,0.5x2,0.25x2" or a tuple of
    # pairs; None/() = homogeneous. Counts must sum to the population.
    tiers: Any = None
    # federation mode (DESIGN.md §12/§16): "sync" runs the round loop;
    # "async" makes the fusion event the unit of progress — rounds
    # counts events, cohort_size is the in-flight concurrency, buffer_k
    # updates fuse per event (None -> cohort_size) under the staleness
    # discount ("constant" | "polynomial(a)"), async-eligible methods
    # only (FedMethod.async_eligible); "one_shot" trains the WHOLE
    # rounds x local_epochs x steps_per_epoch budget locally and fuses
    # exactly once (one_shot_config — the EconML FederatedEstimator
    # shape), refused for client-stateful methods.
    mode: str = "sync"
    buffer_k: int | None = None
    staleness: str = "constant"
    # adversarial federation (fl/attacks.py + fl/robust.py, DESIGN.md
    # §14): attack names a registered byzantine behavior
    # ("label_flip" | "sign_flip(s)" | "scaled_update(s)" |
    # "gauss_noise(sigma)"), attack_fraction flags that share of the
    # population as seed-deterministic attackers (>= 1 = explicit
    # count); robust names a fusion rule ("coordinate_median" |
    # "trimmed_mean(beta)" | "norm_clip(tau)") wrapping the method's
    # fuse. None/"" = honest run / plain fusion.
    attack: str | None = None
    attack_fraction: float = 0.0
    robust: str | None = None
    # engine performance knobs (DESIGN.md §15), each defaulting to the
    # bit-identical seed behavior: compute_dtype runs the LOCAL phase in
    # bf16 with fp32 fusion accumulators ("float32" | "bfloat16",
    # mixed_precision methods only); codec compresses the uplink through
    # fl/codec.py's decode-then-fuse ("identity" | "int8" | "topk(f)",
    # uplink_codec methods only; reducing robust rules refuse lossy
    # codecs); local_unroll batches that many local optimizer steps into
    # one dispatch (lax.scan unroll — same arithmetic, fewer dispatches).
    compute_dtype: str = "float32"
    codec: str | None = None
    local_unroll: int = 1
    # alignment strategy (fl/alignment.py, DESIGN.md §16): how plain
    # coordinate fusion is made feature-aligned. "grouped" — the default
    # — is the method's own structural declaration (Fed2 structure
    # adaptation for uses_groups methods, plain net otherwise:
    # bit-identical to the pre-strategy programs); "pan" adds fixed
    # per-channel position encodings to a plain net (arxiv 2203.14666);
    # "none" is the unaligned plain-net control. The MODEL must be built
    # through alignment.build_model_config for the strategy to bite —
    # FLConfig only validates eligibility and records the choice.
    alignment: str = "grouped"

    def __post_init__(self):
        if self.method not in methods_lib.available():
            raise ValueError(
                f"unknown federated method {self.method!r}; available: "
                f"{', '.join(methods_lib.available())}")
        if self.sampler not in population_lib.available():
            raise ValueError(
                f"unknown client sampler {self.sampler!r}; available: "
                f"{', '.join(population_lib.available())}")
        from repro.fl import statestore as statestore_lib
        if self.store not in statestore_lib.available():
            raise ValueError(
                f"unknown client-state store {self.store!r}; available: "
                f"{', '.join(statestore_lib.available())}")
        if (not isinstance(self.chunk_size, int)
                or isinstance(self.chunk_size, bool)
                or self.chunk_size <= 0):
            raise ValueError(
                f"FLConfig.chunk_size must be a positive int (rows per "
                f"client-state shard), got {self.chunk_size!r}")
        if self.cohort_size is None:
            object.__setattr__(self, "cohort_size", self.population)
        for field in ("rounds", "population", "cohort_size", "batch_size",
                      "local_epochs", "steps_per_epoch"):
            v = getattr(self, field)
            if not isinstance(v, int) or isinstance(v, bool) or v <= 0:
                raise ValueError(
                    f"FLConfig.{field} must be a positive int, got {v!r}")
        if self.cohort_size > self.population:
            raise ValueError(
                f"FLConfig.cohort_size ({self.cohort_size}) must not "
                f"exceed population ({self.population}): the cohort is "
                "the fixed engine width a round's participants are "
                "sampled into")
        if not self.tiers:
            object.__setattr__(self, "tiers", None)
        else:
            from repro.fl import capacity as capacity_lib
            mix = capacity_lib.parse_tiers(self.tiers)
            capacity_lib.validate_mix(mix, self.population)
            object.__setattr__(self, "tiers", mix)
        if self.mode not in ("sync", "async", "one_shot"):
            raise ValueError(
                f"FLConfig.mode must be 'sync', 'async' or 'one_shot', "
                f"got {self.mode!r}")
        if self.mode == "async":
            from repro.fl import async_engine as async_lib
            async_lib.parse_staleness(self.staleness)
            if self.tiers is not None:
                raise ValueError(
                    "FLConfig.tiers and mode='async' are mutually "
                    "exclusive: the buffered-async driver dispatches "
                    "full-width cohort tiles (DESIGN.md §12); drop the "
                    "tiers or run mode='sync'")
            if self.buffer_k is None:
                object.__setattr__(self, "buffer_k", self.cohort_size)
            k = self.buffer_k
            if not isinstance(k, int) or isinstance(k, bool) or k <= 0:
                raise ValueError(
                    f"FLConfig.buffer_k must be a positive int, got "
                    f"{k!r}")
        else:
            if self.buffer_k is not None:
                raise ValueError(
                    "FLConfig.buffer_k is only meaningful with "
                    "mode='async' (the per-fusion-event buffer bound); "
                    "leave it None for sync rounds")
            if self.staleness != "constant":
                raise ValueError(
                    "FLConfig.staleness is only meaningful with "
                    "mode='async'; leave it 'constant' for sync rounds")
        if not self.attack:
            object.__setattr__(self, "attack", None)
            if self.attack_fraction:
                raise ValueError(
                    f"FLConfig.attack_fraction="
                    f"{self.attack_fraction!r} without attack: name the "
                    "byzantine behavior (FLConfig.attack, e.g. "
                    "'sign_flip') or drop the fraction")
        else:
            from repro.fl import attacks as attacks_lib
            attacks_lib.parse_attack(self.attack)
            attacks_lib.attacker_count(self.attack_fraction,
                                       self.population)
        if not self.robust:
            object.__setattr__(self, "robust", None)
        else:
            from repro.fl import robust as robust_lib
            robust_lib.parse_robust(self.robust)
        if self.attack or self.robust:
            what = "attack" if self.attack else "robust"
            if self.tiers is not None:
                raise ValueError(
                    f"FLConfig.{what} and tiers are mutually exclusive "
                    "for now: tiered rounds fuse width-sliced sub-model "
                    "tiles (DESIGN.md §11), where neither the "
                    "malicious-presence row nor a cross-tile robust "
                    "reduction is defined; drop the tiers or the "
                    "adversarial knobs")
            if self.mode == "async":
                raise ValueError(
                    f"FLConfig.{what} and mode='async' are mutually "
                    "exclusive for now: a fusion event mixes updates "
                    "from different global versions, so the "
                    "per-round malicious row / robust reduction "
                    "(DESIGN.md §14) has no buffered form yet; run "
                    "mode='sync'")
        # §15 engine performance knobs: value parsing (the eligibility
        # half lives in compat.validate, which resolve_compute_dtype
        # also consults — a bad config fails at construction, not deep
        # inside engine building)
        from repro.fl.engine import resolve_compute_dtype
        resolve_compute_dtype(self.compute_dtype,
                              methods_lib.get(self.method))
        if (not isinstance(self.local_unroll, int)
                or isinstance(self.local_unroll, bool)
                or self.local_unroll <= 0):
            raise ValueError(
                f"FLConfig.local_unroll must be a positive int (local "
                f"optimizer steps batched per dispatch), got "
                f"{self.local_unroll!r}")
        if not self.codec:
            object.__setattr__(self, "codec", None)
        else:
            from repro.fl import codec as codec_lib
            codec_lib.parse_codec(self.codec)
        if self.compute_dtype != "float32" or self.codec is not None:
            knob = ("compute_dtype" if self.compute_dtype != "float32"
                    else "codec")
            if self.tiers is not None:
                raise ValueError(
                    f"FLConfig.{knob} and tiers are mutually exclusive "
                    "for now: tiered rounds fuse width-sliced sub-model "
                    "tiles (DESIGN.md §11) whose per-tier byte/precision "
                    "accounting the §15 knobs don't define yet; drop the "
                    "tiers or the knob")
            if self.mode == "async":
                raise ValueError(
                    f"FLConfig.{knob} and mode='async' are mutually "
                    "exclusive for now: the buffered-async tile/event "
                    "split (DESIGN.md §12) implements neither the round-"
                    "boundary dtype cast nor the decode-then-fuse "
                    "round-trip; run mode='sync'")
        # method eligibility for every knob above, in ONE place — the
        # capability matrix (fl/compat.py, DESIGN.md §16)
        from repro.fl import compat as compat_lib
        compat_lib.validate(self, methods_lib.get(self.method))


@dataclasses.dataclass
class FLTask:
    """Model-family adapter consumed by ``run_federated``."""
    init_fn: Callable[[jax.Array], PyTree]
    loss_fn: Callable[[PyTree, dict], jnp.ndarray]
    eval_fn: Callable[[PyTree, dict], jnp.ndarray]   # -> accuracy
    group_axes_fn: Callable[[PyTree], PyTree] | None = None  # fed2
    matched_average_fn: Callable | None = None               # fedma
    # fl/evaluation.py engine hooks: (params, batch) -> (pred, gold,
    # weight); None falls back to the eval_fn host loop. n_classes opts
    # into (C, C) confusion counts (None for LM tasks, where C = vocab).
    predict_fn: Callable[[PyTree, dict], tuple] | None = None
    n_classes: int | None = None
    # capacity tiers (fl/capacity.py): width -> TierModel sub-model
    # builder; None = the family has no tier support (lm for now).
    tier_fn: Callable[[float], Any] | None = None
    # False: the model has ops with no batched form on the device (the
    # held-expert layer's ragged products on a TPU), so the engine runs
    # one client per tile, unbatched, and refuses a wider cohort
    batched_clients: bool = True
    # (live, total) attention blocks one layer computes over one sequence
    # (models.attention.attention_blocks); None: no attention, or the
    # sequence length is not known
    attn_blocks: tuple[int, int] | None = None


def _pack_client_batches(parts, get_batch, n_steps, batch_size, rng,
                         poison_fns=None):
    """Per cohort tile: (C, n_steps, B, ...) batch arrays for the given
    clients' shards, sampling with replacement where a shard is short
    (empty shards index sample 0). poison_fns: optional per-client list
    of ``batch -> batch`` hooks (None entries = honest) — data-poisoning
    attacks (DESIGN.md §14) corrupt a malicious client's batches HERE,
    after the rng draw, so the packing rng stream is bit-identical to
    the honest run.

    Each step batch, after its hook, is copied into one fresh
    (C, n_steps, B, ...) host buffer per leaf; the buffers go to the
    device in one ``jax.device_put`` after the last client. A loader may
    return host or device arrays; host arrays (the launcher's) spare a
    device-to-host copy per step. A fresh buffer per tile, because a put
    may still be reading the last one."""
    C = len(parts)
    treedef, slots = None, None
    for ci, idx in enumerate(parts):
        hook = poison_fns[ci] if poison_fns is not None else None
        for s in range(n_steps):
            if len(idx) == 0:
                sel = np.zeros((batch_size,), np.int64)
            else:
                sel = rng.choice(idx, size=batch_size,
                                 replace=len(idx) < batch_size)
            with jax.profiler.TraceAnnotation("fl.load"):
                b = get_batch(sel)
            leaves, treedef = jax.tree_util.tree_flatten(
                b if hook is None else hook(b))
            if slots is None:
                slots = [np.empty((C, n_steps) + x.shape, x.dtype)
                         for x in leaves]
            for slot, x in zip(slots, leaves):
                slot[ci, s] = x
    nbytes = sum(x.nbytes for x in slots)
    with jax.profiler.TraceAnnotation("fl.stack", bytes=nbytes):
        return jax.tree_util.tree_unflatten(treedef, jax.device_put(slots))


def pad_tile_inputs(pop: Population, tids, width: int, get_batch, n_steps,
                    batch_size, rng, uniform_weights: bool = False,
                    gw_cols: int | None = None):
    """Pad one engine tile to ``width`` slots (repeating the first
    participant at zero weight) and assemble its weights / presence rows
    / packed batches — THE shared padding semantics of cohort tiling
    (here) and the per-tier tiles (fl/capacity.py). gw_cols restricts
    the presence rows to the first K group columns (a tier that dropped
    the rest). Returns (padded_ids, weights, group_weights, batches)."""
    with jax.profiler.TraceAnnotation("fl.pack", clients=width,
                                      steps=n_steps,
                                      batch=batch_size) as span:
        tids = np.asarray(tids, np.int64)
        n_real = len(tids)
        padded = np.concatenate(
            [tids, np.full(width - n_real, tids[0], np.int64)])
        w = (np.ones(width) if uniform_weights
             else pop.weights[padded].copy())
        w[n_real:] = 0.0
        gw = None
        if pop.group_weights is not None:
            gw = pop.group_weights[padded]
            gw = (gw if gw_cols is None else gw[:, :gw_cols]).copy()
            gw[n_real:] = 0.0
        pois = None
        if pop.poison is not None and pop.malicious is not None:
            pois = [pop.poison if pop.malicious[i] else None for i in padded]
        batches = _pack_client_batches([pop.parts[i] for i in padded],
                                       get_batch, n_steps, batch_size, rng,
                                       poison_fns=pois)
        if span.is_enabled() and isinstance(batches, dict) \
                and "tokens" in batches:
            span.set_metadata(tokens=int(np.prod(batches["tokens"].shape)))
        return padded, w, gw, batches


def _malicious_inputs(engine, pop: Population, padded, n_real, cfg,
                      round_idx):
    """The engine's traced malicious argument for one tile: the sampled
    slots' attacker flags (pad rows forced honest — they carry zero
    weight anyway) + the per-round key. None for honest engines."""
    if engine.attack is None:
        return None
    if pop.malicious is None:
        raise ValueError(
            "cfg.attack is set but the Population carries no attacker "
            "mask; build the run through run_federated (it assigns "
            "attackers seed-deterministically via "
            "attacks.assign_attackers) or set pop.malicious")
    from repro.fl import attacks as attacks_lib
    row = pop.malicious[np.asarray(padded)].astype(np.float32)
    row[n_real:] = 0.0
    return row, attacks_lib.round_key(cfg.seed, round_idx)


def run_sampled_round(engine, pop: Population, method, server_state,
                      global_params, ids, get_batch, n_steps, cfg, rng,
                      uniform_weights: bool = False, round_idx: int = 0):
    """Execute one round for participant ids — a single engine invocation
    when the cohort holds them all, cohort tiling otherwise. Returns
    (server_state, new_global); per-client state is gathered/scattered on
    ``pop`` in place. uniform_weights: every participant contributes
    equally to fusion (samplers whose draw probability already encodes
    shard size — ``ClientSampler.fusion_weights``). round_idx seeds the
    per-round attack key (model-poisoning runs, DESIGN.md §14)."""
    C = engine.cohort_size
    ids = np.asarray(ids, np.int64)

    def tile_inputs(tids):
        return pad_tile_inputs(pop, tids, C, get_batch, n_steps,
                               cfg.batch_size, rng,
                               uniform_weights=uniform_weights)

    if len(ids) == C:
        _, w, gw, batches = tile_inputs(ids)
        mal = _malicious_inputs(engine, pop, ids, C, cfg, round_idx)
        # whole population in one cohort in natural order: client state
        # needs no slot remapping, so keep it device-resident across
        # rounds (no host round-trip, no per-round sync) — the
        # pre-participation behavior for client-stateful full runs.
        # Out-of-core stores opt out (store.in_memory): their state
        # must stay on their shards, not in device buffers.
        whole = (C == pop.size and pop.store.in_memory
                 and np.array_equal(ids, np.arange(C)))
        state = {"server": server_state,
                 "clients": (pop.clients if whole
                             else pop.gather(method, ids))}
        with jax.profiler.TraceAnnotation("fl.dispatch"):
            state, new_global = engine.run_round(
                state, global_params, batches, weights=w, group_weights=gw,
                malicious=mal)
        if whole:
            pop.clients = state["clients"]
        else:
            pop.scatter(method, ids, state["clients"])
        return state["server"], new_global

    # ---- padded / tiled rounds: participants != cohort_size ---------------
    if not method.cohort_tiling and not method.host_fusion:
        # the server step aggregates over ALL cohort slots (scaffold's
        # control-variate mean), so padded or tiled participant sets
        # would pollute it — such methods need exactly cohort-width ids
        raise ValueError(
            f"{method.name}: server step reads the participating cohort "
            f"slots (cohort_tiling=False), so a round needs exactly "
            f"cohort_size participants — got {len(ids)} for "
            f"cohort_size={C}; "
            + ("raise cohort_size to hold all participants or use a "
               "cohort-sized sampler (uniform/weighted/round_robin)"
               if len(ids) > C else
               "use a sampler that fills the cohort, or lower "
               "cohort_size to the participant count"))
    if pop.group_weights is not None:
        raise ValueError(
            "presence-weighted group fusion needs exactly one unpadded "
            "cohort of participants: tiling renormalizes each group "
            "column per tile, and padded slots would join a no-holder "
            "column's uniform fallback — either biases Eq. 19. Got "
            f"{len(ids)} participants for cohort_size={C}; "
            + ("raise cohort_size to hold all participants or use a "
               "cohort-sized sampler (uniform/weighted/round_robin)"
               if len(ids) > C else
               "use a sampler that fills the cohort, or lower "
               "cohort_size to the participant count"))
    if engine.robust is not None:
        # reducing robust rules (coordinate_median, trimmed_mean) are
        # NOT affine in the weighted client mean: a median of per-tile
        # medians is not the round's median, so the tile-accumulation
        # identity below doesn't hold (norm_clip is a pre-transform and
        # tiles exactly — make_round_engine leaves engine.robust None
        # for it)
        raise ValueError(
            f"robust rule {engine.robust.describe()!r} reduces over the "
            "full cohort and has no exact tiled form (the weighted "
            f"quantile is not affine); got {len(ids)} participants for "
            f"cohort_size={C} — "
            + ("raise cohort_size to hold all participants or use a "
               "cohort-sized sampler (uniform/weighted/round_robin)"
               if len(ids) > C else
               "use a sampler that fills the cohort, or lower "
               "cohort_size to the participant count"))
    acc, w_acc = None, 0.0
    stacked_tiles = []              # host_fusion: stacked params per tile
    for t0 in range(0, len(ids), C):
        tids = ids[t0:t0 + C]
        n_real = len(tids)
        padded, w, gw, batches = tile_inputs(tids)
        mal = _malicious_inputs(engine, pop, padded, n_real, cfg,
                                round_idx)
        cstate = pop.gather(method, padded)
        if acc is not None:
            # the tile is packed while the last one runs; it is dispatched
            # once the running sum holds the last one, whose model-sized
            # output is then freed before this tile allocates: the device
            # memory follows one order in every round
            jax.block_until_ready(acc)
        with jax.profiler.TraceAnnotation("fl.dispatch"):
            new_cstate, fuse_out = engine.run_tile(
                cstate, server_state, global_params, batches, weights=w,
                group_weights=gw, malicious=mal)
        pop.scatter(method, tids, jax.tree_util.tree_map(
            lambda a: a[:n_real], new_cstate))
        if method.host_fusion:
            stacked_tiles.append(jax.tree_util.tree_map(
                lambda a: a[:n_real], fuse_out))
            continue
        s_t = float(w.sum())
        if s_t > 0:     # a tile of no weight adds nothing (its mean is 0/0)
            scaled = jax.tree_util.tree_map(lambda l: l * s_t, fuse_out)
            # the running sum is updated in place, and the tile's output
            # dropped before the next tile runs: a model-sized copy each
            acc = scaled if acc is None else _add_into(acc, scaled)
            del scaled
            w_acc += s_t
        del fuse_out
    if method.host_fusion:
        stacked = jax.tree_util.tree_map(
            lambda *xs: jnp.concatenate(xs, axis=0), *stacked_tiles)
        w_all = (np.ones(len(ids)) if uniform_weights
                 else pop.weights[ids])
        with jax.profiler.TraceAnnotation("fl.dispatch"):
            return server_state, engine.host_fuse(stacked, w_all)
    if acc is None:
        raise ValueError(f"every participant of the round ({len(ids)}) "
                         "has fusion weight 0: the round has no mean")
    fused = _divide_into(acc, np.float32(w_acc))
    del acc
    with jax.profiler.TraceAnnotation("fl.dispatch"):
        return engine.finish_round(server_state, global_params, fused)


# the tiled round's running sum, owned by the loop: each step writes over
# the sum's own buffers (the same adds and division as eager ops)
_add_into = jax.jit(lambda acc, x: jax.tree_util.tree_map(jnp.add, acc, x),
                    donate_argnums=0)
_divide_into = jax.jit(
    lambda acc, w: jax.tree_util.tree_map(lambda l: l / w, acc),
    donate_argnums=0)


def _release(old: PyTree, keep: PyTree) -> None:
    """Free the device buffers of ``old``'s leaves, except those that are
    leaves of ``keep`` too."""
    kept = {id(x) for x in jax.tree_util.tree_leaves(keep)}
    for x in jax.tree_util.tree_leaves(old):
        if id(x) not in kept and isinstance(x, jax.Array) \
                and not x.is_deleted():
            x.delete()


def one_shot_config(cfg: FLConfig) -> FLConfig:
    """The sync config a ``mode='one_shot'`` run actually executes
    (DESIGN.md §16): every client trains the WHOLE round budget locally
    — rounds x local_epochs x steps_per_epoch optimizer steps — and the
    server fuses exactly ONCE, the federated-ensembling shape of one-shot
    FL (cf. EconML's FederatedEstimator: full local fits, one
    aggregation). Mapping it onto a 1-round sync run reuses the entire
    engine unchanged (tiling, tiers, checkpointing, eval), so the only
    new semantics is the budget fold; ``run_federated`` applies this at
    the top and the returned history has exactly one round row."""
    if cfg.mode != "one_shot":
        return cfg
    return dataclasses.replace(
        cfg, mode="sync", rounds=1, local_epochs=1,
        steps_per_epoch=(cfg.rounds * cfg.local_epochs
                         * cfg.steps_per_epoch))


def run_federated(task: FLTask, cfg: FLConfig, parts, get_batch,
                  test_batches, *, latency: str = "zero", log=None,
                  class_counts=None, group_spec=None, mesh=None,
                  use_kernel=None, use_local_kernel: bool = False,
                  checkpoint_dir=None,
                  checkpoint_every: int = 1,
                  resume: bool = False) -> dict:
    """parts: list of cfg.population per-client index arrays;
    get_batch(sel)->batch dict, called once per (client, step) with a
    (B,) row selection; test_batches: list of batch dicts for global
    eval. Each tile's step batches are packed on the host and copied to
    the device once (``_pack_client_batches``), so a loader of host
    arrays (``np.ndarray``) is the cheap one; device arrays are copied
    back to the host first and give the same batches.

    class_counts (population, C) + group_spec enable Eq. 19's non-IID
    refinement for group-structured methods (fed2): group g fuses only
    across participants that hold g's classes (presence-weighted paired
    averaging, rows gathered per cohort).

    mesh: optional launch/mesh.py mesh — shards the cohort axis over
    "data".
    use_kernel: force the Pallas fusion fast path on/off (None = default).
    use_local_kernel: route the default client_update's optimizer tail
    through the fused Pallas ``local_step`` kernel (DESIGN.md §15;
    no-op for methods without ``fused_local_step``).

    Returns history {round, acc, wall, wall_total, participants,
    final_params} — plus, when the task carries ``predict_fn`` and
    ``n_classes``, per-round ``confusion`` (C, C) count matrices and
    ``per_class_acc`` rows from the tiled eval engine (DESIGN.md §10).
    ``acc`` is then the pooled (example-weighted) accuracy over the eval
    set; without ``predict_fn`` the seed per-batch host loop
    (``evaluation.host_loop_eval``) supplies the mean-of-batch
    accuracies as before. ``participants`` records the sampled client
    ids per round. Per-round ``wall`` entries are seconds from the start
    of the loop: with ``log`` given, stamped once the host holds the
    round's eval result (the loop's per-round sync); without it, host
    DISPATCH timestamps (rounds then execute asynchronously —
    client-stateful methods under PARTIAL participation still sync on the
    per-round state scatter). ``wall_total`` is the true end-to-end time
    including the final materialization. The loop writes the host spans
    of ``SPANS`` into a running profiler's trace.

    A round that runs as several cohort tiles frees the device buffers of
    its input global once the round is logged: a hook or a wrapped
    engine that keeps a global of such a round has to copy it to the
    host by then (within ``log``). Without that, a caller that keeps two
    (the benchmark's probe) leaves a model that fills the chip no room to
    load its tile program (``dsv2lite_fed2.silo``, PERF.md §6).

    ``cfg.tiers`` routes the rounds through the heterogeneous-capacity
    engine (fl/capacity.py, DESIGN.md §11): one compiled tile per tier,
    overlap-aware fusion. A single width-1.0 tier is degenerate and runs
    the homogeneous path unchanged (bit-identical;
    tests/test_capacity.py).

    ``cfg.mode == "async"`` routes the whole run through the
    buffered-async driver (fl/async_engine.py, DESIGN.md §12): one
    history row per FUSION EVENT, ``latency`` names the
    seed-deterministic client-latency trace ("zero" | "pareto(a)" |
    "lognormal(sigma)"), and checkpointing is unsupported (the resumable
    state would have to include the in-flight buffer). With
    ``buffer_k == cohort_size``, ``latency="zero"`` and the constant
    staleness weight the async run is BIT-IDENTICAL to this sync loop
    for every async-eligible method (tests/test_async.py). A non-zero
    ``latency`` under mode='sync' is rejected: the sync barrier has no
    use for a trace (bench code simulates sync round times off the trace
    directly via ``async_engine.sync_round_times``).

    checkpoint_dir: save the resumable run state (global params, server
    state, population client state, host rng) after every
    ``checkpoint_every``-th round; with ``resume=True`` an existing
    checkpoint restores it and the loop continues from the saved round —
    bit-identically to the uninterrupted run (history then covers only
    the resumed rounds; resuming an already-finished run trains nothing
    and reports one eval of the restored model). Checkpointing syncs the
    device each saved round; leave checkpoint_dir None for the async
    fast path."""
    if len(parts) != cfg.population:
        raise ValueError(
            f"run_federated got {len(parts)} client shards for "
            f"FLConfig.population={cfg.population}; the partition defines "
            "the logical population — partition with "
            "n_clients=cfg.population or fix the config")
    # one-shot fusion is a config transformation (train everything
    # locally, fuse once) — from here on the run IS a 1-round sync run
    cfg = one_shot_config(cfg)
    if cfg.mode == "async":
        from repro.fl import async_engine as async_lib
        if checkpoint_dir or resume:
            raise ValueError(
                "checkpointing is not supported with mode='async': the "
                "resumable state would have to capture the in-flight "
                "dispatch buffer (DESIGN.md §12); run mode='sync' or "
                "drop checkpoint_dir/resume")
        return async_lib.run_async_federated(
            task, cfg, parts, get_batch, test_batches, latency=latency,
            log=log, class_counts=class_counts, group_spec=group_spec,
            mesh=mesh, use_kernel=use_kernel)
    if latency != "zero":
        from repro.fl import async_engine as async_lib
        async_lib.parse_latency(latency)   # helpful error for typos
        raise ValueError(
            "a latency trace is only meaningful with mode='async': the "
            "sync round barrier just waits out the slowest client — "
            "simulate its round times with "
            "async_engine.sync_round_times instead")
    if checkpoint_dir and (not isinstance(checkpoint_every, int)
                           or isinstance(checkpoint_every, bool)
                           or checkpoint_every < 1):
        raise ValueError(
            f"checkpoint_every must be a positive int (rounds between "
            f"saves; the final round always saves), got "
            f"{checkpoint_every!r}")
    rng = np.random.default_rng(cfg.seed)
    key = jax.random.PRNGKey(cfg.seed)
    global_params = task.init_fn(key)
    method = methods_lib.get(cfg.method)
    sampler = population_lib.get(cfg.sampler)
    gw = None
    if method.uses_groups and class_counts is not None \
            and group_spec is not None:
        gw = fusion_lib.presence_group_weights(class_counts, group_spec)
    from repro.fl import statestore as statestore_lib
    pop = Population.from_parts(parts, group_weights=gw)
    pop.use_store(statestore_lib.get(cfg.store, chunk_size=cfg.chunk_size))
    if cfg.attack is not None:
        from repro.fl import attacks as attacks_lib
        atk = attacks_lib.parse_attack(cfg.attack).build()
        pop.malicious = attacks_lib.assign_attackers(
            cfg.attack_fraction, cfg.population, seed=cfg.seed)
        if atk.data_poisoning:
            if task.n_classes is None:
                raise ValueError(
                    f"attack {cfg.attack!r} poisons labels and needs "
                    "task.n_classes (defined for classification tasks; "
                    "LM tasks have no flip target) — use a "
                    "model-poisoning attack (sign_flip/scaled_update/"
                    "gauss_noise) instead")
            pop.poison = (lambda b, _a=atk, _n=task.n_classes:
                          _a.poison_batch(b, _n))
    tiered = None
    if cfg.tiers is not None:
        from repro.fl import capacity as capacity_lib
        plan = capacity_lib.TierPlan.from_mix(cfg.tiers, cfg.population,
                                              seed=cfg.seed)
        if not plan.trivial:      # single width-1.0 tier IS the
            #                       homogeneous engine (bit-identical)
            pop.tiers = plan.assignment
            tiered = capacity_lib.make_tiered_engine(
                task, cfg, global_params, plan, mesh=mesh,
                use_kernel=use_kernel, method=method,
                use_gw=pop.group_weights is not None)
    if tiered is not None:
        engine = tiered.full
    else:
        engine = make_round_engine(task, cfg, global_params, mesh=mesh,
                                   use_kernel=use_kernel,
                                   use_local_kernel=use_local_kernel,
                                   method=method)
    server_state = engine.init_server_state(global_params)
    # round-0 per-client state: ONE row broadcast at population width by
    # the store (the in-memory store builds the historical stacked tree
    # bit-for-bit; the mmap store streams chunk-sized shards to disk)
    pop.store.initialize(engine.init_client_row(global_params), pop.size)

    eval_engine, eval_tiles = None, None
    if task.predict_fn is not None:
        eval_engine = evaluation_lib.make_eval_engine(
            task.predict_fn, task.n_classes, mesh=mesh)
        eval_tiles = evaluation_lib.stage(test_batches,
                                          tile=cfg.eval_batch, mesh=mesh)

    start_round = 0
    if checkpoint_dir and resume:
        from repro.checkpoint import io as ckpt_io
        if ckpt_io.checkpoint_exists(checkpoint_dir):
            (start_round, global_params, server_state, clients,
             rng_state) = ckpt_io.load_fl_checkpoint(
                checkpoint_dir, like_global=global_params,
                like_server=server_state,
                like_clients=(pop.clients if pop.store.in_memory
                              else None),
                store=pop.store)
            if clients is not None:   # incremental stores restore their
                pop.clients = clients  # shards in place and return None
            rng.bit_generator.state = rng_state
    already_complete = start_round >= cfg.rounds

    history = {"round": [], "acc": [], "wall": [], "participants": []}
    n_steps = cfg.local_epochs * cfg.steps_per_epoch
    counts = []                    # device arrays; materialized at the end
    t0 = time.time()
    uniform_w = sampler.fusion_weights == "uniform"
    full_ids = None       # shared arange: full participation carries no
    #                       per-round information, don't store it R times

    def eval_and_record(r, participants):
        """Evaluate the current global and append one history row — the
        single shape of a per-round record (the round loop and the
        already-complete resume tail must agree). With ``log`` given the
        host waits here for the result, and returns its accuracy, before
        ``wall`` is stamped; otherwise ``wall`` is the dispatch time."""
        if eval_engine is not None:
            with jax.profiler.TraceAnnotation("fl.eval",
                                              tiles=eval_tiles.n_tiles):
                c = eval_engine.run(global_params, eval_tiles)
        else:
            with jax.profiler.TraceAnnotation("fl.eval",
                                              tiles=len(test_batches)):
                c = evaluation_lib.host_loop_eval(
                    engine.eval_fn, global_params, test_batches)
        counts.append(c)
        history["round"].append(r)
        history["participants"].append(participants)
        acc = None
        if log:                    # logging opts into the per-round sync
            with jax.profiler.TraceAnnotation("fl.wait"):
                acc = _count_acc(c)
        history["wall"].append(time.time() - t0)
        return acc

    for r in range(start_round, cfg.rounds):
        with jax.profiler.StepTraceAnnotation("fl.round",
                                              step_num=r) as round_span:
            with jax.profiler.TraceAnnotation("fl.sample"):
                ids = sampler.sample(r, cfg.population, cfg.cohort_size,
                                     rng, weights=pop.weights)
            if round_span.is_enabled():
                stats = {"participants": len(ids),
                         "tiles": (len(tiered.tiles) if tiered is not None
                                   else -(-len(ids) // cfg.cohort_size))}
                if task.attn_blocks is not None:
                    (stats["attn_blocks_live"],
                     stats["attn_blocks"]) = task.attn_blocks
                round_span.set_metadata(**stats)
            if tiered is not None:
                from repro.fl.capacity import run_tiered_round
                server_state, global_params = run_tiered_round(
                    tiered, pop, method, server_state, global_params, ids,
                    get_batch, n_steps, cfg, rng, uniform_weights=uniform_w)
            else:
                round_in = global_params
                server_state, global_params = run_sampled_round(
                    engine, pop, method, server_state, global_params, ids,
                    get_batch, n_steps, cfg, rng, uniform_weights=uniform_w,
                    round_idx=r)
            if checkpoint_dir and ((r + 1) % checkpoint_every == 0
                                   or r == cfg.rounds - 1):
                from repro.checkpoint import io as ckpt_io
                with jax.profiler.TraceAnnotation("fl.checkpoint"):
                    ckpt_io.save_fl_checkpoint(
                        checkpoint_dir, round_idx=r + 1,
                        global_params=global_params,
                        server_state=server_state, client_state=pop.store,
                        rng=rng)
            if len(ids) == cfg.population:
                if full_ids is None:
                    full_ids = np.asarray(ids)
                participants = full_ids
            else:
                participants = np.asarray(ids)
            acc = eval_and_record(r, participants)
            if log:
                with jax.profiler.TraceAnnotation("fl.log"):
                    log(f"round {r:3d} acc {acc:.4f}")
            if tiered is None and len(ids) > engine.cohort_size:
                # a tiled round's input global is freed once the round is
                # logged, so that a caller's leftover reference (a wrapped
                # engine's) does not pin a model-sized copy on the device
                # through the rounds after it
                _release(round_in, (global_params, server_state))
    if already_complete:
        # resuming a finished run: nothing to train, but callers index
        # h["acc"][-1] — report one eval of the restored model instead
        # of an empty history
        eval_and_record(cfg.rounds - 1, np.asarray([], np.int64))
    if eval_engine is not None and task.n_classes is not None:
        conf = [np.asarray(c) for c in counts]
        history["confusion"] = conf
        history["per_class_acc"] = [evaluation_lib.per_class_accuracy(c)
                                    for c in conf]
    history["acc"] = [_count_acc(c) for c in counts]
    losses = [evaluation_lib.mean_loss(c) for c in counts]
    if losses and losses[0] is not None:
        history["eval_loss"] = losses
    history["wall_total"] = time.time() - t0
    history["final_params"] = global_params
    pop.store.close()      # out-of-core stores drop their scratch shards
    return history


def _count_acc(c) -> float:
    """Accuracy from one per-round eval result: a host-loop scalar, a
    (correct, total) pair, or a confusion matrix."""
    c = np.asarray(c)
    return float(c) if c.ndim == 0 else evaluation_lib.accuracy(c)


# ---------------------------------------------------------------------------
# Task builders
# ---------------------------------------------------------------------------


def cnn_task(model_cfg) -> FLTask:
    from repro.models.cnn import apply_cnn, cnn_accuracy, cnn_loss, init_cnn

    def predict(params, batch):
        logits = apply_cnn(params, model_cfg, batch["images"])
        return (jnp.argmax(logits, -1), batch["labels"],
                jnp.ones(batch["labels"].shape, jnp.float32))

    def tier_fn(width):
        from repro.fl import capacity as capacity_lib
        return capacity_lib.cnn_tier_model(model_cfg, width)

    return FLTask(
        init_fn=lambda k: init_cnn(k, model_cfg),
        loss_fn=lambda p, b: cnn_loss(p, model_cfg, b),
        eval_fn=lambda p, b: cnn_accuracy(p, model_cfg, b),
        group_axes_fn=lambda p: fusion_lib.cnn_group_axes(p, model_cfg),
        matched_average_fn=lambda s, w: matching_lib.matched_average(
            s, model_cfg, w),
        predict_fn=predict,
        n_classes=model_cfg.n_classes,
        tier_fn=tier_fn,
    )


def lm_task(model_cfg, seq: int | None = None) -> FLTask:
    """The language-model task; ``seq``, the training sequences' length,
    lets it state the attention blocks a layer computes
    (``FLTask.attn_blocks``)."""
    from repro.models.forward import lm_loss

    def logits_fn(params, batch):
        from repro.models.forward import forward
        from repro.models.transformer import unembed_apply
        h, _ = forward(params, model_cfg, batch["tokens"])
        table = params["embed"]["table"] if model_cfg.tie_embeddings else None
        return unembed_apply(params.get("unembed"), h, model_cfg, table)

    def accuracy(params, batch):
        # next-token top-1 accuracy as the LM "accuracy" analog
        pred = jnp.argmax(logits_fn(params, batch), -1)
        m = batch["mask"]
        return jnp.sum((pred == batch["labels"]) * m) / jnp.maximum(
            jnp.sum(m), 1)

    def predict(params, batch):
        # per-position preds and next-token losses; confusion stays off
        # (n_classes=None: the "classes" are the vocab — a vocab^2 count
        # matrix is not useful)
        logits = logits_fn(params, batch).astype(jnp.float32)
        gold = jnp.take_along_axis(logits, batch["labels"][..., None],
                                   axis=-1)[..., 0]
        nll = jax.nn.logsumexp(logits, axis=-1) - gold
        return jnp.argmax(logits, -1), batch["labels"], batch["mask"], nll

    from repro.models.transformer import init_params
    return FLTask(
        init_fn=lambda k: init_params(k, model_cfg),
        loss_fn=lambda p, b: lm_loss(p, model_cfg, b),
        eval_fn=accuracy,
        group_axes_fn=lambda p: fusion_lib.lm_group_axes(p, model_cfg),
        matched_average_fn=None,
        predict_fn=predict,
        n_classes=None,
        batched_clients=(model_cfg.moe is None
                         or model_cfg.moe.held is None),
        attn_blocks=None if seq is None else _lm_attn_blocks(model_cfg, seq),
    )


def _lm_attn_blocks(model_cfg, seq: int) -> tuple[int, int] | None:
    """(live, total) blocks of one causal self-attention layer over ``seq``
    tokens, at the canonical positions ``forward`` gives it (MLA has no
    window); None for a model with no attention (ssm)."""
    from repro.models.attention import attention_blocks
    if model_cfg.family == "ssm":
        return None
    return attention_blocks(
        seq, seq, causal=True,
        window=None if model_cfg.mla is not None else model_cfg.window,
        q_chunk=model_cfg.attn_q_chunk, kv_chunk=model_cfg.attn_kv_chunk)
