"""Logical client population & participation (DESIGN.md §9).

Fed2's fusion math is defined over the clients that PARTICIPATE in a
round; real federated systems (and the paper's non-IID Dirichlet
experiments) run with far more logical clients than ever train at once.
This module decouples the two widths:

- ``Population``: the P *logical* clients — per-client shard indices,
  sample-count weights, optional (P, G) presence weights, and the
  persistent per-client method state held by a ``ClientStateStore``
  (fl/statestore.py, DESIGN.md §13) that lives host-side, OUTSIDE the
  jitted round (scaffold control variates belong to clients, not to
  cohort slots). The default ``InMemoryStore`` is the historical
  stacked ``(P, ...)`` array behavior bit-for-bit; ``MmapShardStore``
  keeps the population on disk and the server at O(cohort) RAM.
- ``ClientSampler``: the participation strategy — which client ids train
  in round r. Strategies are registered by name exactly like federated
  methods (fl/methods.py): ``register`` / ``get`` / ``available()``;
  ``FLConfig.sampler`` is validated against this registry.

The round engine (fl/engine.py) always runs a fixed-width *cohort*
(width = ``cohort_size``, sharded over the mesh "data" axis); the host
loop (fl/runtime.py) gathers the sampled clients' state into cohort
slots, runs the round, and scatters updated state back. When a sampler
returns more participants than one cohort holds (``full`` participation
with population > cohort_size), the round executes as multiple engine
invocations — *cohort tiling* — whose fusion contributions accumulate in
a running weighted sum (DESIGN.md §9).
"""
from __future__ import annotations

import dataclasses
from typing import Any

import jax
import numpy as np

PyTree = Any


@dataclasses.dataclass
class Population:
    """The P logical clients behind a federated run.

    parts: per-client sample index arrays (the data shards) — a list of
    P arrays or a ``statestore.ShardIndices`` (flat + offsets, the
    O(P)-ints form out-of-core stores mmap).
    weights: (P,) float64 sample counts, floored at 1 (the fusion weights
    before per-cohort renormalization). May be a read-only memory map
    after ``use_store`` offloads it.
    group_weights: optional (P, G) presence weights for fed2's non-IID
    refinement (rows are gathered per cohort; paired_average renormalizes
    columns over the participants it sees).
    store: the ``ClientStateStore`` (fl/statestore.py, DESIGN.md §13)
    holding the persistent per-client method state — ``InMemoryStore``
    by default (stacked host arrays, the historical behavior
    bit-for-bit), ``MmapShardStore`` for out-of-core populations.
    ``clients`` remains the stacked-tree view of it for in-memory runs.
    tiers: optional (P,) int tier index per client — the capacity class
    each logical client trains (fl/capacity.py ``TierPlan.assignment``);
    None for homogeneous runs.
    malicious: optional (P,) bool attacker mask, indexed by logical
    client id (fl/attacks.py ``assign_attackers``, seed-deterministic
    like tier assignment) — carried here exactly like ``tiers`` so the
    flagged set is stable under sampling, cohort tiling and
    gather/scatter; None for honest runs.
    poison: optional host-side batch hook ``batch -> batch`` applied to
    MALICIOUS clients' step batches at packing time (data-poisoning
    attacks, e.g. label_flip); None otherwise.
    """
    parts: Any
    weights: np.ndarray
    group_weights: np.ndarray | None = None
    store: Any = None
    tiers: np.ndarray | None = None
    malicious: np.ndarray | None = None
    poison: Any = None

    def __post_init__(self):
        if self.store is None:
            from repro.fl import statestore
            self.store = statestore.InMemoryStore()

    @classmethod
    def from_parts(cls, parts, group_weights=None) -> "Population":
        from repro.fl import statestore
        if isinstance(parts, statestore.ShardIndices):
            weights = np.maximum(parts.lengths(), 1).astype(np.float64)
        else:
            parts = list(parts)
            weights = np.maximum([len(p) for p in parts],
                                 1).astype(np.float64)
        gw = None if group_weights is None else np.asarray(group_weights,
                                                           np.float64)
        return cls(parts=parts, weights=weights, group_weights=gw)

    @property
    def size(self) -> int:
        return len(self.parts)

    @property
    def clients(self) -> PyTree:
        """The full stacked (P, ...) state tree — the historical view,
        served by the store (out-of-core stores refuse: gather rows)."""
        return self.store.tree

    @clients.setter
    def clients(self, stacked: PyTree) -> None:
        self.store.adopt(stacked)

    def use_store(self, store) -> None:
        """Swap in a ClientStateStore and let it take over whatever
        population-wide storage it owns (out-of-core stores also offload
        parts/weights/presence rows to disk)."""
        self.store = store
        store.offload_aux(self)

    def gather(self, method, ids) -> PyTree:
        """Sampled clients' state rows -> cohort-slot stacked trees (the
        ``fl.gather`` span of ``runtime.SPANS``)."""
        with jax.profiler.TraceAnnotation("fl.gather", clients=len(ids)):
            return method.gather_client_state(self.store, np.asarray(ids))

    def scatter(self, method, ids, new_states) -> None:
        """Write cohort slots back to the sampled clients' rows (the
        ``fl.scatter`` span)."""
        with jax.profiler.TraceAnnotation("fl.scatter", clients=len(ids)):
            method.scatter_client_state(self.store, np.asarray(ids),
                                        new_states)


# ---------------------------------------------------------------------------
# Sampler registry (mirrors the fl/methods.py method registry)
# ---------------------------------------------------------------------------


class ClientSampler:
    """Participation strategy: which client ids train in round r.

    ``sample`` returns a 1-D int array of client ids. Strategies that
    return exactly ``cohort_size`` ids run as one engine invocation;
    longer id lists (``full`` over a large population) are executed by
    cohort tiling in the host loop. ``full`` MUST NOT draw from ``rng`` —
    the batch-packing rng stream then stays bit-identical to the
    pre-sampling engine (the equivalence pin in tests/test_methods.py).
    """

    name: str = ""
    summary: str = ""          # one line for the README sampler table
    # how a cohort's fusion weights are built (the FedAvg sampling
    # duality): "sample" = shard-size weights renormalized over the
    # participants (full/uniform/round_robin); "uniform" = every
    # participant contributes equally, because the sampling probability
    # itself already encodes shard size (weighted). Using shard-size
    # weights under shard-size sampling would double-count large shards.
    fusion_weights: str = "sample"

    def sample(self, round_idx: int, population: int, cohort_size: int,
               rng: np.random.Generator, weights=None) -> np.ndarray:
        raise NotImplementedError


_REGISTRY: dict[str, type[ClientSampler]] = {}


def register(cls: type[ClientSampler]) -> type[ClientSampler]:
    """Class decorator: register ``cls`` under ``cls.name``."""
    if not cls.name:
        raise ValueError(f"{cls.__name__} must set a non-empty .name")
    _REGISTRY[cls.name] = cls
    return cls


def available() -> tuple[str, ...]:
    """All registered sampler names, sorted (the canonical enumeration
    for CLIs, the README sampler table, and FLConfig validation)."""
    return tuple(sorted(_REGISTRY))


def get(name: str) -> ClientSampler:
    """Resolve a fresh sampler instance by registry name."""
    try:
        return _REGISTRY[name]()
    except KeyError:
        raise ValueError(
            f"unknown client sampler {name!r}; available: "
            f"{', '.join(available())}") from None


@register
class FullParticipation(ClientSampler):
    """Every client, every round. With population > cohort_size the host
    loop tiles the population over cohort-width engine invocations."""
    name = "full"
    summary = "every client every round (cohort tiling past the width)"

    def sample(self, round_idx, population, cohort_size, rng, weights=None):
        return np.arange(population, dtype=np.int64)


@register
class UniformSampler(ClientSampler):
    """cohort_size clients drawn uniformly without replacement."""
    name = "uniform"
    summary = "cohort_size clients uniformly, without replacement"

    def sample(self, round_idx, population, cohort_size, rng, weights=None):
        return np.sort(rng.choice(population, size=cohort_size,
                                  replace=False)).astype(np.int64)


@register
class WeightedSampler(ClientSampler):
    """Sampling probability proportional to shard size (weights), without
    replacement — large-shard clients participate more often, and each
    participant then contributes EQUALLY to fusion
    (``fusion_weights = "uniform"``; weighting both the draw and the
    average would double-count large shards).

    Backed by a Walker alias table (fl/statestore.py ``AliasTable``):
    O(P) build ONCE per weights array — cached on the sampler instance
    and rebuilt only when a different weights array arrives — then
    O(cohort log P) per round (O(1) alias draws + rejection for the
    without-replacement cohort) instead of ``rng.choice``'s O(P) scan
    every round. Zero-weight clients are NEVER sampled, and an all-zero
    weight vector raises instead of dividing by the zero total. Returns
    sorted unique ids."""
    name = "weighted"
    summary = "probability proportional to shard size, w/o replacement"
    fusion_weights = "uniform"

    def __init__(self):
        self._src = None          # the weights array the table was built on
        self._table = None

    def _alias_table(self, population, weights):
        from repro.fl.statestore import AliasTable
        if weights is None:
            weights = np.ones(population, np.float64)
        if self._table is None or self._src is not weights \
                or self._table.n != population:
            self._table = AliasTable(weights)
            self._src = weights
        return self._table

    def sample(self, round_idx, population, cohort_size, rng, weights=None):
        table = self._alias_table(population, weights)
        return table.sample_without_replacement(rng, cohort_size)


@register
class RoundRobinSampler(ClientSampler):
    """Deterministic cycling window: round r trains clients
    [r*C, r*C + C) mod population. When C divides the population every
    client participates exactly once per population/C rounds; otherwise
    the window wraps mid-cycle and coverage stays cyclic but uneven over
    short horizons. Pure function of (round_idx, population,
    cohort_size): it never draws from ``rng``, so the same round always
    yields the same (unique, window-ordered) ids."""
    name = "round_robin"
    summary = "deterministic cycling window over client ids"

    def sample(self, round_idx, population, cohort_size, rng, weights=None):
        start = (round_idx * cohort_size) % population
        return ((start + np.arange(cohort_size)) % population).astype(
            np.int64)
