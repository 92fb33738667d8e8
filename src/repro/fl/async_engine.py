"""Buffered-async federation (DESIGN.md §12).

The sync runtime (fl/runtime.py) advances in lockstep rounds: sample a
cohort, run every participant, fuse, step. At production populations the
round clock is the SLOWEST sampled client — stragglers dominate wall
time (ROADMAP item 1). This module makes the FUSION EVENT the unit of
progress instead (FedBuff-style): each dispatched client trains from the
global version current at its dispatch, its update arrives after a
latency drawn from a seed-deterministic heavy-tail trace, arrivals land
in a bounded buffer, and the server fuses every ``buffer_k`` arrivals —
each update weighted by its sample weight times a staleness discount
(``constant`` or ``polynomial(a)``, folded into the fusion weights that
``FedMethod.fuse`` renormalizes over the event).

The compiled pieces are the SAME per-tile programs the sync engine
compiles (fl/engine.py), split at the fusion boundary:

    local_fn(global_v, batches) -> stacked updates     (cohort width C)
    event_fn(server, global, stacked_K, w_eff)         (buffer width K)
                -> fuse + server step, one jit

A dispatch group — the clients dispatched from the same global version —
runs as ONE padded cohort tile (``runtime.pad_tile_inputs``, the shared
padding semantics of cohort tiling and capacity tiers), so a late update
is just a tile row carried forward with a discounted weight.

Correctness anchor (the pin of tests/test_async.py): with
``buffer_k == cohort_size``, a zero-latency trace, and the constant
staleness weight, every dispatch wave IS one sync cohort — same sampler
stream, same batch rng, same traced programs — so the async run is
BIT-IDENTICAL to ``mode="sync"`` for every ``async_eligible`` method.

Eligibility (``FedMethod.async_eligible``, checked by
``check_async_support`` — one source of truth for FLConfig validation
and driver construction): affine-fuse, stateless-client, device-fused
methods qualify; scaffold (per-client state), fedma (host matching), and
presence-weighted fed2 (per-event group-column renormalization biases
Eq. 19 exactly as tiled rounds would) refuse with explicit errors.
"""
from __future__ import annotations

import dataclasses
import re
import time
from typing import Any, Callable

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import fusion as fusion_lib
from repro.fl import evaluation as evaluation_lib
from repro.fl import methods as methods_lib
from repro.fl import population as population_lib
from repro.fl.engine import (_client_sharding, resolve_local_unroll,
                             resolve_use_kernel)
from repro.fl.methods import FedMethod, MethodContext
from repro.fl.population import Population

PyTree = Any

# the trace rng stream id: like capacity's TierPlan (seed + 7331), the
# latency draws use their OWN substream so the run's sampler/batch rng
# (cfg.seed) stays untouched — required for the sync bit-identity pin
_TRACE_STREAM = 7919


# ---------------------------------------------------------------------------
# Staleness discounts
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class StalenessPolicy:
    """Weight discount d(s) for an update that trained from a global
    ``s`` fusion events behind the one it fuses into. ``constant``:
    d(s) = 1 (pure FedBuff buffering); ``polynomial(a)``:
    d(s) = (1 + s)^-a (the FedAsync/FedBuff polynomial family)."""
    kind: str                  # "constant" | "polynomial"
    a: float = 0.0

    def discount(self, staleness) -> float:
        if self.kind == "constant":
            return 1.0
        return float((1.0 + float(staleness)) ** (-self.a))

    @property
    def spec(self) -> str:
        return ("constant" if self.kind == "constant"
                else f"polynomial({self.a:g})")


def parse_staleness(spec) -> StalenessPolicy:
    """``"constant"`` | ``"polynomial(a)"`` (a >= 0) -> StalenessPolicy.
    A StalenessPolicy passes through unchanged."""
    if isinstance(spec, StalenessPolicy):
        return spec
    if not isinstance(spec, str):
        raise ValueError(
            f"staleness spec must be a string, got {type(spec).__name__}")
    s = spec.strip()
    if s == "constant":
        return StalenessPolicy("constant")
    m = re.fullmatch(r"polynomial\(([^)]+)\)", s)
    if m:
        try:
            a = float(m.group(1))
        except ValueError:
            a = -1.0
        if a >= 0.0:
            return StalenessPolicy("polynomial", a)
    raise ValueError(
        f"bad staleness spec {spec!r}: expected 'constant' or "
        "'polynomial(a)' with a >= 0 (e.g. 'polynomial(0.5)')")


def effective_weights(weights, staleness, policy: StalenessPolicy, *,
                      normalize: bool = False) -> np.ndarray:
    """One fusion event's weights: sample weight x staleness discount,
    elementwise. The raw products are what ``event_fn`` consumes —
    ``FedMethod.fuse`` renormalizes over the event, so the event's
    effective weights always sum to 1 after fusion (``normalize=True``
    returns that normalized form; tests/test_async.py pins it)."""
    w = np.asarray(weights, np.float64)
    s = np.asarray(staleness)
    if w.shape != s.shape:
        raise ValueError(
            f"weights {w.shape} and staleness {s.shape} must align")
    d = np.array([policy.discount(x) for x in s.ravel()]).reshape(s.shape)
    out = w * d
    if not normalize:
        return out
    tot = out.sum()
    if tot <= 0:
        raise ValueError("effective weights sum to zero: every update in "
                         "the event has zero weight")
    return out / tot


# ---------------------------------------------------------------------------
# Seed-deterministic heavy-tail latency traces
# ---------------------------------------------------------------------------


def parse_latency(spec: str) -> tuple[str, float]:
    """``"zero"`` | ``"pareto(a)"`` | ``"lognormal(sigma)"`` ->
    (kind, parameter). Pareto(a) draws per-client base latencies with a
    heavy tail (finite mean needs a > 1); lognormal(sigma) is the milder
    alternative."""
    if not isinstance(spec, str):
        raise ValueError(
            f"latency spec must be a string, got {type(spec).__name__}")
    s = spec.strip()
    if s == "zero":
        return "zero", 0.0
    m = re.fullmatch(r"(pareto|lognormal)\(([^)]+)\)", s)
    if m:
        try:
            a = float(m.group(2))
        except ValueError:
            a = -1.0
        if a > 0.0:
            return m.group(1), a
    raise ValueError(
        f"bad latency spec {spec!r}: expected 'zero', 'pareto(a)' or "
        "'lognormal(sigma)' with a positive parameter "
        "(e.g. 'pareto(1.5)')")


@dataclasses.dataclass(frozen=True)
class LatencyTrace:
    """Per-(client, dispatch) training latencies, fully determined by
    (spec, seed, population).

    Straggler structure: each client gets a PERSISTENT base rate drawn
    once from the heavy-tail law (slow clients stay slow — the
    straggler phenomenon the async mode exists for), and every dispatch
    multiplies it by a small lognormal jitter keyed on (client, seq).
    All draws run on counter-based ``default_rng`` substreams under
    ``_TRACE_STREAM``, so the trace never touches the run's own rng."""
    spec: str
    seed: int
    population: int
    rates: np.ndarray          # (population,) per-client base latency

    @classmethod
    def make(cls, spec: str, *, population: int,
             seed: int) -> "LatencyTrace":
        kind, a = parse_latency(spec)
        if kind == "zero":
            rates = np.zeros(population)
        else:
            r = np.random.default_rng([seed, _TRACE_STREAM])
            if kind == "pareto":
                rates = 1.0 + r.pareto(a, size=population)
            else:
                rates = r.lognormal(0.0, a, size=population)
        return cls(spec=spec, seed=seed, population=population,
                   rates=rates)

    @property
    def zero(self) -> bool:
        return parse_latency(self.spec)[0] == "zero"

    def latency(self, client: int, seq: int) -> float:
        """Training latency of dispatch ``seq`` to ``client`` (seq is
        the global dispatch counter — the (client, seq) pair keys the
        jitter substream, so the schedule is order-independent)."""
        if self.zero:
            return 0.0
        jitter = np.random.default_rng(
            [self.seed, _TRACE_STREAM, int(client), int(seq)]
        ).lognormal(0.0, 0.25)
        return float(self.rates[int(client)] * jitter)


# ---------------------------------------------------------------------------
# Eligibility
# ---------------------------------------------------------------------------


# THE eligibility check for buffered-async federation now lives in
# fl/compat.py — the unified capability matrix (DESIGN.md §16);
# re-exported here so historical call sites keep working.
from repro.fl.compat import check_async_support  # noqa: E402,F401


# ---------------------------------------------------------------------------
# The compiled pieces: cohort-width local tiles + buffer-width events
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class AsyncEngine:
    """The two jitted programs of the buffered-async driver plus state
    builders. ``local_fn(global, batches)`` runs one dispatch group's
    padded cohort tile and returns the stacked per-client updates;
    ``event_fn(server, global, stacked_K, w_eff)`` fuses one buffer of
    ``buffer_k`` updates under the effective weights and applies the
    server step."""
    cohort_size: int
    buffer_k: int
    mesh: Any
    method: FedMethod
    local_fn: Callable
    event_fn: Callable
    init_server_state: Callable


def _shardable(mesh, k: int) -> bool:
    """Whether a k-wide leading axis tiles evenly over the mesh's "data"
    axis (sharding specs require even tiling at lower time)."""
    return k % mesh.shape["data"] == 0


def make_async_engine(task, cfg, params_like: PyTree, *, mesh=None,
                      use_kernel: bool | None = None,
                      method: FedMethod | None = None) -> AsyncEngine:
    """Build the async engine for (task, cfg, method).

    The local tile traces the IDENTICAL per-client program as the sync
    engine's ``local_and_fuse`` (broadcast -> vmapped client_update) and
    the event program the identical fuse -> server_update tail, split at
    the fusion boundary — XLA compiles each op the same way on either
    side of a jit boundary, which is what makes the infinite-buffer
    equivalence BIT-exact (tests/test_async.py)."""
    meth = method if method is not None else methods_lib.get(cfg.method)
    check_async_support(meth)
    opt = meth.local_opt(cfg)
    C = cfg.cohort_size
    K = cfg.buffer_k if cfg.buffer_k is not None else C
    use_kernel = resolve_use_kernel(use_kernel, mesh)
    ga = None
    if meth.uses_groups and task.group_axes_fn is not None:
        ga = task.group_axes_fn(params_like)
    steps = cfg.local_epochs * cfg.steps_per_epoch
    ctx = MethodContext(task=task, cfg=cfg, population=cfg.population,
                        cohort_size=C,
                        local_steps=steps,
                        opt=opt, weights=None, raw_weights=None,
                        group_axes=ga, group_weights=None,
                        use_kernel=use_kernel,
                        local_unroll=resolve_local_unroll(cfg, steps))
    meth.check(ctx)

    @jax.named_scope("local")
    def local_phase(global_params, batches):
        stacked = fusion_lib.broadcast_global(global_params, C)
        if mesh is not None:
            stacked = jax.lax.with_sharding_constraint(
                stacked, jax.tree_util.tree_map(
                    lambda l: _client_sharding(mesh, l.ndim), stacked))
        stacked, _ = jax.vmap(
            lambda p, b: meth.client_update(p, b, global_params, (), (),
                                            ctx),
            in_axes=(0, 0))(stacked, batches)
        return stacked

    def event(server_state, global_params, stacked, weights):
        # the K-wide buffer shards over "data" only when K divides the
        # axis — a sub-cohort buffer on a big pod stays replicated (the
        # sharded heavy lifting is the local tile, not the K-row fuse)
        if mesh is not None and _shardable(mesh, K):
            stacked = jax.lax.with_sharding_constraint(
                stacked, jax.tree_util.tree_map(
                    lambda l: _client_sharding(mesh, l.ndim), stacked))
        ctx_r = dataclasses.replace(ctx, weights=weights)
        with jax.named_scope("fuse"):
            fused = meth.fuse(stacked, global_params, ctx_r)
        with jax.named_scope("server"):
            return meth.server_update(server_state, (), (), global_params,
                                      fused, ctx_r)

    return AsyncEngine(cohort_size=C, buffer_k=K, mesh=mesh, method=meth,
                       local_fn=jax.jit(local_phase),
                       event_fn=jax.jit(event),
                       init_server_state=lambda gp: meth.init_server_state(
                           gp, ctx))


def lower_async_event(task, cfg, mesh, *, use_kernel: bool | None = None):
    """Lower one fusion event — the NEW compiled program of the async
    mode (the local tile is the sync engine's, already covered by the
    fl_round dry-run records) — on ``mesh`` from ShapeDtypeStructs, for
    the perf-drift baselines (launch/fl_dryrun.py, check_drift.py)."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    K = cfg.buffer_k if cfg.buffer_k is not None else cfg.cohort_size
    param_shapes = jax.eval_shape(task.init_fn, jax.random.PRNGKey(0))
    engine = make_async_engine(task, cfg, param_shapes, mesh=mesh,
                               use_kernel=use_kernel)

    def spec(l, sharding):
        return jax.ShapeDtypeStruct(l.shape, l.dtype, sharding=sharding)

    gspecs = jax.tree_util.tree_map(
        lambda l: spec(l, NamedSharding(mesh, P())), param_shapes)
    server_shapes = jax.eval_shape(engine.init_server_state, param_shapes)
    sspecs = jax.tree_util.tree_map(
        lambda l: spec(l, NamedSharding(mesh, P())), server_shapes)
    stacked_specs = jax.tree_util.tree_map(
        lambda l: jax.ShapeDtypeStruct(
            (K,) + l.shape, l.dtype,
            sharding=(_client_sharding(mesh, l.ndim + 1)
                      if _shardable(mesh, K)
                      else NamedSharding(mesh, P()))), param_shapes)
    wspec = jax.ShapeDtypeStruct((K,), jnp.float32,
                                 sharding=NamedSharding(mesh, P()))
    with mesh:
        return engine.event_fn.lower(sspecs, gspecs, stacked_specs, wspec)


# ---------------------------------------------------------------------------
# The driver
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class _Dispatch:
    """One in-flight client update: dispatched at ``version`` (it trains
    from that global), finishing at simulated time ``t_finish``. The
    update tree is computed lazily — all same-version dispatches run as
    one padded cohort tile when the first of them must arrive."""
    seq: int
    client: int
    version: int
    t_start: float
    t_finish: float
    update: Any = None
    weight: float = 0.0


class AsyncFederation:
    """The buffered-async event loop.

    Concurrency model: exactly ``cohort_size`` clients are in flight
    (the cohort is the training capacity, as in sync mode). Clients are
    drawn wave-by-wave from the registered sampler (one ``sample()``
    call per wave, popped one id at a time as slots free), each dispatch
    tagged with the current global version and a finish time from the
    latency trace. Arrivals are processed in (finish time, dispatch seq)
    order; every arrival enters the buffer, and the buffer flushes as
    ONE fusion event the moment it holds ``buffer_k`` updates: stack,
    weight by sample weight x staleness discount, ``event_fn``. Slots
    freed by a time-step's arrivals re-dispatch after its fusions
    settle, so new work always trains from the newest global.

    The run ends after ``cfg.rounds`` fusion events. Bookkeeping for the
    property tests (tests/test_async.py): ``fused_seqs`` (every accepted
    update fused exactly once), ``max_buffer_seen`` (the bound), and the
    per-event ``events`` records (participants, staleness, sim time)."""

    def __init__(self, engine: AsyncEngine, pop: Population,
                 sampler, cfg, get_batch, n_steps: int,
                 rng: np.random.Generator, trace: LatencyTrace,
                 policy: StalenessPolicy, *,
                 uniform_weights: bool = False):
        self.engine = engine
        self.pop = pop
        self.sampler = sampler
        self.cfg = cfg
        self.get_batch = get_batch
        self.n_steps = n_steps
        self.rng = rng
        self.trace = trace
        self.policy = policy
        self.uniform_weights = uniform_weights
        self.version = 0
        self.seq = 0
        self.wave_idx = 0
        self.wave_queue: list[int] = []
        self.pending: list[_Dispatch] = []
        self.buffer: list[_Dispatch] = []
        self.free_at = [0.0] * engine.cohort_size
        self.old_globals: dict[int, Any] = {}
        self.events: list[dict] = []
        self.fused_seqs: list[list[int]] = []
        self.max_buffer_seen = 0
        self.local_tiles = 0

    # -- dispatch -----------------------------------------------------------

    def _fill_slots(self, global_params):
        C = self.engine.cohort_size
        while len(self.pending) < C:
            if not self.wave_queue:
                with jax.profiler.TraceAnnotation("fl.sample"):
                    ids = self.sampler.sample(self.wave_idx,
                                              self.cfg.population, C,
                                              self.rng,
                                              weights=self.pop.weights)
                self.wave_queue = [int(i) for i in ids]
                self.wave_idx += 1
            client = self.wave_queue.pop(0)
            t_start = self.free_at.pop(self.free_at.index(
                min(self.free_at)))
            lat = self.trace.latency(client, self.seq)
            self.pending.append(_Dispatch(
                seq=self.seq, client=client, version=self.version,
                t_start=t_start, t_finish=t_start + lat))
            self.seq += 1

    # -- lazy local tiles ---------------------------------------------------

    def _compute_updates(self, arrivals, global_params):
        """Run the padded cohort tile for every global version the
        arriving updates still need — together with the other pending
        dispatches of the same version, so a version's dispatch group
        costs ONE tile (sync-round compute in the degenerate case)."""
        from repro.fl.runtime import pad_tile_inputs

        for v in sorted({d.version for d in arrivals if d.update is None}):
            group = sorted(
                [d for d in list(arrivals) + self.pending
                 if d.version == v and d.update is None],
                key=lambda d: d.seq)
            ids = [d.client for d in group]
            _, w, _, batches = pad_tile_inputs(
                self.pop, ids, self.engine.cohort_size, self.get_batch,
                self.n_steps, self.cfg.batch_size, self.rng,
                uniform_weights=self.uniform_weights)
            gp_v = (global_params if v == self.version
                    else self.old_globals[v])
            with jax.profiler.TraceAnnotation("fl.dispatch"):
                stacked = self.engine.local_fn(gp_v, batches)
            self.local_tiles += 1
            for i, d in enumerate(group):
                d.update = jax.tree_util.tree_map(
                    lambda a, i=i: a[i], stacked)
                d.weight = float(w[i])
            self.old_globals.pop(v, None)

    # -- fusion events ------------------------------------------------------

    def _fuse(self, server_state, global_params):
        staleness = [self.version - d.version for d in self.buffer]
        w_eff = effective_weights([d.weight for d in self.buffer],
                                  staleness, self.policy)
        with jax.profiler.TraceAnnotation("fl.stack"):
            stacked = jax.tree_util.tree_map(
                lambda *xs: jnp.stack(xs), *[d.update for d in self.buffer])
        with jax.profiler.TraceAnnotation("fl.dispatch"):
            server_state, new_global = self.engine.event_fn(
                server_state, global_params, stacked,
                jnp.asarray(w_eff, jnp.float32))
        self.fused_seqs.append([d.seq for d in self.buffer])
        self.events.append({
            "version": self.version,
            "participants": np.asarray([d.client for d in self.buffer],
                                       np.int64),
            "staleness": staleness,
            "sim_time": max(d.t_finish for d in self.buffer),
        })
        # the outgoing global stays live only while a pending dispatch
        # still needs it for its (lazy) local tile
        if any(d.version == self.version and d.update is None
               for d in self.pending):
            self.old_globals[self.version] = global_params
        self.buffer = []
        self.version += 1
        return server_state, new_global

    # -- the loop -----------------------------------------------------------

    def run(self, server_state, global_params, *,
            on_event: Callable | None = None):
        """Run ``cfg.rounds`` fusion events; ``on_event(record, global)``
        fires after each (eval hooks). Returns the final
        (server_state, global_params)."""
        while self.version < self.cfg.rounds:
            self._fill_slots(global_params)
            t_next = min(d.t_finish for d in self.pending)
            arrivals = sorted(
                [d for d in self.pending if d.t_finish == t_next],
                key=lambda d: d.seq)
            self.pending = [d for d in self.pending
                            if d.t_finish != t_next]
            self._compute_updates(arrivals, global_params)
            for d in arrivals:
                self.buffer.append(d)
                self.max_buffer_seen = max(self.max_buffer_seen,
                                           len(self.buffer))
                self.free_at.append(d.t_finish)
                if len(self.buffer) == self.engine.buffer_k:
                    with jax.profiler.TraceAnnotation(
                            "fl.event", buffer=self.engine.buffer_k):
                        server_state, global_params = self._fuse(
                            server_state, global_params)
                        if on_event is not None:
                            on_event(self.events[-1], global_params)
                    if self.version >= self.cfg.rounds:
                        break
        return server_state, global_params


# ---------------------------------------------------------------------------
# The runtime entry point (routed from fl/runtime.run_federated)
# ---------------------------------------------------------------------------


def run_async_federated(task, cfg, parts, get_batch, test_batches, *,
                        latency: str = "zero", log=None,
                        class_counts=None, group_spec=None, mesh=None,
                        use_kernel=None) -> dict:
    """Buffered-async counterpart of ``runtime.run_federated`` — same
    history contract, one row per FUSION EVENT instead of per round,
    plus the async columns: per-event ``staleness`` lists and the
    simulated ``sim_time`` of each event under the latency trace.

    ``cfg.rounds`` counts fusion events; ``cfg.cohort_size`` is the
    in-flight concurrency; ``cfg.buffer_k`` updates fuse per event under
    the ``cfg.staleness`` discount. ``latency`` names the trace
    (``"zero"`` | ``"pareto(a)"`` | ``"lognormal(sigma)"``,
    seed-deterministic from ``cfg.seed``). Presence-weighted group
    fusion (class_counts + group_spec on a uses_groups method) refuses —
    see ``check_async_support``."""
    from repro.fl.runtime import _count_acc

    if len(parts) != cfg.population:
        raise ValueError(
            f"run_async_federated got {len(parts)} client shards for "
            f"FLConfig.population={cfg.population}; partition with "
            "n_clients=cfg.population or fix the config")
    method = methods_lib.get(cfg.method)
    check_async_support(
        method,
        presence_weighted=(method.uses_groups
                           and class_counts is not None
                           and group_spec is not None))
    sampler = population_lib.get(cfg.sampler)
    trace = LatencyTrace.make(latency, population=cfg.population,
                              seed=cfg.seed)
    policy = parse_staleness(cfg.staleness)
    rng = np.random.default_rng(cfg.seed)
    global_params = task.init_fn(jax.random.PRNGKey(cfg.seed))
    pop = Population.from_parts(parts)
    # async-eligible methods are stateless-client (check_async_support),
    # so the store only ever holds the aux arrays here: with
    # store="mmap" the parts/weights offload to disk and every
    # per-arrival dispatch stays O(1) shards — pad_tile_inputs fancy-
    # indexes just the in-flight client's rows off the maps.
    from repro.fl import statestore as statestore_lib
    pop.use_store(statestore_lib.get(cfg.store,
                                     chunk_size=cfg.chunk_size))
    engine = make_async_engine(task, cfg, global_params, mesh=mesh,
                               use_kernel=use_kernel, method=method)
    server_state = engine.init_server_state(global_params)

    eval_engine, eval_tiles = None, None
    eval_fn = jax.jit(task.eval_fn)
    if task.predict_fn is not None:
        eval_engine = evaluation_lib.make_eval_engine(
            task.predict_fn, task.n_classes, mesh=mesh)
        eval_tiles = evaluation_lib.stage(test_batches,
                                          tile=cfg.eval_batch, mesh=mesh)

    driver = AsyncFederation(engine, pop, sampler, cfg, get_batch,
                             cfg.local_epochs * cfg.steps_per_epoch, rng,
                             trace, policy,
                             uniform_weights=(sampler.fusion_weights
                                              == "uniform"))
    history = {"round": [], "acc": [], "wall": [], "participants": [],
               "staleness": [], "sim_time": []}
    counts = []                  # device arrays; materialized at the end
    t0 = time.time()

    def on_event(rec, gp):
        # wall: as in the sync loop, stamped after the wait on the eval
        # result where ``log`` asks for it, else at dispatch
        if eval_engine is not None:
            with jax.profiler.TraceAnnotation("fl.eval",
                                              tiles=eval_tiles.n_tiles):
                c = eval_engine.run(gp, eval_tiles)
        else:
            with jax.profiler.TraceAnnotation("fl.eval",
                                              tiles=len(test_batches)):
                c = evaluation_lib.host_loop_eval(eval_fn, gp,
                                                  test_batches)
        counts.append(c)
        history["round"].append(rec["version"])
        history["participants"].append(rec["participants"])
        history["staleness"].append(list(rec["staleness"]))
        history["sim_time"].append(float(rec["sim_time"]))
        if log:
            with jax.profiler.TraceAnnotation("fl.wait"):
                acc = _count_acc(c)
        history["wall"].append(time.time() - t0)
        if log:
            with jax.profiler.TraceAnnotation("fl.log"):
                log(f"event {rec['version']:3d} acc {acc:.4f} "
                    f"staleness {rec['staleness']} "
                    f"t_sim {rec['sim_time']:.2f}")

    server_state, global_params = driver.run(server_state, global_params,
                                             on_event=on_event)
    if eval_engine is not None and task.n_classes is not None:
        conf = [np.asarray(c) for c in counts]
        history["confusion"] = conf
        history["per_class_acc"] = [evaluation_lib.per_class_accuracy(c)
                                    for c in conf]
    history["acc"] = [_count_acc(c) for c in counts]
    history["wall_total"] = time.time() - t0
    history["final_params"] = global_params
    pop.store.close()
    return history


def sync_round_times(trace: LatencyTrace, participants_per_round) -> list:
    """Simulated duration of each SYNC round under ``trace``: the round
    barrier waits for its slowest sampled client, so round r costs the
    max latency over its cohort (dispatch seqs numbered exactly as the
    sync loop would dispatch them). The async-vs-sync time-to-accuracy
    comparison of ``flbench.py bench_async`` reads sync sim time off
    this."""
    times, seq = [], 0
    for ids in participants_per_round:
        lat = 0.0
        for c in ids:
            lat = max(lat, trace.latency(int(c), seq))
            seq += 1
        times.append(lat)
    return times
