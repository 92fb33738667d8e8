"""Sharded federated round engine (DESIGN.md §5, method hooks §6,
participation §9).

ONE jit-compiled function runs a full federated round over a fixed-width
COHORT of client slots (width = ``cfg.cohort_size`` — the engine never
sees the logical population, fl/population.py):

    stacked <- broadcast(global)             # round start
    stacked, cstate <- vmap(method.client_update)(stacked, batches, cstate)
    fused   <- method.fuse(stacked)          # the only cross-cohort op
    sstate, global <- method.server_update(sstate, fused)

parameterized by *placement*:

  - ``mesh=None``   single host: the cohort axis is a plain vmapped batch.
  - ``mesh=...``    the cohort axis is sharded over the mesh "data" axis
                    (launch/mesh.py); fusion is then a mean over a sharded
                    axis and lowers to ONE all-reduce — Fed2's structural
                    pre-alignment means paired averaging (Eq. 19) costs
                    exactly FedAvg's collective, with zero matching step.

and by *method*: a ``FedMethod`` strategy (fl/methods.py) resolved from the
registry via ``methods.get(cfg.method)``. The engine never branches on the
method name — each method declares its hooks (client update, device fuse,
optional host fuse, server step) and its persistent state:

    state = {"server": <method server tree>, "clients": <stacked (C, ...)>}
    state, new_global = round_fn(state, global_params, batches, w, gw)

Because cohorts are SAMPLED from the population each round, the per-slot
fusion weights ``w`` (and fed2's presence rows ``gw``) are traced round
arguments, not engine constants — fusion renormalizes them over the
participants it sees, which keeps sampled fusion unbiased (DESIGN.md §9).

For rounds whose participant set exceeds one cohort (cohort tiling), the
engine additionally exposes the round split at the fuse boundary:
``run_tile`` executes local phase + fuse for one cohort tile, and
``finish_round`` applies the server step once to the tiles' combined
fusion result (methods opt out via ``cohort_tiling = False`` when their
server step reads per-client state).

``host_fusion`` methods (fedma) end the device program at the stacked
client params; ``method.host_fuse`` completes the round on the host (that
host gather + per-round matching cost is precisely the overhead the
paper's structural alignment removes — see launch/fl_dryrun.py records).

``lower_round`` lowers the same round function against ShapeDtypeStructs
(no arrays allocated) for dry-run compilation on any mesh — the basis of
``python -m repro.launch.fl_dryrun`` and the Makefile smoke target.
"""
from __future__ import annotations

import dataclasses
import logging
from typing import Any, Callable

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.core import fusion as fusion_lib
from repro.fl import attacks as attacks_lib
from repro.fl import codec as codec_lib
from repro.fl import compat as compat_lib
from repro.fl import methods as methods_lib
from repro.fl import robust as robust_lib
from repro.fl.methods import FedMethod, MethodContext
from repro.optim.optimizers import Optimizer

PyTree = Any

_log = logging.getLogger(__name__)


def _say_reference_path(kernel: str, why: str) -> None:
    """A TPU backend runs the compiled Pallas kernels by default; every
    place the engine takes the reference path there instead says so."""
    if jax.default_backend() == "tpu":
        _log.warning("%s kernel off on TPU: %s", kernel, why)


def _client_sharding(mesh, ndim: int) -> NamedSharding:
    """Leading cohort axis on "data", everything else replicated."""
    return NamedSharding(mesh, P("data", *([None] * (ndim - 1))))


def resolve_use_kernel(use_kernel: bool | None, mesh) -> bool:
    """The engine's effective fusion fast-path decision — THE single copy
    of the rule (consumers recording it, e.g. launch/fl_dryrun.py, call
    this instead of re-deriving it): caller's choice (None = the
    platform-driven ``fusion.default_use_kernel()``), forced off on
    multi-device meshes where the tree reduction is the path that lowers
    to one all-reduce (a warning on a TPU backend says so)."""
    if use_kernel is None:
        use_kernel = fusion_lib.default_use_kernel()
    if use_kernel and mesh is not None and mesh.size > 1:
        _say_reference_path("fusion", f"{mesh.size}-device mesh, where the "
                            "tree reduction lowers to one all-reduce")
        return False
    return bool(use_kernel)


def resolve_compute_dtype(compute_dtype, method: FedMethod):
    """The engine's mixed-precision decision — THE single copy of the
    eligibility rule (FLConfig validation and make_round_engine both call
    it): ``"float32"``/None keeps the storage dtype (the bit-identical
    default); ``"bfloat16"`` returns jnp.bfloat16 for the LOCAL phase
    (params, batches, and the broadcast global are downcast after the
    round's broadcast, and the trained params are cast back to the
    storage dtype BEFORE fusion — the fusion accumulators stay fp32,
    DESIGN.md §15). Refused for methods without
    ``FedMethod.mixed_precision``: per-client state would silently
    round-trip through bf16 across rounds, and host fusion never sees
    the fp32 accumulation."""
    if compute_dtype in (None, "", "float32"):
        return None
    if compute_dtype != "bfloat16":
        raise ValueError(
            f"unknown compute_dtype {compute_dtype!r}; choose 'float32' "
            "or 'bfloat16'")
    compat_lib.check_bf16_support(method)
    return jnp.bfloat16


def resolve_local_unroll(cfg, local_steps: int) -> int:
    """Effective scan-unroll of the local phase: ``cfg.local_unroll``
    clamped to the step count (an unroll beyond the scan length buys
    nothing and jax rejects it). 1 — the default — is the seed scan, the
    bit-identical program; unrolling batches dispatches without changing
    the step arithmetic, though XLA may refuse elementwise chains across
    the unrolled steps (equivalence is pinned at tolerance, not
    bit-exactly — tests/test_engine.py)."""
    return max(1, min(int(getattr(cfg, "local_unroll", 1)), local_steps))


def make_local_phase(task, cfg, opt: Optimizer,
                     method: FedMethod | None = None) -> Callable:
    """(stacked, batches, global_params) -> stacked after the local phase:
    the method's stateless client_update vmapped over the cohort axis (the
    decomposed reference for tests/benchmarks; stateful methods run their
    client state through the engine's round_fn instead)."""
    meth = method if method is not None else methods_lib.get(cfg.method)
    if meth.client_stateful:
        raise ValueError(
            f"{meth.name} threads per-client state through its local "
            "phase; use make_round_engine (round_fn carries the state) "
            "instead of the stateless make_local_phase reference")
    steps = cfg.local_epochs * cfg.steps_per_epoch
    ctx = MethodContext(task=task, cfg=cfg, population=cfg.population,
                        cohort_size=cfg.cohort_size,
                        local_steps=steps,
                        opt=opt, weights=None, raw_weights=None,
                        group_axes=None, group_weights=None,
                        use_kernel=False,
                        local_unroll=resolve_local_unroll(cfg, steps))

    def one_client(params, batches, global_params):
        params, _ = meth.client_update(params, batches, global_params,
                                       (), (), ctx)
        return params

    def local_phase(stacked, batches, global_params):
        return jax.vmap(one_client, in_axes=(0, 0, None))(
            stacked, batches, global_params)

    return local_phase


@dataclasses.dataclass
class RoundEngine:
    """One federated round as one compiled function over cohort slots.

    run_round threads the method's persistent state (``init_state`` builds
    round-0 state at cohort width for direct engine drives;
    ``init_client_states(gp, n)`` stacks it at population width for a
    Population):

        state, new_global = engine.run_round(state, global_params,
                                             batches, weights=w,
                                             group_weights=gw)

    ``weights``/``group_weights`` are PER-ROUND: the sampled cohort's
    sample weights (and fed2 presence rows) in slot order — fusion
    renormalizes over them, so sampling stays unbiased.

    For host_fusion methods (fedma) the device round_fn returns the
    stacked client params and ``host_fuse`` completes the round on the
    host (matching is not a device program).

    Cohort tiling (participants > cohort_size) drives ``run_tile`` per
    tile and ``finish_round`` once — see fl/runtime.py.

    Adversarial runs (DESIGN.md §14): when cfg.attack names a
    model-poisoning attack, ``attack`` holds its instance and
    ``malicious`` — a (cohort, malicious-presence row, per-round key)
    pair — is an extra traced round argument; passing None (the only
    option for honest configs) lowers the identical honest program.
    ``robust`` holds the REDUCING robust rule when one is active (the
    tiled-round refusal in fl/runtime.py reads it; pre-only rules stay
    affine and don't set it)."""
    cohort_size: int
    mesh: Any
    method: FedMethod
    round_fn: Callable
    tile_fn: Callable
    server_fn: Callable
    eval_fn: Callable
    init_state: Callable
    init_server_state: Callable
    init_client_states: Callable
    _host_fuse: Callable | None = None
    attack: Any = None
    robust: Any = None

    @staticmethod
    def _w32(w):
        return None if w is None else jnp.asarray(w, jnp.float32)

    @staticmethod
    def _mal(mal):
        if mal is None:
            return None
        row, key = mal
        return jnp.asarray(row, jnp.float32), key

    def init_client_row(self, global_params: PyTree) -> PyTree:
        """ONE client's round-0 state tree as HOST (numpy) arrays — the
        row a ``ClientStateStore`` (fl/statestore.py) broadcasts or
        persists at population width. Only this single row ever touches
        the device: population-wide storage is the store's business."""
        return jax.tree_util.tree_map(
            lambda l: np.asarray(l[0]),
            self.init_client_states(global_params, 1))

    def init_population_state(self, global_params: PyTree,
                              population: int) -> PyTree:
        """Stacked (population, ...) client state as HOST (numpy) arrays:
        the persistent population state lives outside the jitted round,
        so scatter_client_state can write cohort rows in place instead of
        copying the whole population tree on device every round. This is
        exactly ``InMemoryStore.initialize``'s broadcast (np.array makes
        it writable; device buffers are read-only) — kept as the direct
        stacked-tree entry point for benches and tests; out-of-core runs
        call ``store.initialize(engine.init_client_row(gp), P)``
        instead, which never materializes the (P, ...) stack."""
        one = self.init_client_row(global_params)
        return jax.tree_util.tree_map(
            lambda l: np.array(
                np.broadcast_to(l[None], (population,) + l.shape)), one)

    def place_cohort(self, tree: PyTree) -> PyTree:
        """Put a cohort-axis tree (batches, client states) on the mesh with
        the leading axis split over "data", so each device receives only
        its own clients. Identity without a mesh; a cohort that does not
        divide evenly is left to the round's in-graph constraint."""
        if self.mesh is None or self.cohort_size % self.mesh.shape["data"]:
            return tree
        return jax.tree_util.tree_map(
            lambda l: jax.device_put(l, _client_sharding(self.mesh,
                                                         np.ndim(l))), tree)

    def run_round(self, state: PyTree, global_params: PyTree,
                  batches: PyTree, weights=None, group_weights=None,
                  malicious=None) -> tuple:
        state = {"server": state["server"],
                 "clients": self.place_cohort(state["clients"])}
        state, out = self.round_fn(state, global_params,
                                   self.place_cohort(batches),
                                   self._w32(weights),
                                   self._w32(group_weights),
                                   self._mal(malicious))
        if self._host_fuse is not None:
            out = self.host_fuse(out, weights)
        return state, out

    def run_tile(self, client_states: PyTree, server_state: PyTree,
                 global_params: PyTree, batches: PyTree, weights=None,
                 group_weights=None, malicious=None) -> tuple:
        """One cohort tile of a tiled round: local phase + fuse only.
        Returns (new_client_states, fuse_out)."""
        return self.tile_fn(self.place_cohort(client_states), server_state,
                            global_params, self.place_cohort(batches),
                            self._w32(weights),
                            self._w32(group_weights),
                            self._mal(malicious))

    def finish_round(self, server_state: PyTree, global_params: PyTree,
                     fused: PyTree) -> tuple:
        """The server step of a tiled round, applied once to the combined
        fusion result. Only valid for ``method.cohort_tiling`` methods."""
        return self.server_fn(server_state, global_params, fused)

    def host_fuse(self, device_out: PyTree, weights=None) -> PyTree:
        """Host-side fusion completion (host_fusion methods) with the
        participants' weights."""
        return self._host_fuse(device_out, weights)


def make_round_engine(task, cfg, params_like: PyTree, *, mesh=None,
                      use_kernel: bool | None = None,
                      use_local_kernel: bool = False,
                      method: FedMethod | None = None) -> RoundEngine:
    """Build the engine for (task, cfg, method) at width cfg.cohort_size.

    params_like: a params pytree or its eval_shape — only the tree structure
    and leaf shapes are read (to derive the group-axis tree).
    use_kernel: route fusion through the Pallas flatten-to-(N, M) fast path;
    default (None) = ``fusion.default_use_kernel()``. Forced off on
    multi-device meshes, where the tree reduction is the path that lowers
    to one all-reduce (the kernel fast path is a single-host optimization;
    a 1-device mesh keeps the caller's choice so single-host dry-run
    records reflect the kernel path).
    use_local_kernel: route the default client_update's optimizer tail
    through the fused Pallas ``local_step`` kernel (DESIGN.md §15);
    a no-op for methods without ``fused_local_step`` (their
    client_update/local_opt overrides never reach the shared tail),
    logged as a warning on a TPU backend like every forced-off kernel.
    method: an explicit FedMethod instance; default resolves
    ``methods.get(cfg.method)`` from the registry.

    cfg additionally carries the §15 performance knobs, every one
    defaulting to the bit-identical seed behavior: ``compute_dtype``
    (``resolve_compute_dtype`` — bf16 local phase, fp32 fusion),
    ``codec`` (``fl/codec.py`` — decode-then-fuse uplink compression,
    ``check_codec_support`` refuses ineligible methods and lossy codecs
    under reducing robust rules), and ``local_unroll``
    (``resolve_local_unroll`` — batched local-step dispatch)."""
    meth = method if method is not None else methods_lib.get(cfg.method)
    # direct engine drives (benches, dryrun, tests) hit the same
    # capability-matrix refusals as FLConfig construction (§16)
    compat_lib.validate(cfg, meth)
    if meth.host_fusion and (
            type(meth).init_server_state is not FedMethod.init_server_state
            or type(meth).server_update is not FedMethod.server_update):
        raise ValueError(
            f"{meth.name}: host_fusion methods end the device round at the "
            "stacked params — server_update/init_server_state never run; "
            "fold server-side work into host_fuse instead")
    opt = meth.local_opt(cfg)
    n = cfg.cohort_size
    if not task.batched_clients and n > 1:
        raise ValueError(
            f"cohort_size {n}: this task's model has no batched form on the "
            "device (FLTask.batched_clients), so the engine runs one client "
            "per tile; set cohort_size 1 (the round then runs as tiles)")
    use_kernel = resolve_use_kernel(use_kernel, mesh)
    ga = None
    if meth.uses_groups and task.group_axes_fn is not None:
        ga = task.group_axes_fn(params_like)
    # adversarial knobs (DESIGN.md §14), resolved from cfg so every
    # construction path (run_federated, lower_round, direct drives) gets
    # them: only MODEL-poisoning attacks enter the traced round (data
    # poisoning happens at batch assembly); identity-shortcut robust
    # parameters (trimmed_mean(0)/norm_clip(inf)) drop the rule so the
    # compiled round stays bit-identical to plain fusion
    attack = None
    if getattr(cfg, "attack", None):
        atk = attacks_lib.parse_attack(cfg.attack).build()
        if atk.model_poisoning:
            attack = atk
    rule = None
    if getattr(cfg, "robust", None):
        rule = robust_lib.parse_robust(cfg.robust)
        robust_lib.check_robust_support(meth, rule)
        if not rule.active:
            rule = None
        elif use_kernel and rule.reduces:
            # sort-based reductions have no kernel path
            _say_reference_path("fusion", f"robust rule {rule.describe()} "
                                "reduces without it")
            use_kernel = False
    # §15 performance knobs, resolved through THE single-copy rules so
    # direct engine drives hit the same refusals as FLConfig validation
    cdtype = resolve_compute_dtype(getattr(cfg, "compute_dtype", None),
                                   meth)
    codec = None
    if getattr(cfg, "codec", None):
        codec = codec_lib.parse_codec(cfg.codec)
        codec_lib.check_codec_support(meth, codec, rule)
    steps = cfg.local_epochs * cfg.steps_per_epoch
    if use_local_kernel and not compat_lib.supports(meth, "kernel"):
        _say_reference_path("local_step", f"{meth.name} runs its own "
                            "local update")
        use_local_kernel = False
    ctx = MethodContext(task=task, cfg=cfg, population=cfg.population,
                        cohort_size=n,
                        local_steps=steps,
                        opt=opt, weights=None, raw_weights=None,
                        group_axes=ga, group_weights=None,
                        use_kernel=use_kernel,
                        robust=rule if (rule is not None and rule.reduces)
                        else None,
                        local_unroll=resolve_local_unroll(cfg, steps),
                        use_local_kernel=bool(use_local_kernel))
    meth.check(ctx)

    def init_server_state(global_params):
        return meth.init_server_state(global_params, ctx)

    def init_client_states(global_params, width):
        one = meth.init_client_state(global_params, ctx)
        return fusion_lib.broadcast_global(one, width)

    def init_state(global_params):
        return {"server": init_server_state(global_params),
                "clients": init_client_states(global_params, n)}

    def over_cohort(fn, *args):
        """``fn`` over the cohort axis of ``args``: vmapped, or, where the
        task's model has no batched form (``FLTask.batched_clients``), on
        the cohort's one client, unbatched."""
        if task.batched_clients:
            return jax.vmap(fn)(*args)
        one = fn(*jax.tree_util.tree_map(lambda l: l[0], args))
        return jax.tree_util.tree_map(lambda l: l[None], one)

    def _to_compute(t):
        # bf16 local phase (§15): downcast every float leaf, keep ints
        return jax.tree_util.tree_map(
            lambda l: l.astype(cdtype)
            if jnp.issubdtype(l.dtype, jnp.floating) else l, t)

    def local_and_fuse(clients_state, server_state, global_params, batches,
                       ctx_r, malicious):
        """The shared cohort-tile body: broadcast -> vmapped local phase
        -> device fuse (used by both round_fn and tile_fn so the two
        compile the identical per-tile program). ``malicious`` is the
        traced (presence row, round key) pair when a model-poisoning
        attack is configured, else None — an empty pytree, so honest
        configs lower the identical program.

        The §15 knobs slot in at the round boundaries: ``cdtype`` casts
        the broadcast params/batches down for the local phase and the
        trained params back to storage dtype before fusion (the fusion
        accumulators stay fp32); ``codec`` round-trips the stacked
        params through the uplink encode/decode against the round's
        global BEFORE any robust pre-step — the server defends against
        what it actually received."""
        with jax.named_scope("local"):
            stacked = fusion_lib.broadcast_global(global_params, n)
            if mesh is not None:
                constrain = lambda t: jax.lax.with_sharding_constraint(  # noqa: E731
                    t, jax.tree_util.tree_map(
                        lambda l: _client_sharding(mesh, l.ndim), t))
                stacked = constrain(stacked)
                clients_state = constrain(clients_state)
            gp_local = global_params
            if cdtype is not None:
                stacked = _to_compute(stacked)
                batches = _to_compute(batches)
                gp_local = _to_compute(global_params)
            if attack is not None and malicious is not None:
                row, key = malicious
                keys = jax.vmap(jax.random.fold_in, in_axes=(None, 0))(
                    key, jnp.arange(n))

                def one(p, b, cs, m, k):
                    p2, cs2 = meth.client_update(p, b, gp_local, cs,
                                                 server_state, ctx_r)
                    return attack.poison_update(p2, global_params, m, k), cs2

                stacked, new_clients = over_cohort(
                    one, stacked, batches, clients_state, row, keys)
            else:
                stacked, new_clients = over_cohort(
                    lambda p, b, cs: meth.client_update(
                        p, b, gp_local, cs, server_state, ctx_r),
                    stacked, batches, clients_state)
            if cdtype is not None:
                stacked = jax.tree_util.tree_map(
                    lambda l, g: l.astype(g.dtype), stacked, global_params)
        if codec is not None:
            with jax.named_scope("codec"):
                stacked = codec.roundtrip(stacked, global_params)
        with jax.named_scope("fuse"):
            if rule is not None and rule.has_pre:
                stacked = rule.pre(stacked, global_params)
            fused = meth.fuse(stacked, global_params, ctx_r)
        return new_clients, fused

    def round_fn(state, global_params, batches, weights, group_weights,
                 malicious):
        ctx_r = dataclasses.replace(ctx, weights=weights,
                                    group_weights=group_weights)
        new_clients, fused = local_and_fuse(
            state["clients"], state["server"], global_params, batches,
            ctx_r, malicious)
        if meth.host_fusion:
            return {"server": state["server"],
                    "clients": new_clients}, fused
        with jax.named_scope("server"):
            new_server, new_global = meth.server_update(
                state["server"], state["clients"], new_clients,
                global_params, fused, ctx_r)
        return {"server": new_server, "clients": new_clients}, new_global

    def tile_fn(clients_state, server_state, global_params, batches,
                weights, group_weights, malicious):
        ctx_r = dataclasses.replace(ctx, weights=weights,
                                    group_weights=group_weights)
        return local_and_fuse(clients_state, server_state, global_params,
                              batches, ctx_r, malicious)

    def server_fn(server_state, global_params, fused):
        # tiled rounds: the server step sees no client states (methods
        # that read them declare cohort_tiling = False and never get here)
        with jax.named_scope("server"):
            return meth.server_update(server_state, (), (), global_params,
                                      fused, ctx)

    host_fuse = None
    if meth.host_fusion:
        def host_fuse(out, weights):
            ctx_h = ctx if weights is None else dataclasses.replace(
                ctx, raw_weights=weights)
            return meth.host_fuse(out, ctx_h)

    return RoundEngine(cohort_size=n, mesh=mesh, method=meth,
                       round_fn=jax.jit(round_fn),
                       tile_fn=jax.jit(tile_fn),
                       server_fn=jax.jit(server_fn),
                       eval_fn=jax.jit(task.eval_fn),
                       init_state=init_state,
                       init_server_state=init_server_state,
                       init_client_states=init_client_states,
                       _host_fuse=host_fuse,
                       attack=attack,
                       robust=rule if (rule is not None and rule.reduces)
                       else None)


# ---------------------------------------------------------------------------
# Dry-run lowering (no arrays allocated)
# ---------------------------------------------------------------------------


def lower_round(task, cfg, mesh, batch_elems: dict, *, local_steps: int,
                use_kernel: bool | None = None):
    """Lower one full round on ``mesh`` from ShapeDtypeStructs.

    batch_elems: per-sample batch element specs WITHOUT the leading
    (cohort, steps) axes, e.g. ``{"images": ((B, 32, 32, 3), jnp.float32),
    "labels": ((B,), jnp.int32)}``. use_kernel threads the caller's fusion
    fast-path choice to the engine (multi-device meshes still force it
    off). cfg's own step-count fields are overridden so that
    ``ctx.local_steps`` — which method numerics read (scaffold's K*lr,
    fednova's tau) — equals the ``local_steps`` the lowered round scans.
    The per-round cohort weights lower as a replicated (cohort_size,)
    f32 argument; ``uses_groups`` methods additionally lower a
    replicated (cohort_size, n_groups) f32 group-weights argument — the
    presence rows fl/runtime.py passes every round, so the dry-run gate
    covers the presence-weighted fusion program rather than the
    unweighted special case (lowering gw=None used to compile a round
    the sampled-participation path never runs). A model-poisoning
    cfg.attack adds the replicated malicious-presence row + round-key
    specs (honest configs pass None — an empty pytree, so their lowering
    is unchanged). Returns the jax ``Lowered`` for
    ``round_fn(state_specs, global_specs, batch_specs, w_spec, gw_spec,
    mal_specs)``.
    """
    cfg = dataclasses.replace(cfg, local_epochs=1,
                              steps_per_epoch=local_steps)
    n = cfg.cohort_size
    param_shapes = jax.eval_shape(task.init_fn, jax.random.PRNGKey(0))
    engine = make_round_engine(task, cfg, param_shapes, mesh=mesh,
                               use_kernel=use_kernel)

    def spec(l, sharding):
        return jax.ShapeDtypeStruct(l.shape, l.dtype, sharding=sharding)

    gspecs = jax.tree_util.tree_map(
        lambda l: spec(l, NamedSharding(mesh, P())), param_shapes)
    state_shapes = jax.eval_shape(engine.init_state, param_shapes)
    sspecs = {
        "server": jax.tree_util.tree_map(
            lambda l: spec(l, NamedSharding(mesh, P())),
            state_shapes["server"]),
        "clients": jax.tree_util.tree_map(
            lambda l: spec(l, _client_sharding(mesh, l.ndim)),
            state_shapes["clients"]),
    }
    bspecs = {
        name: jax.ShapeDtypeStruct(
            (n, local_steps) + tuple(shape), dtype,
            sharding=_client_sharding(mesh, 2 + len(shape)))
        for name, (shape, dtype) in batch_elems.items()
    }
    wspec = jax.ShapeDtypeStruct((n,), jnp.float32,
                                 sharding=NamedSharding(mesh, P()))
    gwspec = None
    if engine.method.uses_groups:
        gaxes = [g for g in jax.tree_util.tree_leaves(
                     task.group_axes_fn(param_shapes),
                     is_leaf=lambda x: isinstance(x, fusion_lib.GroupAxis))
                 if isinstance(g, fusion_lib.GroupAxis)]
        gwspec = jax.ShapeDtypeStruct((n, gaxes[0].n_groups), jnp.float32,
                                      sharding=NamedSharding(mesh, P()))
    mspec = None
    if engine.attack is not None:
        kshape = jax.eval_shape(lambda: jax.random.PRNGKey(0))
        mspec = (jax.ShapeDtypeStruct((n,), jnp.float32,
                                      sharding=NamedSharding(mesh, P())),
                 jax.ShapeDtypeStruct(kshape.shape, kshape.dtype,
                                      sharding=NamedSharding(mesh, P())))
    with mesh:
        return engine.round_fn.lower(sspecs, gspecs, bspecs, wspec, gwspec,
                                     mspec)


def stacked_param_bytes(task, n_clients: int) -> int:
    """Size of the stacked client tree — what a host-side fusion (fedma)
    must gather off-device every round."""
    shapes = jax.eval_shape(task.init_fn, jax.random.PRNGKey(0))
    return n_clients * sum(
        int(np.prod(l.shape)) * l.dtype.itemsize
        for l in jax.tree_util.tree_leaves(shapes))
