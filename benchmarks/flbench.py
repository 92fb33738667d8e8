"""Shared FL benchmark runner (paper experiment scaffolding, CPU-scaled).

Scaling note (EXPERIMENTS.md §Scaling): the paper runs 10-100 clients x
50-100 rounds of VGG9/VGG16/MobileNet on CIFAR; this container is one CPU
core. Benchmarks keep the paper's PROTOCOL (N x C / Dirichlet partitions,
methods, metrics) at reduced extent (nodes, rounds, channels) and validate
RELATIVE orderings, not absolute accuracies.
"""
from __future__ import annotations

import json
import os
import time

import jax.numpy as jnp
import numpy as np

from repro.configs import vgg9, vgg16, mobilenet
from repro.data.synthetic import (dirichlet_partition, make_image_dataset,
                                  nxc_partition)
from repro.fl import methods as methods_lib
from repro.fl.runtime import FLConfig, cnn_task, run_federated

ARTIFACTS = os.path.join(os.path.dirname(__file__), "artifacts")
QUICK = os.environ.get("REPRO_BENCH_QUICK", "1") == "1"

N_CLASSES = 10
NOISE = 1.2   # calibrated: centralized VGG9-reduced reaches ~0.85-0.98 at
              # the per-benchmark step budget, leaving FL-ordering headroom
_cache = {}


def dataset():
    if "ds" not in _cache:
        _cache["ds"] = make_image_dataset(3000, n_classes=N_CLASSES, seed=0,
                                          noise=NOISE)
        _cache["test"] = make_image_dataset(600, n_classes=N_CLASSES,
                                            seed=99, noise=NOISE)
    return _cache["ds"], _cache["test"]


_BENCH_PLANS = {
    # width-calibrated reduced nets: per-group capacity >= ~10 channels at
    # G=5 (the grouping-viability threshold found in the tuning sweep)
    "vgg9": ((("c", 24), ("p",), ("c", 48), ("p",), ("c", 48), ("p",)),
             (160,)),
    "vgg16": ((("c", 24), ("p",), ("c", 48), ("p",), ("c", 48), ("c", 48),
               ("p",)), (160,)),
    "mobilenet": ((("c", 24), ("dw", 48, 2), ("dw", 48, 1), ("dw", 96, 2)),
                  ()),
}


def model_cfg(arch: str, method: str, *, groups=5, decouple=2, norm=None):
    """Group-structured net for group-structured methods (registry
    capability flag), plain baseline net otherwise."""
    from repro.models.cnn import CNNConfig
    plan, fc = _BENCH_PLANS[arch]
    if methods_lib.get(method).uses_groups:
        return CNNConfig(arch_id=f"{arch}-bench", plan=plan, fc_dims=fc,
                         n_classes=N_CLASSES, fed2_groups=groups,
                         decouple=decouple, norm=norm or "gn")
    return CNNConfig(arch_id=f"{arch}-bench", plan=plan, fc_dims=fc,
                     n_classes=N_CLASSES, fed2_groups=0,
                     norm=norm or "none")


def run_case(name: str, method: str, *, arch="vgg9", nodes=6, cpn=None,
             alpha=None, rounds=None, local_epochs=1, steps_per_epoch=8,
             batch=16, lr=0.008, seed=0, cfg=None, cohort_size=None,
             sampler="full") -> dict:
    rounds = rounds or (8 if QUICK else 14)
    ds, test = dataset()
    if alpha is not None:
        parts = dirichlet_partition(ds.labels, nodes, alpha, N_CLASSES,
                                    seed=seed)
    else:
        parts = nxc_partition(ds.labels, nodes, cpn or N_CLASSES, N_CLASSES,
                              seed=seed)

    def get_batch(sel):
        return {"images": ds.images[sel], "labels": ds.labels[sel]}

    test_batches = [{"images": jnp.asarray(test.images),
                     "labels": jnp.asarray(test.labels)}]
    cfg = cfg if cfg is not None else model_cfg(arch, method)
    fl = FLConfig(population=nodes, cohort_size=cohort_size,
                  sampler=sampler, rounds=rounds, local_epochs=local_epochs,
                  steps_per_epoch=steps_per_epoch, batch_size=batch, lr=lr,
                  momentum=0.9, method=method, seed=seed)
    # Presence-weighted pairing is OPT-IN: the calibration study showed it
    # HURTS (−0.2 acc) — nodes lacking group g's classes still provide the
    # negative (softmax-suppression) signal that calibrates cross-group
    # logit scales. Kept available for the high-skew regimes where it was
    # designed (EXPERIMENTS.md §Boundary).
    class_counts, spec = None, None
    if methods_lib.get(method).uses_groups and cfg.fed2_groups and \
            os.environ.get("REPRO_FED2_PRESENCE", "0") == "1":
        from repro.core.grouping import GroupSpec
        spec = GroupSpec.contiguous(cfg.fed2_groups, N_CLASSES)
        class_counts = np.stack([
            np.bincount(ds.labels[p], minlength=N_CLASSES) for p in parts])
    t0 = time.time()
    h = run_federated(cnn_task(cfg), fl, parts, get_batch, test_batches,
                      class_counts=class_counts, group_spec=spec)
    rec = {"name": name, "method": method, "arch": arch, "nodes": nodes,
           "cpn": cpn, "alpha": alpha, "rounds": rounds,
           "local_epochs": local_epochs, "acc": h["acc"],
           "final_acc": h["acc"][-1], "best_acc": max(h["acc"]),
           "wall_s": round(time.time() - t0, 1)}
    os.makedirs(ARTIFACTS, exist_ok=True)
    with open(os.path.join(ARTIFACTS, f"fl_{name}.json"), "w") as f:
        json.dump(rec, f, indent=1)
    return rec


def csv_line(rec, extra=""):
    epochs = rec["rounds"] * rec["local_epochs"]
    return (f"{rec['name']},{rec['wall_s'] * 1e6 / max(epochs, 1):.0f},"
            f"best_acc={rec['best_acc']:.4f}{extra}")


# ---------------------------------------------------------------------------
# Engine throughput: one jitted round vs the seed-style host loop
# ---------------------------------------------------------------------------

ARTIFACTS_PERF = os.path.join(os.path.dirname(__file__), "artifacts_perf")


def _engine_fixture(nodes, steps_per_epoch, batch):
    """Shared setup for the engine benchmarks: partition, packed batch
    set (fixed rng), and max-1-floored sample weights."""
    from repro.fl.runtime import _pack_client_batches

    ds, _ = dataset()
    parts = nxc_partition(ds.labels, nodes, 5, N_CLASSES, seed=0)

    def get_batch(sel):
        return {"images": ds.images[sel], "labels": ds.labels[sel]}

    batches = _pack_client_batches(parts, get_batch, steps_per_epoch,
                                   batch, np.random.default_rng(0))
    weights = np.maximum([len(p) for p in parts], 1).astype(np.float64)
    return batches, weights


def bench_engine(*, nodes=4, rounds=None, steps_per_epoch=6,
                 batch=16, local_unroll=6, codec="int8") -> dict:
    """Steady-state rounds/sec: the jitted round engine vs the seed-style
    loop, both warmed up (compile excluded) and fed the same fixed batch
    set. Three engine rows (DESIGN.md §15):

      engine           the default config — bit-comparable to the seed
                       loop (final params must agree to 1e-4)
      engine_fused     + local_unroll batched dispatch (the fused local
                       phase; same arithmetic, tolerance-equal params).
                       Its speedup is the record's headline ``speedup``
                       — the number the honest-numbers tables quote.
      engine_bf16_*    + bf16 local phase + uplink codec; its row also
                       carries the per-client uplink bytes against the
                       dense uplink (the compression economics).

    If a committed flbench_engine.json exists, a fresh headline speedup
    more than 20% below it prints a NON-BLOCKING [WARN] (wall clock is
    machine noise; the committed number is the claim)."""
    import jax
    from repro.core import fusion as fusion_lib
    from repro.fl import codec as codec_lib
    from repro.fl.engine import (make_local_phase, make_round_engine,
                                 stacked_param_bytes)
    from repro.optim.optimizers import sgd

    rounds = rounds or (6 if QUICK else 14)
    batches, weights = _engine_fixture(nodes, steps_per_epoch, batch)
    cfg = model_cfg("vgg9", "fed2")
    task = cnn_task(cfg)
    gp0 = task.init_fn(jax.random.PRNGKey(0))

    def fl_cfg(**kw):
        return FLConfig(population=nodes, rounds=rounds, local_epochs=1,
                        steps_per_epoch=steps_per_epoch, batch_size=batch,
                        lr=0.008, momentum=0.9, method="fed2", seed=0,
                        **kw)

    # -- the seed-style loop: host-driven broadcast/local/fuse, synced
    #    every round (the pre-engine reference semantics)
    fl0 = fl_cfg()
    local = jax.jit(make_local_phase(task, fl0, sgd(fl0.lr, fl0.momentum)))
    ga = task.group_axes_fn(gp0)

    def seed_round(g):
        stacked = fusion_lib.broadcast_global(g, nodes)
        stacked = local(stacked, batches, g)
        out = fusion_lib.paired_average(stacked, ga, weights=weights)
        jax.block_until_ready(out)    # the seed loop synced every round
        return out

    seed_round(gp0)                                           # compile
    t0 = time.time()
    g_s = gp0
    for _ in range(rounds):
        g_s = seed_round(g_s)
    seed_s = time.time() - t0
    seed_leaves = jax.tree_util.tree_leaves(g_s)

    def engine_row(name, fl, **extra):
        engine = make_round_engine(task, fl, gp0)
        state0 = engine.init_state(gp0)
        jax.block_until_ready(engine.run_round(state0, gp0, batches,
                                               weights=weights))  # compile
        t0 = time.time()
        st, g = state0, gp0
        for _ in range(rounds):
            st, g = engine.run_round(st, g, batches, weights=weights)
        jax.block_until_ready(g)
        dt = time.time() - t0
        diff = max(float(jnp.max(jnp.abs(
            a.astype(jnp.float32) - b.astype(jnp.float32))))
            for a, b in zip(jax.tree_util.tree_leaves(g), seed_leaves))
        return {"name": name, "s": round(dt, 3),
                "rounds_per_s": round(rounds / dt, 3),
                "speedup_vs_seed": round(seed_s / dt, 3),
                "max_param_diff": diff, **extra}

    base = engine_row("engine", fl_cfg())
    fused = engine_row("engine_fused", fl_cfg(local_unroll=local_unroll),
                       local_unroll=local_unroll)
    dense = stacked_param_bytes(task, 1)
    up = codec_lib.parse_codec(codec).bytes_per_client(
        jax.eval_shape(task.init_fn, jax.random.PRNGKey(0)))
    fast = engine_row(f"engine_bf16_{codec.split('(', 1)[0]}",
                      fl_cfg(local_unroll=local_unroll,
                             compute_dtype="bfloat16", codec=codec),
                      local_unroll=local_unroll,
                      compute_dtype="bfloat16", codec=codec,
                      uplink_bytes_per_client=up,
                      dense_bytes_per_client=dense,
                      uplink_frac=round(up / dense, 4))

    rec = {"name": "flbench_engine", "nodes": nodes, "rounds": rounds,
           "method": "fed2",
           "seed_loop_s": round(seed_s, 3),
           "seed_rounds_per_s": round(rounds / seed_s, 3),
           # headline: the fp32 fused-dispatch row — same arithmetic as
           # the seed loop, so its speedup is the apples-to-apples claim
           "engine_s": fused["s"],
           "engine_rounds_per_s": fused["rounds_per_s"],
           "speedup": fused["speedup_vs_seed"],
           "max_param_diff": fused["max_param_diff"],
           # two separate claims: the default fp32 engine reproduces the
           # seed loop BIT-identically (params_match), while the unrolled
           # row is tolerance-class — XLA re-association drift compounds
           # through training, so the bound scales with the round count
           "params_match": bool(base["max_param_diff"] == 0.0),
           "fused_within_tol": bool(
               fused["max_param_diff"] < 5e-4 * rounds),
           "rows": [base, fused, fast]}
    path = os.path.join(ARTIFACTS_PERF, "flbench_engine.json")
    if os.path.exists(path):      # WARN vs the committed claim, never red
        try:
            with open(path) as f:
                old = json.load(f).get("speedup")
        except (OSError, ValueError):
            old = None
        if isinstance(old, (int, float)) and rec["speedup"] < 0.8 * old:
            print(f"[WARN] flbench_engine: fresh speedup "
                  f"{rec['speedup']:.2f}x fell >20% below the committed "
                  f"{old:.2f}x (non-blocking: wall clock is machine "
                  "noise; regenerate+commit if the regression is real)")
    os.makedirs(ARTIFACTS_PERF, exist_ok=True)
    with open(path, "w") as f:
        json.dump(rec, f, indent=1)
    return rec


def bench_methods(*, nodes=4, rounds=None, steps_per_epoch=4,
                  batch=16) -> list:
    """Steady-state rounds/sec for EVERY registered method (the registry
    is the enumeration — a newly registered strategy shows up here with no
    benchmark change), same data/partition/net family per method."""
    import jax
    from repro.fl.engine import make_round_engine

    rounds = rounds or (4 if QUICK else 10)
    batches, weights = _engine_fixture(nodes, steps_per_epoch, batch)
    recs = []
    for method in methods_lib.available():
        cfg = model_cfg("vgg9", method)
        fl = FLConfig(population=nodes, rounds=rounds, local_epochs=1,
                      steps_per_epoch=steps_per_epoch, batch_size=batch,
                      lr=0.008, momentum=0.9, method=method, seed=0)
        task = cnn_task(cfg)
        gp = task.init_fn(jax.random.PRNGKey(0))
        engine = make_round_engine(task, fl, gp)
        state = engine.init_state(gp)
        state, gp = engine.run_round(state, gp, batches,
                                     weights=weights)     # compile
        jax.block_until_ready(gp)
        t0 = time.time()
        for _ in range(rounds):
            state, gp = engine.run_round(state, gp, batches,
                                         weights=weights)
        jax.block_until_ready(gp)
        dt = time.time() - t0
        recs.append({"method": method, "rounds": rounds,
                     "rounds_per_s": round(rounds / dt, 3),
                     "us_per_round": round(1e6 * dt / rounds)})
    os.makedirs(ARTIFACTS_PERF, exist_ok=True)
    with open(os.path.join(ARTIFACTS_PERF, "flbench_methods.json"),
              "w") as f:
        json.dump(recs, f, indent=1)
    return recs


def _rss_mb() -> float:
    """Current resident set (VmRSS, MB) from /proc — the O(cohort)
    server-memory evidence column of bench_cohort."""
    try:
        with open("/proc/self/status") as f:
            for line in f:
                if line.startswith("VmRSS:"):
                    return round(int(line.split()[1]) / 1024, 1)
    except OSError:
        pass
    return float("nan")


def bench_cohort(*, populations=None, cohort=8, rounds=None,
                 steps_per_epoch=4, batch=16, method="fedavg",
                 sampler="weighted", store="mmap",
                 chunk_size=4096) -> list:
    """Rounds/sec AND resident memory of the sampled host loop vs
    population size at a fixed cohort (engine width), at out-of-core
    scale: 10^4 / 10^5 / 10^6 logical clients (DESIGN.md §9, §13).

    The engine compiles once per cohort width; the client-state store
    (fl/statestore.py) keeps per-client rows and the population's aux
    arrays (shard indices, weights) on disk; the weighted sampler draws
    from a Walker alias table (O(P) build once, O(cohort log P) per
    round). So growing the population 100x must leave steady-state
    rounds/sec flat (±10%) and peak RSS O(cohort), not O(P) — the two
    claims the committed flbench_cohort.json pins. O(P) setup (striped
    partition, alias build, aux offload) happens before the timer.

    ``REPRO_BENCH_POPULATIONS`` (comma-separated) overrides the
    population ladder — CI smoke runs the 10^4 rung only."""
    import jax

    if populations is None:
        env = os.environ.get("REPRO_BENCH_POPULATIONS", "")
        populations = (tuple(int(x) for x in env.split(",") if x)
                       if env else (10_000, 100_000, 1_000_000))
    rounds = rounds or (4 if QUICK else 10)
    ds, _ = dataset()

    def get_batch(sel):
        return {"images": ds.images[sel], "labels": ds.labels[sel]}

    from repro.fl.population import Population
    from repro.fl import population as population_lib
    from repro.fl import statestore as statestore_lib
    from repro.fl.engine import make_round_engine
    from repro.fl.runtime import run_sampled_round
    from repro.fl.statestore import ShardIndices

    recs = []
    cfg = model_cfg("vgg9", method)
    task = cnn_task(cfg)
    meth = methods_lib.get(method)
    smp = population_lib.get(sampler)
    gp0 = task.init_fn(jax.random.PRNGKey(0))
    # ONE engine for every population: the compiled round is cohort-width
    # parameterized — that invariance is the point of the benchmark.
    # (ctx.population is only read by scaffold's server scale; reusing
    # the engine across populations is exact for stateless methods.)
    engine = make_round_engine(
        task, FLConfig(population=populations[0], cohort_size=cohort,
                       sampler=sampler, rounds=rounds, local_epochs=1,
                       steps_per_epoch=steps_per_epoch, batch_size=batch,
                       lr=0.008, momentum=0.9, method=method, seed=0),
        gp0)
    for population in populations:
        # striped synthetic partition: two vectorized ops, no P-element
        # python list (nxc_partition's per-client loop IS an O(P) server
        # cost this bench exists to avoid)
        parts = ShardIndices.striped(len(ds.labels), population)
        fl = FLConfig(population=population, cohort_size=cohort,
                      sampler=sampler, rounds=rounds, local_epochs=1,
                      steps_per_epoch=steps_per_epoch, batch_size=batch,
                      lr=0.008, momentum=0.9, method=method, seed=0,
                      store=store, chunk_size=chunk_size)
        pop = Population.from_parts(parts)
        pop.use_store(statestore_lib.get(store, chunk_size=chunk_size))
        gp = gp0
        server = engine.init_server_state(gp)
        pop.store.initialize(engine.init_client_row(gp), pop.size)
        rng = np.random.default_rng(0)

        uniform_w = smp.fusion_weights == "uniform"

        def one_round(r, server, gp):
            ids = smp.sample(r, population, cohort, rng,
                             weights=pop.weights)
            return run_sampled_round(engine, pop, meth, server, gp, ids,
                                     get_batch, steps_per_epoch, fl, rng,
                                     uniform_weights=uniform_w)

        server, gp = one_round(0, server, gp)              # compile +
        jax.block_until_ready(gp)                          # alias build
        t0 = time.time()
        for r in range(1, rounds + 1):
            server, gp = one_round(r, server, gp)
        jax.block_until_ready(gp)
        dt = time.time() - t0
        import resource
        recs.append({"population": population, "cohort_size": cohort,
                     "sampler": sampler, "method": method,
                     "store": store, "chunk_size": chunk_size,
                     "rounds": rounds,
                     "rounds_per_s": round(rounds / dt, 3),
                     "us_per_round": round(1e6 * dt / rounds),
                     "rss_mb": _rss_mb(),
                     "peak_rss_mb": round(
                         resource.getrusage(
                             resource.RUSAGE_SELF).ru_maxrss / 1024, 1)})
        pop.store.close()
    os.makedirs(ARTIFACTS_PERF, exist_ok=True)
    with open(os.path.join(ARTIFACTS_PERF, "flbench_cohort.json"),
              "w") as f:
        json.dump(recs, f, indent=1)
    return recs


def bench_tiers(*, population=6, rounds=None, steps_per_epoch=4,
                batch=16, mix=((1.0, 2), (0.5, 2), (0.25, 2)),
                method="fedavg") -> dict:
    """Heterogeneous-capacity rounds/sec and uplink bytes vs the
    homogeneous baseline (fl/capacity.py, DESIGN.md §11): the same
    population/partition/net runs once with every client full-width and
    once under the tier mix. Uplink per round = Σ over participants of
    their (tier) sub-model bytes — width-w tiers scale both in- and
    out-channels, so a 0.25-width tier uplinks ~1/16 the dense bytes."""
    import jax
    from repro.fl.capacity import TierPlan, cnn_tier_model
    from repro.fl.engine import stacked_param_bytes

    rounds = rounds or (4 if QUICK else 10)
    ds, test = dataset()
    parts = nxc_partition(ds.labels, population, 5, N_CLASSES, seed=0)

    def get_batch(sel):
        return {"images": ds.images[sel], "labels": ds.labels[sel]}

    test_batches = [{"images": jnp.asarray(test.images),
                     "labels": jnp.asarray(test.labels)}]
    cfg = model_cfg("vgg9", method)
    task = cnn_task(cfg)

    def timed_run(tiers):
        fl = FLConfig(population=population, rounds=rounds,
                      local_epochs=1, steps_per_epoch=steps_per_epoch,
                      batch_size=batch, lr=0.008, momentum=0.9,
                      method=method, seed=0, tiers=tiers)
        t0 = time.time()
        h = run_federated(task, fl, parts, get_batch, test_batches)
        jax.block_until_ready(h["final_params"])
        return h, time.time() - t0

    h_hom, hom_s = timed_run(None)
    h_tier, tier_s = timed_run(mix)

    full_bytes = stacked_param_bytes(task, 1)
    plan = TierPlan.from_mix(mix, population, seed=0)
    tier_bytes = {w: cnn_tier_model(cfg, w).param_bytes for w, _ in mix}
    uplink_tiered = sum(c * tier_bytes[w] for w, c in mix)
    uplink_hom = population * full_bytes
    rec = {"name": "flbench_tiers", "population": population,
           "rounds": rounds, "method": method,
           "mix": [[w, c] for w, c in plan.mix],
           "hom_s": round(hom_s, 3), "tier_s": round(tier_s, 3),
           "hom_rounds_per_s": round(rounds / hom_s, 3),
           "tier_rounds_per_s": round(rounds / tier_s, 3),
           "uplink_bytes_per_round_hom": uplink_hom,
           "uplink_bytes_per_round_tiered": uplink_tiered,
           "uplink_frac": round(uplink_tiered / uplink_hom, 4),
           "tier_uplink_frac": {f"{w:g}": round(b / full_bytes, 4)
                                for w, b in tier_bytes.items()},
           "hom_final_acc": round(float(h_hom["acc"][-1]), 4),
           "tier_final_acc": round(float(h_tier["acc"][-1]), 4)}
    os.makedirs(ARTIFACTS_PERF, exist_ok=True)
    with open(os.path.join(ARTIFACTS_PERF, "flbench_tiers.json"),
              "w") as f:
        json.dump(rec, f, indent=1)
    return rec


def bench_eval(*, n_eval=4096, eval_batches=(128, 512), repeats=None) \
        -> list:
    """Evaluation throughput: the jitted tiled engine (fl/evaluation.py
    — ONE dispatch over the staged tiles, confusion counts included) vs
    the seed host loop (one jit dispatch per eval batch, mean of
    per-batch accuracies) on the same staged eval set, per tile width.
    Both warmed up; accuracies must agree (equal-width batches)."""
    import jax
    from repro.fl import evaluation as evaluation_lib

    repeats = repeats or (10 if QUICK else 30)
    cfg = model_cfg("vgg9", "fedavg")
    task = cnn_task(cfg)
    params = task.init_fn(jax.random.PRNGKey(0))
    test = make_image_dataset(n_eval, n_classes=N_CLASSES, seed=99,
                              noise=NOISE)
    recs = []
    for eb in eval_batches:
        batches = [{"images": jnp.asarray(test.images[s:s + eb]),
                    "labels": jnp.asarray(test.labels[s:s + eb])}
                   for s in range(0, n_eval, eb)]
        eval_jit = jax.jit(task.eval_fn)
        ref = evaluation_lib.host_loop_eval(eval_jit, params, batches)
        jax.block_until_ready(ref)                          # compile
        t0 = time.time()
        for _ in range(repeats):
            out = evaluation_lib.host_loop_eval(eval_jit, params, batches)
        jax.block_until_ready(out)
        host_s = time.time() - t0

        engine = evaluation_lib.make_eval_engine(task.predict_fn,
                                                 N_CLASSES)
        tiles = evaluation_lib.stage(batches, tile=eb)
        conf = engine.run(params, tiles)
        jax.block_until_ready(conf)                         # compile
        t0 = time.time()
        for _ in range(repeats):
            conf = engine.run(params, tiles)
        jax.block_until_ready(conf)
        engine_s = time.time() - t0

        acc = evaluation_lib.accuracy(np.asarray(conf))
        recs.append({
            "eval_batch": eb, "n_eval": n_eval, "repeats": repeats,
            "engine_path": ("host_dispatch" if tiles.host_dispatch
                            else "fused"),
            "n_tiles": tiles.n_tiles,
            "host_loop_s": round(host_s, 3),
            "engine_s": round(engine_s, 3),
            "host_evals_per_s": round(repeats / host_s, 3),
            "engine_evals_per_s": round(repeats / engine_s, 3),
            "speedup": round(host_s / engine_s, 3),
            "engine_acc": round(acc, 6),
            "host_acc": round(float(ref), 6),
            "acc_match": bool(abs(acc - float(ref)) < 1e-6)})
    os.makedirs(ARTIFACTS_PERF, exist_ok=True)
    with open(os.path.join(ARTIFACTS_PERF, "flbench_eval.json"),
              "w") as f:
        json.dump(recs, f, indent=1)
    return recs


def bench_async(*, population=8, cohort_size=4, buffer_k=2,
                staleness="polynomial(0.5)", latency="pareto(1.1)",
                rounds=None, steps_per_epoch=4, batch=16,
                method="fedavg") -> dict:
    """Buffered-async vs sync under stragglers (fl/async_engine.py,
    DESIGN.md §12): the same population/partition/net runs once in
    lockstep rounds and once buffered-async, under the SAME
    seed-deterministic heavy-tail latency trace. The sync barrier pays
    the slowest sampled client every round (``sync_round_times``); the
    async driver keeps ``cohort_size`` clients in flight and fuses every
    ``buffer_k`` arrivals, so its simulated clock advances at the
    buffer's pace. Both runs get the same client-update budget
    (``rounds * cohort_size`` updates = ``rounds * C / K`` fusion
    events) and are compared on simulated time to the shared target
    accuracy (the weaker run's best — both runs provably reach it).
    The partition is IID: this bench isolates the STRAGGLER effect (the
    clock), so both accuracy curves must be smooth enough for
    time-to-target to mean something at laptop scale — heterogeneity
    orderings stay with the scenario matrix/claims suite."""
    import jax
    from repro.fl.async_engine import LatencyTrace, sync_round_times

    rounds = rounds or (8 if QUICK else 14)
    events = rounds * cohort_size // buffer_k
    ds, test = dataset()
    parts = nxc_partition(ds.labels, population, N_CLASSES, N_CLASSES,
                          seed=0)

    def get_batch(sel):
        return {"images": ds.images[sel], "labels": ds.labels[sel]}

    test_batches = [{"images": jnp.asarray(test.images),
                     "labels": jnp.asarray(test.labels)}]
    cfg = model_cfg("vgg9", method)
    task = cnn_task(cfg)

    def timed_run(**kw):
        fl = FLConfig(population=population, cohort_size=cohort_size,
                      sampler="uniform", local_epochs=1,
                      steps_per_epoch=steps_per_epoch, batch_size=batch,
                      lr=0.008, momentum=0.9, method=method, seed=0, **kw)
        t0 = time.time()
        h = run_federated(task, fl, parts, get_batch, test_batches,
                          latency=("zero" if fl.mode == "sync"
                                   else latency))
        jax.block_until_ready(h["final_params"])
        return h, time.time() - t0

    h_sync, sync_s = timed_run(rounds=rounds)
    h_async, async_s = timed_run(rounds=events, mode="async",
                                 buffer_k=buffer_k, staleness=staleness)

    # simulated clocks under the ONE committed trace: sync rounds end at
    # the cumulative per-round straggler max, async events at their
    # buffer-filling arrival
    trace = LatencyTrace.make(latency, population=population, seed=0)
    sync_t = np.cumsum(sync_round_times(trace, h_sync["participants"]))
    async_t = np.asarray(h_async["sim_time"])

    def time_to(ts, accs, target):
        for t, a in zip(ts, accs):
            if a >= target:
                return float(t)
        return None

    target = round(min(max(h_sync["acc"]), max(h_async["acc"])), 4)
    sync_tt = time_to(sync_t, h_sync["acc"], target)
    async_tt = time_to(async_t, h_async["acc"], target)
    rec = {"name": "flbench_async", "population": population,
           "cohort_size": cohort_size, "buffer_k": buffer_k,
           "method": method, "staleness": staleness, "latency": latency,
           "rounds_sync": rounds, "events_async": events,
           "sync_s": round(sync_s, 3), "async_s": round(async_s, 3),
           "sync_rounds_per_s": round(rounds / sync_s, 3),
           "async_events_per_s": round(events / async_s, 3),
           "target_acc": target,
           "sync_sim_time_to_target": round(sync_tt, 3),
           "async_sim_time_to_target": round(async_tt, 3),
           "sim_speedup_to_target": round(sync_tt / async_tt, 3),
           "sync_sim_total": round(float(sync_t[-1]), 3),
           "async_sim_total": round(float(async_t[-1]), 3),
           "sync_final_acc": round(float(h_sync["acc"][-1]), 4),
           "async_final_acc": round(float(h_async["acc"][-1]), 4),
           "max_staleness": int(max(max(s) for s in
                                    h_async["staleness"]))}
    os.makedirs(ARTIFACTS_PERF, exist_ok=True)
    with open(os.path.join(ARTIFACTS_PERF, "flbench_async.json"),
              "w") as f:
        json.dump(rec, f, indent=1)
    return rec


ROBUST_RULES = ("mean", "coordinate_median", "trimmed_mean(0.2)")


def bench_robust(*, cohorts=(8, 32), rounds=None, steps_per_epoch=4,
                 batch=16, method="fedavg") -> list:
    """Steady-state rounds/sec of robust fusion vs the plain weighted
    mean (fl/robust.py, DESIGN.md §14), same data/partition/net per
    cohort width. Reducing rules replace fusion's O(n) affine sum with a
    per-coordinate argsort over the client axis — O(n log n) per
    parameter and no Pallas fast path — so the ``overhead_vs_mean``
    column is the price of the breakdown guarantee, and it grows with
    the cohort. The attack path is OFF here: poisoning changes which
    values flow, not the lowered program's cost."""
    import jax
    from repro.fl.engine import make_round_engine

    rounds = rounds or (4 if QUICK else 10)
    recs = []
    for cohort in cohorts:
        batches, weights = _engine_fixture(cohort, steps_per_epoch, batch)
        base_rps = None
        for rule in ROBUST_RULES:
            cfg = model_cfg("vgg9", method)
            fl = FLConfig(population=cohort, rounds=rounds, local_epochs=1,
                          steps_per_epoch=steps_per_epoch,
                          batch_size=batch, lr=0.008, momentum=0.9,
                          method=method, seed=0,
                          robust=None if rule == "mean" else rule)
            task = cnn_task(cfg)
            gp = task.init_fn(jax.random.PRNGKey(0))
            engine = make_round_engine(task, fl, gp)
            state = engine.init_state(gp)
            state, gp = engine.run_round(state, gp, batches,
                                         weights=weights)     # compile
            jax.block_until_ready(gp)
            t0 = time.time()
            for _ in range(rounds):
                state, gp = engine.run_round(state, gp, batches,
                                             weights=weights)
            jax.block_until_ready(gp)
            dt = time.time() - t0
            rps = round(rounds / dt, 3)
            if rule == "mean":
                base_rps = rps
            recs.append({"cohort_size": cohort, "method": method,
                         "robust": rule, "rounds": rounds,
                         "rounds_per_s": rps,
                         "us_per_round": round(1e6 * dt / rounds),
                         "overhead_vs_mean": round(base_rps / rps, 3)})
    os.makedirs(ARTIFACTS_PERF, exist_ok=True)
    with open(os.path.join(ARTIFACTS_PERF, "flbench_robust.json"),
              "w") as f:
        json.dump(recs, f, indent=1)
    return recs


# the alignment judge panel (fl/alignment.py, DESIGN.md §16): strategy,
# method, federation mode — Fed2's structural adaptation vs PAN position
# encodings on a plain net vs the unaligned control, plus the one-shot
# communication-minimal extreme on the same step budget
ALIGN_CASES = (("grouped", "fed2", "sync"),
               ("pan", "fedavg", "sync"),
               ("none", "fedavg", "sync"),
               ("none", "fedavg", "one_shot"))


def bench_alignment(*, nodes=6, cpn=2, rounds=None, steps_per_epoch=6,
                    batch=16, lr=0.015) -> dict:
    """Alignment strategies head to head under label skew (N x C at
    cpn classes per client): rounds/sec AND final accuracy per
    (strategy, method, mode) row of ``ALIGN_CASES`` — the bench-scale
    mirror of the scenario judge panel (fl/scenarios.py; the claims
    pins live in tests/test_paper_claims.py over the committed scenario
    records, this bench stamps the wall-clock economics next to them).
    The one-shot row spends the identical rounds x steps budget in a
    single fusion, so its rounds/sec column is the amortized cost of
    the whole run."""
    import jax
    from repro.fl import alignment as alignment_lib
    from repro.models.cnn import CNNConfig

    rounds = rounds or (8 if QUICK else 12)
    ds, test = dataset()
    parts = nxc_partition(ds.labels, nodes, cpn, N_CLASSES, seed=0)

    def get_batch(sel):
        return {"images": ds.images[sel], "labels": ds.labels[sel]}

    test_batches = [{"images": jnp.asarray(test.images),
                     "labels": jnp.asarray(test.labels)}]
    plan, fc = _BENCH_PLANS["vgg9"]

    def plain_cfg():
        return CNNConfig(arch_id="vgg9-bench", plan=plan, fc_dims=fc,
                         n_classes=N_CLASSES, fed2_groups=0, norm="none")

    rows = []
    for strat_name, method, mode in ALIGN_CASES:
        cfg = alignment_lib.build_model_config(
            alignment_lib.get(strat_name), methods_lib.get(method),
            grouped_fn=lambda m=method: model_cfg("vgg9", m),
            plain_fn=plain_cfg)
        fl = FLConfig(population=nodes, rounds=rounds, local_epochs=1,
                      steps_per_epoch=steps_per_epoch, batch_size=batch,
                      lr=lr, momentum=0.9, method=method, seed=0,
                      mode=mode, alignment=strat_name)
        t0 = time.time()
        h = run_federated(cnn_task(cfg), fl, parts, get_batch,
                          test_batches)
        jax.block_until_ready(h["final_params"])
        dt = time.time() - t0
        rows.append({"alignment": strat_name, "method": method,
                     "mode": mode, "pan_scale": cfg.pan,
                     "rounds": len(h["acc"]),
                     "local_steps_total": rounds * steps_per_epoch,
                     "s": round(dt, 3),
                     "rounds_per_s": round(len(h["acc"]) / dt, 3),
                     "final_acc": round(float(h["acc"][-1]), 4),
                     "best_acc": round(float(max(h["acc"])), 4)})
    rec = {"name": "flbench_alignment", "nodes": nodes, "cpn": cpn,
           "rounds": rounds, "steps_per_epoch": steps_per_epoch,
           "lr": lr, "rows": rows}
    os.makedirs(ARTIFACTS_PERF, exist_ok=True)
    with open(os.path.join(ARTIFACTS_PERF, "flbench_alignment.json"),
              "w") as f:
        json.dump(rec, f, indent=1)
    return rec


BENCHES = {"bench_engine": None, "bench_methods": None,
           "bench_cohort": None, "bench_eval": None,
           "bench_tiers": None, "bench_async": None,
           "bench_robust": None,
           "bench_alignment": None}  # CLI subcommands


def main(argv=None):
    import sys
    chosen = (argv if argv is not None else sys.argv[1:]) or \
        ["bench_engine", "bench_methods", "bench_cohort", "bench_eval",
         "bench_tiers", "bench_async", "bench_robust",
         "bench_alignment"]
    bad = [c for c in chosen if c not in BENCHES]
    if bad:
        raise SystemExit(f"unknown bench {bad}; available: "
                         f"{', '.join(BENCHES)}")
    if "bench_engine" in chosen:
        rec = bench_engine()
        us = 1e6 * rec["engine_s"] / rec["rounds"]
        print(f"fl_engine_round,{us:.0f},"
              f"speedup_vs_seed_loop={rec['speedup']:.2f}x,"
              f"params_match={rec['params_match']}")
        for r in rec["rows"]:
            extra = (f",uplink_frac={r['uplink_frac']}"
                     if "uplink_frac" in r else "")
            print(f"fl_engine_{r['name']},"
                  f"{round(1e6 * r['s'] / rec['rounds'])},"
                  f"speedup_vs_seed_loop={r['speedup_vs_seed']:.2f}x"
                  f"{extra}")
    if "bench_methods" in chosen:
        for r in bench_methods():
            print(f"fl_method_{r['method']},{r['us_per_round']},"
                  f"rounds_per_s={r['rounds_per_s']}")
    if "bench_cohort" in chosen:
        for r in bench_cohort():
            print(f"fl_cohort_pop{r['population']},{r['us_per_round']},"
                  f"rounds_per_s={r['rounds_per_s']},"
                  f"cohort={r['cohort_size']},store={r['store']},"
                  f"rss_mb={r['rss_mb']},peak_rss_mb={r['peak_rss_mb']}")
    if "bench_eval" in chosen:
        for r in bench_eval():
            print(f"fl_eval_b{r['eval_batch']},"
                  f"{round(1e6 * r['engine_s'] / r['repeats'])},"
                  f"speedup_vs_host_loop={r['speedup']:.2f}x,"
                  f"acc_match={r['acc_match']}")
    if "bench_tiers" in chosen:
        r = bench_tiers()
        print(f"fl_tiers,{round(1e6 * r['tier_s'] / r['rounds'])},"
              f"rounds_per_s={r['tier_rounds_per_s']}"
              f"(hom {r['hom_rounds_per_s']}),"
              f"uplink_frac={r['uplink_frac']}")
    if "bench_async" in chosen:
        r = bench_async()
        print(f"fl_async,{round(1e6 * r['async_s'] / r['events_async'])},"
              f"sim_speedup_to_target={r['sim_speedup_to_target']:.2f}x,"
              f"target_acc={r['target_acc']},"
              f"max_staleness={r['max_staleness']}")
    if "bench_robust" in chosen:
        for r in bench_robust():
            print(f"fl_robust_c{r['cohort_size']}_{r['robust']},"
                  f"{r['us_per_round']},"
                  f"rounds_per_s={r['rounds_per_s']},"
                  f"overhead_vs_mean={r['overhead_vs_mean']}x")
    if "bench_alignment" in chosen:
        for r in bench_alignment()["rows"]:
            print(f"fl_align_{r['alignment']}_{r['method']}_{r['mode']},"
                  f"{round(1e6 * r['s'] / max(r['rounds'], 1))},"
                  f"rounds_per_s={r['rounds_per_s']},"
                  f"final_acc={r['final_acc']}")


if __name__ == "__main__":
    main()
