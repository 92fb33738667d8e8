"""Population & participation API (fl/population.py, DESIGN.md §9):
sampler registry + FLConfig validation; partial participation leaves
absent clients' method state untouched; cohort tiling is an unbiased
split of the full-participation round; no consumer in src/ conflates the
engine axis width with the population."""
import pathlib
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import vgg9
from repro.data.synthetic import make_image_dataset, nxc_partition
from repro.fl import methods as methods_lib
from repro.fl import population as population_lib
from repro.fl.engine import make_round_engine
from repro.fl.population import Population
from repro.fl.runtime import (FLConfig, cnn_task, run_federated,
                              run_sampled_round)

_DS = make_image_dataset(300, n_classes=4, seed=0, noise=0.8)
_TEST = make_image_dataset(80, n_classes=4, seed=9, noise=0.8)


def _get_batch(sel):
    return {"images": jnp.asarray(_DS.images[sel]),
            "labels": jnp.asarray(_DS.labels[sel])}


_TEST_BATCHES = [{"images": jnp.asarray(_TEST.images),
                  "labels": jnp.asarray(_TEST.labels)}]


def _plain_cfg():
    return vgg9.reduced(n_classes=4, fed2_groups=0, norm="none")


def _fl(method="fedavg", population=4, cohort_size=None, sampler="full",
        rounds=1, momentum=0.9):
    return FLConfig(population=population, cohort_size=cohort_size,
                    sampler=sampler, rounds=rounds, local_epochs=1,
                    steps_per_epoch=2, batch_size=8, lr=0.02,
                    momentum=momentum, method=method, seed=0)


# ---------------------------------------------------------------------------
# Sampler registry + FLConfig validation
# ---------------------------------------------------------------------------


def test_sampler_registry_contents():
    avail = population_lib.available()
    for name in ("full", "uniform", "weighted", "round_robin"):
        assert name in avail, (name, avail)
    assert avail == tuple(sorted(avail))


def test_get_unknown_sampler_lists_available():
    with pytest.raises(ValueError, match="uniform"):
        population_lib.get("not-a-sampler")


def test_flconfig_validates_sampler_at_construction():
    with pytest.raises(ValueError, match="available"):
        FLConfig(sampler="unifrom")
    for name in population_lib.available():
        FLConfig(population=4, cohort_size=2, sampler=name)


@pytest.mark.parametrize("field,value", [
    ("rounds", 0), ("rounds", -3), ("population", 0), ("cohort_size", 0),
    ("batch_size", 0), ("local_epochs", -1), ("steps_per_epoch", 0),
    ("rounds", 2.5),
])
def test_flconfig_rejects_nonpositive_numerics(field, value):
    with pytest.raises(ValueError, match=f"FLConfig.{field}"):
        FLConfig(**{field: value})


def test_flconfig_rejects_cohort_larger_than_population():
    with pytest.raises(ValueError, match="cohort_size"):
        FLConfig(population=4, cohort_size=8)


def test_flconfig_cohort_defaults_to_population():
    cfg = FLConfig(population=7)
    assert cfg.cohort_size == 7 and cfg.sampler == "full"


def test_samplers_return_valid_cohorts():
    rng = np.random.default_rng(0)
    for name in population_lib.available():
        s = population_lib.get(name)
        ids = s.sample(3, population=10, cohort_size=4, rng=rng,
                       weights=np.arange(1, 11, dtype=np.float64))
        assert ids.ndim == 1
        assert np.all((ids >= 0) & (ids < 10))
        if name == "full":
            np.testing.assert_array_equal(ids, np.arange(10))
        else:
            assert len(ids) == 4
            assert len(np.unique(ids)) == 4      # without replacement


def test_weighted_sampler_fuses_participants_uniformly():
    """The FedAvg sampling duality: when the draw probability encodes
    shard size (weighted sampler), fusion must weight participants
    EQUALLY — shard-size fusion weights on top of shard-size sampling
    would double-count large shards."""
    assert population_lib.get("weighted").fusion_weights == "uniform"
    assert population_lib.get("uniform").fusion_weights == "sample"
    fl = _fl("fedavg", population=3, cohort_size=3, sampler="weighted")
    task = cnn_task(_plain_cfg())
    parts = nxc_partition(_DS.labels, 3, 2, 4, seed=1)   # unequal shards
    assert len(set(len(p) for p in parts)) > 1
    method = methods_lib.get("fedavg")
    sampler = population_lib.get("weighted")
    pop = Population.from_parts(parts)
    gp = task.init_fn(jax.random.PRNGKey(0))
    engine = make_round_engine(task, fl, gp, use_kernel=False)
    server = engine.init_server_state(gp)
    pop.clients = engine.init_population_state(gp, pop.size)

    rng = np.random.default_rng(0)
    ids = sampler.sample(0, 3, 3, rng, weights=pop.weights)
    _, got = run_sampled_round(engine, pop, method, server, gp, ids,
                               _get_batch, 2, fl, rng,
                               uniform_weights=True)

    from repro.fl.runtime import _pack_client_batches
    rng2 = np.random.default_rng(0)
    sampler.sample(0, 3, 3, rng2, weights=pop.weights)   # same rng dance
    batches = _pack_client_batches([parts[i] for i in ids], _get_batch, 2,
                                   fl.batch_size, rng2)
    _, want = engine.run_round(engine.init_state(gp), gp, batches,
                               weights=np.ones(3))
    for a, b in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(want)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_round_robin_covers_population():
    s = population_lib.get("round_robin")
    rng = np.random.default_rng(0)
    seen = np.concatenate([s.sample(r, 6, 2, rng) for r in range(3)])
    np.testing.assert_array_equal(np.sort(seen), np.arange(6))


# ---------------------------------------------------------------------------
# Sampler edge cases (the contracts DESIGN.md §9 states)
# ---------------------------------------------------------------------------


def test_weighted_sampler_never_draws_zero_weight_clients():
    """A zero-weight client (an empty shard a caller chose not to floor)
    must NEVER be sampled, over many rounds."""
    s = population_lib.get("weighted")
    w = np.array([0.0, 3.0, 0.0, 1.0, 2.0, 0.0, 4.0, 5.0])
    rng = np.random.default_rng(0)
    for r in range(200):
        ids = s.sample(r, 8, 3, rng, weights=w)
        assert not np.isin(ids, [0, 2, 5]).any(), (r, ids)


def test_weighted_sampler_rejects_all_zero_weights():
    s = population_lib.get("weighted")
    with pytest.raises(ValueError, match="zero"):
        s.sample(0, 4, 2, np.random.default_rng(0),
                 weights=np.zeros(4))


def test_weighted_sampler_rejects_cohort_beyond_support():
    """cohort_size > #nonzero-weight clients cannot yield distinct ids —
    refuse instead of looping forever in rejection sampling."""
    s = population_lib.get("weighted")
    with pytest.raises(ValueError, match="distinct"):
        s.sample(0, 5, 3, np.random.default_rng(0),
                 weights=np.array([0.0, 1.0, 0.0, 2.0, 0.0]))


def test_weighted_sampler_reuses_alias_table_per_weights_array():
    """The O(P) alias build runs ONCE per weights array: same array
    object -> same cached table; a different array triggers a rebuild."""
    s = population_lib.get("weighted")
    w = np.arange(1.0, 9.0)
    rng = np.random.default_rng(0)
    s.sample(0, 8, 3, rng, weights=w)
    t0 = s._table
    s.sample(1, 8, 3, rng, weights=w)
    assert s._table is t0
    s.sample(2, 8, 3, rng, weights=np.arange(1.0, 9.0))
    assert s._table is not t0


def test_uniform_and_weighted_return_sorted_unique_cohorts():
    rng = np.random.default_rng(3)
    for name in ("uniform", "weighted"):
        s = population_lib.get(name)
        for r in range(20):
            ids = s.sample(r, 12, 5, rng,
                           weights=np.arange(1.0, 13.0))
            assert len(np.unique(ids)) == 5
            np.testing.assert_array_equal(ids, np.sort(ids))


def test_round_robin_wraps_deterministically_without_rng():
    """round_robin is a pure function of (round, P, C): wrapping windows
    are reproducible and never consume the rng stream (the batch-packing
    stream must stay aligned across reruns)."""
    s = population_lib.get("round_robin")
    rng = np.random.default_rng(0)
    state_before = rng.bit_generator.state
    np.testing.assert_array_equal(s.sample(0, 5, 3, rng), [0, 1, 2])
    np.testing.assert_array_equal(s.sample(1, 5, 3, rng), [3, 4, 0])
    np.testing.assert_array_equal(s.sample(2, 5, 3, rng), [1, 2, 3])
    # period P rounds later the same window returns
    np.testing.assert_array_equal(s.sample(5, 5, 3, rng), [0, 1, 2])
    assert rng.bit_generator.state == state_before    # rng untouched


# ---------------------------------------------------------------------------
# Partial participation: absent clients keep their state
# ---------------------------------------------------------------------------


def test_scaffold_absent_client_state_untouched():
    """A client that sits a round out keeps its SCAFFOLD control variate
    bit-for-bit: round 0 trains clients {0, 1} (round_robin), so {2, 3}
    must stay at zero; round 1 trains {2, 3}, so {0, 1} must keep round
    0's values exactly."""
    fl = _fl("scaffold", population=4, cohort_size=2,
             sampler="round_robin", momentum=0.0)
    task = cnn_task(_plain_cfg())
    parts = nxc_partition(_DS.labels, 4, 2, 4, seed=1)
    method = methods_lib.get("scaffold")
    sampler = population_lib.get("round_robin")
    pop = Population.from_parts(parts)
    gp = task.init_fn(jax.random.PRNGKey(0))
    engine = make_round_engine(task, fl, gp, use_kernel=False)
    server = engine.init_server_state(gp)
    pop.clients = engine.init_population_state(gp, pop.size)
    rng = np.random.default_rng(0)

    ids0 = sampler.sample(0, 4, 2, rng)
    np.testing.assert_array_equal(ids0, [0, 1])
    server, gp = run_sampled_round(engine, pop, method, server, gp, ids0,
                                   _get_batch, 2, fl, rng)
    absent = jax.tree_util.tree_map(lambda a: np.asarray(a[2:]),
                                    pop.clients)
    for leaf in jax.tree_util.tree_leaves(absent):
        np.testing.assert_array_equal(leaf, np.zeros_like(leaf))
    trained = jax.tree_util.tree_map(lambda a: np.asarray(a[:2]),
                                     pop.clients)
    assert sum(float(np.sum(np.abs(l)))
               for l in jax.tree_util.tree_leaves(trained)) > 0

    ids1 = sampler.sample(1, 4, 2, rng)
    np.testing.assert_array_equal(ids1, [2, 3])
    server, gp = run_sampled_round(engine, pop, method, server, gp, ids1,
                                   _get_batch, 2, fl, rng)
    after = jax.tree_util.tree_map(lambda a: np.asarray(a[:2]),
                                   pop.clients)
    for a, b in zip(jax.tree_util.tree_leaves(trained),
                    jax.tree_util.tree_leaves(after)):
        np.testing.assert_array_equal(a, b)      # bit-for-bit untouched


def test_scaffold_partial_participation_runs_end_to_end():
    h = run_federated(cnn_task(_plain_cfg()),
                      _fl("scaffold", population=6, cohort_size=3,
                          sampler="uniform", rounds=2, momentum=0.0),
                      nxc_partition(_DS.labels, 6, 2, 4, seed=1),
                      _get_batch, _TEST_BATCHES)
    assert all(np.isfinite(a) for a in h["acc"])
    assert all(len(p) == 3 for p in h["participants"])


def test_fednova_normalizes_over_participants_only():
    """Under uniform tau, fednova reduces to fedavg (FedNova Prop. 1) —
    and that reduction must survive partial participation: the
    normalization runs over the sampled participants' tau, not the
    population's. Same seed -> same sampled cohorts for both methods."""
    kw = dict(population=6, cohort_size=3, sampler="uniform", rounds=2)
    parts = nxc_partition(_DS.labels, 6, 2, 4, seed=1)
    a = run_federated(cnn_task(_plain_cfg()), _fl("fedavg", **kw), parts,
                      _get_batch, _TEST_BATCHES)
    b = run_federated(cnn_task(_plain_cfg()), _fl("fednova", **kw), parts,
                      _get_batch, _TEST_BATCHES)
    for pa, pb in zip(a["participants"], b["participants"]):
        np.testing.assert_array_equal(pa, pb)
    for la, lb in zip(jax.tree_util.tree_leaves(a["final_params"]),
                      jax.tree_util.tree_leaves(b["final_params"])):
        np.testing.assert_allclose(np.asarray(la), np.asarray(lb),
                                   atol=1e-5)


# ---------------------------------------------------------------------------
# Cohort tiling
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("method", ["fedavg", "fednova", "fedavgm"])
def test_cohort_tiling_matches_single_cohort(method):
    """Full participation tiled over cohort_size=2 (3 tiles, last one
    padded) must equal the single-cohort round: the running weighted sum
    over tiles is an unbiased split of the cohort-wide weighted mean."""
    parts = nxc_partition(_DS.labels, 5, 2, 4, seed=1)
    a = run_federated(cnn_task(_plain_cfg()),
                      _fl(method, population=5), parts, _get_batch,
                      _TEST_BATCHES)
    b = run_federated(cnn_task(_plain_cfg()),
                      _fl(method, population=5, cohort_size=2), parts,
                      _get_batch, _TEST_BATCHES)
    for la, lb in zip(jax.tree_util.tree_leaves(a["final_params"]),
                      jax.tree_util.tree_leaves(b["final_params"])):
        np.testing.assert_allclose(np.asarray(la), np.asarray(lb),
                                   atol=1e-5)


def _tiled_round(weights):
    """One fedavg round of 4 participants in tiles of 2, the population's
    fusion weights set to ``weights``."""
    parts = nxc_partition(_DS.labels, 4, 2, 4, seed=1)
    fl = _fl("fedavg", population=4, cohort_size=2)
    task = cnn_task(_plain_cfg())
    pop = Population.from_parts(parts)
    pop.weights = np.asarray(weights, np.float64)
    gp = task.init_fn(jax.random.PRNGKey(0))
    engine = make_round_engine(task, fl, gp, use_kernel=False)
    pop.clients = engine.init_population_state(gp, pop.size)
    return run_sampled_round(engine, pop, methods_lib.get("fedavg"),
                             engine.init_server_state(gp), gp,
                             np.arange(4), _get_batch, 2, fl,
                             np.random.default_rng(0))[1]


def test_cohort_tiling_tile_of_no_weight_adds_nothing():
    """A tile whose participants all weigh 0 leaves the round's mean to
    the other tiles (its own mean would be 0/0); a round of no weight at
    all has no mean."""
    got = _tiled_round([3.0, 1.0, 0.0, 0.0])
    assert all(bool(jnp.all(jnp.isfinite(x)))
               for x in jax.tree_util.tree_leaves(got))
    with pytest.raises(ValueError, match="no mean"):
        _tiled_round([0.0, 0.0, 0.0, 0.0])


def test_tiled_round_input_global_is_freed_once_logged(monkeypatch):
    """A caller that keeps the globals a tiled round returns (a wrapped
    engine) pins no device copy: each round's input global is deleted
    once the round is logged; the final global is kept."""
    from repro.fl import runtime
    kept = []
    make = runtime.make_round_engine

    def keeping(*a, **k):
        engine = make(*a, **k)
        finish = engine.finish_round

        def finish_round(server_state, gp, fused):
            out = finish(server_state, gp, fused)
            kept.append(out[1])
            return out
        engine.finish_round = finish_round
        return engine
    monkeypatch.setattr(runtime, "make_round_engine", keeping)
    parts = nxc_partition(_DS.labels, 4, 2, 4, seed=1)
    h = run_federated(cnn_task(_plain_cfg()),
                      _fl("fedavg", population=4, cohort_size=2, rounds=3),
                      parts, _get_batch, _TEST_BATCHES, log=lambda m: None)
    leaves = [jax.tree_util.tree_leaves(g) for g in kept]
    assert all(x.is_deleted() for g in leaves[:-1] for x in g)
    assert not any(x.is_deleted() for x in leaves[-1])
    assert leaves[-1][0] is jax.tree_util.tree_leaves(h["final_params"])[0]


def test_cohort_tiling_host_fusion_concatenates_participants():
    """fedma under tiling: tiles hand their stacked params to the host,
    matching runs ONCE over all participants — same result as one tile."""
    parts = nxc_partition(_DS.labels, 4, 2, 4, seed=1)
    a = run_federated(cnn_task(_plain_cfg()), _fl("fedma", population=4),
                      parts, _get_batch, _TEST_BATCHES)
    b = run_federated(cnn_task(_plain_cfg()),
                      _fl("fedma", population=4, cohort_size=2), parts,
                      _get_batch, _TEST_BATCHES)
    for la, lb in zip(jax.tree_util.tree_leaves(a["final_params"]),
                      jax.tree_util.tree_leaves(b["final_params"])):
        np.testing.assert_allclose(np.asarray(la), np.asarray(lb),
                                   atol=1e-5)


def test_scaffold_rejects_tiled_rounds():
    """scaffold's server step reads participating client state, so a
    round must fit one cohort — the runtime fails with a helpful error
    instead of silently mis-updating the server variate."""
    with pytest.raises(ValueError, match="cohort"):
        run_federated(cnn_task(_plain_cfg()),
                      _fl("scaffold", population=4, cohort_size=2,
                          sampler="full", momentum=0.0),
                      nxc_partition(_DS.labels, 4, 2, 4, seed=1),
                      _get_batch, _TEST_BATCHES)


def test_run_federated_rejects_mismatched_partition():
    with pytest.raises(ValueError, match="population"):
        run_federated(cnn_task(_plain_cfg()), _fl(population=4),
                      nxc_partition(_DS.labels, 3, 2, 4, seed=1),
                      _get_batch, _TEST_BATCHES)


def test_presence_weighted_fusion_rejects_tiled_rounds():
    """Presence weighting (fed2's non-IID refinement) renormalizes each
    group column over ONE cohort's participants; tiling would renormalize
    per tile and bias Eq. 19 — the runtime refuses instead."""
    from repro.core.grouping import GroupSpec
    cfg = vgg9.reduced(n_classes=4, fed2_groups=2, decouple=1, norm="gn")
    parts = nxc_partition(_DS.labels, 4, 2, 4, seed=1)
    counts = np.stack([np.bincount(_DS.labels[p], minlength=4)
                       for p in parts])
    spec = GroupSpec.contiguous(2, 4)
    kw = dict(class_counts=counts, group_spec=spec)
    with pytest.raises(ValueError, match="presence"):
        run_federated(cnn_task(cfg),
                      _fl("fed2", population=4, cohort_size=2), parts,
                      _get_batch, _TEST_BATCHES, **kw)
    # one-cohort presence weighting stays supported (full and sampled)
    h = run_federated(cnn_task(cfg),
                      _fl("fed2", population=4, cohort_size=2,
                          sampler="uniform"), parts, _get_batch,
                      _TEST_BATCHES, **kw)
    assert all(np.isfinite(a) for a in h["acc"])


def test_population_state_stays_host_side():
    """The persistent per-client state is host numpy — scatter writes
    cohort rows in place (O(cohort)), it does not rebuild a device copy
    of the whole (population, ...) tree every round."""
    fl = _fl("scaffold", population=6, cohort_size=2,
             sampler="round_robin", momentum=0.0)
    task = cnn_task(_plain_cfg())
    parts = nxc_partition(_DS.labels, 6, 2, 4, seed=1)
    method = methods_lib.get("scaffold")
    pop = Population.from_parts(parts)
    gp = task.init_fn(jax.random.PRNGKey(0))
    engine = make_round_engine(task, fl, gp, use_kernel=False)
    server = engine.init_server_state(gp)
    pop.clients = engine.init_population_state(gp, pop.size)
    before = jax.tree_util.tree_leaves(pop.clients)
    assert all(isinstance(l, np.ndarray) for l in before)
    rng = np.random.default_rng(0)
    ids = population_lib.get("round_robin").sample(0, 6, 2, rng)
    run_sampled_round(engine, pop, method, server, gp, ids, _get_batch,
                      2, fl, rng)
    after = jax.tree_util.tree_leaves(pop.clients)
    # same buffers, mutated in place — only the sampled rows changed
    assert all(a is b for a, b in zip(before, after))


def test_fed2_partial_participation_runs():
    """The paper method under the sampled regime its non-IID experiments
    assume: fed2 with a uniform cohort of a larger population."""
    cfg = vgg9.reduced(n_classes=4, fed2_groups=2, decouple=1, norm="gn")
    h = run_federated(cnn_task(cfg),
                      _fl("fed2", population=8, cohort_size=4,
                          sampler="uniform", rounds=2),
                      nxc_partition(_DS.labels, 8, 2, 4, seed=1),
                      _get_batch, _TEST_BATCHES)
    assert all(np.isfinite(a) for a in h["acc"])


# ---------------------------------------------------------------------------
# The acceptance grep: axis width != population anywhere in src/
# ---------------------------------------------------------------------------


def test_no_population_width_conflation_in_src():
    """cfg.n_nodes is gone: no consumer constructs client batches or
    method state by assuming the vmapped/sharded axis width equals the
    population — the engine runs cohorts (cfg.cohort_size), populations
    live in fl/population.py."""
    root = pathlib.Path(__file__).resolve().parents[1] / "src"
    offenders = []
    pat = re.compile(r"\bn_nodes\b")
    for py in root.rglob("*.py"):
        for i, line in enumerate(py.read_text().splitlines(), 1):
            if pat.search(line):
                offenders.append(f"{py}:{i}: {line.strip()}")
    assert not offenders, offenders
