"""Round engine: one jitted round == the decomposed reference round; the
same function serves single-host vmap and mesh-sharded placement; the
dry-run lowering path compiles on the 1-device host mesh."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import vgg9
from repro.core import fusion as fusion_lib
from repro.data.synthetic import make_image_dataset, nxc_partition
from repro.fl.engine import (lower_round, make_local_phase,
                             make_round_engine, stacked_param_bytes)
from repro.fl.runtime import (FLConfig, _pack_client_batches, cnn_task,
                              run_federated)
from repro.launch.mesh import make_host_mesh
from repro.optim.optimizers import sgd

_DS = make_image_dataset(240, n_classes=4, seed=0, noise=0.8)
_TEST = make_image_dataset(80, n_classes=4, seed=9, noise=0.8)


def _get_batch(sel):
    return {"images": jnp.asarray(_DS.images[sel]),
            "labels": jnp.asarray(_DS.labels[sel])}


_TEST_BATCHES = [{"images": jnp.asarray(_TEST.images),
                  "labels": jnp.asarray(_TEST.labels)}]


def _fl(method, rounds=2):
    return FLConfig(population=3, rounds=rounds, local_epochs=1,
                    steps_per_epoch=2, batch_size=8, lr=0.02, momentum=0.9,
                    method=method, seed=0)


def _cfg(method):
    if method == "fed2":
        return vgg9.reduced(n_classes=4, fed2_groups=2, decouple=1,
                            norm="gn")
    return vgg9.reduced(n_classes=4, fed2_groups=0, norm="none")


@pytest.mark.parametrize("method", ["fedavg", "fed2"])
def test_engine_round_matches_decomposed_reference(method):
    """The single jitted round must equal broadcast -> local phase ->
    fusion run as separate host-driven steps (the seed semantics)."""
    cfg, fl = _cfg(method), _fl(method, rounds=1)
    task = cnn_task(cfg)
    parts = nxc_partition(_DS.labels, fl.population, 2, 4, seed=1)
    weights = np.maximum([len(p) for p in parts], 1).astype(np.float64)
    gp = task.init_fn(jax.random.PRNGKey(fl.seed))
    rng = np.random.default_rng(fl.seed)
    batches = _pack_client_batches(parts, _get_batch, 2, fl.batch_size, rng)

    engine = make_round_engine(task, fl, gp, use_kernel=False)
    _, got = engine.run_round(engine.init_state(gp), gp, batches,
                              weights=weights)

    local = make_local_phase(task, fl, sgd(fl.lr, fl.momentum))
    stacked = fusion_lib.broadcast_global(gp, fl.population)
    stacked = jax.jit(local)(stacked, batches, gp)
    if method == "fed2":
        want = fusion_lib.paired_average(stacked, task.group_axes_fn(gp),
                                         weights=weights)
    else:
        want = fusion_lib.fedavg(stacked, weights)
    for a, b in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(want)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-5)


def test_engine_kernel_fusion_round_matches_reference_round():
    """use_kernel=True inside the jitted round == reference fusion round."""
    cfg, fl = _cfg("fed2"), _fl("fed2", rounds=2)
    task = cnn_task(cfg)
    parts = nxc_partition(_DS.labels, fl.population, 2, 4, seed=1)
    a = run_federated(task, fl, parts, _get_batch, _TEST_BATCHES,
                      use_kernel=False)
    b = run_federated(task, fl, parts, _get_batch, _TEST_BATCHES,
                      use_kernel=True)
    for la, lb in zip(jax.tree_util.tree_leaves(a["final_params"]),
                      jax.tree_util.tree_leaves(b["final_params"])):
        np.testing.assert_allclose(np.asarray(la), np.asarray(lb),
                                   atol=5e-5)


def test_engine_host_mesh_placement():
    """The same round function executes with the client axis sharded over
    the mesh "data" axis (1-device host mesh here)."""
    cfg, fl = _cfg("fed2"), _fl("fed2", rounds=2)
    task = cnn_task(cfg)
    parts = nxc_partition(_DS.labels, fl.population, 2, 4, seed=1)
    mesh = make_host_mesh()
    with mesh:
        h = run_federated(task, fl, parts, _get_batch, _TEST_BATCHES,
                          mesh=mesh)
    assert len(h["acc"]) == fl.rounds
    assert all(np.isfinite(a) for a in h["acc"])


def test_engine_fedma_host_fuse():
    cfg, fl = _cfg("fedma"), _fl("fedma", rounds=1)
    task = cnn_task(cfg)
    parts = nxc_partition(_DS.labels, fl.population, 2, 4, seed=1)
    h = run_federated(task, fl, parts, _get_batch, _TEST_BATCHES)
    assert np.isfinite(h["acc"][-1])


def test_lower_round_host_mesh():
    """Dry-run mode: lowering one full round from ShapeDtypeStructs (no
    arrays) compiles on the host mesh."""
    cfg, fl = _cfg("fed2"), _fl("fed2")
    task = cnn_task(cfg)
    lowered = lower_round(task, fl, make_host_mesh(),
                          {"images": ((8, 32, 32, 3), jnp.float32),
                           "labels": ((8,), jnp.int32)},
                          local_steps=2)
    compiled = lowered.compile()
    assert compiled.memory_analysis().temp_size_in_bytes >= 0


def test_stacked_param_bytes():
    cfg = _cfg("fedavg")
    task = cnn_task(cfg)
    one = stacked_param_bytes(task, 1)
    assert stacked_param_bytes(task, 4) == 4 * one
    assert one > 0


# --------------------------------------------------------------------------
# §15: fused local phase (unroll + kernel route), bf16, uplink codecs
# --------------------------------------------------------------------------

from repro.fl import methods as methods_lib  # noqa: E402
from repro.fl.engine import (resolve_compute_dtype,  # noqa: E402
                             resolve_local_unroll)

_MP_METHODS = [n for n in methods_lib.available()
               if methods_lib.get(n).mixed_precision]


def _fl15(method="fed2", rounds=2, **kw):
    return FLConfig(population=3, rounds=rounds, local_epochs=1,
                    steps_per_epoch=2, batch_size=8, lr=0.02, momentum=0.9,
                    method=method, seed=0, **kw)


def _run15(fl, **kw):
    cfg = _cfg(fl.method)
    parts = nxc_partition(_DS.labels, fl.population, 2, 4, seed=1)
    return run_federated(cnn_task(cfg), fl, parts, _get_batch,
                         _TEST_BATCHES, **kw)


def _leafcmp(a, b, atol=None):
    for la, lb in zip(jax.tree_util.tree_leaves(a["final_params"]),
                      jax.tree_util.tree_leaves(b["final_params"])):
        la = np.asarray(la, np.float32)
        lb = np.asarray(lb, np.float32)
        if atol is None:
            np.testing.assert_array_equal(la, lb)
        else:
            np.testing.assert_allclose(la, lb, atol=atol)


def test_resolve_local_unroll_clamps():
    fl = _fl15(local_unroll=16)
    assert resolve_local_unroll(fl, 2) == 2      # never past local steps
    assert resolve_local_unroll(_fl15(), 2) == 1  # default untouched


def test_resolve_compute_dtype():
    meth = methods_lib.get("fedavg")
    assert resolve_compute_dtype("float32", meth) is None
    assert resolve_compute_dtype(None, meth) is None
    assert resolve_compute_dtype("bfloat16", meth) == jnp.bfloat16
    with pytest.raises(ValueError, match="unknown compute_dtype"):
        resolve_compute_dtype("float16", meth)
    with pytest.raises(ValueError, match="bfloat16 local phase"):
        resolve_compute_dtype("bfloat16", methods_lib.get("scaffold"))


def test_local_unroll_matches_seed_scan_at_tolerance():
    """unroll=2 batches both local steps into one dispatch; XLA may
    re-associate the elementwise chain, so equivalence is pinned at
    tolerance (unroll=1 stays the seed program bit-for-bit)."""
    base = _run15(_fl15("fed2"))
    unrolled = _run15(_fl15("fed2", local_unroll=2))
    _leafcmp(base, unrolled, atol=5e-5)


def test_kernel_local_phase_matches_scan():
    """use_local_kernel routes momentum-SGD through the fused Pallas
    local_step kernel on the raveled params — same rounds at tolerance."""
    base = _run15(_fl15("fed2"))
    kern = _run15(_fl15("fed2"), use_local_kernel=True)
    _leafcmp(base, kern, atol=1e-4)


def test_kernel_route_noops_for_custom_client_update():
    """scaffold overrides client_update, so fused_local_step is False and
    the flag must silently no-op — bit-identical rounds."""
    assert not methods_lib.get("scaffold").fused_local_step
    base = _run15(_fl15("scaffold", rounds=1))
    kern = _run15(_fl15("scaffold", rounds=1), use_local_kernel=True)
    _leafcmp(base, kern)


@pytest.mark.parametrize("method", _MP_METHODS)
def test_bfloat16_round_matches_fp32_at_tolerance(method):
    """bf16 local phase + fp32 fusion accumulators: final params within
    bf16 resolution of the fp32 round for every eligible method."""
    base = _run15(_fl15(method, rounds=1))
    half = _run15(_fl15(method, rounds=1, compute_dtype="bfloat16"))
    for leaf in jax.tree_util.tree_leaves(half["final_params"]):
        assert leaf.dtype == jnp.float32    # storage dtype restored
    _leafcmp(base, half, atol=0.05)
    assert np.isfinite(half["acc"][-1])


def test_identity_codec_round_is_bit_identical():
    base = _run15(_fl15("fed2"))
    ident = _run15(_fl15("fed2", codec="identity"))
    _leafcmp(base, ident)


@pytest.mark.parametrize("codec", ["int8", "topk(0.3)"])
def test_lossy_codec_rounds_stay_finite(codec):
    h = _run15(_fl15("fed2", codec=codec))
    assert np.isfinite(h["acc"][-1])
    for leaf in jax.tree_util.tree_leaves(h["final_params"]):
        assert np.isfinite(np.asarray(leaf)).all()


def test_config_refusals():
    """FLConfig validation carries THE single copy of each eligibility
    rule — the refusal fires at construction, not deep in tracing."""
    with pytest.raises(ValueError, match="does not support"):
        _fl15("scaffold", codec="int8")
    with pytest.raises(ValueError, match="bfloat16 local phase"):
        _fl15("fedma", compute_dtype="bfloat16")
    with pytest.raises(ValueError, match="lossy codec"):
        _fl15("fedavg", codec="int8", robust="coordinate_median")
    with pytest.raises(ValueError, match="unknown compute_dtype"):
        _fl15("fedavg", compute_dtype="float16")
    with pytest.raises(ValueError, match="local_unroll"):
        _fl15("fedavg", local_unroll=0)
    with pytest.raises(ValueError, match="mode='sync'"):
        _fl15("fedavg", codec="int8", mode="async", buffer_k=2)
    with pytest.raises(ValueError, match="tiers"):
        _fl15("fedavg", compute_dtype="bfloat16", tiers="1.0x2,0.5x1")
    # fedadam fuses on device but its adaptive server step amplifies
    # uplink noise — it opts out of bf16 and codecs (methods.py)
    with pytest.raises(ValueError, match="bfloat16 local phase"):
        _fl15("fedadam", compute_dtype="bfloat16")
    with pytest.raises(ValueError, match="does not support"):
        _fl15("fedadam", codec="int8")
    # identity composes with reducing robust rules (exact codec)
    _fl15("fedavg", codec="identity", robust="coordinate_median")


def test_lower_round_carries_group_weights_for_fed2():
    """Regression: lower_round used to pass group_weights=None, so the
    drift gate never covered the presence-weighted fed2 program. The
    lowered module must now take the (cohort, n_groups) gw argument."""
    cfg, fl = _cfg("fed2"), _fl("fed2")
    task = cnn_task(cfg)
    lowered = lower_round(task, fl, make_host_mesh(),
                          {"images": ((8, 32, 32, 3), jnp.float32),
                           "labels": ((8,), jnp.int32)},
                          local_steps=2)
    assert "tensor<3x2xf32>" in lowered.as_text()  # cohort=3, groups=2

    cfg_a, fl_a = _cfg("fedavg"), _fl("fedavg")
    lowered_a = lower_round(cnn_task(cfg_a), fl_a, make_host_mesh(),
                            {"images": ((8, 32, 32, 3), jnp.float32),
                             "labels": ((8,), jnp.int32)},
                            local_steps=2)
    assert "tensor<3x2xf32>" not in lowered_a.as_text()


# ---------------------------------------------------------------------------
# _pack_client_batches
# ---------------------------------------------------------------------------


def _idx_batch(sel):
    """Identity batch: carries the selected indices through the packer."""
    return {"idx": jnp.asarray(np.asarray(sel, np.int64))}


def _idx_batch_host(sel):
    """The identity batch as host arrays (as the launcher's loader)."""
    return {"idx": np.asarray(sel, np.int64)}


_IDX_LOADERS = pytest.mark.parametrize(
    "idx_batch", [_idx_batch, _idx_batch_host], ids=["device", "host"])


@_IDX_LOADERS
def test_pack_client_batches_shapes_and_membership(idx_batch):
    parts = [np.array([0, 1, 2, 3, 4]), np.array([10, 11, 12])]
    out = _pack_client_batches(parts, idx_batch, n_steps=3, batch_size=2,
                               rng=np.random.default_rng(0))
    assert out["idx"].shape == (2, 3, 2)          # (N, steps, B)
    for c, part in enumerate(parts):
        assert set(np.asarray(out["idx"][c]).ravel()) <= set(part)


@_IDX_LOADERS
def test_pack_client_batches_empty_shard_selects_index_zero(idx_batch):
    """An empty client shard must still produce full-shape batches
    (index 0 placeholders) so the vmapped round never sees ragged data."""
    parts = [np.array([], np.int64), np.array([5, 6, 7, 8])]
    out = _pack_client_batches(parts, idx_batch, n_steps=2, batch_size=3,
                               rng=np.random.default_rng(0))
    assert out["idx"].shape == (2, 2, 3)
    np.testing.assert_array_equal(np.asarray(out["idx"][0]),
                                  np.zeros((2, 3), np.int64))


@_IDX_LOADERS
def test_pack_client_batches_short_shard_samples_with_replacement(
        idx_batch):
    """A shard shorter than the batch size samples WITH replacement —
    every batch is full and draws only from the client's own shard."""
    parts = [np.array([41, 42])]                   # shard < batch_size
    out = _pack_client_batches(parts, idx_batch, n_steps=2, batch_size=5,
                               rng=np.random.default_rng(0))
    got = np.asarray(out["idx"][0])
    assert got.shape == (2, 5)
    assert set(got.ravel()) <= {41, 42}
    # with replacement, 5 draws from 2 values must repeat something
    assert any(len(np.unique(row)) < len(row) for row in got)


@_IDX_LOADERS
def test_pack_client_batches_deterministic_under_fixed_seed(idx_batch):
    parts = [np.arange(20), np.arange(30, 50), np.array([7])]
    a = _pack_client_batches(parts, idx_batch, n_steps=4, batch_size=6,
                             rng=np.random.default_rng(123))
    b = _pack_client_batches(parts, idx_batch, n_steps=4, batch_size=6,
                             rng=np.random.default_rng(123))
    np.testing.assert_array_equal(np.asarray(a["idx"]),
                                  np.asarray(b["idx"]))
    c = _pack_client_batches(parts, idx_batch, n_steps=4, batch_size=6,
                             rng=np.random.default_rng(124))
    assert not np.array_equal(np.asarray(a["idx"]), np.asarray(c["idx"]))


def _host_batch(sel):
    return {"images": _DS.images[sel], "labels": _DS.labels[sel]}


@pytest.mark.parametrize("poisoned", [False, True])
def test_pack_client_batches_host_and_device_paths_agree(poisoned):
    """A loader of host arrays and one of device arrays give the same
    packed batches bit for bit, as device arrays of the loader's dtypes,
    with the rng left in the same state, and a label-flip hook applied
    alike to both."""
    from repro.fl import attacks
    flip = attacks.get("label_flip")
    hooks = ([None, lambda b: flip.poison_batch(b, 4), None]
             if poisoned else None)
    parts = [np.arange(30), np.array([], np.int64), np.array([40, 41])]
    rngs = [np.random.default_rng(5), np.random.default_rng(5)]
    dev, host = (_pack_client_batches(parts, get, 3, 8, rng,
                                      poison_fns=hooks)
                 for get, rng in zip((_get_batch, _host_batch), rngs))
    for k in ("images", "labels"):
        assert isinstance(host[k], jax.Array)
        assert host[k].dtype == getattr(_DS, k).dtype == dev[k].dtype
        assert host[k].shape == (3, 3, 8) + getattr(_DS, k).shape[1:]
        np.testing.assert_array_equal(np.asarray(host[k]),
                                      np.asarray(dev[k]))
    np.testing.assert_array_equal(rngs[0].integers(2**31, size=4),
                                  rngs[1].integers(2**31, size=4))
    honest = _pack_client_batches(parts, _host_batch, 3, 8,
                                  np.random.default_rng(5))
    flipped = np.asarray(host["labels"][1]) != np.asarray(
        honest["labels"][1])
    assert flipped.any() == poisoned


@pytest.mark.parametrize("cohort_size", [3, 2], ids=["full", "tiled"])
def test_host_and_device_loaders_give_the_same_run(cohort_size):
    """A whole run with a loader of host arrays equals the run with one
    of device arrays, bit for bit: one cohort of every client, and the
    clients in cohort tiles."""
    task = cnn_task(_cfg("fedavg"))
    fl = FLConfig(population=3, cohort_size=cohort_size, rounds=2,
                  local_epochs=1, steps_per_epoch=2, batch_size=8,
                  lr=0.02, momentum=0.9, method="fedavg", seed=0)
    parts = nxc_partition(_DS.labels, 3, 2, 4, seed=0)
    dev, host = (run_federated(task, fl, parts, get, _TEST_BATCHES)
                 for get in (_get_batch, _host_batch))
    assert dev["acc"] == host["acc"]
    for a, b in zip(jax.tree_util.tree_leaves(dev["final_params"]),
                    jax.tree_util.tree_leaves(host["final_params"])):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


# ---------------------------------------------------------------------------
# pad_tile_inputs — THE shared padding semantics of cohort tiling,
# capacity tiers (fl/capacity.py) and async dispatch groups
# (fl/async_engine.py)
# ---------------------------------------------------------------------------


def _pop(group_weights=None):
    from repro.fl.population import Population
    parts = [np.array([0, 1, 2, 3]), np.array([4, 5]),
             np.array([6, 7, 8])]
    return Population.from_parts(parts, group_weights=group_weights)


def test_pad_tile_inputs_pads_first_id_at_zero_weight():
    from repro.fl.runtime import pad_tile_inputs
    pop = _pop()
    ids, w, gw, batches = pad_tile_inputs(
        pop, [2, 0], 4, _idx_batch, 2, 3, np.random.default_rng(0))
    np.testing.assert_array_equal(ids, [2, 0, 2, 2])   # repeat first id
    assert (w[:2] > 0).all() and (w[2:] == 0).all()    # pad rows: w = 0
    assert gw is None
    assert batches["idx"].shape == (4, 2, 3)           # full tile width
    # pad-row batches draw from the repeated client's own shard
    assert set(np.asarray(batches["idx"][2]).ravel()) <= {6, 7, 8}


def test_pad_tile_inputs_zeroes_presence_rows():
    from repro.fl.runtime import pad_tile_inputs
    gw = np.arange(12, dtype=np.float64).reshape(3, 4) + 1.0
    pop = _pop(group_weights=gw)
    _, w, got, _ = pad_tile_inputs(
        pop, [1], 3, _idx_batch, 1, 2, np.random.default_rng(0))
    np.testing.assert_array_equal(got[0], gw[1])       # real presence row
    np.testing.assert_array_equal(got[1:], 0.0)        # pad rows zeroed
    # gw_cols=K: a tier keeps only its first K group columns
    _, _, cut, _ = pad_tile_inputs(
        pop, [1], 3, _idx_batch, 1, 2, np.random.default_rng(0),
        gw_cols=2)
    np.testing.assert_array_equal(cut, got[:, :2])
    assert cut.shape == (3, 2)


def test_pad_tile_inputs_uniform_weights():
    from repro.fl.runtime import pad_tile_inputs
    _, w, _, _ = pad_tile_inputs(
        _pop(), [1, 2], 4, _idx_batch, 1, 2, np.random.default_rng(0),
        uniform_weights=True)
    np.testing.assert_array_equal(w, [1.0, 1.0, 0.0, 0.0])


def test_pad_tile_inputs_matches_pack_client_batches():
    """The padded tile's batches are exactly _pack_client_batches over
    the padded id list under the same rng state — the agreement that
    makes the sync fast path, cohort tiling, the tiered path and async
    dispatch groups interchangeable at equal rng position."""
    from repro.fl.runtime import pad_tile_inputs
    pop = _pop()
    ids, w, _, got = pad_tile_inputs(
        pop, [2, 1], 3, _idx_batch, 2, 2, np.random.default_rng(7))
    want = _pack_client_batches([pop.parts[i] for i in ids], _idx_batch,
                                2, 2, np.random.default_rng(7))
    np.testing.assert_array_equal(np.asarray(got["idx"]),
                                  np.asarray(want["idx"]))
    np.testing.assert_array_equal(w[:2], pop.weights[[2, 1]])
