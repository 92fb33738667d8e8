"""The Pallas flatten-to-(N, M) fusion fast path must produce the SAME
global params as the tree_map reference reduction — for plain, weighted,
presence-weighted (non-IID), and permuted-pairing fusion."""
import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import vgg9
from repro.core import fusion
from repro.core.grouping import GroupSpec
from repro.models.cnn import init_cnn

KEY = jax.random.PRNGKey(0)


def _stacked_params(n=3):
    cfg = vgg9.reduced()
    p = init_cnn(KEY, cfg)
    ga = fusion.cnn_group_axes(p, cfg)
    stacked = jax.tree_util.tree_map(
        lambda a: jnp.stack([a * (1.0 + 0.5 * i) + 0.1 * i
                             for i in range(n)]), p)
    return cfg, stacked, ga


def _assert_trees_equal(a, b, atol=2e-5):
    fa = jax.tree_util.tree_flatten_with_path(a)[0]
    fb = jax.tree_util.tree_flatten_with_path(b)[0]
    assert len(fa) == len(fb)
    for (ka, la), (_, lb) in zip(fa, fb):
        np.testing.assert_allclose(np.asarray(la), np.asarray(lb),
                                   atol=atol, err_msg=str(ka))


def test_fedavg_kernel_matches_reference():
    _, stacked, _ = _stacked_params()
    _assert_trees_equal(fusion.fedavg(stacked),
                        fusion.fedavg(stacked, use_kernel=True))


def test_fedavg_kernel_matches_reference_weighted():
    _, stacked, _ = _stacked_params()
    w = [1.0, 5.0, 2.0]
    _assert_trees_equal(fusion.fedavg(stacked, w),
                        fusion.fedavg(stacked, w, use_kernel=True))


def test_paired_average_kernel_matches_reference_weighted():
    _, stacked, ga = _stacked_params()
    w = [3.0, 1.0, 2.0]
    ref = fusion.paired_average(stacked, ga, weights=w)
    fast = fusion.paired_average(stacked, ga, weights=w, use_kernel=True)
    _assert_trees_equal(ref, fast)


def test_paired_average_kernel_matches_presence_weighted():
    """Non-IID case: per-(node, group) presence weights — the fast path
    fuses each group column in its own kernel pass."""
    cfg, stacked, ga = _stacked_params()
    spec = GroupSpec.contiguous(cfg.fed2_groups, cfg.n_classes)
    rng = np.random.default_rng(7)
    counts = rng.integers(0, 6, size=(3, cfg.n_classes))
    counts[0, :4] = 0            # node 0 misses some groups entirely
    gw = fusion.presence_group_weights(counts, spec)
    ref = fusion.paired_average(stacked, ga, weights=[1.0, 2.0, 3.0],
                                group_weights=gw)
    fast = fusion.paired_average(stacked, ga, weights=[1.0, 2.0, 3.0],
                                 group_weights=gw, use_kernel=True)
    _assert_trees_equal(ref, fast)


def test_paired_average_kernel_with_perms():
    """The fast path applies pairing permutations as a pre-gather; result
    must match the reference permuted fusion."""
    rng = np.random.default_rng(0)
    n, g, blk = 3, 4, 5
    base = rng.normal(size=(n, g * blk, 6)).astype(np.float32)
    perms = np.stack([rng.permutation(g) for _ in range(n)])
    stacked = {"w": jnp.asarray(base)}
    ga = {"w": fusion.GroupAxis(0, g)}
    ref = fusion.paired_average(stacked, ga, perms=perms)
    fast = fusion.paired_average(stacked, ga, perms=perms, use_kernel=True)
    _assert_trees_equal(ref, fast)


def test_kernel_fuse_inside_jit():
    """The fast path is jittable (it runs inside the engine's one-round
    program)."""
    _, stacked, ga = _stacked_params()

    @jax.jit
    def f(s):
        return fusion.paired_average(s, ga, weights=jnp.array([1., 2., 3.]),
                                     use_kernel=True)

    _assert_trees_equal(f(stacked),
                        fusion.paired_average(stacked, ga,
                                              weights=[1.0, 2.0, 3.0]))


def test_default_use_kernel_env(monkeypatch):
    monkeypatch.setenv("REPRO_FUSION_KERNEL", "1")
    assert fusion.default_use_kernel()
    monkeypatch.setenv("REPRO_FUSION_KERNEL", "0")
    assert not fusion.default_use_kernel()
    monkeypatch.delenv("REPRO_FUSION_KERNEL")
    # unset: the platform decides — kernel fusion on a TPU backend only
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert fusion.default_use_kernel()
    monkeypatch.setattr(jax, "default_backend", lambda: "cpu")
    assert not fusion.default_use_kernel()
    # the env overrides the platform either way
    monkeypatch.setenv("REPRO_FUSION_KERNEL", "1")
    assert fusion.default_use_kernel()
