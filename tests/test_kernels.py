"""Pallas kernels vs pure-jnp oracles: shape/dtype sweeps (interpret mode)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref

KEY = jax.random.PRNGKey(0)


@pytest.mark.parametrize("m,g,k,n", [
    (64, 4, 32, 48), (128, 8, 128, 128), (200, 5, 100, 70),
    (16, 2, 256, 512), (1, 10, 52, 4), (130, 13, 13, 13),
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_grouped_matmul(m, g, k, n, dtype):
    x = jax.random.normal(KEY, (m, g * k), dtype)
    w = jax.random.normal(jax.random.PRNGKey(1), (g, k, n), dtype)
    b = jax.random.normal(jax.random.PRNGKey(2), (g, n), dtype)
    got = ops.grouped_matmul(x, w, b)
    want = ref.grouped_matmul_ref(x, w, b)
    tol = 1e-4 if dtype == jnp.float32 else 0.3
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               atol=tol * np.sqrt(k), rtol=tol)


def test_grouped_matmul_leading_dims():
    x = jax.random.normal(KEY, (3, 5, 4 * 16))
    w = jax.random.normal(jax.random.PRNGKey(1), (4, 16, 8))
    got = ops.grouped_matmul(x, w)
    want = ref.grouped_matmul_ref(x, w)
    assert got.shape == (3, 5, 32)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-4)


def test_grouped_matmul_matches_dense_blockdiag():
    """Block-diagonal semantics: equal to a dense matmul against the
    explicitly block-diagonal weight matrix."""
    g, k, n, m = 3, 8, 6, 10
    x = jax.random.normal(KEY, (m, g * k))
    w = jax.random.normal(jax.random.PRNGKey(1), (g, k, n))
    dense = np.zeros((g * k, g * n), np.float32)
    for i in range(g):
        dense[i * k:(i + 1) * k, i * n:(i + 1) * n] = np.asarray(w[i])
    np.testing.assert_allclose(np.asarray(ops.grouped_matmul(x, w)),
                               np.asarray(x) @ dense, atol=1e-4)


@pytest.mark.parametrize("b,i", [(32, 100), (256, 512), (100, 1000), (7, 3)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_feature_stats(b, i, dtype):
    a = jax.random.normal(KEY, (b, i), dtype)
    g = jax.random.normal(jax.random.PRNGKey(3), (b, i), dtype)
    got = ops.feature_stats(a, g)
    want = ref.feature_stats_ref(a, g)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=2e-1 if dtype == jnp.bfloat16 else 1e-3,
                               rtol=1e-2)


@pytest.mark.parametrize("n,shape", [(4, (33, 7)), (10, (128,)),
                                     (3, (5, 6, 7)), (2, (1,)),
                                     (600, (3000,))])
def test_paired_fusion(n, shape):
    s = jax.random.normal(KEY, (n,) + shape)
    w = jnp.abs(jax.random.normal(jax.random.PRNGKey(4), (n,))) + 0.1
    got = ops.paired_fusion(s, w)
    wn = w / jnp.sum(w)
    want = ref.paired_fusion_ref(s.reshape(n, -1), wn).reshape(shape)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-5)


@pytest.mark.parametrize("n", [3, 8, 600, 4096])
def test_fusion_block_cols_bounds_vmem(n):
    """The (N, bm) fusion block is lane-aligned, at most the default
    1024 columns, no wider than a small leaf needs, and inside the VMEM
    budget at any cohort up to 4096 clients."""
    bm = ops.fusion_block_cols(n, 10 ** 7)
    assert bm % 128 == 0 and 128 <= bm <= 1024
    assert -(-n // 8) * 8 * bm * 4 <= ops.FUSION_BLOCK_BYTES
    assert ops.fusion_block_cols(n, 5) == 128


@pytest.mark.parametrize("b,h,p,n", [(2, 8, 16, 32), (1, 3, 8, 8),
                                     (4, 20, 32, 64)])
def test_ssd_update(b, h, p, n):
    hs = jax.random.normal(KEY, (b, h, p, n))
    x = jax.random.normal(jax.random.PRNGKey(1), (b, h, p))
    dt = jax.nn.softplus(jax.random.normal(jax.random.PRNGKey(2), (b, h)))
    a_log = jax.random.normal(jax.random.PRNGKey(3), (h,)) * 0.1
    bm = jax.random.normal(jax.random.PRNGKey(4), (b, n))
    cm = jax.random.normal(jax.random.PRNGKey(5), (b, n))
    d = jnp.ones((h,))
    hn1, y1 = ops.ssd_update(hs, x, dt, a_log, bm, cm, d, bh=4)
    hn2, y2 = ref.ssd_update_ref(hs, x, dt, a_log, bm, cm, d)
    np.testing.assert_allclose(np.asarray(hn1), np.asarray(hn2), atol=1e-4,
                               rtol=1e-4)
    np.testing.assert_allclose(np.asarray(y1), np.asarray(y2), atol=1e-4,
                               rtol=1e-4)


def test_ssd_update_matches_model_step():
    """Kernel == models/ssm.ssd_step (the production decode recurrence)."""
    from repro.models.ssm import ssd_step
    b, h, p, n = 2, 8, 16, 32
    hs = jax.random.normal(KEY, (b, h, p, n))
    x = jax.random.normal(jax.random.PRNGKey(1), (b, h, p))
    dt = jax.nn.softplus(jax.random.normal(jax.random.PRNGKey(2), (b, h)))
    a_log = jnp.zeros((h,))
    bm = jax.random.normal(jax.random.PRNGKey(4), (b, n))
    cm = jax.random.normal(jax.random.PRNGKey(5), (b, n))
    d = jnp.ones((h,))
    hn1, y1 = ops.ssd_update(hs, x, dt, a_log, bm, cm, d)
    hn2, y2 = ssd_step(hs, x, dt, a_log, bm, cm, d)
    np.testing.assert_allclose(np.asarray(hn1), np.asarray(hn2), atol=1e-4)
    np.testing.assert_allclose(np.asarray(y1), np.asarray(y2), atol=1e-4)


def test_paired_fusion_with_perms():
    s = jax.random.normal(KEY, (2, 8, 4))
    perms = np.array([[0, 1, 2, 3], [2, 3, 0, 1]])
    got = ops.paired_fusion(s, jnp.ones(2), group_axis=(0, 4), perms=perms)
    permuted = np.asarray(s[1]).reshape(4, 2, 4)[perms[1]].reshape(8, 4)
    want = 0.5 * (np.asarray(s[0]) + permuted)
    np.testing.assert_allclose(np.asarray(got), want, atol=1e-6)


@pytest.mark.parametrize("m", [64, 128, 1000, 5000, 1])
@pytest.mark.parametrize("mu", [0.0, 0.9])
def test_local_step(m, mu):
    p = jax.random.normal(KEY, (m,))
    v = jax.random.normal(jax.random.PRNGKey(1), (m,)) * 0.1
    g = jax.random.normal(jax.random.PRNGKey(2), (m,))
    p2, v2 = ops.local_step(p, v, g, lr=0.05, mu=mu)
    pr, vr = ref.local_step_ref(p, v, g, lr=0.05, mu=mu)
    np.testing.assert_allclose(np.asarray(p2), np.asarray(pr), atol=1e-6)
    np.testing.assert_allclose(np.asarray(v2), np.asarray(vr), atol=1e-6)


def test_local_step_bf16_storage_fp32_compute():
    """bf16 params/velocity round through an fp32 update (DESIGN.md §15):
    the kernel must match the fp32 oracle to bf16 resolution, not
    accumulate in bf16."""
    m = 512
    p = jax.random.normal(KEY, (m,), jnp.bfloat16)
    v = (jax.random.normal(jax.random.PRNGKey(1), (m,)) * 0.1
         ).astype(jnp.bfloat16)
    g = jax.random.normal(jax.random.PRNGKey(2), (m,), jnp.bfloat16)
    p2, v2 = ops.local_step(p, v, g, lr=0.05, mu=0.9)
    assert p2.dtype == jnp.bfloat16 and v2.dtype == jnp.bfloat16
    pr, vr = ref.local_step_ref(p, v, g, lr=0.05, mu=0.9)
    np.testing.assert_allclose(np.asarray(p2, np.float32),
                               np.asarray(pr, np.float32), atol=2e-2)
    np.testing.assert_allclose(np.asarray(v2, np.float32),
                               np.asarray(vr, np.float32), atol=2e-2)


def test_local_step_under_vmap():
    """The engine calls the kernel inside a vmapped client axis."""
    n, m = 3, 700
    p = jax.random.normal(KEY, (n, m))
    v = jnp.zeros((n, m))
    g = jax.random.normal(jax.random.PRNGKey(2), (n, m))
    p2, v2 = jax.vmap(
        lambda a, b, c: ops.local_step(a, b, c, lr=0.1, mu=0.5))(p, v, g)
    pr, vr = jax.vmap(
        lambda a, b, c: ref.local_step_ref(a, b, c, lr=0.1, mu=0.5))(p, v, g)
    np.testing.assert_allclose(np.asarray(p2), np.asarray(pr), atol=1e-6)
    np.testing.assert_allclose(np.asarray(v2), np.asarray(vr), atol=1e-6)


def test_pallas_interpret_reads_env_per_call(monkeypatch):
    """The interpret/compile switch is the platform rule — compiled on a
    TPU backend, interpreted on any other — resolved per call, never
    frozen at import (a module constant would keep a CPU answer after
    the backend changes). fusion's default_use_kernel shares the rule."""
    from repro.core import fusion
    monkeypatch.delenv("REPRO_FUSION_KERNEL", raising=False)
    assert ops.pallas_interpret() is (jax.default_backend() != "tpu")
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert ops.pallas_interpret() is False
    assert fusion.default_use_kernel() is True
    for other in ("cpu", "gpu"):
        monkeypatch.setattr(jax, "default_backend", lambda o=other: o)
        assert ops.pallas_interpret() is True
        assert fusion.default_use_kernel() is False
