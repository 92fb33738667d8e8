"""The main path's Pallas kernels compile for a TPU v5e chip at the sizes
Fed2 on VGG9 puts through them, and so does the causal attention of
DeepSeek-V2-Lite's latent attention at 4,096 tokens.

Nothing runs: each test compiles for a chip that is described, not
attached, so the TPU compiler's refusals (block tiling, VMEM) surface here
on a CPU host. The topology is described inside a fixture, so importing or
collecting this file never loads the TPU library; the tests skip where it
cannot be described. The kernels are called with ``interpret=False``
directly, because on a CPU backend ``ops.pallas_interpret()`` is True.
"""
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import vgg9
from repro.core import fusion
from repro.kernels import ops
from repro.kernels.local_step import local_step_kernel
from repro.kernels.paired_fusion import paired_fusion_kernel
from repro.models.attention import chunked_attention
from repro.models.cnn import init_cnn

FED2_GROUPS = 8   # the launcher's --fed2-groups default


@pytest.fixture(scope="module")
def one_chip():
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")   # no compiler logs in /tmp
        from jax.experimental import topologies
        try:
            topo = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:  # noqa: BLE001 - any failure means "can't"
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        yield SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def no_compile_cache():
    """A compile for a described chip is written to the persistent cache
    but cannot be read back without one: keep the cache out of it."""
    from jax.experimental.compilation_cache import compilation_cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _leaves(cfg):
    params = jax.eval_shape(lambda k: init_cnn(k, cfg), jax.random.PRNGKey(0))
    axes = fusion.cnn_group_axes(params, cfg)
    return list(zip(
        jax.tree_util.tree_leaves(params),
        jax.tree_util.tree_leaves(
            axes, is_leaf=lambda x: x is None
            or isinstance(x, fusion.GroupAxis))))


def _fusion_width(leaf_kind: str) -> int:
    """Columns of the (N, M) view one kernel call fuses: the largest
    shared leaf of VGG9 at its published widths, or one group's block of
    the largest grouped leaf of the Fed2-adapted VGG9."""
    if leaf_kind == "shared":
        return max(int(np.prod(l.shape)) for l, _ in _leaves(vgg9.baseline()))
    return max(int(np.prod(l.shape)) // ga.n_groups
               for l, ga in _leaves(vgg9.full(fed2_groups=FED2_GROUPS))
               if ga is not None)


def _compiled_text(fn, *specs) -> str:
    return jax.jit(fn).lower(*specs).compile().as_text()


@pytest.mark.parametrize("leaf_kind,n", [("shared", 8), ("fed2-group", 8),
                                         ("shared", 256)])
def test_paired_fusion_compiles_for_v5e(one_chip, no_compile_cache,
                                        leaf_kind, n):
    m = _fusion_width(leaf_kind)
    bm = ops.fusion_block_cols(n, m)
    x = jax.ShapeDtypeStruct((n, -(-m // bm) * bm), jnp.float32,
                             sharding=one_chip)
    w = jax.ShapeDtypeStruct((n,), jnp.float32, sharding=one_chip)
    hlo = _compiled_text(
        lambda a, b: paired_fusion_kernel(a, b, bm=bm, interpret=False),
        x, w)
    assert "tpu_custom_call" in hlo
    # the kernel's own name, which the benchmark's trace readers match
    assert re.search(r"%paired_fusion(\.\d+)? = ", hlo)


@pytest.mark.parametrize("cohort", [None, 8])
def test_local_step_compiles_for_v5e(one_chip, no_compile_cache, cohort):
    """The flattened Fed2-VGG9 params, padded as ops.local_step pads them;
    ``cohort``: vmapped over the clients as the engine's local phase
    calls it."""
    m0 = sum(int(np.prod(l.shape))
             for l, _ in _leaves(vgg9.full(fed2_groups=FED2_GROUPS)))
    bm = min(1024, -(-m0 // 128) * 128)
    shape = (1, -(-m0 // bm) * bm)

    def step(p, v, g):
        return local_step_kernel(p, v, g, lr=0.01, mu=0.9, bm=bm,
                                 interpret=False)

    if cohort is not None:
        shape = (cohort,) + shape
        step = jax.vmap(step)
    spec = jax.ShapeDtypeStruct(shape, jnp.float32, sharding=one_chip)
    hlo = _compiled_text(step, spec, spec, spec)
    assert "tpu_custom_call" in hlo
    assert re.search(r"%local_step(\.\d+)? = ", hlo)


def test_causal_attention_compiles_for_v5e_without_conditionals(
        one_chip, no_compile_cache):
    """MLA's shapes (16 heads, q/k width 192, V padded to it) at 4,096
    tokens, forward and backward under the block's remat: the block
    schedule is static, so the program holds no ``conditional``."""
    spec = jax.ShapeDtypeStruct((1, 4096, 16, 192), jnp.float32,
                                sharding=one_chip)

    def loss(q, k, v):
        attend = jax.checkpoint(
            lambda q, k, v: chunked_attention(q, k, v, causal=True))
        return jnp.sum(attend(q, k, v) ** 2)

    hlo = _compiled_text(jax.grad(loss, argnums=(0, 1, 2)), spec, spec, spec)
    assert " while(" in hlo
    assert "conditional(" not in hlo
