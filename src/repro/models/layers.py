"""Primitive layers: dense, grouped (block-diagonal) dense, convs, norms, RoPE.

GroupedDense is the transformer-side analog of the paper's group convolution
(DESIGN.md §3): weight is stored block-diagonally as (G, d_in/G, d_out/G), so
gradients cannot flow across groups — Fed2's feature isolation (Eq. 13-14).
"""
from __future__ import annotations

import dataclasses
import math

import jax
import jax.numpy as jnp
import numpy as np

from repro.models.module import default_init

# ---------------------------------------------------------------------------
# Dense / GroupedDense
# ---------------------------------------------------------------------------


def dense_init(key, d_in: int, d_out: int, *, bias: bool = False,
               dtype=jnp.float32):
    p = {"w": default_init(key, (d_in, d_out), fan_in=d_in, dtype=dtype)}
    if bias:
        p["b"] = jnp.zeros((d_out,), dtype)
    return p


def dense_apply(p, x):
    y = jnp.einsum("...i,io->...o", x, p["w"])
    if "b" in p:
        y = y + p["b"]
    return y


def grouped_dense_init(key, groups: int, d_in: int, d_out: int, *,
                       bias: bool = False, dtype=jnp.float32):
    """Block-diagonal dense: group g maps x[..., g-th in-slice] -> g-th out-slice."""
    assert d_in % groups == 0 and d_out % groups == 0, (groups, d_in, d_out)
    gi, go = d_in // groups, d_out // groups
    p = {"w": default_init(key, (groups, gi, go), fan_in=gi, dtype=dtype)}
    if bias:
        p["b"] = jnp.zeros((groups, go), dtype)
    return p


def grouped_dense_apply(p, x, *, use_kernel: bool = False):
    """x: (..., G*gi) -> (..., G*go). Pallas kernel path optional (ops.py)."""
    if use_kernel:
        from repro.kernels import ops as _kops
        return _kops.grouped_matmul(x, p["w"], p.get("b"))
    g, gi, go = p["w"].shape
    xg = x.reshape(x.shape[:-1] + (g, gi))
    y = jnp.einsum("...gi,gio->...go", xg, p["w"])
    if "b" in p:
        y = y + p["b"]
    return y.reshape(x.shape[:-1] + (g * go,))


# ---------------------------------------------------------------------------
# Norms
# ---------------------------------------------------------------------------


def rmsnorm_init(d: int, dtype=jnp.float32):
    return {"scale": jnp.ones((d,), dtype)}


def rmsnorm_apply(p, x, *, eps: float = 1e-6):
    dt = x.dtype
    x32 = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(x32), axis=-1, keepdims=True)
    return (x32 * jax.lax.rsqrt(var + eps)).astype(dt) * p["scale"]


def layernorm_init(d: int, dtype=jnp.float32):
    return {"scale": jnp.ones((d,), dtype), "bias": jnp.zeros((d,), dtype)}


def layernorm_apply(p, x, *, eps: float = 1e-5):
    dt = x.dtype
    x32 = x.astype(jnp.float32)
    mu = jnp.mean(x32, axis=-1, keepdims=True)
    var = jnp.var(x32, axis=-1, keepdims=True)
    y = (x32 - mu) * jax.lax.rsqrt(var + eps)
    return y.astype(dt) * p["scale"] + p["bias"]


def groupnorm_init(d: int, dtype=jnp.float32):
    return {"scale": jnp.ones((d,), dtype), "bias": jnp.zeros((d,), dtype)}


def groupnorm_apply(p, x, *, groups: int, eps: float = 1e-5,
                    channel_axis: int = -1):
    """GroupNorm (Wu & He 2018) over the channel axis, per Fed2 §5.1.

    x: (..., C) with channels last (NHWC for convs). Statistics are computed
    per (sample, group) over within-group channels and all spatial dims.
    """
    dt = x.dtype
    x32 = x.astype(jnp.float32)
    c = x.shape[channel_axis]
    assert c % groups == 0, (c, groups)
    shp = x.shape[:-1] + (groups, c // groups)
    xg = x32.reshape(shp)
    # reduce over spatial dims and within-group channels: all but batch, group
    red_axes = tuple(range(1, xg.ndim - 2)) + (xg.ndim - 1,)
    mu = jnp.mean(xg, axis=red_axes, keepdims=True)
    var = jnp.var(xg, axis=red_axes, keepdims=True)
    y = ((xg - mu) * jax.lax.rsqrt(var + eps)).reshape(x.shape).astype(dt)
    return y * p["scale"] + p["bias"]


def batchnorm_init(d: int, dtype=jnp.float32):
    # Training-mode batch statistics (per-batch, as in FL local training).
    return {"scale": jnp.ones((d,), dtype), "bias": jnp.zeros((d,), dtype)}


def batchnorm_apply(p, x, *, eps: float = 1e-5):
    """Batch-stat normalization over all axes but channels-last."""
    dt = x.dtype
    x32 = x.astype(jnp.float32)
    red = tuple(range(x.ndim - 1))
    mu = jnp.mean(x32, axis=red, keepdims=True)
    var = jnp.var(x32, axis=red, keepdims=True)
    y = ((x32 - mu) * jax.lax.rsqrt(var + eps)).astype(dt)
    return y * p["scale"] + p["bias"]


# ---------------------------------------------------------------------------
# Convolutions (NHWC)
# ---------------------------------------------------------------------------


def conv2d_init(key, c_in: int, c_out: int, k: int, *, groups: int = 1,
                bias: bool = True, dtype=jnp.float32):
    assert c_in % groups == 0 and c_out % groups == 0
    fan_in = (c_in // groups) * k * k
    p = {"w": default_init(key, (k, k, c_in // groups, c_out), fan_in=fan_in,
                           dtype=dtype)}
    if bias:
        p["b"] = jnp.zeros((c_out,), dtype)
    return p


def conv2d_apply(p, x, *, stride: int = 1, groups: int = 1,
                 padding: str = "SAME"):
    y = jax.lax.conv_general_dilated(
        x, p["w"], window_strides=(stride, stride), padding=padding,
        dimension_numbers=("NHWC", "HWIO", "NHWC"),
        feature_group_count=groups)
    if "b" in p:
        y = y + p["b"]
    return y


def conv1d_depthwise_init(key, channels: int, k: int, dtype=jnp.float32):
    p = {"w": default_init(key, (k, 1, channels), fan_in=k, dtype=dtype),
         "b": jnp.zeros((channels,), dtype)}
    return p


def conv1d_depthwise_apply(p, x, *, causal: bool = True):
    """x: (B, L, C) depthwise causal conv (Mamba-style)."""
    k = p["w"].shape[0]
    pad = [(k - 1, 0)] if causal else [((k - 1) // 2, k // 2)]
    y = jax.lax.conv_general_dilated(
        x, p["w"], window_strides=(1,), padding=pad,
        dimension_numbers=("NLC", "LIO", "NLC"),
        feature_group_count=x.shape[-1])
    return y + p["b"]


# ---------------------------------------------------------------------------
# Rotary position embeddings
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class YaRN:
    """YaRN rope scaling (arXiv:2309.00071) as DeepSeek-V2's
    ``rope_scaling`` states it: frequencies past the correction range are
    divided by ``factor``, those inside it are kept, with a linear ramp
    between; attention logits are scaled by ``softmax_mscale``."""
    factor: float = 40.0
    original_max_position: int = 4096
    beta_fast: float = 32.0
    beta_slow: float = 1.0
    mscale: float = 0.707
    mscale_all_dim: float = 0.707

    def __post_init__(self):
        # the rotation's cos/sin carry m(factor, mscale) / m(factor,
        # mscale_all_dim), which is 1 where the two agree, as in both
        # DeepSeek-V2 configs; rope_freqs applies no other factor
        if self.mscale != self.mscale_all_dim:
            raise ValueError("YaRN with mscale != mscale_all_dim scales "
                             "cos/sin, which apply_rope does not")


def yarn_mscale(scale: float, mscale: float = 1.0) -> float:
    return 0.1 * mscale * math.log(scale) + 1.0 if scale > 1 else 1.0


def yarn_softmax_mscale(yarn: YaRN | None) -> float:
    """The factor on the ``d**-0.5`` softmax scale: ``m(factor,
    mscale_all_dim)**2`` (1 without YaRN)."""
    if yarn is None or not yarn.mscale_all_dim:
        return 1.0
    return yarn_mscale(yarn.factor, yarn.mscale_all_dim) ** 2


def _yarn_inv_freq(rd: int, theta: float, yarn: YaRN) -> np.ndarray:
    extra = 1.0 / (theta ** (np.arange(0, rd, 2, dtype=np.float32) / rd))
    inter = extra / yarn.factor

    def dim(rotations):
        return (rd * math.log(yarn.original_max_position
                              / (rotations * 2 * math.pi))
                / (2 * math.log(theta)))
    low = max(math.floor(dim(yarn.beta_fast)), 0)
    high = min(math.ceil(dim(yarn.beta_slow)), rd - 1)
    ramp = np.clip((np.arange(rd // 2, dtype=np.float32) - low)
                   / max(high - low, 1e-3), 0.0, 1.0)
    keep = 1.0 - ramp
    return (inter * (1.0 - keep) + extra * keep).astype(np.float32)


def rope_freqs(head_dim: int, theta: float = 10000.0,
               rotary_dim: int | None = None, yarn: YaRN | None = None):
    rd = rotary_dim if rotary_dim is not None else head_dim
    assert rd % 2 == 0
    if yarn is not None:
        return jnp.asarray(_yarn_inv_freq(rd, theta, yarn))
    inv = 1.0 / (theta ** (np.arange(0, rd, 2, dtype=np.float32) / rd))
    return jnp.asarray(inv)  # (rd/2,)


def apply_rope(x, positions, inv_freq, *, rotary_dim: int | None = None):
    """x: (B, S, H, D); positions: (B, S) int32. Rotates first rotary_dim dims."""
    d = x.shape[-1]
    rd = rotary_dim if rotary_dim is not None else d
    ang = positions[..., None].astype(jnp.float32) * inv_freq  # (B,S,rd/2)
    cos = jnp.cos(ang)[:, :, None, :]
    sin = jnp.sin(ang)[:, :, None, :]
    xr, xp = x[..., :rd], x[..., rd:]
    x1, x2 = xr[..., : rd // 2], xr[..., rd // 2:]
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return jnp.concatenate([out.astype(x.dtype), xp], axis=-1) if rd < d \
        else out.astype(x.dtype)


# ---------------------------------------------------------------------------
# Embedding
# ---------------------------------------------------------------------------


def embed_init(key, vocab: int, d: int, dtype=jnp.float32):
    return {"table": default_init(key, (vocab, d), fan_in=d, dtype=dtype)}


def embed_apply(p, ids):
    return jnp.take(p["table"], ids, axis=0)


def silu(x):
    return x * jax.nn.sigmoid(x)


def gelu(x):
    return jax.nn.gelu(x)
