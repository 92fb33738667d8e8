"""jit'd public wrappers around the Pallas kernels: padding, layout, bias,
and group-pairing gathers. The platform picks the kernel mode: compiled
on a TPU backend, interpreted everywhere else (``pallas_interpret``).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels.feature_stats import feature_stats_kernel
from repro.kernels.grouped_matmul import grouped_matmul_kernel
from repro.kernels.local_step import local_step_kernel
from repro.kernels.paired_fusion import paired_fusion_kernel
from repro.kernels.ssd_update import ssd_update_kernel


# VMEM bytes one (N, bm) fp32 fusion block may take; Pallas double-buffers
# it, and the kernel body holds one fp32 product of the same size, so a
# step stays well inside the 16 MiB of scoped VMEM on a v5e core.
FUSION_BLOCK_BYTES = 2 << 20


def pallas_interpret() -> bool:
    """Whether Pallas kernels run in interpret mode — THE single copy of
    the rule: compiled on a TPU backend, interpreted on any other. Read
    per call, never at import (importing must not touch device state).
    ``fusion.default_use_kernel()`` follows the same rule, so "compile for
    real" and "kernels on by default" flip together."""
    return jax.default_backend() != "tpu"


def fusion_block_cols(n: int, m: int, bm: int = 1024) -> int:
    """Column tile of the (N, M) fusion kernel: at most ``bm``, a lane
    multiple (128) no wider than M needs, and narrow enough that the
    (N, bm) fp32 block fits FUSION_BLOCK_BYTES (N rounded up to the
    8-row sublane tile). Never below one lane tile."""
    rows = -(-n // 8) * 8
    cap = max(128, FUSION_BLOCK_BYTES // (rows * 4) // 128 * 128)
    return min(bm, cap, -(-m // 128) * 128)


def _pad_to(x, mult, axis):
    size = x.shape[axis]
    pad = (-size) % mult
    if pad == 0:
        return x, size
    cfg = [(0, 0)] * x.ndim
    cfg[axis] = (0, pad)
    return jnp.pad(x, cfg), size


def grouped_matmul(x, w, b=None, *, bm: int = 128, bn: int = 128,
                   bk: int = 128):
    """Block-diagonal matmul. x: (..., G*K); w: (G, K, N); b: (G, N)."""
    g, k, n = w.shape
    lead = x.shape[:-1]
    xm = x.reshape(-1, x.shape[-1])
    m0 = xm.shape[0]
    # pad M
    xm, _ = _pad_to(xm, bm, 0)
    # pad K: pad each group column panel -> reshape (M, G, K) pad K
    kp = (-k) % bk
    np_ = (-n) % bn
    if kp:
        xg = xm.reshape(xm.shape[0], g, k)
        xg = jnp.pad(xg, ((0, 0), (0, 0), (0, kp)))
        xm = xg.reshape(xm.shape[0], g * (k + kp))
        w = jnp.pad(w, ((0, 0), (0, kp), (0, 0)))
    if np_:
        w = jnp.pad(w, ((0, 0), (0, 0), (0, np_)))
    y = grouped_matmul_kernel(xm, w, bm=bm, bn=bn, bk=bk,
                              interpret=pallas_interpret())
    y = y.reshape(y.shape[0], g, n + np_)[:m0, :, :n]
    if b is not None:
        y = y + b
    return y.reshape(lead + (g * n,))


def feature_stats(a, grad, *, bi: int = 512, bb: int = 256):
    """Fused per-neuron sum_b A*G. a, grad: (B, I) -> (I,) fp32."""
    a, i0 = _pad_to(a, bi, 1)
    grad, _ = _pad_to(grad, bi, 1)
    a, _ = _pad_to(a, bb, 0)
    grad, _ = _pad_to(grad, bb, 0)
    out = feature_stats_kernel(a, grad, bi=bi, bb=bb,
                               interpret=pallas_interpret())
    return out[0, :i0]


def ssd_update(h, x, dt, a_log, b, c, d_skip, *, bh: int = 8):
    """Fused SSD decode step. Pads H to a multiple of bh."""
    bs, hh, p, n = h.shape
    bh = min(bh, hh)
    pad = (-hh) % bh
    if pad:
        h = jnp.pad(h, ((0, 0), (0, pad), (0, 0), (0, 0)))
        x = jnp.pad(x, ((0, 0), (0, pad), (0, 0)))
        dt = jnp.pad(dt, ((0, 0), (0, pad)))
        a_log = jnp.pad(a_log, (0, pad))
        d_skip = jnp.pad(d_skip, (0, pad))
    hn, y = ssd_update_kernel(h, x, dt, a_log, b, c, d_skip, bh=bh,
                              interpret=pallas_interpret())
    return hn[:, :hh], y[:, :hh]


def local_step(params, vel, grads, *, lr: float, mu: float,
               bm: int = 1024):
    """Fused momentum-SGD step on FLAT (M,) views: v' = mu*v + g,
    p' = p - lr*v' in one fp32 pass (kernels/local_step.py). ``lr``/``mu``
    are static — the caller (methods.py's kernel-backed client_update)
    bakes the config values in. Pads to a lane-aligned tile like
    ``paired_fusion`` and slices back."""
    m0 = params.shape[0]
    bm = min(bm, -(-m0 // 128) * 128)       # lane-aligned, no 1024-padding
    p, _ = _pad_to(params.reshape(1, -1), bm, 1)
    v, _ = _pad_to(vel.reshape(1, -1), bm, 1)
    g, _ = _pad_to(grads.reshape(1, -1), bm, 1)
    p2, v2 = local_step_kernel(p, v, g, lr=float(lr), mu=float(mu), bm=bm,
                               interpret=pallas_interpret())
    return p2[0, :m0], v2[0, :m0]


def paired_fusion(stacked, weights, *, group_axis=None, perms=None,
                  bm: int = 1024):
    """Fused weighted client averaging of ONE stacked leaf (N, ...) — the
    unit the engine's flatten-to-(N, M) fast path (core/fusion.py) calls
    per bucket. Optional Fed2 pairing: reorder each client's group blocks
    (group_axis = (axis, n_groups) in the per-client view) by ``perms``
    (N, G) before the reduction. The column tile comes from
    ``fusion_block_cols``: small buckets don't pad to a full ``bm`` block,
    and large cohorts narrow it to keep the block's VMEM bounded."""
    n = stacked.shape[0]
    x = stacked
    if perms is not None and group_axis is not None:
        ax, g = group_axis
        ax = ax + 1  # account for the client axis
        size = x.shape[ax]
        blk = size // g
        shp = x.shape[:ax] + (g, blk) + x.shape[ax + 1:]
        xr = x.reshape(shp)
        xr = jax.vmap(lambda one, p: jnp.take(one, p, axis=ax - 1))(
            xr, jnp.asarray(perms))
        x = xr.reshape(x.shape)
    flat = x.reshape(n, -1)
    m0 = flat.shape[1]
    bm = fusion_block_cols(n, m0, bm)
    flat, _ = _pad_to(flat, bm, 1)
    w = jnp.asarray(weights, jnp.float32)
    w = w / jnp.sum(w)
    out = paired_fusion_kernel(flat, w, bm=bm,
                               interpret=pallas_interpret())
    return out[0, :m0].reshape(stacked.shape[1:])
