"""Pallas TPU kernel: fused momentum-SGD parameter step (DESIGN.md §15).

One local-SGD step's optimizer tail — velocity update + parameter update
— fused into a single elementwise pass:

    v' = mu * v + g
    p' = p - lr * v'

The naive optimizer (optim/optimizers.sgd) issues this as four separate
elementwise ops per leaf, each reading/writing HBM; this kernel streams
(p, v, g) tiles through VMEM once and writes (p', v') once, computing in
fp32 regardless of the storage dtype (bf16 params keep an exact fp32
update before the downcast — the mixed-precision policy of DESIGN.md
§15). ``lr``/``mu`` are STATIC — the scan that drives the local phase
bakes them into the compiled body, so no scalar operands ride the vmap
over clients.

Tiling: grid (M/bm,); p/v/g ride (1, bm) blocks of the padded (1, M)
flattened views (lane-aligned like paired_fusion).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _ls_kernel(p_ref, v_ref, g_ref, po_ref, vo_ref, *, lr: float,
               mu: float):
    v = mu * v_ref[...].astype(jnp.float32) + g_ref[...].astype(jnp.float32)
    p = p_ref[...].astype(jnp.float32) - lr * v
    po_ref[...] = p.astype(po_ref.dtype)
    vo_ref[...] = v.astype(vo_ref.dtype)


@functools.partial(jax.jit,
                   static_argnames=("lr", "mu", "bm", "interpret"))
def local_step_kernel(p, v, g, *, lr: float, mu: float, bm: int = 1024,
                      interpret: bool = True):
    """p, v, g: (1, M) with M % bm == 0 -> (p', v') same shapes/dtypes."""
    _, m = p.shape
    assert m % bm == 0, (m, bm)
    grid = (m // bm,)
    blk = pl.BlockSpec((1, bm), lambda mi: (0, mi))
    return pl.pallas_call(
        functools.partial(_ls_kernel, lr=lr, mu=mu),
        grid=grid,
        in_specs=[blk, blk, blk],
        out_specs=[blk, blk],
        out_shape=[jax.ShapeDtypeStruct((1, m), p.dtype),
                   jax.ShapeDtypeStruct((1, m), v.dtype)],
        interpret=interpret,
        name="local_step",
    )(p, v, g)
