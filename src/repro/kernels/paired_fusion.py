"""Pallas TPU kernel: fused N-way weighted parameter averaging.

The fusion step (Eq. 18/19) is memory-bound: read N stacked client tensors
once, write the global tensor once. A naive stack-multiply-mean materializes
an (N, M) fp32 temp; this kernel streams column tiles of all N client rows
through VMEM and reduces them in fp32. Group pairing permutations are
applied as a cheap index-gather in ops.py before the kernel (identity under
Fed2's structural pre-alignment) — the heavy reduction is what needs fusing.

Tiling: grid (M/bm,). Each step loads one (N, bm) block — the whole client
axis, which satisfies the TPU block rule because it equals the array's
leading dim — plus the (N, 1) weight column as one resident block, and
writes one (1, bm) output tile. ops.paired_fusion sizes ``bm`` so the
(N, bm) block stays inside a fixed VMEM budget at any cohort size.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _pf_kernel(x_ref, w_ref, o_ref):
    x = x_ref[...].astype(jnp.float32)                   # (N, bm)
    o_ref[...] = jnp.sum(w_ref[...] * x, axis=0,
                         keepdims=True).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("bm", "interpret"))
def paired_fusion_kernel(stacked, weights, *, bm: int = 1024,
                         interpret: bool = True):
    """stacked: (N, M); weights: (N,) normalized -> (1, M) weighted mean.
    M pre-padded to a multiple of bm (itself a multiple of 128)."""
    n, m = stacked.shape
    assert m % bm == 0, (m, bm)
    w2 = weights.reshape(n, 1).astype(jnp.float32)
    return pl.pallas_call(
        _pf_kernel,
        grid=(m // bm,),
        in_specs=[
            pl.BlockSpec((n, bm), lambda mi: (0, mi)),
            pl.BlockSpec((n, 1), lambda mi: (0, 0)),
        ],
        out_specs=pl.BlockSpec((1, bm), lambda mi: (0, mi)),
        out_shape=jax.ShapeDtypeStruct((1, m), stacked.dtype),
        interpret=interpret,
        name="paired_fusion",
    )(stacked, w2)
