"""Minimal pure-pytree module utilities.

Params are nested dicts of jnp arrays. Every layer is an (init, apply) pair of
pure functions. Layer stacks are built by vmapping init over a leading layer
axis and scanning apply — this keeps the lowered HLO size independent of depth
(essential for 512-device dry-run compiles).
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable

import jax
import jax.numpy as jnp
import numpy as np

Params = Any  # nested dict pytree of arrays
PyTree = Any


def split_keys(key: jax.Array, n: int) -> list[jax.Array]:
    return list(jax.random.split(key, n))


def stack_init(init_fn: Callable[..., Params], key: jax.Array, n: int,
               *args, **kwargs) -> Params:
    """Initialize ``n`` copies of a layer with a leading stacking axis."""
    keys = jax.random.split(key, n)
    return jax.vmap(lambda k: init_fn(k, *args, **kwargs))(keys)


def scan_apply(apply_fn: Callable, stacked_params: Params, x: PyTree,
               *, unroll: int = 1) -> PyTree:
    """Run ``apply_fn(params_i, x) -> x`` across a stacked layer axis."""

    def body(carry, layer_params):
        return apply_fn(layer_params, carry), None

    out, _ = jax.lax.scan(body, x, stacked_params, unroll=unroll)
    return out


def param_count(params: Params) -> int:
    return sum(int(np.prod(p.shape)) for p in jax.tree_util.tree_leaves(params))


def param_bytes(params: Params) -> int:
    return sum(int(np.prod(p.shape)) * p.dtype.itemsize
               for p in jax.tree_util.tree_leaves(params))


def tree_cast(params: Params, dtype) -> Params:
    return jax.tree_util.tree_map(
        lambda p: p.astype(dtype) if jnp.issubdtype(p.dtype, jnp.floating) else p,
        params)


def tree_zeros_like_spec(tree: PyTree) -> PyTree:
    """ShapeDtypeStruct skeleton of a pytree (no allocation)."""
    return jax.tree_util.tree_map(
        lambda p: jax.ShapeDtypeStruct(p.shape, p.dtype), tree)


@dataclasses.dataclass(frozen=True)
class Initializer:
    """Fan-in scaled normal initializer (matches torch kaiming-ish defaults)."""
    scale: float = 1.0

    def __call__(self, key, shape, fan_in=None, dtype=jnp.float32):
        fan_in = fan_in if fan_in is not None else shape[0]
        # python float (weak type) so bf16 params stay bf16
        std = float(self.scale / np.sqrt(max(fan_in, 1)))
        return jax.random.normal(key, shape, dtype) * std


default_init = Initializer()


# ---------------------------------------------------------------------------
# Mesh-aware sharding constraints (no-ops outside a mesh context)
# ---------------------------------------------------------------------------

_BATCH = "__batch__"  # placeholder: all batch axes present in the mesh


def maybe_shard(x, *spec):
    """with_sharding_constraint that degrades to identity when no mesh is
    active, drops axes absent from the mesh, and skips non-divisible dims
    (so model code is runnable on CPU and under any mesh).

    Use module.BATCH for the ("pod","data") batch axes."""
    import jax.sharding as shx
    mesh = shx.get_abstract_mesh()
    names = set(mesh.axis_names or ())
    if not names:
        return x
    sizes = dict(zip(mesh.axis_names, mesh.shape.values())) \
        if hasattr(mesh.shape, "values") else dict(mesh.shape)
    out = []
    for dim, s in zip(x.shape, spec):
        if s == _BATCH:
            axes = tuple(a for a in ("pod", "data") if a in names)
            size = int(np.prod([sizes[a] for a in axes])) if axes else 1
            out.append(axes if axes and dim % size == 0 and dim >= size
                       else None)
        elif s is None:
            out.append(None)
        else:
            ok = s in names and dim % sizes[s] == 0 and dim >= sizes[s]
            out.append(s if ok else None)
    spec = tuple(a if not (isinstance(a, tuple) and len(a) == 1) else a[0]
                 for a in out)
    return jax.lax.with_sharding_constraint(
        x, jax.sharding.PartitionSpec(*spec))


BATCH = _BATCH
