"""Mesh construction: TPU v5e pods, the 1-device host mesh, and a data
mesh over a host's local chips.

Defined as FUNCTIONS so importing this module never touches jax device
state — dryrun.py must set XLA_FLAGS before any jax initialization.
Every mesh has Auto axes: the engine places the cohort with
``with_sharding_constraint``, which refuses Explicit axes.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType

# TPU v5e hardware constants (per chip) — used by the roofline analysis
PEAK_FLOPS_BF16 = 197e12        # FLOP/s
HBM_BW = 819e9                  # B/s
ICI_BW = 50e9                   # B/s per link


def _auto_mesh(shape, axes, devices=None):
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes),
                         devices=devices)


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _auto_mesh(shape, axes)


def make_host_mesh():
    """1-device mesh for CPU smoke runs of the same sharded code paths."""
    return _auto_mesh((1, 1), ("data", "model"))


def make_data_mesh(n: int):
    """(n, 1) mesh on ("data", "model") over the first ``n`` local devices:
    the cohort axis shards over all of them (a 4-chip host: ``n=4``)."""
    devices = jax.local_devices()
    if n > len(devices):
        raise ValueError(f"make_data_mesh({n}): only {len(devices)} local "
                         "devices")
    return _auto_mesh((n, 1), ("data", "model"), devices=devices[:n])


def batch_axes(mesh) -> tuple:
    return ("pod", "data") if "pod" in mesh.axis_names else ("data",)


def mesh_chips(mesh) -> int:
    return mesh.devices.size
