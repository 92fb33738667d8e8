"""Expert-parallel all-to-all MoE (shard_map) vs the dense oracle."""
import dataclasses
import subprocess
import sys

import jax
import numpy as np

from repro.configs import get_config
from repro.models import moe as M
from repro.models.moe_ep import moe_apply_ep


def test_ep_matches_dense_single_device():
    cfg = dataclasses.replace(get_config("mixtral-8x22b", reduced=True).moe,
                              capacity_factor=16.0)
    p = M.moe_init(jax.random.PRNGKey(0), cfg)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 16, cfg.d_model))
    mesh = jax.make_mesh((1, 1), ("data", "model"))
    with mesh:
        y1, _ = moe_apply_ep(p, x, cfg, mesh)
    y2, _ = M.moe_apply_dense_reference(p, x, cfg)
    np.testing.assert_allclose(np.asarray(y1), np.asarray(y2), atol=1e-4)


_SUBPROC = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import dataclasses, jax, jax.numpy as jnp
from repro.configs import get_config
from repro.models import moe as M
from repro.models.moe_ep import moe_apply_ep
cfg = dataclasses.replace(get_config("mixtral-8x22b", reduced=True).moe,
                          capacity_factor=16.0)
p = M.moe_init(jax.random.PRNGKey(0), cfg)
x = jax.random.normal(jax.random.PRNGKey(1), (4, 16, cfg.d_model))
mesh = jax.make_mesh((2, 4), ("data", "model"))
with mesh:
    y1, _ = moe_apply_ep(p, x, cfg, mesh)
y2, _ = M.moe_apply_dense_reference(p, x, cfg)
err = float(jnp.max(jnp.abs(y1 - y2)))
assert err < 1e-4, err
print("OK", err)
"""


def test_ep_all_to_all_on_8_devices():
    """Real multi-shard all_to_all path (separate process: device count is
    locked at jax init)."""
    # the child runs on 8 virtual CPU devices and must never reach for a
    # chip, which the parent process may hold
    out = subprocess.run([sys.executable, "-c", _SUBPROC], env={
        "PYTHONPATH": "src", "PATH": "/usr/bin:/bin",
        **{k: v for k, v in __import__("os").environ.items()
           if k not in ("XLA_FLAGS",)},
        "JAX_PLATFORMS": "cpu",
    }, capture_output=True, text=True, timeout=300, cwd=".")
    assert "OK" in out.stdout, out.stdout + out.stderr
