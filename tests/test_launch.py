"""Launch plumbing: where the compile cache lives, meshes with Auto axes,
the launcher called as a function, the warning when a TPU run takes a
reference path, and chip_smoke.py's refusal to run without a TPU."""
import logging
import os
import subprocess
import sys

import jax
import pytest
from jax.sharding import AxisType

from repro.fl import engine as engine_lib
from repro.launch import compile_cache, train
from repro.launch.mesh import make_data_mesh, make_host_mesh

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_compile_cache_defaults_to_the_checkout(monkeypatch):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    was = jax.config.jax_compilation_cache_dir
    try:
        got = compile_cache.setup_compile_cache()
        assert got == os.path.join(REPO_ROOT, ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == got
    finally:
        jax.config.update("jax_compilation_cache_dir", was)


def test_compile_cache_env_dir_wins(monkeypatch, tmp_path):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    was = jax.config.jax_compilation_cache_dir
    assert compile_cache.setup_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == was   # nothing set


def test_meshes_have_auto_axes():
    n = len(jax.local_devices())
    for mesh in (make_host_mesh(), make_data_mesh(n)):
        assert mesh.axis_names == ("data", "model")
        assert mesh.axis_types == (AxisType.Auto, AxisType.Auto)
    assert dict(make_data_mesh(n).shape) == {"data": n, "model": 1}
    with pytest.raises(ValueError, match="local devices"):
        make_data_mesh(n + 1)


def test_train_main_takes_argv_and_returns_history(monkeypatch, tmp_path):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    h = train.main(["--mode", "fl", "--arch", "vgg9", "--method", "fed2",
                    "--reduced", "--rounds", "2", "--nodes", "2",
                    "--steps-per-epoch", "1", "--batch", "4",
                    "--train-size", "40"])
    assert h["round"] == [0, 1] and len(h["acc"]) == 2
    assert "final_params" in h


def test_profile_dir_traces_the_run(monkeypatch, tmp_path):
    """``--profile-dir`` writes a trace holding the program's spans."""
    from jax.profiler import ProfileData

    from repro.fl.runtime import SPANS
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    out = tmp_path / "profile"
    train.main(["--mode", "fl", "--arch", "vgg9", "--method", "fed2",
                "--reduced", "--rounds", "2", "--nodes", "2",
                "--steps-per-epoch", "1", "--batch", "4",
                "--train-size", "40", "--profile-dir", str(out)])
    path, = out.glob("plugins/profile/*/*.xplane.pb")
    names = [ev.name for plane in ProfileData.from_file(str(path)).planes
             for line in plane.lines for ev in line.events
             if ev.name in SPANS]
    assert names.count("fl.round") == 2 and names.count("fl.pack") == 2
    with pytest.raises(SystemExit):
        train.parse_args(["--mode", "lm", "--profile-dir", str(out)])


class _FourDeviceMesh:
    size = 4


@pytest.mark.parametrize("backend,said", [("tpu", True), ("cpu", False)])
def test_forced_off_fusion_kernel_is_said_on_tpu(monkeypatch, caplog,
                                                 backend, said):
    """A TPU runs the fusion kernel by default; a multi-device mesh takes
    the tree reduction instead, and says so there (only there)."""
    monkeypatch.delenv("REPRO_FUSION_KERNEL", raising=False)
    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    with caplog.at_level(logging.WARNING, logger=engine_lib.__name__):
        assert engine_lib.resolve_use_kernel(None, _FourDeviceMesh()) is False
        assert engine_lib.resolve_use_kernel(None, None) is said
    assert any("fusion kernel off on TPU" in r.getMessage()
               for r in caplog.records) is said


def test_chip_smoke_refuses_without_a_tpu():
    env = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
    env["JAX_PLATFORMS"] = "cpu"
    out = subprocess.run([sys.executable, "chip_smoke.py"], cwd=REPO_ROOT,
                         env=env, capture_output=True, text=True, timeout=300)
    assert out.returncode != 0
    assert '"ok"' not in out.stdout
    assert "platform cpu" in out.stdout
