"""What the entry points promise before any chip is involved: where the
compile cache goes, that ``chip_smoke.py`` refuses to report success
without a TPU, and that the mesh-scaling suite refuses a single device."""

import os
import shutil
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _recorded_updates(monkeypatch):
    import jax

    calls = []
    monkeypatch.setattr(jax.config, "update",
                        lambda name, value: calls.append((name, value)))
    return calls


def test_compile_cache_honours_environment(monkeypatch, tmp_path):
    from repro.launch.compile_cache import enable_compile_cache

    calls = _recorded_updates(monkeypatch)
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert enable_compile_cache() == str(tmp_path)
    # JAX reads the variable itself; only the compile-time threshold is set
    assert calls == [("jax_persistent_cache_min_compile_time_secs", 0.0)]


def test_compile_cache_defaults_to_checkout(monkeypatch):
    from repro.launch.compile_cache import (CHECKOUT_CACHE_DIR,
                                            enable_compile_cache)

    calls = _recorded_updates(monkeypatch)
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    assert CHECKOUT_CACHE_DIR == os.path.join(REPO, ".jax_cache")
    assert enable_compile_cache() == CHECKOUT_CACHE_DIR
    assert calls == [("jax_persistent_cache_min_compile_time_secs", 0.0),
                     ("jax_compilation_cache_dir", CHECKOUT_CACHE_DIR)]


@pytest.mark.parametrize("cmd,enabled", [
    (["list"], False), (["show", "hft"], False),
    (["run", "hft", "--duration-s", "8e-05", "--no-back-annotation",
      "--top-k", "2"], True)])
def test_cli_enables_cache_only_for_compiling_commands(monkeypatch, capsys,
                                                       cmd, enabled):
    from repro.api import cli
    from repro.launch import compile_cache

    seen = []
    monkeypatch.setattr(compile_cache, "enable_compile_cache",
                        lambda: seen.append(True))
    assert cli.main(cmd) in (0, None)
    assert bool(seen) is enabled


def _run_smoke(cwd, script):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    return subprocess.run([sys.executable, script], cwd=cwd, env=env,
                          capture_output=True, text=True, timeout=300)


def test_chip_smoke_fails_without_a_tpu():
    out = _run_smoke(REPO, os.path.join(REPO, "chip_smoke.py"))
    assert out.returncode != 0
    assert '"ok"' not in out.stdout
    assert "needs a TPU" in out.stderr


def test_chip_smoke_fails_outside_the_repo(tmp_path):
    script = tmp_path / "chip_smoke.py"
    shutil.copy(os.path.join(REPO, "chip_smoke.py"), script)
    out = _run_smoke(tmp_path, str(script))
    assert out.returncode != 0
    assert '"ok"' not in out.stdout


@pytest.mark.parametrize("available,counts", [(2, (1, 2)), (4, (1, 2, 4)),
                                              (6, (1, 2, 4)),
                                              (8, (1, 2, 4, 8))])
def test_mesh_scaling_device_counts(available, counts):
    from benchmarks.mesh_scaling import device_counts

    assert device_counts(available) == counts


def test_mesh_scaling_refuses_one_device():
    from benchmarks.mesh_scaling import device_counts

    with pytest.raises(RuntimeError, match="at least 2 devices, found 1"):
        device_counts(1)
