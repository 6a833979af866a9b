"""CPU rehearsals of ``chip_smoke.py`` and the start-up helpers it uses.

The smoke's phases take their sizes as arguments, so the same code that
drives the chip runs here at a tiny size, with the Pallas kernels in the
interpreter (resolved from the platform, not from an option).  The script
itself must refuse the CPU.
"""
import importlib.util
import os
import subprocess
import sys
from pathlib import Path

import jax
import pytest

from repro import compile_cache

ROOT = Path(__file__).resolve().parents[1]


@pytest.fixture(scope="module")
def smoke():
    spec = importlib.util.spec_from_file_location(
        "chip_smoke", ROOT / "chip_smoke.py"
    )
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _child_env(**extra):
    env = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
    env["JAX_PLATFORMS"] = "cpu"
    env.update(extra)
    return env


def test_smoke_phases_rehearse_on_cpu(smoke, capsys):
    summary = smoke.run_phases(
        0.0015, 0.06, n_check=256, n_knn=64, batch=64, dense_points=512,
        count_chunk=64, pairs_chunk=32,
    )
    out = capsys.readouterr().out
    assert summary["n"] == 3000 and summary["knn_rows"] == 64
    for phase in ("count_jnp", "pairs_jnp", "count_pallas_indexed",
                  "count_pallas_dense", "brute_check", "service"):
        assert f'"phase": "{phase}"' in out
    # interpret mode on the CPU: the count program holds no Mosaic kernel
    assert summary["tpu_custom_call"] is False


def test_four_chip_phases_rehearse_on_four_cpu_devices():
    code = (
        "import sys; sys.argv = ['chip_smoke']\n"
        f"sys.path.insert(0, {str(ROOT)!r})\n"
        "import chip_smoke\n"
        "print(chip_smoke.run_four_chip_phases(0.001, 0.06, "
        "count_chunk=256, pairs_chunk=64))\n"
    )
    out = subprocess.run(
        [sys.executable, "-c", code], capture_output=True, text=True,
        timeout=600, cwd=ROOT,
        env=_child_env(XLA_FLAGS="--xla_force_host_platform_device_count=4"),
    )
    assert out.returncode == 0, out.stderr[-3000:]
    assert '"phase": "count_fused_ring"' in out.stdout
    assert '"phase": "pairs_fused_ring"' in out.stdout


def test_script_refuses_the_cpu():
    out = subprocess.run(
        [sys.executable, str(ROOT / "chip_smoke.py")],
        capture_output=True, text=True, timeout=300, cwd=ROOT,
        env=_child_env(),
    )
    assert out.returncode != 0
    assert '"ok"' not in out.stdout
    assert "no TPU" in out.stderr


def test_script_alone_fails(tmp_path):
    """Without the rest of the repo the script cannot import the system."""
    (tmp_path / "chip_smoke.py").write_bytes(
        (ROOT / "chip_smoke.py").read_bytes()
    )
    out = subprocess.run(
        [sys.executable, "chip_smoke.py"], capture_output=True, text=True,
        timeout=300, cwd=tmp_path, env=_child_env(PYTHONPATH=""),
    )
    assert out.returncode != 0
    assert '"ok"' not in out.stdout


@pytest.mark.parametrize("preset", [True, False])
def test_enable_compile_cache_writes_where_configured(tmp_path, preset):
    """Set: JAX writes to the given directory.  Unset: the fixed repo path."""
    code = (
        "from repro.compile_cache import enable_compile_cache\n"
        "print('DIR', enable_compile_cache())\n"
        "import jax, jax.numpy as jnp\n"
        "jax.jit(lambda x: x * 3 + 1)(jnp.ones(4)).block_until_ready()\n"
    )
    extra = {"PYTHONPATH": str(ROOT / "src")}
    if preset:
        extra["JAX_COMPILATION_CACHE_DIR"] = str(tmp_path)
        extra["JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS"] = "0"
    else:
        code = code.split("import jax,")[0]  # report only: write nothing
    env = _child_env(**extra)
    if not preset:
        env.pop("JAX_COMPILATION_CACHE_DIR", None)
    out = subprocess.run(
        [sys.executable, "-c", code], capture_output=True, text=True,
        timeout=300, env=env,
    )
    assert out.returncode == 0, out.stderr[-2000:]
    got = out.stdout.split("DIR", 1)[1].strip()
    if preset:
        assert got == str(tmp_path)
        assert any(tmp_path.iterdir()), "nothing was cached"
    else:
        assert got == str(ROOT / ".jax_cache")
        assert got == str(compile_cache.REPO_CACHE_DIR)


def test_smoke_main_stops_off_the_chip(smoke, capsys):
    """``main`` stops before any phase when the device is not a TPU."""
    assert jax.devices()[0].platform == "cpu"
    assert smoke.main([]) == 1
    assert '"ok"' not in capsys.readouterr().out
