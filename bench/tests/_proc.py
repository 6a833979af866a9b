"""Start a rehearsal process (``bench/tests/rehearse.py``) on the CPU."""
import json
import os
import subprocess
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]


def cpu_env(tmp: Path, devices: int = 1) -> dict:
    env = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
    env.update(JAX_PLATFORMS="cpu", JAX_COMPILATION_CACHE_DIR=str(tmp / "jax_cache"))
    if devices > 1:
        env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={devices}"
    return env


def rehearse(tmp: Path, cell: str, points: int, *args: str, devices: int = 1):
    """The RESULT lines of one rehearsal process, parsed."""
    proc = subprocess.run(
        [sys.executable, "-m", "bench.tests.rehearse", cell, str(points), *args],
        cwd=ROOT, env=cpu_env(tmp, devices), capture_output=True, text=True,
        timeout=300,
    )
    assert proc.returncode == 0, proc.stderr[-4000:]
    return [
        json.loads(line[len("RESULT "):])
        for line in proc.stdout.splitlines() if line.startswith("RESULT ")
    ]
