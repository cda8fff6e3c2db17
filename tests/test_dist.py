"""Distribution-layer tests.

Multi-device correctness runs in a subprocess with
``XLA_FLAGS=--xla_force_host_platform_device_count=8`` (the main pytest
process must keep seeing exactly 1 device). The single-process pieces of
``repro.dist`` are covered by ``test_dist_halo_unit.py``.
"""

import os
import subprocess
import sys
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parent.parent


def _run_subprocess(script: str, n_dev: int = 8) -> str:
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={n_dev}"
    env["PYTHONPATH"] = str(REPO / "src")
    env["JAX_PLATFORMS"] = "cpu"
    proc = subprocess.run(
        [sys.executable, str(REPO / "tests" / "multidev" / script)],
        capture_output=True,
        text=True,
        env=env,
        timeout=600,
    )
    assert proc.returncode == 0, f"STDOUT:\n{proc.stdout}\nSTDERR:\n{proc.stderr}"
    return proc.stdout


@pytest.mark.multidev
def test_halo_and_compression_multidevice():
    """Sharded hdiff == single-device hdiff on 8 fake devices: depth-parallel,
    4- and 8-way row halos, and the unlimited variant."""
    out = _run_subprocess("_halo_check.py")
    assert "ALL_OK" in out
