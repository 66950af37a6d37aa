"""Wrapper: run the multi-device checks in a subprocess with 8 forced host
devices (the main test process must keep seeing 1 device)."""

import os
import subprocess
import sys

import pytest


@pytest.mark.slow
def test_multidevice_checks_subprocess():
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["PYTHONPATH"] = os.path.abspath(
        os.path.join(os.path.dirname(__file__), "..", "src"))
    script = os.path.join(os.path.dirname(__file__), "multidevice_checks.py")
    proc = subprocess.run([sys.executable, script], env=env,
                          capture_output=True, text=True, timeout=900)
    sys.stdout.write(proc.stdout)
    sys.stderr.write(proc.stderr[-4000:])
    assert proc.returncode == 0, "multidevice checks failed"
    assert "ALL MULTIDEVICE CHECKS PASSED" in proc.stdout
