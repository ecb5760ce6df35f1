import os
import subprocess
import sys

import pytest

# NOTE (per instructions): XLA_FLAGS / host-device-count is deliberately NOT
# set here — unit tests see the real single CPU device. Multi-device tests run
# in subprocesses via `run_multidevice`.

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def run_multidevice(code: str, ndev: int = 8, timeout: int = 600):
    """Run a python snippet with N forced host devices; returns stdout."""
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={ndev}"
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    proc = subprocess.run([sys.executable, "-c", code], env=env,
                          capture_output=True, text=True, timeout=timeout)
    if proc.returncode != 0:
        raise AssertionError(
            f"subprocess failed:\nSTDOUT:\n{proc.stdout}\nSTDERR:\n{proc.stderr}")
    return proc.stdout


@pytest.fixture
def multidevice():
    return run_multidevice
