"""Shared fixtures.  NOTE: the host-device count is NOT forced here — smoke
tests and benches see the container's single CPU device.  Tests that need a
multi-device mesh (dataframe collectives, elastic FT, HLO SPMD analysis)
run their body in a subprocess with XLA_FLAGS set (see tests/spawn/)."""
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SPAWN = os.path.join(REPO, "tests", "spawn")


def run_spawned(script_name: str, devices: int = 8, timeout: int = 600):
    """Run tests/spawn/<script>.py with N host devices; assert success."""
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={devices}"
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    r = subprocess.run(
        [sys.executable, os.path.join(SPAWN, script_name)],
        capture_output=True, text=True, timeout=timeout, env=env,
    )
    assert r.returncode == 0, f"spawned {script_name} failed:\n{r.stdout[-3000:]}\n{r.stderr[-3000:]}"
    return r.stdout


@pytest.fixture(scope="session")
def spawned():
    return run_spawned
