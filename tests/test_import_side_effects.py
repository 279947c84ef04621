"""Importing paddle_tpu must not initialise the JAX backend.

``paddle_tpu.testing.force_host_cpu_devices`` (used by conftest and the
driver's multi-chip dryrun) can only work if the package import graph has
no module-level jax array/op: backend init is lazy in JAX and the first
concrete computation pins the platform. Guard the whole class of failure
(a future module-level ``jnp.array(...)`` anywhere in the eager import
graph would silently initialise the default backend before tests can
force CPU).
"""
import subprocess
import sys

import pytest


@pytest.mark.slow
def test_import_does_not_init_backend():
    code = (
        "from paddle_tpu.testing import force_host_cpu_devices\n"
        "force_host_cpu_devices(4)\n"  # raises if backend already inited
        "print('OK')\n"
    )
    out = subprocess.run([sys.executable, "-c", code],
                         capture_output=True, text=True, timeout=300)
    assert out.returncode == 0, out.stderr[-2000:]
    assert "OK" in out.stdout
