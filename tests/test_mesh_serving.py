"""Mesh-sharded serving: RouteProgram launches sharded over an 8-device
fake mesh must serve results identical to the single-device engine,
sync and async. Runs in a subprocess because device count is locked at
first jax init."""
import os
import subprocess
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))


@pytest.mark.slow
def test_mesh_serving_matches_single_device():
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    proc = subprocess.run(
        [sys.executable, os.path.join(HERE, "_mesh_serve_runner.py")],
        capture_output=True, text=True, env=env, timeout=600)
    assert proc.returncode == 0, proc.stdout + "\n" + proc.stderr
    assert "MESH_SERVE_OK" in proc.stdout


def test_mesh_h2d_puts_inputs_with_the_launch_sharding():
    """On 4 virtual devices a sharded program's h2d puts each input
    split over the mesh's ``data`` axis, and a bucket the mesh does not
    divide on one device."""
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    proc = subprocess.run(
        [sys.executable, os.path.join(HERE, "_h2d_mesh_runner.py")],
        capture_output=True, text=True, env=env, timeout=600)
    assert proc.returncode == 0, proc.stdout + "\n" + proc.stderr
    assert "H2D_MESH_OK" in proc.stdout
