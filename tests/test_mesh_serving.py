"""Mesh-sharded serving: RouteProgram launches sharded over a fake
mesh (8 devices, or 4 as on one v5e host) must serve results identical
to the single-device engine, sync and async. Each runs in a subprocess
because device count is locked at first jax init."""
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


def test_mesh_spatial_matches_reference_and_counts_shards():
    """On 4 virtual devices the spatial route of a meshed engine serves
    noisy uint8 slices at buckets 1, 4, 8, 16 and 64 as a plain float32
    FCM_S does, bitwise as a one-device engine does (uint8 labels from
    both), and its shard counters count the sharded buckets only,
    matching a hand count of each shard's lane iterations on a batch
    with one slow lane."""
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    proc = subprocess.run(
        [sys.executable, os.path.join(HERE, "_mesh_spatial_runner.py")],
        capture_output=True, text=True, env=env, timeout=300)
    assert proc.returncode == 0, proc.stdout + "\n" + proc.stderr
    assert "MESH_SPATIAL_OK" in proc.stdout
