"""A run with the timed path broken underneath comes out not correct,
for each fault a cell can have; a sound run and the control (the
reference in bfloat16 in the program's place) are the two ends."""
import json
import os
import subprocess
import sys

import pytest

from bench.tests import drive

ONE_CHIP = ["slice-hist-open", "slice-fcms-open", "study-hist-batch"]
MESH = "study-fcms-batch-mesh4"
RUNNER = os.path.join(os.path.dirname(__file__), "mesh_runner.py")


@pytest.fixture(autouse=True)
def fresh_programs():
    from repro.serving import fcm_engine as FE
    FE._LAUNCH_CACHE.clear()
    yield
    FE._LAUNCH_CACHE.clear()


def failing(result):
    return sorted(k for k, c in result["checks"].items()
                  if c["value"] > c["limit"])


@pytest.mark.parametrize("cell", ONE_CHIP)
def test_sound_run_is_correct(cell):
    res = drive.run(cell)
    assert res["correct"] is True, res["checks"]
    assert res["attempted"] > 0 and res["failed"] == 0


@pytest.mark.parametrize("cell", ONE_CHIP)
def test_control_is_not_correct(cell):
    res = drive.run(cell, control=True)
    assert res["correct"] is False
    assert "center_dev" in failing(res)


@pytest.mark.parametrize("cell", ONE_CHIP)
@pytest.mark.parametrize("fault", ["half_batch_left_out", "answer_altered"])
def test_planted_fault_is_not_correct(cell, fault):
    res = drive.run(cell, fault=getattr(drive, fault))
    assert res["correct"] is False, res["checks"]


@pytest.mark.parametrize("cell", ONE_CHIP)
def test_state_unchanged_is_not_correct(cell, monkeypatch):
    drive.state_unchanged(monkeypatch)
    res = drive.run(cell)
    assert res["correct"] is False, res["checks"]


@pytest.mark.parametrize("fault", ["none", "control", "state_unchanged",
                                   "half_batch_left_out", "answer_altered",
                                   "exchange_left_out"])
def test_mesh_cell(fault):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    out = subprocess.run([sys.executable, RUNNER, MESH, fault], env=env,
                         capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    res = json.loads(out.stdout.strip().splitlines()[-1])
    assert res["correct"] is (fault == "none"), res["checks"]
