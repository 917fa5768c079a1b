"""In-graph consensus collectives need >1 device; run the checks in a
subprocess with a forced 8-device host platform so the main test process
keeps its single-device view (required by the smoke tests)."""
import os
import pathlib
import subprocess
import sys

import pytest

CHILD = pathlib.Path(__file__).parent / "collective_child.py"


def _run_child(*args):
    env = dict(os.environ)
    env["PYTHONPATH"] = str(pathlib.Path(__file__).parents[1] / "src")
    env.pop("XLA_FLAGS", None)
    res = subprocess.run(
        [sys.executable, str(CHILD), *args], capture_output=True, text=True, env=env,
        timeout=600,
    )
    assert res.returncode == 0, f"stdout:\n{res.stdout}\nstderr:\n{res.stderr}"
    assert "COLLECTIVE-OK" in res.stdout


@pytest.mark.slow  # subprocess XLA compiles on a forced 8-device host platform
def test_collective_consensus_multidevice():
    _run_child()


@pytest.mark.slow  # subprocess compile of the train step on four host devices
@pytest.mark.parametrize("track", ["fast", "classic"])
def test_train_step_barrier_excludes_and_rolls_back(track):
    """The real step on a (4, 1) mesh: n_yes counts the replicas whose loss
    is finite, a step that does not commit leaves params, m, v, master and
    step bit for bit, and one that commits advances step."""
    _run_child(track)
