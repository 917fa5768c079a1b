"""chip_smoke.py's phases at REDUCED widths on the CPU, and its refusal to
run, or to print its result line, off the chip."""
import os
import pathlib
import subprocess
import sys

import pytest

import chip_smoke
from repro.configs import registry
from repro.runtime.controlplane import ControlPlane

REPO = pathlib.Path(__file__).resolve().parents[1]


@pytest.fixture(scope="module")
def arch():
    return registry.get("qwen3-1.7b", reduced=True)


def test_train_then_resume_phases(arch, tmp_path):
    control = ControlPlane(n_nodes=3, seed=0)
    kw = dict(steps=3, batch=2, seq=16, ckpt_dir=str(tmp_path), control=control)
    chip_smoke.phase_train(arch, **kw)
    assert any(c.startswith("ckpt:3:") for c in control.applied)
    chip_smoke.phase_resume(arch, **kw)
    assert any(c.startswith("ckpt:4:") for c in control.applied)


def test_serve_phase_matches_forward(arch):
    control = ControlPlane(n_nodes=3, seed=0)
    err = chip_smoke.phase_serve(arch, batch=2, prompt_len=16, gen=4,
                                 max_len=32, control=control)
    assert err <= chip_smoke.SERVE_TOL
    assert f"rollout:{arch.name}@v1" in control.applied


def test_main_refuses_off_chip(capsys):
    assert chip_smoke.main([]) != 0
    assert '"ok": true' not in capsys.readouterr().out


@pytest.mark.slow  # subprocess: three train-step compiles on 4 host devices
def test_four_chip_phase_on_host_devices(tmp_path):
    """The --four-chips phase, on four forced CPU devices at REDUCED
    widths: n_yes == 4, shards on four devices, tracks agree."""
    child = (
        "import chip_smoke\n"
        "from repro.configs import registry\n"
        "chip_smoke.phase_four_chips(registry.get('qwen3-1.7b', reduced=True),"
        " steps=2, batch=4, seq=16)\n"
        "print('FOUR-OK')\n"
    )
    env = dict(os.environ, PYTHONPATH=f"{REPO}{os.pathsep}{REPO / 'src'}",
               XLA_FLAGS="--xla_force_host_platform_device_count=4"
               # XLA:CPU only: its all-reduce promotion crashes on bf16 sums.
               " --xla_disable_hlo_passes=all-reduce-promotion",
               JAX_PLATFORMS="cpu")
    res = subprocess.run([sys.executable, "-c", child], capture_output=True,
                         text=True, env=env, timeout=600, cwd=tmp_path)
    assert res.returncode == 0, f"{res.stdout}\n{res.stderr[-3000:]}"
    assert "FOUR-OK" in res.stdout


def test_script_alone_fails(tmp_path):
    """In a directory that holds chip_smoke.py and nothing else of the
    repo, the script fails and prints no result."""
    (tmp_path / "chip_smoke.py").write_text((REPO / "chip_smoke.py").read_text())
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    res = subprocess.run([sys.executable, "chip_smoke.py"], capture_output=True,
                         text=True, env=env, timeout=120, cwd=tmp_path)
    assert res.returncode != 0
    assert '"ok": true' not in res.stdout
