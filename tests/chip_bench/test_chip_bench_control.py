"""Whole runs of the tiny copy on the CPU, past the chip check: the result
line, the control that has to fail the comparison, and each fault a cell
can have, planted under the timed path, turning ``correct`` false."""
import contextlib
import io
import itertools
import json
import os
import subprocess
import sys

import numpy as np
import pytest

from benchmarks.chip import calibrate
from benchmarks.chip import run as bench_run
from repro.runtime import spmd
from repro.runtime.trainer import Trainer

CONTRACT_KEYS = {"correct", "attempted", "failed", "metrics", "device", "breakdown", "checks"}


def whole_run(tiny, cell, trace=0, seed=3000000007):
    root, checkout = tiny
    out = io.StringIO()
    with contextlib.redirect_stdout(out):
        rc = bench_run.main(["--workload", cell, "--seed", str(seed), "--seconds", "1",
                             "--trace", str(trace)], root=root, checkout=checkout)
    assert rc == 0
    return json.loads(out.getvalue().strip().splitlines()[-1])


@pytest.mark.parametrize("cell", ["tiny-train-1chip", "tiny-decode-long-cache"])
@pytest.mark.parametrize("trace", [0, 1])
def test_the_result_line_keeps_to_the_contract(tiny, on_cpu, cell, trace):
    line = whole_run(tiny, cell, trace)
    assert set(line) <= CONTRACT_KEYS and list(line)[-1] == "checks"
    assert line["correct"] is True and line["failed"] == 0 and line["attempted"] > 0
    names = set(line["metrics"])
    if trace:
        assert {"busy_s", "window_s"} <= set(line["device"]) and "breakdown" in line
        assert "setup_s" not in names
    else:
        assert "setup_s" in names and len(names) >= 2
    assert all(set(c) == {"value", "limit"} for c in line["checks"].values())


def _readings(tiny, cell):
    root, checkout = tiny
    out = io.StringIO()
    with contextlib.redirect_stdout(out):
        calibrate.CHECKOUT, saved = checkout, calibrate.CHECKOUT
        try:
            calibrate.main(["--workload", cell, "--seeds", "11", "12", "13", "--faults", "3"],
                           root=root)
        finally:
            calibrate.CHECKOUT = saved
    rows = [json.loads(x) for x in out.getvalue().splitlines()]
    return [r for r in rows if "kind" in r]


@pytest.mark.parametrize("cell", ["tiny-train-1chip", "tiny-decode-long-cache"])
def test_the_control_fails_the_limits_and_the_program_meets_them(tiny, on_cpu, cell):
    root, _ = tiny
    limits = json.loads((root / "workloads" / f"{cell}.json").read_text())["limits"]
    rows = _readings(tiny, cell)
    for r in rows:
        over = [k for k in limits if r[k] > limits[k]]
        if r["kind"] == "program":
            assert not over, r
        else:  # the float8 control and every planted fault fail some number
            assert over, r


@contextlib.contextmanager
def _planted(monkeypatch, fault):
    if fault == "unchanged":  # the step returns the state it was given
        build = spmd.build_train_step

        def still_step(*a, **kw):
            step, shardings, batch_fn = build(*a, **dict(kw, donate=False))
            return (lambda state, batch: (state, step(state, batch)[1])), shardings, batch_fn
        monkeypatch.setattr(spmd, "build_train_step", still_step)
    elif fault == "half":  # half of the rows left out, the mean over the rest
        place = Trainer.place_batch

        def half(self, raw):
            mask = raw["loss_mask"].copy()
            mask[mask.shape[0] // 2:] = 0.0
            return place(self, dict(raw, loss_mask=mask))
        monkeypatch.setattr(Trainer, "place_batch", half)
    elif fault == "token":  # one served token altered where it is produced
        serve = spmd.build_serve_fns

        def altered(model, mesh, max_len):
            prefill, decode = serve(model, mesh, max_len)
            calls = itertools.count()

            def dec(params, cache, batch):
                logits, cache = decode(params, cache, batch)
                if next(calls) % 4 == 3:
                    logits = logits.at[:, 7].add(1e4)
                return logits, cache
            return prefill, dec
        monkeypatch.setattr(spmd, "build_serve_fns", altered)
    yield


@pytest.mark.parametrize("cell,fault", [("tiny-train-1chip", "unchanged"),
                                        ("tiny-train-1chip", "half"),
                                        ("tiny-decode-long-cache", "token")])
def test_a_planted_fault_turns_correct_false(tiny, on_cpu, monkeypatch, cell, fault):
    with _planted(monkeypatch, fault):
        line = whole_run(tiny, cell)
    assert line["correct"] is False
    assert any(c["value"] > c["limit"] for c in line["checks"].values())


CHILD = """
import contextlib, io, json, pathlib, sys
import jax
from benchmarks.chip import harness, peaks, run as bench_run
root, checkout = pathlib.Path(sys.argv[1]), pathlib.Path(sys.argv[2])
wl = json.loads((root / "workloads" / "tiny-train-dp4.json").read_text())
(root / "workloads" / "tiny-dp4-four.json").write_text(json.dumps(dict(wl, chips=4, mesh=[4, 1])))
harness.require_chips = lambda n: jax.devices()[:n]
harness.configure_compile_cache = lambda c: None
peaks.PEAKS["cpu"] = peaks.PEAKS["TPU v5 lite"]
if sys.argv[3] == "solo":  # the exchange between replicas left out
    jax.lax.psum = lambda x, axes, **kw: x
out = io.StringIO()
with contextlib.redirect_stdout(out):
    bench_run.main(["--workload", "tiny-dp4-four", "--seed", "5", "--seconds", "1",
                    "--trace", "0"], root=root, checkout=checkout)
print(out.getvalue().strip().splitlines()[-1])
"""


@pytest.mark.parametrize("fault", ["none", "solo"])
def test_four_replicas_on_host_devices(tiny, fault):
    """train-dp4's path on four CPU devices: sound, and with the gradient
    exchange left out."""
    root, checkout = tiny
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=os.pathsep.join([str(bench_run.CHECKOUT), str(bench_run.CHECKOUT / "src")]),
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    res = subprocess.run([sys.executable, "-c", CHILD, str(root), str(checkout), fault],
                         env=env, capture_output=True, text=True, timeout=600)
    assert res.returncode == 0, res.stderr[-3000:]
    line = json.loads(res.stdout.strip().splitlines()[-1])
    assert line["device"]["count"] == 4
    assert line["correct"] is (fault == "none"), line["checks"]
    if fault == "none":
        assert np.isclose(line["checks"]["bad_steps"]["value"], 0)
