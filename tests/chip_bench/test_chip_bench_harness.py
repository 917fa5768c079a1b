"""The benchmark harness: cells, configurations, traffic and metrics found
by name; BENCHMARK.json in step with the files; the refusals off the chip;
and the result line of a whole run at a tiny size on the CPU."""
import json
import os
import pathlib
import re
import shutil
import subprocess
import sys

import pytest

from benchmarks.chip import harness, peaks

REPO = pathlib.Path(__file__).resolve().parents[2]
BENCH = json.loads((REPO / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
CONTRACT_KEYS = {"correct", "attempted", "failed", "metrics", "device", "breakdown", "checks"}


def test_benchmark_json_matches_the_cell_files():
    for w in BENCH["workloads"]:
        cell = harness.Cell(w["name"], harness.HERE, BENCH)
        assert (cell.workload["config"], cell.workload["traffic"], cell.chips) == (
            w["config"], w["traffic"], w["chips"])
        assert cell.per_layer and cell.end_to_end
    for c in BENCH["configs"]:
        assert (REPO / c["file"]).is_file()
    names = ([m["name"] for m in BENCH["end_to_end"] + BENCH["per_layer"]]
             + [w["name"] for w in BENCH["workloads"]] + [c["name"] for c in BENCH["configs"]])
    assert len(names) == len(set(names)) and all(NAME.match(n) for n in names)
    for m in BENCH["per_layer"]:
        assert (harness.HERE / "metrics" / f"{m['name']}.py").is_file()
    assert sum(w["chips"] == 4 for w in BENCH["workloads"]) <= max(1, len(BENCH["workloads"]) // 2)


def test_a_new_cell_and_metric_are_found_by_name(tmp_path):
    root = tmp_path / "chip"
    shutil.copytree(harness.HERE, root, ignore=shutil.ignore_patterns("__pycache__"))
    (root / "configs" / "other.json").write_text(
        (root / "configs" / "qwen3-1.7b.json").read_text())
    (root / "traffic" / "other-mix.json").write_text(json.dumps({"batch": 1}))
    (root / "workloads" / "other-cell.json").write_text(json.dumps(
        {"config": "other", "traffic": "other-mix", "chips": 1, "driver": "decode"}))
    (root / "metrics" / "other.metric.py").write_text("def read(run):\n    return 42.0\n")
    bench = dict(BENCH, per_layer=BENCH["per_layer"] + [
        {"name": "other.metric", "unit": "%", "workloads": ["other-cell"]}])
    cell = harness.Cell("other-cell", root, bench)
    assert cell.config["num_hidden_layers"] == 28 and cell.traffic == {"batch": 1}
    assert [n for n, _, _ in cell.per_layer] == ["other.metric"]
    assert cell.per_layer[0][2].read(None) == 42.0
    assert cell.driver.__name__.endswith("decode")
    with pytest.raises(FileNotFoundError):
        harness.Cell("no-such-cell", root, bench)


def test_an_unknown_device_kind_is_an_error():
    assert peaks.peaks("TPU v5 lite")["bf16_flops"] == 197e12
    with pytest.raises(KeyError):
        peaks.peaks("TPU v9 imaginary")


def _command(cwd, *extra):
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env["JAX_PLATFORMS"] = "cpu"
    return subprocess.run([sys.executable, "benchmarks/chip/run.py", "--workload", "train-1chip",
                           "--seed", "3000000001", "--seconds", "1", "--trace", "0", *extra],
                          cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_the_command_refuses_to_run_off_the_tpu():
    res = _command(REPO)
    assert res.returncode != 0
    assert '"correct"' not in res.stdout
    assert "no accelerator" in res.stderr


def test_the_command_fails_without_the_program(tmp_path):
    (tmp_path / "benchmarks").mkdir()
    shutil.copytree(harness.HERE, tmp_path / "benchmarks" / "chip",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(REPO / "BENCHMARK.json", tmp_path)
    res = _command(tmp_path)
    assert res.returncode != 0 and '"correct"' not in res.stdout
