"""A tiny copy of the benchmark for CPU tests: the harness's files with a
small Qwen3 configuration in float32 and small traffic, and cells that use
them with the real cells' drivers and limits. The chip check, the compile
cache and the peaks table are stood in for, in the test's process only."""
import json
import os
import pathlib
import shutil

import jax
import pytest

from benchmarks.chip import harness, peaks

REPO = pathlib.Path(__file__).resolve().parents[2]
# Serving compares the widest logit gap, which grows with the logits' scale
# (the embedding's 0.02 x sqrt(hidden)) and with the tokens compared: at a
# hidden size of 1024 and 4 x 64 served tokens the float8 control reads
# 0.29, over the real cells' limit, as it does at full size. The tiny
# traffic serves some 80 tokens a request, so that a sample compares more.
CONFIGS = {
    "tiny": {"hidden_size": 64, "intermediate_size": 96, "num_attention_heads": 4,
             "num_key_value_heads": 2, "head_dim": 16, "vocab_size": 256,
             "num_hidden_layers": 2, "torch_dtype": "float32"},
    "tiny-wide": {"hidden_size": 1024, "intermediate_size": 1024, "num_attention_heads": 8,
                  "num_key_value_heads": 4, "head_dim": 128, "vocab_size": 1024,
                  "num_hidden_layers": 2, "torch_dtype": "float32"},
}
TRAFFIC = {"tiny-train": {"global_batch": 4, "seq_len": 16, "mean_doc_len": 512},
           "tiny-serve": {"batch": 4, "max_len": 128, "rounds": 2,
                          "prompt_len": {"median": 16, "sigma": 0.5, "buckets": [16, 32]},
                          "output_len": {"median": 80, "sigma": 0.25, "max": 96}}}


@pytest.fixture(scope="session")
def tiny(tmp_path_factory):
    """(benchmark root, checkout) of the tiny copy; cell ``tiny-<cell>``
    stands for each real cell."""
    base = tmp_path_factory.mktemp("tiny")
    root = base / "chip"
    shutil.copytree(harness.HERE, root, ignore=shutil.ignore_patterns("__pycache__"))
    cfg = json.loads((root / "configs" / "qwen3-1.7b.json").read_text())
    for name, small in CONFIGS.items():
        (root / "configs" / f"{name}.json").write_text(json.dumps({**cfg, **small}))
    for name, t in TRAFFIC.items():
        (root / "traffic" / f"{name}.json").write_text(json.dumps(t))
    bench = json.loads((REPO / "BENCHMARK.json").read_text())
    for path in sorted((root / "workloads").glob("*.json")):
        wl = json.loads(path.read_text())
        train = wl["driver"] == "train"
        wl.update(config="tiny" if train else "tiny-wide", traffic="tiny-train" if train else "tiny-serve")
        if wl["chips"] > 1:
            wl.update(chips=1, mesh=[1, 1])
        (root / "workloads" / f"tiny-{path.name}").write_text(json.dumps(wl))
    for m in bench["end_to_end"] + bench["per_layer"]:
        if "workloads" in m:
            m["workloads"] = m["workloads"] + [f"tiny-{w}" for w in m["workloads"]]
    checkout = base / "checkout"
    (checkout / "src").mkdir(parents=True)
    os.symlink(REPO / "src" / "repro", checkout / "src" / "repro")
    (checkout / "BENCHMARK.json").write_text(json.dumps(bench))
    return root, checkout


@pytest.fixture
def on_cpu(monkeypatch):
    """The harness runs on the CPU device: no chip check, no compile cache,
    and the CPU given the v5e's peaks so that the readers have a table."""
    monkeypatch.setattr(harness, "require_chips", lambda n: jax.devices()[:1])
    monkeypatch.setattr(harness, "configure_compile_cache", lambda checkout: None)
    monkeypatch.setitem(peaks.PEAKS, "cpu", peaks.PEAKS["TPU v5 lite"])
