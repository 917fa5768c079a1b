"""Readings that the limits of a cell's comparison are set from, on the
chip at the cell's own size, in one process:

  python3 benchmarks/chip/calibrate.py --workload <cell> --seeds 1 2 ... [--faults 3]

For each seed, the program's numbers (the timed path's compared steps, or
the requests of one cycle of the traffic's rounds, sampled as a run samples
them). For the first ``--faults`` seeds, the
control's (the float8 reference put in the program's place) and each
fault's that the cell can have: training, half of each replica's rows left
out (``half``), on several chips replica 0's gradient alone (``solo``, the
exchange left out), and a step that returns its state unchanged (reads 1 by
the measure, no run); serving, one served token altered (``token``). One
JSON object a line on standard output, then the summary: per number, the
largest program reading (lower) and the smallest of each other kind.
"""
from __future__ import annotations

import argparse
import gc
import json
import os
import pathlib
import sys

CHECKOUT = pathlib.Path(__file__).resolve().parents[2]
sys.path[:0] = [str(CHECKOUT), str(CHECKOUT / "src")]

import numpy as np  # noqa: E402

from benchmarks.chip import compare, harness, inputs  # noqa: E402
from benchmarks.chip.reference import runs  # noqa: E402
from benchmarks.chip.run import Run  # noqa: E402


def train_readings(cell, devices, seeds, n_faults):
    drv = cell.driver
    for i, seed in enumerate(seeds):
        run = Run(cell, seed, 0.0, False, devices, CHECKOUT)
        trainer, _, replicas, _, prog = drv.setup(run)
        del trainer
        gc.collect()
        ref = drv.reference(run, replicas)
        compare.report_worst(prog, ref, sys.stderr)
        yield seed, "program", compare.train_numbers(prog, ref)
        if i >= n_faults:
            continue
        yield seed, "control", compare.train_numbers(drv.reference(run, replicas, lowp=True), ref)
        for fault in ("half",) + (("solo",) if replicas > 1 else ()):
            yield seed, fault, compare.train_numbers(drv.reference(run, replicas, fault=fault), ref)
        still = dict(prog, grad={k: 0.0 for k in prog["grad"]},
                     change={k: 0.0 for k in prog["change"]})
        yield seed, "unchanged", compare.train_numbers(still, ref)


def serve_readings(cell, devices, seeds, n_faults):
    drv = cell.driver
    cfg = cell.config
    for i, seed in enumerate(seeds):
        run = Run(cell, seed, 0.0, False, devices, CHECKOUT)
        params, prefill, decode, _ = drv.build(run)
        done = []
        for r in range(cell.traffic["rounds"]):
            rd = drv.new_round(run, params, prefill, decode, r)
            done += rd.requests(rd.finish())
        del params, rd
        gc.collect()
        picked = drv.sample(run, done)
        s32 = inputs.seed32(seed)
        yield seed, "program", {"logit_gap": max(runs.serve_gaps(cfg, s32, picked))}
        if i >= n_faults:
            continue
        yield seed, "control", {"logit_gap": max(runs.serve_gaps(cfg, s32, picked, lowp=True))}
        rng = np.random.default_rng([s32, 2])
        p, s = picked[0]
        s = s.copy()
        at = int(rng.integers(len(s)))
        s[at] = (s[at] + 1 + int(rng.integers(cfg["vocab_size"] - 1))) % cfg["vocab_size"]
        yield seed, "token", {"logit_gap": max(runs.serve_gaps(cfg, s32, [(p, s)] + picked[1:]))}


def main(argv=None, root: pathlib.Path = harness.HERE) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--faults", type=int, default=3)
    args = ap.parse_args(argv)
    bench = json.loads((CHECKOUT / "BENCHMARK.json").read_text())
    cell = harness.Cell(args.workload, root, bench)
    devices = harness.require_chips(cell.chips)
    harness.configure_compile_cache(CHECKOUT)
    readings = train_readings if cell.workload["driver"] == "train" else serve_readings
    table: dict = {}
    for seed, kind, nums in readings(cell, devices, args.seeds, args.faults):
        print(json.dumps({"cell": args.workload, "seed": seed, "kind": kind, **nums}), flush=True)
        for k, v in nums.items():
            table.setdefault(k, {}).setdefault(kind, []).append(v)
    for k, kinds in table.items():
        summary = {kind: (max(v) if kind == "program" else min(v)) for kind, v in kinds.items()}
        print(json.dumps({"cell": args.workload, "number": k, "lower_and_least": summary}), flush=True)
    return 0


if __name__ == "__main__":
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    raise SystemExit(main())
