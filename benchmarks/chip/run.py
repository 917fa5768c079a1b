"""Run one benchmark cell on the chips of this machine.

  python3 benchmarks/chip/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

From the root of a checkout. One process: set-up (compile or cache load,
weights, warm-up of the cell's own shapes), a measured window of
``--seconds``, then the comparison with the plain reference that decides
``correct``. The last line of standard output is one JSON object:
``correct``, ``attempted``, ``failed``, ``metrics`` (the end-to-end metrics
with ``--trace 0``, the per-layer ones with ``--trace 1``), ``device`` and,
last, ``checks``: each number compared, with its limit. The checks are also
the last lines of standard error. Without a TPU, or with fewer chips than
the cell asks for, it exits 3 and prints no result.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import pathlib  # noqa: E402
import sys  # noqa: E402

CHECKOUT = pathlib.Path(__file__).resolve().parents[2]
if str(CHECKOUT) not in sys.path:
    sys.path.insert(0, str(CHECKOUT))
if str(CHECKOUT / "src") not in sys.path:
    sys.path.insert(1, str(CHECKOUT / "src"))

from benchmarks.chip import harness  # noqa: E402


class Run:
    """What a driver is given and fills in. ``setup_done()`` marks the first
    timed operation; the driver then sets ``attempted``, ``failed``, ``e2e``
    (end-to-end metric values), ``checks`` (``compare.check`` results) and
    ``facts`` for its metric readers; a traced window leaves ``summary``."""

    def __init__(self, cell: harness.Cell, seed: int, seconds: float, trace: bool,
                 devices, checkout: pathlib.Path):
        self.cell, self.seed, self.seconds, self.trace = cell, seed, seconds, trace
        self.devices, self.checkout = devices, checkout
        self.setup_s = None
        self.attempted = self.failed = 0
        self.e2e: dict = {}
        self.checks: dict = {}
        self.memory_peak_bytes = 0
        self.summary = None          # trace.Summary of the traced window
        self.facts: dict = {}        # the driver's numbers that metric readers use

    def setup_done(self) -> None:
        self.setup_s = time.perf_counter() - T_START

    def read_memory_peak(self) -> None:
        self.memory_peak_bytes = harness.memory_peak_bytes(self.devices)


def main(argv=None, root: pathlib.Path = harness.HERE, checkout: pathlib.Path = CHECKOUT) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    if not (checkout / "src" / "repro").is_dir():
        print(f"run.py: the program (src/repro) is not in {checkout}", file=sys.stderr)
        return 2
    bench_file = checkout / "BENCHMARK.json"
    benchmark = json.loads(bench_file.read_text()) if bench_file.is_file() else {}
    cell = harness.Cell(args.workload, root, benchmark)
    try:
        devices = harness.require_chips(cell.chips)
    except harness.NoChip as e:
        print(f"run.py: {e}", file=sys.stderr)
        return 3
    harness.configure_compile_cache(checkout)

    run = Run(cell, args.seed, args.seconds, bool(args.trace), devices, checkout)
    cell.driver.run(run)
    checks = run.checks
    correct = all(c["ok"] for c in checks.values()) and run.failed == 0
    checks = {k: {"value": c["value"], "limit": c["limit"]} for k, c in checks.items()}
    device = {"platform": devices[0].platform, "kind": devices[0].device_kind,
              "count": len(devices), "memory_peak_bytes": run.memory_peak_bytes}
    breakdown = None
    if run.trace:
        s = run.summary
        device["busy_s"], device["window_s"] = s.busy_s, s.window_s
        breakdown = s.breakdown()
        metrics = {}
        for name, unit, reader in cell.per_layer:
            value = reader.read(run)
            if value is not None:
                metrics[name] = {"value": value, "unit": unit}
    else:
        metrics = {"setup_s": {"value": run.setup_s, "unit": "s"}}
        for name, unit in cell.end_to_end:
            if name in run.e2e:
                metrics[name] = {"value": run.e2e[name], "unit": unit}
    harness.print_checks(checks)
    print(harness.result_line(correct=correct, attempted=run.attempted, failed=run.failed,
                              metrics=metrics, device=device, checks=checks,
                              breakdown=breakdown), flush=True)
    return 0


if __name__ == "__main__":
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    raise SystemExit(main())
