"""What every cell shares: finding a cell's files by name, the device
check, timing, the compile cache and the result line.

A cell is ``workloads/<cell>.json``. It names its configuration
(``configs/<config>.json``), its traffic (``traffic/<traffic>.json``), the
``driver`` whose window loop runs it (``drivers/<driver>.py``), its chips
and the limits of its comparison. Per-layer metrics are
``metrics/<metric>.py``, each with ``read(run) -> float | None``. A later
cell or metric is a new file of its own; nothing here names one.
"""
from __future__ import annotations

import importlib.util
import json
import pathlib
import sys

HERE = pathlib.Path(__file__).resolve().parent


class NoChip(RuntimeError):
    """No accelerator, or fewer chips than the cell asks for."""


def load_json(root: pathlib.Path, kind: str, name: str) -> dict:
    path = root / kind / f"{name}.json"
    if not path.is_file():
        raise FileNotFoundError(f"no {kind[:-1]} named {name!r} ({path})")
    return json.loads(path.read_text())


def load_module(path: pathlib.Path, name: str):
    if not path.is_file():
        raise FileNotFoundError(f"no file {path}")
    spec = importlib.util.spec_from_file_location(f"chipbench_{name.replace('.', '_').replace('-', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


class Cell:
    """A workload with its configuration, traffic, driver and metrics,
    found by name under ``root``."""

    def __init__(self, name: str, root: pathlib.Path = HERE, benchmark: dict | None = None):
        self.name, self.root = name, root
        self.workload = load_json(root, "workloads", name)
        self.config = load_json(root, "configs", self.workload["config"])
        self.traffic = load_json(root, "traffic", self.workload["traffic"])
        self.driver = load_module(root / "drivers" / f"{self.workload['driver']}.py",
                                  self.workload["driver"])
        self.chips = int(self.workload["chips"])
        self.per_layer = []
        for m in (benchmark or {}).get("per_layer", []):
            if name in m.get("workloads", [name]):
                self.per_layer.append((m["name"], m["unit"], load_module(
                    root / "metrics" / f"{m['name']}.py", m["name"])))
        self.end_to_end = [(m["name"], m["unit"]) for m in (benchmark or {}).get("end_to_end", [])
                           if name in m.get("workloads", [name])]


def require_chips(n: int):
    """The first ``n`` accelerator devices, or ``NoChip``."""
    import jax

    devices = jax.devices()
    if devices[0].platform == "cpu":
        raise NoChip(f"no accelerator: JAX found only {devices[0].platform}")
    if len(devices) < n:
        raise NoChip(f"the cell needs {n} chips, JAX found {len(devices)}")
    return devices[:n]


def configure_compile_cache(checkout: pathlib.Path) -> pathlib.Path:
    """JAX's persistent cache at a fixed path inside the checkout, keeping
    every program however fast it compiled, so that only a checkout's first
    run compiles."""
    import jax

    path = checkout / ".jax_cache" / "chipbench"
    path.mkdir(parents=True, exist_ok=True)
    jax.config.update("jax_compilation_cache_dir", str(path))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    jax.config.update("jax_compilation_cache_max_size", -1)
    return path


def memory_peak_bytes(devices) -> int:
    return max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0)) for d in devices)


class CompileCounter:
    """Counts JAX's tracing, compiling and compile-cache events while
    ``open``, to show that nothing compiles inside a measured window."""

    def __init__(self):
        import jax

        self.count = 0
        self.open = False
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **kw):
        if self.open and event.startswith(("/jax/core/compile/", "/jax/compilation_cache/")):
            self.count += 1


def result_line(*, correct, attempted, failed, metrics, device, checks, breakdown=None) -> str:
    out = {"correct": bool(correct), "attempted": int(attempted), "failed": int(failed),
           "metrics": metrics, "device": device}
    if breakdown is not None:
        out["breakdown"] = breakdown
    out["checks"] = checks
    return json.dumps(out)


def print_checks(checks: dict) -> None:
    for name, c in checks.items():
        print(f"check {name}: {c['value']!r} limit {c['limit']!r}", file=sys.stderr, flush=True)
