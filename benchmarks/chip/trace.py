"""The traced run: the profiler around the measured window, and the
reduction of its trace (``jax.profiler.ProfileData``) to what the
per-layer metric readers take.

Per device plane (``/device:TPU:<n>``), the ``XLA Ops`` line holds one event
per operation run, named by its HLO text (``%fusion.12 = bf16[..] fusion(..``;
a ``while`` event spans the operations of its body, which have events of
their own), the ``Async XLA Ops`` line the asynchronous operations from
start to done, and the ``XLA Modules`` line one event per program run
(``jit_decode(<fingerprint>)``). Host and device events come on nearly one
clock (see ``from_file``). The host planes hold the harness's own spans
(``bench.*``), which label the device's idle gaps. Every interval is clipped
to the window, the host span ``bench.window``.
"""
from __future__ import annotations

import glob
import os
import re
import shutil
import sys
import time

import jax

from benchmarks.chip import harness

WINDOW = "bench.window"
OPS_LINE, ASYNC_LINE, MODULES_LINE = "XLA Ops", "Async XLA Ops", "XLA Modules"
DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
HLO = re.compile(r"^%(\S+) = (.*?) ([a-z][\w-]*)\(")
# Operations that only hold others: their body's operations are the work.
CONTAINERS = {"while", "conditional", "call"}
COLLECTIVE = re.compile(r"^(all-reduce|all-gather|reduce-scatter|collective-permute|all-to-all"
                        r"|collective-broadcast|ragged-all-to-all)(-start|-done)?$")


def opcode(text: str) -> str:
    """The HLO opcode of an ``XLA Ops`` event (``fusion``, ``while``, ...)."""
    m = HLO.match(text)
    return m.group(3) if m else text.split("(")[0]


def op_label(text: str) -> str:
    """``<name> <result shape>`` of an op event, shortened."""
    m = HLO.match(text)
    return f"{m.group(1)} {m.group(2)}"[:120] if m else text[:120]


def _async_collective(text: str) -> bool:
    """An async op whose work is a collective (``all-gather-start``, or an
    ``async-start`` wrapping one)."""
    return bool(COLLECTIVE.match(opcode(text))) or (
        opcode(text) == "async-start" and bool(re.search(
            r"(all-reduce|all-gather|reduce-scatter|collective-permute|all-to-all)", text)))


class Window:
    """The measured window; with ``run.trace``, traced by the profiler from
    its start, under the host span ``bench.window``, for ``limit_s`` seconds
    or to its end. The loop calls ``tick()`` after each step; the trace
    stops at the first tick past the limit. After the window,
    ``run.summary`` holds the reduced trace."""

    compiles = None

    def __init__(self, run, limit_s: float | None = None):
        self.run, self.limit_s = run, limit_s
        self.out = run.checkout / ".bench_trace"
        self.active = False
        if Window.compiles is None:
            Window.compiles = harness.CompileCounter()

    def __enter__(self):
        Window.compiles.count, Window.compiles.open = 0, True
        if self.run.trace:
            shutil.rmtree(self.out, ignore_errors=True)
            jax.profiler.start_trace(str(self.out))
            self.span = jax.profiler.TraceAnnotation(WINDOW)
            self.span.__enter__()
            self.active = True
        self.t0 = time.perf_counter()
        return self

    def tick(self) -> None:
        if self.active and self.limit_s is not None and time.perf_counter() - self.t0 >= self.limit_s:
            self._stop()

    def _stop(self) -> None:
        self.span.__exit__(None, None, None)
        jax.profiler.stop_trace()
        self.active = False

    def __exit__(self, *exc):
        Window.compiles.open = False
        self.run.facts["window_compiles"] = Window.compiles.count
        print(f"compile events inside the window: {Window.compiles.count}", file=sys.stderr)
        if self.active:
            self._stop()
        if self.run.trace and exc[0] is None:
            self.run.summary = Summary.from_dir(str(self.out), n_devices=len(self.run.devices))
        shutil.rmtree(self.out, ignore_errors=True)
        return False


def union(intervals):
    """Merged, sorted (start, end) intervals."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def total(intervals) -> float:
    return sum(e - s for s, e in intervals)


def subtract(a, b):
    """Parts of the merged intervals ``a`` that the merged ``b`` leave free."""
    out, j = [], 0
    for s, e in a:
        cur = s
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < e:
            if b[k][0] > cur:
                out.append((cur, b[k][0]))
            cur = max(cur, b[k][1])
            k += 1
        if cur < e:
            out.append((cur, e))
    return out


def _clip(s, e, lo, hi):
    s, e = max(s, lo), min(e, hi)
    return (s, e) if e > s else None


class Summary:
    """The window's device activity, times in seconds.

    Per device d: ``ops[d]`` and ``async_ops[d]``, (HLO text, start, end) of
    each operation; ``modules[d]``, (program name, start, end) of each
    program run. ``spans``: the harness's host spans (name, start, end);
    ``window``: (start, end). ``busy[d]``: the union of ``ops[d]``. Host
    spans are the harness's (``bench.*``) and, where the profiler's Python
    tracer recorded them, Python calls (``$<file>:<line> <function>``)."""

    def __init__(self, ops, async_ops, modules, spans, window_span):
        self.ops, self.async_ops, self.modules = ops, async_ops, modules
        self.spans, self.window = spans, window_span
        self.window_s = window_span[1] - window_span[0]
        self.busy = [union([(s, e) for _, s, e in dev]) for dev in ops]
        self.busy_s = sum(total(b) for b in self.busy) / max(len(self.busy), 1)

    @classmethod
    def from_dir(cls, path: str, n_devices: int) -> "Summary":
        files = sorted(glob.glob(os.path.join(path, "plugins", "profile", "*", "*.xplane.pb")))
        if not files:
            raise FileNotFoundError(f"no trace under {path}")
        return cls.from_file(files[-1], n_devices)

    @classmethod
    def from_file(cls, path: str, n_devices: int | None = None) -> "Summary":
        from jax.profiler import ProfileData

        devices, spans = [], []

        def events(lines, name):
            return ([(e.name, e.start_ns * 1e-9, (e.start_ns + e.duration_ns) * 1e-9)
                     for e in lines[name].events] if name in lines else [])

        for plane in ProfileData.from_file(path).planes:
            m = DEVICE_PLANE.match(plane.name)
            if m:
                lines = {line.name: line for line in plane.lines}
                devices.append((int(m.group(1)), events(lines, OPS_LINE),
                                events(lines, ASYNC_LINE), events(lines, MODULES_LINE)))
            elif plane.name.startswith("/host"):
                for line in plane.lines:
                    spans += [(e.name, e.start_ns * 1e-9, (e.start_ns + e.duration_ns) * 1e-9)
                              for e in line.events if e.name.startswith(("bench.", "$"))
                              and not e.name.startswith(("$builtins", "$<unknown>"))]
        devices.sort(key=lambda d: d[0])
        devices = devices[:n_devices]
        wins = [(s, e) for n, s, e in spans if n == WINDOW]
        if not wins:
            raise ValueError(f"no {WINDOW} span in {path}")
        lo, hi = wins[-1]

        def clip(evs, shift):
            return [(n, *c) for n, s, e in evs if (c := _clip(s + shift, e + shift, lo, hi))]

        # The device's clock runs about a millisecond behind the host's. The
        # window's work is all dispatched inside the window, so a device
        # whose first operation starts before the window is moved forward to
        # the window's start.
        shifts = [max(0.0, lo - min((s for _, s, _ in d[1]), default=lo)) for d in devices]
        return cls([clip(d[1], x) for d, x in zip(devices, shifts)],
                   [clip(d[2], x) for d, x in zip(devices, shifts)],
                   [clip(d[3], x) for d, x in zip(devices, shifts)],
                   [sp for sp in spans if sp[0] != WINDOW and _clip(sp[1], sp[2], lo, hi)],
                   (lo, hi))

    # ------------------------------------------------------------ readings

    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s

    def module_runs(self, pattern: str, device: int = 0):
        """Durations (s) of the runs of programs whose name matches."""
        rx = re.compile(pattern)
        mods = self.modules[device] if device < len(self.modules) else []
        return [e - s for n, s, e in mods if rx.search(n)]

    def heaviest_module_runs(self, device: int = 0):
        """Durations (s) of the runs of the program that took most device
        time in the window."""
        per = {}
        for n, s, e in (self.modules[device] if device < len(self.modules) else []):
            per.setdefault(n, []).append(e - s)
        return max(per.values(), key=sum) if per else []

    def collective(self, device: int):
        """(collective time, exposed collective time) on ``device``: the union
        of its collective operations, synchronous and asynchronous (start to
        done), and the part of that in which no other operation runs."""
        coll = union([(s, e) for n, s, e in self.ops[device] if COLLECTIVE.match(opcode(n))]
                     + [(s, e) for n, s, e in self.async_ops[device] if _async_collective(n)])
        other = union([(s, e) for n, s, e in self.ops[device]
                       if not COLLECTIVE.match(opcode(n)) and opcode(n) not in CONTAINERS])
        return total(coll), total(subtract(coll, other))

    def breakdown(self, top: int = 10) -> dict:
        """The operations that took most device time (mean over devices;
        loops are counted by their body's operations), and the longest idle
        gaps of device 0, each named by the innermost harness span open on the
        host in the middle of it."""
        per = {}
        for dev in self.ops:
            for n, s, e in dev:
                if opcode(n) not in CONTAINERS:
                    key = op_label(n)
                    per[key] = per.get(key, 0.0) + (e - s) / len(self.ops)
        ops = sorted(per.items(), key=lambda kv: -kv[1])[:top]
        gaps = []
        if self.busy:
            edges = [self.window[0]] + [x for iv in self.busy[0] for x in iv] + [self.window[1]]
            gaps = sorted(((s, e) for s, e in zip(edges[0::2], edges[1::2]) if e > s),
                          key=lambda g: g[0] - g[1])[:top]
        return {"device_ops": [[n, t] for n, t in ops],
                "idle_gaps": [[self.host_label((s + e) / 2), e - s] for s, e in gaps]}

    def host_label(self, t: float) -> str:
        """What the host was doing at ``t``: the innermost harness span and
        the innermost Python call open then."""
        def inner(prefix):
            open_ = [sp for sp in self.spans if sp[0].startswith(prefix) and sp[1] <= t <= sp[2]]
            return min(open_, key=lambda sp: sp[2] - sp[1])[0] if open_ else None
        return " / ".join(x for x in (inner("bench.") or WINDOW, inner("$")) if x)
