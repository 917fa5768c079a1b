"""Device time by the program's named scopes, and the program's host spans.

The program names its device code with ``jax.named_scope`` (``SCOPES``):
every HLO instruction traced under a scope carries it in its ``op_name``
metadata (``jit(decode)/while/body/closed_call/attention/kv_update/...``;
backward ops as ``transpose(jvp(head))``). A traced window's op events are
named by their HLO text, instruction name and result, and not by scope; the
reduced trace (``trace.Summary``) keeps only that. So the scope of each op
comes from the HLO of the program that ran it: the cell's compiled program
is built again from the cell's files with the window's shapes and shardings
(the compile cache returns the executable that ran); where over 1% of the
window's op events name no instruction of it with the same result, it is
not the program that ran, and nothing is read.

An instruction whose ``op_name`` names no scope (a copy XLA inserts, the
layer scan's slicing and stacking of its inputs and outputs) takes the scope
of the nearest instruction with one whose data it moves: its operands first,
then its users, through tuples, loops and their bodies. What no scope claims
stays counted (``unclaimed_share``).

Host spans (``repro.*``, ``jax.profiler`` annotations) are on the host plane
of the trace file; ``program_spans`` reads them.
"""
from __future__ import annotations

import re
import sys
import traceback
from collections import deque

from benchmarks.chip import trace

# The program's device scopes (the yardstick's own list: a program without
# them reads as having none).
SCOPES = ("train/fwd_bwd", "train/vote", "train/reduce", "train/clip", "train/adamw",
          "train/gate", "embed", "attention", "kv_update", "ffn", "head")
SPAN_PREFIX = "repro."

_INSTR = re.compile(r"^\s*(?:ROOT )?%(\S+) = (.*?) ([a-z][\w-]*)\((.*)$")
_COMP = re.compile(r"^(?:ENTRY )?%(\S+) \(.*\{\s*$")   # a computation's header, unindented
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_REF = re.compile(r"%([\w.\-]+)")
_ATTR = re.compile(r"\b(calls|body|condition)=%([\w.\-]+)")
_INDEX = re.compile(r"\bindex=(\d+)")
_ARRAY = re.compile(r"^\(*(\w+)\[([\d,]*)\]")   # the first array of a result


def _rx(name: str):
    return re.compile(r"(?:^|[/(])" + re.escape(name) + r"(?=[/)]|$)")


_SCOPE_RX = {name: _rx(name) for name in SCOPES}


def has_scope(op_name: str | None, name: str | None = None) -> bool:
    """Whether ``op_name`` lies under scope ``name`` (any program scope if None)."""
    if not op_name:
        return False
    names = [name] if name else SCOPES
    return any((_SCOPE_RX.get(n) or _rx(n)).search(op_name) for n in names)


def _split_operands(rest: str) -> tuple[str, str]:
    """The operand list of an instruction line's tail, and what follows it."""
    depth = 1
    for i, ch in enumerate(rest):
        depth += ch == "("
        depth -= ch == ")"
        if depth == 0:
            return rest[:i], rest[i + 1:]
    return rest, ""


class Hlo:
    """An HLO module's text: each instruction's result, opcode, operands,
    called computations and ``op_name``, and the scope each instruction is
    attributed to."""

    def __init__(self, text: str):
        m = re.search(r"^HloModule (\S+?)[,\s]", text, re.M)
        self.module = m.group(1) if m else None
        self.instrs: dict[str, dict] = {}
        self.comps: dict[str, list] = {}
        comp = None
        for line in text.split("\n"):
            c = _COMP.match(line)
            if c:
                comp = c.group(1)
                self.comps[comp] = []
                continue
            m = _INSTR.match(line)
            if not m or comp is None:
                continue
            name, shape, opcode, rest = m.groups()
            operands, attrs = _split_operands(rest)
            op = _OP_NAME.search(attrs)
            idx = _INDEX.search(attrs)
            self.instrs[name] = {
                "shape": shape, "opcode": opcode, "comp": comp,
                "root": line.lstrip().startswith("ROOT "),
                "operands": _REF.findall(operands),
                "called": dict(_ATTR.findall(attrs)),
                "index": int(idx.group(1)) if idx else None,
                "op_name": op.group(1).split(";")[0] if op else None,
            }
            self.comps[comp].append(name)
        self._users: dict[str, list] = {}
        self._loop_of: dict[str, str] = {}    # while body -> the while
        for name, ins in self.instrs.items():
            for o in ins["operands"]:
                self._users.setdefault(o, []).append(name)
            if ins["opcode"] == "while" and "body" in ins["called"]:
                self._loop_of[ins["called"]["body"]] = name
        self._scope: dict[str, str | None] = {}

    def stripped(self) -> str:
        """The module as HLO text that holds only what this class reads,
        fused computations left out."""
        fused = {i["called"]["calls"] for i in self.instrs.values()
                 if i["opcode"] == "fusion" and "calls" in i["called"]}
        out = [f"HloModule {self.module},"]
        for comp, names in self.comps.items():
            if comp in fused:
                continue
            out.append(f"%{comp} () -> () {{")
            for n in names:
                i = self.instrs[n]
                attrs = "".join(f", {k}=%{v}" for k, v in i["called"].items())
                attrs += f", index={i['index']}" if i["index"] is not None else ""
                attrs += f', metadata={{op_name="{i["op_name"]}"}}' if i["op_name"] else ""
                ops = ", ".join("%" + o for o in i["operands"])
                out.append(f"  {'ROOT ' if i['root'] else ''}%{n} = {i['shape']} {i['opcode']}({ops}){attrs}")
            out.append("}")
        return "\n".join(out) + "\n"

    def _root(self, comp: str) -> str | None:
        return next((n for n in self.comps.get(comp, ()) if self.instrs[n]["root"]), None)

    def _own(self, name: str) -> str | None:
        """The instruction's own op_name, if it names a scope."""
        op = self.instrs[name]["op_name"]
        return op if has_scope(op) else None

    def _moves(self, name: str) -> bool:
        """Whether ``name`` holds data: its result (the first of a tuple's,
        as an asynchronous copy's) is a floating-point array, not a scalar
        or an index."""
        m = _ARRAY.match(self.instrs[name]["shape"])
        return bool(m) and m.group(2) != "" and m.group(1).startswith(("bf16", "f16", "f32", "f64", "f8"))

    def _element(self, src: str, i: int):
        """Instructions that hold element ``i`` of the tuple ``src`` makes."""
        ins = self.instrs.get(src)
        if ins is None:
            return []
        if ins["opcode"] == "tuple":
            return ins["operands"][i:i + 1]
        if ins["opcode"] == "while":
            root = self._root(ins["called"].get("body", ""))
            return [src] + (self.instrs[root]["operands"][i:i + 1] if root else [])
        if ins["opcode"] == "parameter" and ins["comp"] in self._loop_of:
            loop = self.instrs[self._loop_of[ins["comp"]]]
            return self._element(loop["operands"][0], i) if loop["operands"] else []
        return [src]

    def _gtes(self, src: str, i: int):
        """The get-tuple-elements of element ``i`` of ``src``."""
        return [u for u in self._users.get(src, [])
                if self.instrs[u]["opcode"] == "get-tuple-element" and self.instrs[u]["index"] == i]

    def _element_users(self, tup: str, i: int):
        """Instructions that take element ``i`` of the tuple ``tup``: its
        get-tuple-elements, and where it enters or leaves a loop, the loop
        and the loop's own get-tuple-elements of it."""
        out = self._gtes(tup, i)
        for u in self._users.get(tup, []):
            if self.instrs[u]["opcode"] == "while":
                body = self.comps.get(self.instrs[u]["called"].get("body", ""), ())
                out += [u] + self._gtes(u, i) + [g for n in body if self.instrs[n]["opcode"] == "parameter"
                                                 for g in self._gtes(n, i)]
        comp = self.instrs[tup]["comp"]
        if self.instrs[tup]["root"] and comp in self._loop_of:
            out += [self._loop_of[comp]] + self._gtes(self._loop_of[comp], i)
        return out

    def _neighbours(self, name: str):
        """Instructions whose data ``name`` moves: its producers, then its
        users, following each value through tuples and loops."""
        ins = self.instrs[name]
        if ins["opcode"] == "get-tuple-element":
            out = self._element(ins["operands"][0], ins["index"]) if ins["operands"] else []
        elif ins["opcode"] in ("tuple", "while", "parameter"):
            out = []
        else:
            out = [o for o in ins["operands"] if o in self.instrs and self._moves(o)]
        for u in self._users.get(name, []):
            if self.instrs[u]["opcode"] == "tuple":
                out += [x for k, o in enumerate(self.instrs[u]["operands"]) if o == name
                        for x in self._element_users(u, k)]
            elif self._moves(u):
                out.append(u)
        return out

    def scope_of(self, name: str, depth: int = 8) -> str | None:
        """The op_name that attributes instruction ``name`` to a scope:
        its own, else that of the nearest instruction whose data it moves
        (breadth first, at most ``depth`` steps); None if none is found."""
        if name in self._scope:
            return self._scope[name]
        found = self._own(name)
        if found is None and name in self.instrs:
            seen, queue = {name}, deque([(name, 0)])
            while queue and found is None:
                cur, d = queue.popleft()
                if d >= depth:
                    continue
                for nb in self._neighbours(cur):
                    if nb in seen or nb not in self.instrs:
                        continue
                    seen.add(nb)
                    found = self._own(nb)
                    if found is not None:
                        break
                    queue.append((nb, d + 1))
        self._scope[name] = found
        return found


def _result(text: str) -> str:
    """Result shape of an op event's HLO text, layouts and all."""
    m = trace.HLO.match(text)
    return m.group(2) if m else ""


class Attribution:
    """The window's runs of one compiled program, each op event of them
    paired with its scope. ``runs`` counts the program's runs that lie whole
    inside the window. ``unmatched`` counts the op events that name no
    instruction of ``hlo`` with the same result (they count as unclaimed);
    ``matched`` is False where they are over ``MISMATCH`` of the ops:
    ``hlo`` is then not the program that ran, and nothing is read."""

    MISMATCH = 0.01

    def __init__(self, summary, hlo: Hlo, device: int = 0):
        self.hlo = hlo
        lo, hi = summary.window
        mods = summary.modules[device] if device < len(summary.modules) else []
        runs = sorted((s, e) for n, s, e in mods
                      if n.split("(")[0] == hlo.module and lo < s and e < hi)
        ops = sorted((s, e, n) for n, s, e in (summary.ops[device] if device < len(summary.ops) else [])
                     if trace.opcode(n) not in trace.CONTAINERS)
        self.runs, self.run_s = len(runs), sum(e - s for s, e in runs)
        self.ops, self.unmatched = [], 0
        j = 0
        for s, e in runs:
            while j < len(ops) and ops[j][0] < s:
                j += 1
            while j < len(ops) and ops[j][0] < e:
                os_, oe, text = ops[j]
                j += 1
                m = trace.HLO.match(text)
                ins = hlo.instrs.get(m.group(1)) if m else None
                known = ins is not None and ins["shape"] == _result(text)
                self.unmatched += not known
                self.ops.append((hlo.scope_of(m.group(1)) if known else None, min(oe, e) - os_))
        self.matched = self.unmatched <= self.MISMATCH * len(self.ops)

    def ms(self, *names: str, minus: str | None = None) -> float | None:
        """Device time per run (ms) of the ops under any of the scopes
        ``names`` and not under ``minus``; None if no op of the window lies
        under one of ``names``."""
        t = [d for sc, d in self.ops if any(has_scope(sc, n) for n in names)
             and not (minus and has_scope(sc, minus))]
        if not self.runs or not self.matched or not t:
            return None
        return 1e3 * sum(t) / self.runs

    def op_ms(self) -> float:
        """Device time per run (ms) of all the program's ops."""
        return 1e3 * sum(d for _, d in self.ops) / self.runs if self.runs else 0.0

    def unclaimed_share(self) -> float | None:
        """Share of the program's op time that no scope claims."""
        total = sum(d for _, d in self.ops)
        if not total or not self.matched:
            return None
        return sum(d for sc, d in self.ops if sc is None) / total


# ----------------------------------------------- the cells' compiled programs


def _train_step_hlo(run) -> str:
    import jax

    from benchmarks.chip.drivers import train
    from repro.data.pipeline import SyntheticLM

    trainer, _, _ = train.build(run)
    state = jax.tree_util.tree_map(
        lambda a, s: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=s),
        jax.eval_shape(trainer.init_state), trainer.state_shardings)
    raw = next(iter(SyntheticLM(trainer.data_cfg, shard_id=0, n_shards=1)))
    batch = trainer.place_batch(raw)
    with trainer.mesh:
        return trainer.step_fn.lower(state, batch).compile().as_text()


def _decode_step_hlo(run) -> str:
    import jax
    import jax.numpy as jnp

    from benchmarks.chip import arch
    from repro.launch.mesh import make_host_mesh
    from repro.models import zoo
    from repro.runtime import spmd

    cell = run.cell
    tf = cell.traffic
    model = zoo.build(arch.arch_config(cell.workload["config"], cell.config),
                      dtype=arch.DTYPES[cell.config["torch_dtype"]])
    prefill, decode = spmd.build_serve_fns(model, make_host_mesh(run.devices[:1]), tf["max_len"])
    params = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    prompt = {"tokens": jax.ShapeDtypeStruct((tf["batch"], min(tf["prompt_len"]["buckets"])), jnp.int32)}
    _, cache = jax.eval_shape(prefill, params, prompt)
    tokens = {"tokens": jax.ShapeDtypeStruct((tf["batch"], 1), jnp.int32)}
    return decode.lower(params, cache, tokens).compile().as_text()


_PROGRAMS = {"train": _train_step_hlo, "decode": _decode_step_hlo}


def for_run(run, program: str) -> Attribution | None:
    """The attribution of ``program`` (``train``: the train step; ``decode``:
    the decode step) in the run's traced window, built once a run and kept
    in ``run.facts``; None where the program or its window cannot be read."""
    key = f"scopes.{program}"
    if key not in run.facts:
        a = None
        if run.summary is not None:
            try:
                a = Attribution(run.summary, Hlo(_PROGRAMS[program](run)))
            except Exception:  # a reader must not end the run: report and read nothing
                traceback.print_exc(file=sys.stderr)
            if a is not None:
                print(f"scopes.{program}: {a.runs} runs of {a.hlo.module}, "
                      f"{len(a.ops)} ops ({a.unmatched} unmatched), unclaimed share "
                      f"{a.unclaimed_share()}", file=sys.stderr)
        run.facts[key] = a
    return run.facts[key]


# ------------------------------------------------------------ host spans


def program_spans(path: str):
    """(name, start s, end s, arguments) of the program's host spans
    (``repro.*``) in the trace file ``path``, by start."""
    from jax.profiler import ProfileData

    out = []
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host"):
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith(SPAN_PREFIX):
                    out.append((e.name, e.start_ns * 1e-9, (e.start_ns + e.duration_ns) * 1e-9,
                                dict(e.stats)))
    return sorted(out, key=lambda sp: (sp[1], -sp[2]))
