"""Window loop of the training cells: ``Trainer.train()`` with the in-graph
Fast Raft commit barrier, the shard lease committed by a ``ControlPlane``.

Set-up builds one ``Trainer``, makes its initial state with the program's
own ``init_state()`` and drives that object from the seed through its first
steps with the window's own call (``train()``: the synthetic packed rows,
``place_batch``, the compiled step, the per-step metric sync): one step,
whose optimizer state gives the first gradient, then the rest of the
compared steps, whose state gives each leaf's change. The window is one more
``train()`` call of the same object, resuming at the next data step with
the state the set-up left, for as many steps as fill ``--seconds`` at the
set-up's step time. ``train()`` takes that state through
``restore_or_init``, the hook it resumes a checkpoint through, so that its
own re-initialisation (a fresh jit of ``init_state`` on every call, traced
and loaded anew) stays in set-up. ``train_tokens_per_s`` is the window's
trained tokens, all replicas, over the wall time of its ``train()`` call.
Then the program's state is freed and the reference follows the compared
steps from the seed.
"""
from __future__ import annotations

import gc
import math
import sys
import time

import jax

from benchmarks.chip import arch, compare, inputs, trace
from benchmarks.chip.reference import runs


def build(run):
    """The cell's Trainer, its control plane and its replica count."""
    from repro.launch.mesh import make_host_mesh, make_mesh
    from repro.optim.adamw import AdamWConfig
    from repro.runtime.controlplane import ControlPlane
    from repro.runtime.trainer import Trainer, TrainerConfig

    cell, seed = run.cell, inputs.seed32(run.seed)
    wl, tf = cell.workload, cell.traffic
    mesh_shape = tuple(wl["mesh"])
    mesh = (make_host_mesh(run.devices[:1]) if cell.chips == 1
            else make_mesh(mesh_shape, ("data", "model"), run.devices))
    control = ControlPlane(n_nodes=wl["control_plane_nodes"], seed=seed)
    tcfg = TrainerConfig(
        arch=arch.arch_config(wl["config"], cell.config), steps=1,
        global_batch=tf["global_batch"], seq_len=tf["seq_len"], seed=seed,
        dtype=arch.DTYPES[cell.config["torch_dtype"]], track=wl["track"], opt=AdamWConfig(**wl["optimizer"]))
    trainer = Trainer(tcfg, mesh=mesh, control=control)
    if trainer.data_cfg.mean_doc_len != tf["mean_doc_len"]:
        raise ValueError(f"the program packs documents of mean {trainer.data_cfg.mean_doc_len}, "
                         f"the traffic asks for {tf['mean_doc_len']}")
    return trainer, control, mesh_shape[0]


def _bad_steps(logs, replicas: int) -> int:
    return sum(1 for m in logs if not (m["committed"] == 1.0 and m["n_yes"] == replicas
                                       and math.isfinite(m["loss"])))


def report_steps(logs, wall: float, out) -> None:
    """The window's step times as ``train()`` logs them: where its wall time
    went beyond steps of the median length (a slow first step, stalls)."""
    ms = sorted(m["wall_ms"] for m in logs)
    med = ms[len(ms) // 2]
    print(f"window steps: {len(ms)}, wall {wall * 1e3:.1f} ms, first {logs[0]['wall_ms']:.1f}, "
          f"median {med:.2f}, max {ms[-1]:.1f}, beyond the median {wall * 1e3 - len(ms) * med:.1f} ms, "
          f"steps over 1.5x the median {sum(1 for v in ms if v > 1.5 * med)}", file=out)


def _resume(trainer, step: int, state) -> None:
    """Have the next ``train()`` start at data step ``step`` from ``state``."""
    trainer.restore_or_init = lambda: (step, state)


def setup(run):
    """Build the cell's Trainer and drive it through the compared steps.
    Returns the trainer (ready to resume at the next data step), its control
    plane, its replica count, the set-up's step logs and the program's side
    of the comparison: each compared step's loss, the first gradient's and
    each leaf's change's norms."""
    wl = run.cell.workload
    n_check = wl["check_steps"]
    trainer, control, replicas = build(run)
    # The first step: its optimizer state holds the first gradient as the
    # update took it (m = (1 - b1) g after one step). The initial weights
    # wait on the host for the change (the step donates the state; the
    # master starts as their f32 copy), so that the chip holds no copy of
    # them while it steps.
    state = trainer.init_state()
    p0 = jax.device_get(state.params)
    _resume(trainer, 0, state)
    del state
    trainer.cfg.steps = 1
    checked = trainer.train()
    grad = arch.leaf_norms(trainer.state.opt.m, scale=1.0 / (1.0 - wl["optimizer"]["b1"]))
    # The rest of the compared steps, then each leaf's change from the
    # initial weights.
    _resume(trainer, 1, trainer.state)
    trainer.cfg.steps = n_check
    checked += trainer.train()
    change = arch.leaf_norms(trainer.state.opt.master, p0)
    del p0
    prog = {"losses": [m["loss"] for m in checked], "grad": grad, "change": change}
    return trainer, control, replicas, checked, prog


def reference(run, replicas: int, lowp: bool = False, fault=None) -> dict:
    wl = run.cell.workload
    return runs.train(run.cell.config, wl["optimizer"], run.cell.traffic, inputs.seed32(run.seed),
                      replicas, wl["check_steps"], lowp=lowp, fault=fault)


def run(run):
    tf = run.cell.traffic
    n_check = run.cell.workload["check_steps"]
    trainer, control, replicas, checked, prog = setup(run)
    # the quickest set-up step: the first of each train() call also waits
    # for the data thread's first batch
    step_s = min(m["wall_ms"] for m in checked[1:]) / 1e3
    n = max(1, round(run.seconds / step_s))
    _resume(trainer, n_check, trainer.state)
    trainer.state = None
    trainer.cfg.steps = n_check + n
    tokens = n * tf["global_batch"] * tf["seq_len"]
    gc.collect()

    run.setup_done()
    with trace.Window(run):
        t0 = time.perf_counter()
        logs = trainer.train()
        wall = time.perf_counter() - t0
    run.read_memory_peak()
    report_steps(logs, wall, sys.stderr)
    run.attempted, run.failed = len(logs), _bad_steps(logs, replicas)
    run.e2e["train_tokens_per_s"] = tokens / wall
    bad_setup = _bad_steps(checked, replicas)
    lease = any(c.startswith("lease:") for c in control.applied)
    del trainer
    gc.collect()

    ref = reference(run, replicas)
    compare.report_worst(prog, ref, sys.stderr)
    nums = compare.train_numbers(prog, ref)
    lim = run.cell.workload["limits"]
    run.checks = {name: compare.check(nums[name], lim[name]) for name in ("loss", "grad", "change")}
    run.checks["bad_steps"] = compare.check(bad_setup + run.failed, 0)
    run.checks["no_lease"] = compare.check(0 if lease else 1, 0)
