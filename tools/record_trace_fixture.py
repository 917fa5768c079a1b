"""Record the scoped trace fixture of ``tests/chip_bench``: a few train steps
of ``Trainer.train()`` and a few decode steps of ``build_serve_fns``, at the
REDUCED Qwen3 widths in bf16, traced by the profiler under ``bench.window``,
with the HLO of the two compiled programs beside the trace.

  python tools/record_trace_fixture.py --out DIR            # on the chip
  python tools/record_trace_fixture.py --strip SRC DST      # then on the CPU

Run it on the chip, from the repository's root. It writes
``fixture_scoped.xplane.pb`` and ``fixture_scoped.hlo.gz`` (each program's
instructions with their results, operands, called computations and
``op_name``; nothing else of the HLO) to ``--out``, and prints the host
spans of each train step and the device time each scope takes. The same
steps traced with the profiler's defaults, as the benchmark traces them
(Python calls, and each program's HLO in the metadata plane), go to
``defaults.xplane.pb`` beside them, for reading by hand. ``--strip`` cuts
a trace to what ``trace.Summary`` and ``scopes.program_spans`` read: the
device's op, async-op and module lines with each op's name, and the host's
``bench.*`` and ``repro.*`` spans; it needs TensorFlow's XPlane protobuf
module, installed beside the profiler plugin.
"""
from __future__ import annotations

import argparse
import glob
import gzip
import os
import pathlib
import shutil
import sys
import tempfile

ROOT = pathlib.Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from benchmarks.chip import scopes, trace  # noqa: E402
from repro.configs import registry  # noqa: E402
from repro.data.pipeline import SyntheticLM  # noqa: E402
from repro.models import zoo  # noqa: E402
from repro.runtime import spmd  # noqa: E402
from repro.runtime.trainer import Trainer, TrainerConfig  # noqa: E402

STEPS, BATCH, SEQ, MAX_LEN = 2, 2, 64, 64


def strip_trace(src: str, dst: str) -> None:
    """Write to ``dst`` the trace ``src`` cut as ``--strip`` says."""
    from tensorflow.tsl.profiler.protobuf import xplane_pb2

    space = xplane_pb2.XSpace()
    space.ParseFromString(pathlib.Path(src).read_bytes())
    keep = []
    for plane in space.planes:
        device = bool(trace.DEVICE_PLANE.match(plane.name))
        if not device and not plane.name.startswith("/host:CPU"):
            continue
        names = {k: m.name for k, m in plane.event_metadata.items()}
        lines = []
        for line in plane.lines:
            if device and line.name not in (trace.OPS_LINE, trace.ASYNC_LINE, trace.MODULES_LINE):
                continue
            events = [e for e in line.events
                      if device or names[e.metadata_id].startswith(("bench.", scopes.SPAN_PREFIX))]
            if not events:
                continue
            for e in events:
                if device:
                    del e.stats[:]
            del line.events[:]
            line.events.extend(events)
            lines.append(line)
        used = {e.metadata_id for line in lines for e in line.events}
        for k in list(plane.event_metadata):
            if k not in used:
                del plane.event_metadata[k]
            else:
                m = plane.event_metadata[k]
                del m.stats[:]
                m.display_name = ""
        if device:
            plane.stat_metadata.clear()
        del plane.stats[:]
        del plane.lines[:]
        plane.lines.extend(lines)
        keep.append(plane)
    del space.planes[:]
    space.planes.extend(keep)
    pathlib.Path(dst).write_bytes(space.SerializeToString())


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--out", help="directory for the recorded files")
    ap.add_argument("--strip", nargs=2, metavar=("SRC", "DST"))
    args = ap.parse_args()
    if args.strip:
        strip_trace(*args.strip)
        return 0
    if not args.out:
        ap.error("--out is required to record")
    out = pathlib.Path(args.out)
    out.mkdir(parents=True, exist_ok=True)

    arch = registry.get("qwen3-1.7b", reduced=True)
    trainer = Trainer(TrainerConfig(arch=arch, steps=STEPS, global_batch=BATCH, seq_len=SEQ,
                                    dtype=jnp.bfloat16))
    trainer.restore_or_init = lambda: (0, trainer.init_state())
    trainer.train()                                   # compiles the step

    model = zoo.build(arch, dtype=jnp.bfloat16)
    prefill, decode = spmd.build_serve_fns(model, trainer.mesh, MAX_LEN)
    params = jax.jit(model.init)(jax.random.PRNGKey(0))
    tok = {"tokens": jnp.ones((BATCH, 1), jnp.int32)}
    _, cache = prefill(params, {"tokens": jnp.ones((BATCH, 16), jnp.int32)})
    _, cache = decode(params, cache, tok)             # compiles decode

    def window(path, cache, options=None):
        """Trace the steps into ``path`` (the profiler's defaults, or
        ``options``); returns the decode cache."""
        state = trainer.init_state()
        trainer.restore_or_init = lambda: (0, state)
        np.asarray(jax.tree_util.tree_leaves(cache)[0])
        tmp = tempfile.mkdtemp()
        jax.profiler.start_trace(tmp, profiler_options=options)
        with jax.profiler.TraceAnnotation(trace.WINDOW):
            trainer.train()
            for _ in range(STEPS):
                logits, cache = decode(params, cache, tok)
                np.asarray(logits)
        jax.profiler.stop_trace()
        shutil.copy(sorted(glob.glob(os.path.join(tmp, "plugins", "profile", "*", "*.xplane.pb")))[-1], path)
        shutil.rmtree(tmp, ignore_errors=True)
        return cache

    # with the profiler's defaults, as the benchmark traces (kept out of the
    # fixture: the metadata plane then holds each program's HLO)
    cache = window(out / "defaults.xplane.pb", cache)
    # the fixture: no Python calls and no HLO in the trace; the HLO follows
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level, options.enable_hlo_proto = 0, False
    cache = window(out / "fixture_scoped.xplane.pb", cache, options)

    batch = trainer.place_batch(next(iter(SyntheticLM(trainer.data_cfg, shard_id=0, n_shards=1))))
    with trainer.mesh:
        train_hlo = trainer.step_fn.lower(trainer.state, batch).compile().as_text()
    decode_hlo = decode.lower(params, cache, tok).compile().as_text()
    with gzip.open(out / "fixture_scoped.hlo.gz", "wt") as f:
        f.write(scopes.Hlo(train_hlo).stripped() + scopes.Hlo(decode_hlo).stripped())

    spans = scopes.program_spans(str(out / "fixture_scoped.xplane.pb"))
    for name, s, e, stats in spans:
        print(f"span {name} {1e3 * (e - s):.3f} ms {stats}")
    summary = trace.Summary.from_file(str(out / "fixture_scoped.xplane.pb"), 1)
    for text in (train_hlo, decode_hlo):
        a = scopes.Attribution(summary, scopes.Hlo(text))
        print(f"{a.hlo.module}: {a.runs} runs, {len(a.ops)} ops, matched {a.matched}, "
              f"op {a.op_ms():.3f} ms a run, unclaimed {a.unclaimed_share()}, "
              + ", ".join(f"{n} {a.ms(n)}" for n in scopes.SCOPES))
    for p in sorted(out.iterdir()):
        print(p.name, p.stat().st_size, "bytes")
    return 0


if __name__ == "__main__":
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    raise SystemExit(main())
