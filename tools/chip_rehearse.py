"""Size ``chip_smoke.py`` without a chip: compile its train step and its
serve functions for a described TPU v5e and print XLA's memory analysis.

  JAX_PLATFORMS=cpu PYTHONPATH=src python tools/chip_rehearse.py \
      [--layers 4] [--batch 4] [--seq 1024] [--chips 1|4]

Nothing runs and nothing is allocated: every input is a shape with a
sharding on the described devices. A program that does not fit the chip's
16 GB is refused here as it would be there. Compiles of the full widths
take tens of seconds on the CPU and about a GB of host memory.
"""
from __future__ import annotations

import argparse
import os
import pathlib
import sys
import time

os.environ.setdefault("TPU_LOG_DIR", "disabled")
ROOT = pathlib.Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
from jax.sharding import NamedSharding  # noqa: E402

import chip_smoke  # noqa: E402
from repro.configs.qwen3_1_7b import CONFIG  # noqa: E402
from repro.launch.dryrun import _shaped  # noqa: E402
from repro.launch.mesh import make_mesh  # noqa: E402
from repro.models import zoo  # noqa: E402
from repro.runtime import sharding as shd  # noqa: E402
from repro.runtime import spmd  # noqa: E402


def report(name: str, compiled, seconds: float) -> None:
    m = compiled.memory_analysis()
    total = (m.argument_size_in_bytes + m.output_size_in_bytes
             + m.temp_size_in_bytes - m.alias_size_in_bytes)
    print(f"{name}: compile {seconds:.1f} s; per device: arguments "
          f"{m.argument_size_in_bytes / 1e9:.3f} GB, outputs "
          f"{m.output_size_in_bytes / 1e9:.3f} GB (aliased "
          f"{m.alias_size_in_bytes / 1e9:.3f}), temporaries "
          f"{m.temp_size_in_bytes / 1e9:.3f} GB -> {total / 1e9:.3f} GB",
          flush=True)


def rehearse_train(mesh, layers: int, batch: int, seq: int) -> None:
    arch = chip_smoke.train_arch(layers)
    print("reckoning:", chip_smoke.train_reckoning(arch, batch, seq))
    cfg = chip_smoke.train_config(arch, steps=1, batch=batch, seq=seq)
    model = zoo.build(arch, dtype=cfg.dtype)
    step_fn, _, batch_shard_fn = spmd.build_train_step(model, cfg.opt, mesh)
    tpl = jax.eval_shape(lambda r: spmd.make_train_state(model, cfg.opt, r),
                         jax.random.PRNGKey(0))
    state = _shaped(tpl, mesh, spmd.state_specs(model, cfg.opt, mesh))
    shapes = {"tokens": ((batch, seq), jnp.int32),
              "labels": ((batch, seq), jnp.int32),
              "loss_mask": ((batch, seq), jnp.float32)}
    tpl_b = {k: jax.ShapeDtypeStruct(s, d) for k, (s, d) in shapes.items()}
    shardings = batch_shard_fn(tpl_b)
    b = {k: jax.ShapeDtypeStruct(s, d, sharding=shardings[k])
         for k, (s, d) in shapes.items()}
    t0 = time.perf_counter()
    compiled = step_fn.lower(state, b).compile()
    report(f"train step L={layers} batch={batch}x{seq}", compiled,
           time.perf_counter() - t0)


def rehearse_serve(mesh) -> None:
    model = zoo.build(CONFIG)
    max_len, B, P = chip_smoke.SERVE_MAX_LEN, chip_smoke.SERVE_BATCH, chip_smoke.PROMPT_LEN
    prefill_fn, decode_fn = spmd.build_serve_fns(model, mesh, max_len)
    p_tpl = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    params = _shaped(p_tpl, mesh, shd.tree_param_specs(p_tpl, mesh, fsdp=False))
    one = NamedSharding(mesh, jax.sharding.PartitionSpec())
    tok = lambda t: {"tokens": jax.ShapeDtypeStruct((B, t), jnp.int32, sharding=one)}  # noqa: E731
    t0 = time.perf_counter()
    report(f"prefill {B}x{P} into max_len {max_len}",
           prefill_fn.lower(params, tok(P)).compile(), time.perf_counter() - t0)
    c_tpl = jax.eval_shape(lambda: model.init_cache(B, max_len))
    cache = _shaped(c_tpl, mesh, shd.tree_cache_specs(c_tpl, mesh))
    t0 = time.perf_counter()
    report("decode one token", decode_fn.lower(params, cache, tok(1)).compile(),
           time.perf_counter() - t0)


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--layers", type=int, default=chip_smoke.TRAIN_LAYERS)
    ap.add_argument("--batch", type=int, default=chip_smoke.TRAIN_BATCH)
    ap.add_argument("--seq", type=int, default=chip_smoke.TRAIN_SEQ)
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    args = ap.parse_args()

    from jax.experimental import topologies

    topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    devices = topo.devices[: args.chips]
    rehearse_train(make_mesh((args.chips, 1), ("data", "model"), devices),
                   args.layers, args.batch, args.seq)
    if args.chips == 1:
        rehearse_serve(make_mesh((1, 1), ("data", "model"), devices))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
