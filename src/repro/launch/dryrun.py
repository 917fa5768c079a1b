"""Multi-pod dry-run: lower + compile every (arch x shape) cell on the
production mesh and extract its cost terms from the compiled artifact.

  PYTHONPATH=src python -m repro.launch.dryrun --arch qwen3-4b \
      --shape train_4k --mesh single
  PYTHONPATH=src python -m repro.launch.dryrun --all --mesh both

Per cell this produces artifacts/dryrun/<mesh>/<arch>__<shape>.json with:
  memory_analysis, cost_analysis (per-device HLO FLOPs/bytes), the summed
  collective-bytes table parsed from the post-SPMD HLO, and timing, for
  reading by hand: these are projections from the compiler, not chip
  measurements (those are benchmarks/chip's).

Success of this script for every cell on BOTH meshes is the multi-pod
dry-run deliverable: it proves the sharding config is coherent (no
mismatched specs, no OOM-at-compile, no unsupported collective).
"""
import os

if __name__ == "__main__":
    # Set only when run as a script, before jax is imported (jax locks the
    # device count on first init); importing this module leaves the
    # caller's XLA_FLAGS alone.
    os.environ["XLA_FLAGS"] = (
        "--xla_force_host_platform_device_count=512"
        # XLA:CPU-only workaround: the all-reduce-promotion pass CHECK-crashes
        # cloning reducers that layout assignment gave a copy root (our fused
        # psum tuples). Promotion is a CPU numerics nicety; TPU lowers the
        # same HLO without it. See DESIGN.md §Notes.
        " --xla_disable_hlo_passes=all-reduce-promotion"
    )

import argparse
import json
import re
import time
import traceback
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs import registry
from repro.configs.shapes import SHAPES, ShapeConfig, applicable
from repro.launch.mesh import make_production_mesh
from repro.models import zoo
from repro.optim.adamw import AdamWConfig
from repro.runtime import sharding as shd
from repro.runtime import spmd

ARTIFACT_DIR = os.path.join("artifacts", "dryrun")

# ---------------------------------------------------------------- input specs


def input_specs(arch: str, shape_name: str, mesh) -> Dict[str, Any]:
    """ShapeDtypeStruct stand-ins (weak-type-correct, shardable, no device
    allocation) for every model input of this (arch, shape) cell."""
    cfg = registry.get(arch)
    shape = SHAPES[shape_name]
    B, S = shape.global_batch, shape.seq_len

    def struct(shp, dtype, spec):
        return jax.ShapeDtypeStruct(shp, dtype, sharding=NamedSharding(mesh, spec))

    baxes = shd.batch_axes(mesh)
    bspec = shd.batch_spec("tokens", (B, S), mesh)
    b0 = bspec[0]

    if shape.kind == "train":
        batch = {}
        if cfg.frontend is not None:
            batch["embeddings"] = struct((B, S, cfg.d_model), jnp.bfloat16,
                                         P(b0, None, None))
        else:
            batch["tokens"] = struct((B, S), jnp.int32, P(b0, None))
        batch["labels"] = struct((B, S), jnp.int32, P(b0, None))
        batch["loss_mask"] = struct((B, S), jnp.float32, P(b0, None))
        return batch
    if shape.kind == "prefill":
        if cfg.frontend is not None:
            return {"embeddings": struct((B, S, cfg.d_model), jnp.bfloat16,
                                         P(b0, None, None))}
        return {"tokens": struct((B, S), jnp.int32, P(b0, None))}
    # decode: one new token against a cache of length S.
    db = shd.batch_spec("tokens", (B, 1), mesh)[0]
    return {"tokens": struct((B, 1), jnp.int32, P(db, None))}


def _shaped(tree, mesh, spec_tree):
    return jax.tree_util.tree_map(
        lambda leaf, spec: jax.ShapeDtypeStruct(
            leaf.shape, leaf.dtype, sharding=NamedSharding(mesh, spec)
        ),
        tree, spec_tree,
        is_leaf=lambda x: hasattr(x, "shape") and not isinstance(x, P),
    )


# --------------------------------------------------------- collective parsing

_COLL_RE = re.compile(
    r"(all-gather|all-reduce|reduce-scatter|all-to-all|collective-permute)"
    r"(?:-start)?(?:\.\d+)?\s*=?\s*"
)
_SHAPE_RE = re.compile(r"=\s*\(?([a-z0-9]+)\[([0-9,]*)\]")
# Two textual formats: iota form `replica_groups=[G,S]<=[N]` (group size S)
# and explicit lists `replica_groups={{0,16,...},{1,17,...}}`.
_GROUPS_IOTA_RE = re.compile(r"replica_groups=\[(\d+),(\d+)\]")
_GROUPS_LIST_RE = re.compile(r"replica_groups=\{\{([0-9, ]+)\}")

_DTYPE_BYTES = {
    "f64": 8, "f32": 4, "bf16": 2, "f16": 2, "s64": 8, "u64": 8,
    "s32": 4, "u32": 4, "s16": 2, "u16": 2, "s8": 1, "u8": 1, "pred": 1,
    "f8e4m3fn": 1, "f8e5m2": 1,
}


def parse_collectives(hlo_text: str, n_devices: int) -> Dict[str, Any]:
    """Sum per-device bytes moved by every collective in the post-SPMD HLO.

    Ring estimates per op (result shape R bytes, group size G):
      all-gather          R * (G-1)/G      (received)
      all-reduce          2R * (G-1)/G     (reduce-scatter + all-gather)
      reduce-scatter      R * (G-1)       (input is R*G, receives (G-1) shards)
      all-to-all          R * (G-1)/G
      collective-permute  R
    """
    totals: Dict[str, float] = {}
    counts: Dict[str, int] = {}
    ops = []
    for line in hlo_text.splitlines():
        m = _COLL_RE.search(line)
        if not m or "= " not in line:
            continue
        kind = m.group(1)
        if f" {kind}(" not in line and f"{kind}-start(" not in line:
            continue
        sm = _SHAPE_RE.search(line)
        if not sm:
            continue
        dtype, dims = sm.group(1), sm.group(2)
        if dtype not in _DTYPE_BYTES:
            continue
        size = _DTYPE_BYTES[dtype]
        for d in dims.split(","):
            if d:
                size *= int(d)
        gm = _GROUPS_IOTA_RE.search(line)
        if gm:
            G = int(gm.group(2))
        else:
            gl = _GROUPS_LIST_RE.search(line)
            G = len(gl.group(1).split(",")) if gl else n_devices
        if kind == "all-gather":
            moved = size * (G - 1) / max(G, 1)
        elif kind == "all-reduce":
            moved = 2 * size * (G - 1) / max(G, 1)
        elif kind == "reduce-scatter":
            moved = size * (G - 1)
        elif kind == "all-to-all":
            moved = size * (G - 1) / max(G, 1)
        else:
            moved = size
        totals[kind] = totals.get(kind, 0.0) + moved
        counts[kind] = counts.get(kind, 0) + 1
        ops.append({"kind": kind, "result_bytes": size, "group": G, "moved": moved})
    biggest = sorted(ops, key=lambda o: -o["moved"])[:12]
    return {
        "bytes_by_kind": totals,
        "counts": counts,
        "total_bytes": float(sum(totals.values())),
        "n_ops": len(ops),
        "biggest_ops": biggest,
    }


# --------------------------------------------------------------- cell runner


def lower_cell(arch: str, shape_name: str, mesh, mesh_name: str,
               fsdp_stream: bool = True) -> Dict[str, Any]:
    cfg = registry.get(arch)
    shape = SHAPES[shape_name]
    model = zoo.build(cfg, dtype=jnp.bfloat16)
    t0 = time.time()

    if shape.kind == "train":
        opt_cfg = AdamWConfig()
        step_fn, state_shardings, _ = spmd.build_train_step(
            model, opt_cfg, mesh, track="fast", donate=True,
            fsdp_stream=fsdp_stream,
        )
        state_tpl = jax.eval_shape(
            lambda rng: spmd.make_train_state(model, opt_cfg, rng, False),
            jax.random.PRNGKey(0),
        )
        specs = spmd.state_specs(model, opt_cfg, mesh, False)
        state_structs = _shaped(state_tpl, mesh, specs)
        batch = input_specs(arch, shape_name, mesh)
        lowered = step_fn.lower(state_structs, batch)
    else:
        p_tpl = jax.eval_shape(model.init, jax.random.PRNGKey(0))
        # Serving cells: TP-only parameter shardings (no FSDP gathers).
        p_specs = shd.tree_param_specs(p_tpl, mesh, fsdp=False)
        p_structs = _shaped(p_tpl, mesh, p_specs)
        batch = input_specs(arch, shape_name, mesh)
        if shape.kind == "prefill":
            fn = jax.jit(lambda p, b: model.prefill(p, b, shape.seq_len))
            lowered = fn.lower(p_structs, batch)
        else:  # decode
            cache_tpl = jax.eval_shape(
                lambda: model.init_cache(shape.global_batch, shape.seq_len)
            )
            c_specs = shd.tree_cache_specs(cache_tpl, mesh)
            c_structs = _shaped(cache_tpl, mesh, c_specs)
            fn = jax.jit(model.decode_step, donate_argnums=(1,))
            lowered = fn.lower(p_structs, c_structs, batch)

    t_lower = time.time() - t0
    t0 = time.time()
    compiled = lowered.compile()
    t_compile = time.time() - t0

    # --- extract analyses
    try:
        mem = compiled.memory_analysis()
        mem_out = {
            k: int(getattr(mem, k))
            for k in dir(mem)
            if k.endswith("_bytes") or k.endswith("size_in_bytes")
            if isinstance(getattr(mem, k, None), (int, np.integer))
        } if mem is not None else {}
    except Exception as e:  # platform-dependent
        mem_out = {"error": str(e)}
    try:
        cost = compiled.cost_analysis() or {}
        cost_out = {k: float(v) for k, v in cost.items()
                    if isinstance(v, (int, float, np.floating)) and np.isfinite(float(v))}
    except Exception as e:
        cost_out = {"error": str(e)}

    t0 = time.time()
    hlo = compiled.as_text()
    coll = parse_collectives(hlo, n_devices=mesh.devices.size)
    from repro.launch import hlo_analysis
    deep = hlo_analysis.analyze(hlo, n_devices=mesh.devices.size)
    deep.pop("biggest_collectives", None)
    t_parse = time.time() - t0

    return {
        "arch": arch,
        "shape": shape_name,
        "mesh": mesh_name,
        "n_devices": int(mesh.devices.size),
        "kind": shape.kind,
        "seq_len": shape.seq_len,
        "global_batch": shape.global_batch,
        "param_count": cfg.param_count(),
        "active_param_count": cfg.active_param_count(),
        "timings_s": {"lower": t_lower, "compile": t_compile, "parse": t_parse},
        "memory_analysis": mem_out,
        "cost_analysis": cost_out,
        "collectives": coll,
        "hlo_analysis": deep,  # trip-count-aware (scan bodies x trips)
        "hlo_bytes": len(hlo),
    }


def run_cell(arch: str, shape_name: str, mesh_name: str,
             force: bool = False, fsdp_stream: bool = True,
             artifact_dir: Optional[str] = None) -> Optional[Dict[str, Any]]:
    cfg = registry.get(arch)
    shape = SHAPES[shape_name]
    outdir = os.path.join(artifact_dir or ARTIFACT_DIR, mesh_name)
    os.makedirs(outdir, exist_ok=True)
    path = os.path.join(outdir, f"{arch}__{shape_name}.json")
    if os.path.exists(path) and not force:
        with open(path) as f:
            return json.load(f)
    if not applicable(cfg, shape):
        result = {
            "arch": arch, "shape": shape_name, "mesh": mesh_name,
            "skipped": f"{shape_name} requires sub-quadratic decode; "
                       f"{arch} is full-attention (see DESIGN.md)",
        }
        with open(path, "w") as f:
            json.dump(result, f, indent=1)
        return result
    mesh = make_production_mesh(multi_pod=(mesh_name == "multi"))
    print(f"[dryrun] {mesh_name}/{arch}/{shape_name}: lowering...", flush=True)
    try:
        result = lower_cell(arch, shape_name, mesh, mesh_name,
                            fsdp_stream=fsdp_stream)
        print(
            f"[dryrun] {mesh_name}/{arch}/{shape_name}: OK "
            f"compile={result['timings_s']['compile']:.1f}s "
            f"flops={result['cost_analysis'].get('flops', -1):.3g} "
            f"coll={result['collectives']['total_bytes']:.3g}B",
            flush=True,
        )
    except Exception as e:
        result = {
            "arch": arch, "shape": shape_name, "mesh": mesh_name,
            "error": f"{type(e).__name__}: {e}",
            "traceback": traceback.format_exc()[-4000:],
        }
        print(f"[dryrun] {mesh_name}/{arch}/{shape_name}: FAIL {e}", flush=True)
    with open(path, "w") as f:
        json.dump(result, f, indent=1)
    return result


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=registry.list_archs())
    ap.add_argument("--shape", choices=list(SHAPES))
    ap.add_argument("--mesh", choices=["single", "multi", "both"], default="single")
    ap.add_argument("--all", action="store_true", help="every (arch x shape)")
    ap.add_argument("--force", action="store_true")
    ap.add_argument("--no-stream", action="store_true",
                    help="fsdp_stream=False baseline (whole-tree gather)")
    ap.add_argument("--out", default=None, help="artifact dir override")
    args = ap.parse_args()

    meshes = ["single", "multi"] if args.mesh == "both" else [args.mesh]
    cells = []
    if args.all:
        for a in registry.list_archs():
            for s in SHAPES:
                cells.append((a, s))
    else:
        assert args.arch and args.shape, "--arch/--shape or --all"
        cells = [(args.arch, args.shape)]

    failures = 0
    for mesh_name in meshes:
        for arch, shape_name in cells:
            r = run_cell(arch, shape_name, mesh_name, force=args.force,
                         fsdp_stream=not args.no_stream, artifact_dir=args.out)
            if r and "error" in r:
                failures += 1
    print(f"[dryrun] done; failures={failures}")
    return 1 if failures else 0


if __name__ == "__main__":
    raise SystemExit(main())
