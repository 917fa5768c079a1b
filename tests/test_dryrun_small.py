"""Dry-run machinery CI: exercises input_specs, lowering, compile, and the
collective parser on an 8-device host mesh in a SUBPROCESS (so the main
pytest process keeps one device)."""
import json
import os
import pathlib
import subprocess
import sys

import pytest

CHILD = r"""
import os
os.environ["XLA_FLAGS"] = ("--xla_force_host_platform_device_count=8"
                           " --xla_disable_hlo_passes=all-reduce-promotion")
import json
import jax, jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P
from repro.configs import registry
from repro.launch.dryrun import parse_collectives, _shaped
from repro.launch.mesh import make_mesh
from repro.models import zoo
from repro.optim.adamw import AdamWConfig
from repro.runtime import sharding as shd, spmd

mesh = make_mesh((4, 2), ("data", "model"))

for arch in ("qwen3-1.7b", "jamba-v0.1-52b", "xlstm-1.3b"):
    cfg = registry.get(arch, reduced=True)
    model = zoo.build(cfg, dtype=jnp.bfloat16)
    opt = AdamWConfig()
    step_fn, _, _ = spmd.build_train_step(model, opt, mesh)
    tpl = jax.eval_shape(lambda r: spmd.make_train_state(model, opt, r),
                         jax.random.PRNGKey(0))
    specs = spmd.state_specs(model, opt, mesh)
    structs = _shaped(tpl, mesh, specs)
    B, T = 8, 32
    batch = {
        k: jax.ShapeDtypeStruct((B, T), jnp.int32,
                                sharding=NamedSharding(mesh, P("data", None)))
        for k in ("tokens", "labels")
    }
    batch["loss_mask"] = jax.ShapeDtypeStruct(
        (B, T), jnp.float32, sharding=NamedSharding(mesh, P("data", None)))
    compiled = step_fn.lower(structs, batch).compile()
    cost = compiled.cost_analysis()
    coll = parse_collectives(compiled.as_text(), 8)
    assert cost.get("flops", 0) > 0, (arch, cost)
    assert coll["total_bytes"] > 0, (arch, "no collectives found")
    assert coll["counts"].get("all-reduce", 0) > 0
    # FSDP leaves must reduce-scatter, not all-reduce.
    assert coll["counts"].get("reduce-scatter", 0) > 0, (arch, coll["counts"])
    print(f"{arch}: OK flops={cost['flops']:.3g} coll={coll['total_bytes']:.3g}")

    # Serve path: decode against a 2k cache.
    p_tpl = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    p_specs = shd.tree_param_specs(p_tpl, mesh)
    p_structs = _shaped(p_tpl, mesh, p_specs)
    cache_tpl = jax.eval_shape(lambda: model.init_cache(8, 2048))
    c_specs = shd.tree_cache_specs(cache_tpl, mesh)
    c_structs = _shaped(cache_tpl, mesh, c_specs)
    dbatch = {"tokens": jax.ShapeDtypeStruct(
        (8, 1), jnp.int32, sharding=NamedSharding(mesh, P("data", None)))}
    dec = jax.jit(model.decode_step).lower(p_structs, c_structs, dbatch).compile()
    assert dec.cost_analysis().get("flops", 0) > 0
    print(f"{arch}: decode OK")

print("DRYRUN-SMALL-OK")
"""


@pytest.mark.slow  # subprocess XLA compile of 3 archs (train + decode), minutes
def test_dryrun_small_mesh():
    env = dict(os.environ)
    env["PYTHONPATH"] = str(pathlib.Path(__file__).parents[1] / "src")
    env.pop("XLA_FLAGS", None)
    res = subprocess.run([sys.executable, "-c", CHILD], capture_output=True,
                         text=True, env=env, timeout=900)
    assert res.returncode == 0, f"stdout:\n{res.stdout}\nstderr:\n{res.stderr[-3000:]}"
    assert "DRYRUN-SMALL-OK" in res.stdout


def test_collective_parser_units():
    from repro.launch.dryrun import parse_collectives

    hlo = """
  %ag = bf16[16,512]{1,0} all-gather(%x), replica_groups=[16,16]<=[256], dimensions={0}
  %ar = f32[1024]{0} all-reduce(%y), replica_groups=[16,16]<=[256], to_apply=%add
  %rs = bf16[4,128]{1,0} reduce-scatter(%z), replica_groups=[16,16]<=[256], dimensions={0}
  %cp = bf16[8,8]{1,0} collective-permute(%w), source_target_pairs={{0,1}}
"""
    out = parse_collectives(hlo, 256)
    assert out["counts"] == {"all-gather": 1, "all-reduce": 1,
                             "reduce-scatter": 1, "collective-permute": 1}
    ag = 16 * 512 * 2 * 15 / 16
    ar = 2 * 1024 * 4 * 15 / 16
    rs = 4 * 128 * 2 * 15
    cp = 8 * 8 * 2
    assert abs(out["total_bytes"] - (ag + ar + rs + cp)) < 1e-6
