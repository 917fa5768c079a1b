"""Serving launcher: batched prefill + decode with consensus-coordinated
model-version rollout.

  PYTHONPATH=src python -m repro.launch.serve --arch qwen3-1.7b --reduced \
      --batch 4 --prompt-len 32 --gen 16
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import registry
from repro.launch import compile_cache
from repro.launch.mesh import make_host_mesh
from repro.models import zoo
from repro.runtime import spmd
from repro.runtime.controlplane import ControlPlane


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True, choices=registry.list_archs())
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--gen", type=int, default=16)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()
    compile_cache.configure()

    cfg = registry.get(args.arch, reduced=args.reduced)
    mesh = make_host_mesh()
    model = zoo.build(cfg)
    params = model.init(jax.random.PRNGKey(args.seed))
    max_len = args.prompt_len + args.gen
    prefill_fn, decode_fn = spmd.build_serve_fns(model, mesh, max_len)

    control = ControlPlane(n_nodes=3, seed=args.seed)
    assert control.rollout(f"{cfg.name}@v1"), "rollout not committed"
    print(f"serving {cfg.name}@v1 (rollout committed via Fast Raft)")

    rng = np.random.RandomState(args.seed)
    if cfg.frontend is not None:
        prompt = {"embeddings": jnp.asarray(
            rng.randn(args.batch, args.prompt_len, cfg.d_model), jnp.float32)}
    else:
        prompt = {"tokens": jnp.asarray(
            rng.randint(0, cfg.vocab_size, (args.batch, args.prompt_len)), jnp.int32)}

    t0 = time.perf_counter()
    logits, cache = prefill_fn(params, prompt)
    logits.block_until_ready()
    t_prefill = time.perf_counter() - t0

    tokens = jnp.argmax(logits, axis=-1)[:, None]
    outs = [tokens]
    t0 = time.perf_counter()
    for _ in range(args.gen - 1):
        logits, cache = decode_fn(params, cache, {"tokens": tokens})
        tokens = jnp.argmax(logits, axis=-1)[:, None]
        outs.append(tokens)
    jax.block_until_ready(outs[-1])
    t_decode = time.perf_counter() - t0

    gen = np.concatenate([np.asarray(t) for t in outs], axis=1)
    print(f"prefill: {args.batch}x{args.prompt_len} tok in {t_prefill*1e3:.1f} ms")
    print(f"decode:  {args.gen-1} steps x {args.batch} seqs in {t_decode*1e3:.1f} ms "
          f"({(args.gen-1)*args.batch/max(t_decode,1e-9):.1f} tok/s)")
    print("sample generations (token ids):")
    for row in gen[: min(2, args.batch)]:
        print("  ", row.tolist())
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
