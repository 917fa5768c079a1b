"""Bring-up smoke run of qwen3-1.7b on TPU, through the entry points a
user calls: ``Trainer``, ``build_serve_fns`` and ``ControlPlane`` (Fast Raft
commits the shard lease, the checkpoint and the model rollout), plus each
Pallas kernel compiled for the chip and checked against ``kernels/ref.py``.

  python chip_smoke.py               # one chip: kernels, train + resume, serve
  python chip_smoke.py --four-chips  # four chips: data-parallel train only

Widths are the published ones; weights are random from ``--seed``.
Training keeps 4 of the 28 layers (see ``train_reckoning``); serving runs
all 28. Lines before the last are bring-up readings (compile and steady
seconds, peak device memory), not benchmark numbers. A failed check
raises, so the script exits non-zero and never prints its last line,
``{"ok": true, "device": {...}}``. Without a TPU it exits 1.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import pathlib
import shutil
import sys
import tempfile
import time

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent / "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.configs.base import ArchConfig  # noqa: E402
from repro.configs.qwen3_1_7b import CONFIG  # noqa: E402
from repro.data.pipeline import SyntheticLM  # noqa: E402
from repro.kernels import ops, ref  # noqa: E402
from repro.launch import compile_cache  # noqa: E402
from repro.launch.mesh import make_host_mesh, make_mesh  # noqa: E402
from repro.models import zoo  # noqa: E402
from repro.optim.adamw import AdamWConfig  # noqa: E402
from repro.runtime import spmd  # noqa: E402
from repro.runtime.controlplane import ControlPlane  # noqa: E402
from repro.runtime.trainer import Trainer, TrainerConfig  # noqa: E402

# Sizes chosen with tools/chip_rehearse.py (XLA's memory analysis of the
# compiled train step for a described v5e), not by trial on the chip.
TRAIN_LAYERS = 4
TRAIN_BATCH, TRAIN_SEQ = 4, 1024
TRAIN_STEPS = 5
SERVE_BATCH, PROMPT_LEN, GEN_TOKENS, SERVE_MAX_LEN = 8, 512, 32, 1024
KERNEL_BATCH, KERNEL_SEQ, KERNEL_CACHE = 2, 2048, 4096
FOUR_CHIP_STEPS = 2

# Each kernel's output against its f32 oracle on the same bf16 inputs, as
# max|out - ref| / max|ref|. The kernels round p (and ds) to bf16 before
# their second matmul and store bf16, each a relative step of 2^-8; a few
# such steps stay under 2^-6. The backward sums 2048 rows of rounded ds.
KERNEL_TOL = {"flash_fwd": 2.0 ** -6, "flash_grad": 2.0 ** -5,
              "decode": 2.0 ** -6, "rmsnorm": 2.0 ** -6}

# Decode logits against one full forward pass over prompt + generated
# tokens, same bf16 params, as max|decode - forward| / max|forward|. The two
# paths run the same math with different shapes, so XLA rounds the bf16
# residual stream at different points in each of 28 layers (chunked
# attention with f32 blocks vs one f32 softmax); 2^-8 per layer-rounding
# over 28 layers, added in the worst case, is about 2^-3.2; allow 2^-4,
# which a wrong cache position or mask (an error of order 1) cannot meet.
SERVE_TOL = 2.0 ** -4

# Four data-parallel replicas against one device, same global batch, as
# |master_a - master_b| / |master_b - master_init| over all weights. bf16
# gradients summed in another order differ by about 2^-8 relative, and
# AdamW's first steps move each weight by about +-lr whatever the size of
# its gradient, so a weight whose gradient lies within that rounding of
# zero may step the other way (about 1% of them at REDUCED widths on the
# CPU, a ratio of 0.07-0.08). A different batch or a lost replica moves the
# update by order 1.
FOUR_CHIP_TOL = 0.25


def reading(msg: str) -> None:
    print(f"bring-up reading: {msg}", flush=True)


def peak_gb() -> float:
    stats = jax.devices()[0].memory_stats() or {}
    return stats.get("peak_bytes_in_use", float("nan")) / 1e9


def rel_err(out, want) -> float:
    """max|out - want| / max|want|, computed where the arrays live."""
    out = jnp.asarray(out, jnp.float32)
    want = jnp.asarray(want, jnp.float32)
    return float(jnp.max(jnp.abs(out - want))
                 / jnp.maximum(jnp.max(jnp.abs(want)), 1e-30))


def check(name: str, ok: bool, detail: str) -> None:
    if not ok:
        raise AssertionError(f"{name}: {detail}")


# ------------------------------------------------------------------ kernels


def _compile_and_run(fn, *args):
    """(outputs, lowered text, compile s, steady s) for one jitted call."""
    t0 = time.perf_counter()
    lowered = jax.jit(fn).lower(*args)
    compiled = lowered.compile()
    t_compile = time.perf_counter() - t0
    jax.block_until_ready(compiled(*args))
    t0 = time.perf_counter()
    out = jax.block_until_ready(compiled(*args))
    return out, lowered.as_text(), t_compile, time.perf_counter() - t0


def phase_kernels(arch: ArchConfig, *, batch: int, seq: int, cache_len: int,
                  seed: int = 0) -> None:
    """ops.flash_attention (forward and grad) at ``batch`` x ``seq``, and
    ops.decode_attention and ops.rmsnorm for ``SERVE_BATCH`` sequences
    against a ``cache_len`` cache, compiled at ``arch``'s widths."""
    a, B, T, S = arch, batch, seq, cache_len
    ks = jax.random.split(jax.random.PRNGKey(seed), 8)

    def normal(k, shape, dtype=jnp.bfloat16):
        return jax.random.normal(k, shape, jnp.float32).astype(dtype)

    q = normal(ks[0], (B, T, a.n_heads, a.head_dim))
    k = normal(ks[1], (B, T, a.n_kv_heads, a.head_dim))
    v = normal(ks[2], (B, T, a.n_kv_heads, a.head_dim))
    do = normal(ks[3], q.shape)
    q1 = normal(ks[4], (SERVE_BATCH, a.n_heads, a.head_dim))
    kc = normal(ks[5], (SERVE_BATCH, S, a.n_kv_heads, a.head_dim))
    vc = normal(ks[6], kc.shape)
    # Full, partial and single-entry caches, so some splits are all masked.
    kv_len = jnp.asarray(np.linspace(S, 1, SERVE_BATCH), jnp.int32)
    x = normal(ks[7], (SERVE_BATCH, PROMPT_LEN, a.d_model))
    scale = 1.0 + 0.1 * jax.random.normal(ks[0], (a.d_model,), jnp.float32)

    def grads(attn):
        def f(q, k, v, do):
            return jnp.sum(attn(q, k, v).astype(jnp.float32) * do.astype(jnp.float32))
        return jax.grad(f, argnums=(0, 1, 2))

    flash = lambda q, k, v: ops.flash_attention(q, k, v, True)  # noqa: E731
    cases = [
        ("flash_fwd", flash, ref.attention, (q, k, v)),
        ("flash_grad", grads(flash), grads(ref.attention), (q, k, v, do)),
        ("decode", ops.decode_attention, ref.decode_attention, (q1, kc, vc, kv_len)),
        ("rmsnorm", ops.rmsnorm, ref.rmsnorm, (x, scale)),
    ]
    for name, kernel, oracle, args in cases:
        out, text, t_c, t_s = _compile_and_run(kernel, *args)
        check(name, "tpu_custom_call" in text, "no Mosaic kernel in the lowering")
        # The oracle sees the same values, upcast, with exact f32 matmuls.
        args32 = [x.astype(jnp.float32) if x.dtype == jnp.bfloat16 else x for x in args]
        with jax.default_matmul_precision("highest"):
            want = jax.jit(oracle)(*args32)
        errs = [rel_err(o, w) for o, w in zip(jax.tree_util.tree_leaves(out),
                                              jax.tree_util.tree_leaves(want))]
        check(name, max(errs) <= KERNEL_TOL[name],
              f"rel err {errs} > {KERNEL_TOL[name]}")
        reading(f"kernel {name}: compile {t_c:.3f} s, steady {t_s * 1e3:.3f} ms, "
                f"rel err {max(errs):.3e} (tol {KERNEL_TOL[name]:.3e})")
    reading(f"after kernels: peak {peak_gb():.3f} GB")


# -------------------------------------------------------------------- train


def train_arch(layers: int = TRAIN_LAYERS) -> ArchConfig:
    """qwen3-1.7b with every width untouched and ``layers`` of its 28."""
    return dataclasses.replace(CONFIG, n_layers=layers)


def train_reckoning(arch: ArchConfig, batch: int, seq: int) -> str:
    """Device bytes the train step must hold, from the parameter count:
    bf16 params + f32 AdamW m, v and master = 14 B per parameter, plus the
    f32 embedding gradient and the f32 logits inside the loss."""
    embed = arch.vocab_size * arch.d_model
    per_layer = dataclasses.replace(arch, n_layers=1).param_count() - embed
    n = arch.param_count()
    return (f"L={arch.n_layers} batch={batch}x{seq}: {n / 1e6:.1f} M params "
            f"({per_layer / 1e6:.1f} M per layer, {embed / 1e6:.1f} M tied "
            f"embedding) x 14 B = {14 * n / 1e9:.2f} GB of state; f32 embedding "
            f"grad {4 * embed / 1e9:.2f} GB; f32 logits "
            f"{4 * batch * seq * arch.vocab_size / 1e9:.2f} GB")


def train_config(arch: ArchConfig, *, steps: int, batch: int, seq: int,
                 ckpt_dir=None, track: str = "fast", seed: int = 0) -> TrainerConfig:
    return TrainerConfig(
        arch=arch, steps=steps, global_batch=batch, seq_len=seq, seed=seed,
        dtype=jnp.bfloat16, track=track, ckpt_dir=ckpt_dir, keep_last=1,
        opt=AdamWConfig(lr=1e-4, warmup_steps=1, total_steps=100),
    )


def check_step_logs(logs, n_yes: int) -> None:
    for m in logs:
        check(f"train step {m['data_step']}",
              m["committed"] == 1.0 and m["n_yes"] == n_yes
              and np.isfinite(m["loss"]),
              f"committed={m['committed']} n_yes={m['n_yes']} loss={m['loss']}")


def phase_train(arch: ArchConfig, *, steps: int, batch: int, seq: int,
                ckpt_dir: str, control: ControlPlane, seed: int = 0) -> None:
    """``steps`` fast-track steps under the control plane's shard lease,
    then a checkpoint committed through Fast Raft."""
    trainer = Trainer(train_config(arch, steps=steps, batch=batch, seq=seq,
                                   ckpt_dir=ckpt_dir, seed=seed), control=control)
    t0 = time.perf_counter()
    logs = trainer.train()
    t_total = time.perf_counter() - t0
    check("train", len(logs) == steps, f"{len(logs)} steps logged")
    check_step_logs(logs, n_yes=1)
    check("lease", any(c.startswith("lease:") for c in control.applied),
          "no shard lease committed")
    check("checkpoint", any(c.startswith(f"ckpt:{steps}:") for c in control.applied),
          "no checkpoint committed")
    steady = [m["wall_ms"] for m in logs[1:]]
    reading(f"train {arch.n_layers} layers, batch {batch}x{seq}: first step "
            f"(compile + run) {logs[0]['wall_ms'] / 1e3:.3f} s, steady step "
            f"{np.mean(steady) / 1e3:.4f} s (mean of {len(steady)}), "
            f"train() incl. checkpoint {t_total:.3f} s, "
            f"loss {logs[0]['loss']:.4f} -> {logs[-1]['loss']:.4f}")
    reading(f"after train: peak {peak_gb():.3f} GB")


def phase_resume(arch: ArchConfig, *, steps: int, batch: int, seq: int,
                 ckpt_dir: str, control: ControlPlane, seed: int = 0) -> None:
    """A fresh Trainer on the same directory restores the committed step
    and takes one more."""
    trainer = Trainer(train_config(arch, steps=steps + 1, batch=batch, seq=seq,
                                   ckpt_dir=ckpt_dir, seed=seed), control=control)
    check("resume", trainer.ckpt.latest_step() == steps,
          f"latest committed step {trainer.ckpt.latest_step()} != {steps}")
    t0 = time.perf_counter()
    logs = trainer.train()
    check("resume", len(logs) == 1 and logs[0]["data_step"] == steps
          and logs[0]["step"] == steps + 1,
          f"resumed logs {[(m['data_step'], m['step']) for m in logs]}")
    check_step_logs(logs, n_yes=1)
    reading(f"resume from step {steps}: restore + 1 step + checkpoint "
            f"{time.perf_counter() - t0:.3f} s")


# -------------------------------------------------------------------- serve


def phase_serve(arch: ArchConfig, *, batch: int, prompt_len: int, gen: int,
                max_len: int, control: ControlPlane, seed: int = 0) -> float:
    """Commit the rollout, prefill, decode ``gen`` tokens greedily, and hold
    every decoded position's logits to one forward pass. Returns the error."""
    check("rollout", control.rollout(f"{arch.name}@v1"), "rollout not committed")
    model = zoo.build(arch)
    params = jax.jit(model.init)(jax.random.PRNGKey(seed))
    prefill_fn, decode_fn = spmd.build_serve_fns(model, make_host_mesh(), max_len)
    prompt = jax.random.randint(jax.random.PRNGKey(seed + 1), (batch, prompt_len),
                                1, arch.vocab_size, jnp.int32)

    t0 = time.perf_counter()
    logits, cache = jax.block_until_ready(prefill_fn(params, {"tokens": prompt}))
    t_prefill_first = time.perf_counter() - t0
    t0 = time.perf_counter()
    logits, cache = jax.block_until_ready(prefill_fn(params, {"tokens": prompt}))
    t_prefill = time.perf_counter() - t0

    outs, toks, t_decode = [logits], [], []
    for _ in range(gen - 1):
        toks.append(jnp.argmax(outs[-1], axis=-1).astype(jnp.int32)[:, None])
        t0 = time.perf_counter()
        logits, cache = jax.block_until_ready(
            decode_fn(params, cache, {"tokens": toks[-1]}))
        t_decode.append(time.perf_counter() - t0)
        outs.append(logits)

    full = jnp.concatenate([prompt] + toks, axis=1)        # prompt + gen-1
    want, _ = jax.jit(model.forward)(params, {"tokens": full})
    got = jnp.stack(outs, axis=1)                          # (B, gen, V)
    err = rel_err(got, want[:, prompt_len - 1:])
    check("serve", err <= SERVE_TOL,
          f"decode logits vs forward: rel err {err:.3e} > {SERVE_TOL:.3e}")
    reading(f"serve {arch.n_layers} layers, {batch}x{prompt_len} prompt, "
            f"{gen} tokens, max_len {max_len}: prefill first call {t_prefill_first:.3f} s, "
            f"steady {t_prefill:.4f} s; decode first call {t_decode[0]:.3f} s, "
            f"steady {np.mean(t_decode[1:]) * 1e3:.3f} ms/token "
            f"(mean of {len(t_decode) - 1}); logits rel err {err:.3e} "
            f"(tol {SERVE_TOL:.3e})")
    reading(f"after serve: peak {peak_gb():.3f} GB")
    return err


# --------------------------------------------------------------- four chips


def phase_four_chips(arch: ArchConfig, *, steps: int, batch: int, seq: int,
                     seed: int = 0) -> None:
    """The step barrier across four data-parallel replicas: every replica
    votes, fast and classic tracks commit the same update, and both agree
    with the same global batch on one device."""
    devices = jax.devices()
    check("four chips", len(devices) == 4, f"{len(devices)} devices")
    init, masters = None, {}
    for name, mesh, track, n_yes in (
        ("fast", make_mesh((4, 1), ("data", "model")), "fast", 4),
        ("classic", make_mesh((4, 1), ("data", "model")), "classic", 4),
        ("one device", make_host_mesh(devices[:1]), "fast", 1),
    ):
        trainer = Trainer(train_config(arch, steps=steps, batch=batch, seq=seq,
                                       track=track, seed=seed), mesh=mesh)
        if init is None:  # every run starts from this state (same seed)
            init = jax.device_get(trainer.init_state().opt.master)
        logs = trainer.train()
        check_step_logs(logs, n_yes=n_yes)
        if n_yes == 4:
            placed = trainer.place_batch(SyntheticLM(trainer.data_cfg).batch_at(0))
            for what, leaf in (("batch tokens", placed["tokens"]),
                               ("embedding master", trainer.state.opt.master["embed"]["tok"])):
                shards = leaf.addressable_shards
                devs = {sh.device for sh in shards}
                parts = {str(sh.index) for sh in shards}
                check(name, len(devs) == 4 and len(parts) == 4,
                      f"{what}: {len(parts)} distinct slices on {len(devs)} devices")
        masters[name] = jax.device_get(trainer.state.opt.master)
        reading(f"{name}: {steps} steps, first {logs[0]['wall_ms'] / 1e3:.3f} s, "
                f"last {logs[-1]['wall_ms'] / 1e3:.4f} s, n_yes {logs[-1]['n_yes']:.0f}, "
                f"peak {peak_gb():.3f} GB")
        del trainer

    def dist(a, b):
        return np.sqrt(sum(float(np.sum(np.square(x - y, dtype=np.float64)))
                           for x, y in zip(jax.tree_util.tree_leaves(a),
                                           jax.tree_util.tree_leaves(b))))

    for other in ("classic", "one device"):
        diff = dist(masters["fast"], masters[other])
        upd = dist(masters[other], init)
        check(f"fast vs {other}", diff / upd <= FOUR_CHIP_TOL,
              f"update differs by {diff / upd:.3e} > {FOUR_CHIP_TOL:.3e}")
        reading(f"fast vs {other}: |f32 master diff| / |update| = {diff / upd:.6e} "
                f"(tol {FOUR_CHIP_TOL:.3e}) after {steps} steps")


# --------------------------------------------------------------------- main


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the four-chip data-parallel phase")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU, found {dev.platform}", file=sys.stderr)
        return 1
    cache_dir = compile_cache.configure()
    reading(f"device {dev.device_kind} x{len(jax.devices())}, jax {jax.__version__}, "
            f"compile cache {cache_dir}")

    arch = train_arch()
    reading("train sizing: " + train_reckoning(arch, TRAIN_BATCH, TRAIN_SEQ))
    if args.four_chips:
        phase_four_chips(arch, steps=FOUR_CHIP_STEPS, batch=TRAIN_BATCH,
                         seq=TRAIN_SEQ, seed=args.seed)
    else:
        phase_kernels(CONFIG, batch=KERNEL_BATCH, seq=KERNEL_SEQ,
                      cache_len=KERNEL_CACHE, seed=args.seed)
        control = ControlPlane(n_nodes=3, seed=args.seed)
        ckpt_dir = tempfile.mkdtemp(prefix="chip_smoke_ckpt_")
        try:
            kw = dict(steps=TRAIN_STEPS, batch=TRAIN_BATCH, seq=TRAIN_SEQ,
                      ckpt_dir=ckpt_dir, control=control, seed=args.seed)
            phase_train(arch, **kw)
            phase_resume(arch, **kw)
        finally:
            shutil.rmtree(ckpt_dir, ignore_errors=True)
        phase_serve(CONFIG, batch=SERVE_BATCH, prompt_len=PROMPT_LEN,
                    gen=GEN_TOKENS, max_len=SERVE_MAX_LEN, control=control,
                    seed=args.seed)

    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
