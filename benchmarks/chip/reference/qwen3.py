"""Plain float32 Qwen3 in ``jax.numpy``: forward, loss, gradient and AdamW
step, written from the published architecture and apart from the program.

Architecture (Qwen3 dense, Hugging Face ``Qwen3ForCausalLM``): token
embedding; per layer, pre-RMSNorm grouped-query attention with a per-head
RMSNorm on q and k before RoPE (rotate-half, base ``rope_theta``), then
pre-RMSNorm SwiGLU MLP, each with a residual; final RMSNorm; output head tied
to the embedding. Every product is float32 at ``Precision.HIGHEST``.

``lowp=True`` is the control: the same computation with every product in
float8, the step below the bf16 the configurations state, as float8
training does it: each operand rounded to e4m3 with one scale a tensor, and
in the backward pass each incoming gradient rounded to e5m2 with its own
scale. Its readings have to fail the comparison.

Weights are trees in the layout ``inputs.params`` makes. Nothing here holds
a whole batch's activations: training runs one row at a time and sums the
gradients, serving one sequence at a time.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST


def _scaled_round(x, dtype):
    """x rounded to ``dtype`` with one scale for the tensor, back in f32."""
    s = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / float(jnp.finfo(dtype).max)
    return (x / s).astype(dtype).astype(jnp.float32) * s


@jax.custom_vjp
def _round8(x):
    return _scaled_round(x, jnp.float8_e4m3fn)


def _round8_fwd(x):
    return _round8(x), None


def _round8_bwd(_, g):
    return (_scaled_round(g, jnp.float8_e5m2),)


_round8.defvjp(_round8_fwd, _round8_bwd)


def _ein(spec, a, b, lowp):
    if lowp:
        a, b = _round8(a), _round8(b)
    return jnp.einsum(spec, a, b, precision=HIGHEST)


def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def _rope(x, theta):
    """x: (T, H, D); positions 0..T-1, rotate-half convention."""
    T, _, D = x.shape
    inv = 1.0 / (theta ** (jnp.arange(0, D, 2, dtype=jnp.float32) / D))
    ang = jnp.arange(T, dtype=jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., : D // 2], x[..., D // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def _layer(cfg, x, p, lowp):
    """One decoder layer over one sequence x: (T, d)."""
    T = x.shape[0]
    H, Hkv, D = cfg["num_attention_heads"], cfg["num_key_value_heads"], cfg["head_dim"]
    eps = cfg["rms_norm_eps"]
    a = p["mixer"]
    h = _rms(x, p["norm1"]["scale"], eps)
    q = _ein("td,de->te", h, a["wq"], lowp).reshape(T, H, D)
    k = _ein("td,de->te", h, a["wk"], lowp).reshape(T, Hkv, D)
    v = _ein("td,de->te", h, a["wv"], lowp).reshape(T, Hkv, D)
    q = _rope(_rms(q, a["q_norm"], eps), cfg["rope_theta"])
    k = _rope(_rms(k, a["k_norm"], eps), cfg["rope_theta"])
    k = jnp.repeat(k, H // Hkv, axis=1)          # query head h reads kv head h // (H/Hkv)
    v = jnp.repeat(v, H // Hkv, axis=1)
    s = _ein("qhd,khd->hqk", q, k, lowp) / math.sqrt(D)
    causal = jnp.arange(T)[:, None] >= jnp.arange(T)[None, :]
    s = jnp.where(causal[None], s, -jnp.inf)
    o = _ein("hqk,khd->qhd", jax.nn.softmax(s, axis=-1), v, lowp).reshape(T, H * D)
    x = x + _ein("te,ed->td", o, a["wo"], lowp)
    f = p["ffn"]
    h = _rms(x, p["norm2"]["scale"], eps)
    g = _ein("td,df->tf", h, f["w_gate"], lowp)
    u = _ein("td,df->tf", h, f["w_up"], lowp)
    return x + _ein("tf,fd->td", jax.nn.silu(g) * u, f["w_down"], lowp)


def hidden(cfg, params, tokens, lowp=False):
    """Final-normed hidden states (T, d) of one sequence of token ids."""
    x = params["embed"]["tok"].astype(jnp.float32)[tokens]

    def body(x, p):
        p = jax.tree_util.tree_map(lambda w: w.astype(jnp.float32), p)
        return _layer(cfg, x, p["b0"], lowp), None

    x, _ = jax.lax.scan(body, x, params["stack"])
    return _rms(x, params["final_norm"]["scale"].astype(jnp.float32), cfg["rms_norm_eps"])


def logits(cfg, params, h, lowp=False):
    return _ein("td,vd->tv", h, params["embed"]["tok"].astype(jnp.float32), lowp)


# ------------------------------------------------------------------ training


def _row_nll(cfg, params, tokens, labels, mask, lowp):
    """Sum over one row of the masked next-token negative log-likelihood."""
    lg = logits(cfg, params, hidden(cfg, params, tokens, lowp), lowp)
    lp = jax.nn.log_softmax(lg, axis=-1)
    nll = -jnp.take_along_axis(lp, labels[:, None], axis=-1)[:, 0]
    return jnp.sum(nll * mask)


@functools.partial(jax.jit, static_argnums=(0, 3, 4))
def loss_and_grad(cfg_items, params, batch, replicas: int, lowp: bool):
    """Loss and gradient of a global batch split over ``replicas`` data-
    parallel replicas: each replica's loss is the mean over its unmasked
    tokens, and the step's loss the mean of the replicas'. Rows run one at a
    time; their gradients are summed with each row's weight."""
    cfg = dict(cfg_items)
    B = batch["tokens"].shape[0]
    per = B // replicas
    msum = batch["loss_mask"].reshape(replicas, per, -1).sum(axis=(1, 2))
    w = jnp.repeat(1.0 / (replicas * jnp.maximum(msum, 1.0)), per)
    f = jax.value_and_grad(lambda p, t, l, m: _row_nll(cfg, p, t, l, m, lowp))

    def body(acc, row):
        t, l, m, wr = row
        val, g = f(params, t, l, m)
        loss, gsum = acc
        return (loss + wr * val, jax.tree_util.tree_map(lambda a, b: a + wr * b, gsum, g)), None

    zeros = jax.tree_util.tree_map(jnp.zeros_like, params)
    (loss, grads), _ = jax.lax.scan(
        body, (jnp.zeros((), jnp.float32), zeros),
        (batch["tokens"], batch["labels"], batch["loss_mask"], w))
    return loss, grads


def lr_at(opt: dict, step: int) -> float:
    """Linear warm-up then cosine to ``min_lr_ratio`` of ``lr``; ``step`` counts
    from 1."""
    warm = min(step / max(opt["warmup_steps"], 1), 1.0)
    t = min(max((step - opt["warmup_steps"]) / max(opt["total_steps"] - opt["warmup_steps"], 1), 0.0), 1.0)
    return opt["lr"] * warm * (opt["min_lr_ratio"] + (1 - opt["min_lr_ratio"]) * 0.5 * (1 + math.cos(math.pi * t)))


@functools.partial(jax.jit, donate_argnums=(0, 1, 2, 3))
def _adamw(params, m, v, grads, hyper):
    """AdamW with global-norm clipping and decoupled weight decay on every
    leaf. Returns new (params, m, v) and the gradient as the update used it
    (after clipping)."""
    lr, b1, b2, eps, wd, clip, c1, c2 = [hyper[i] for i in range(8)]
    leaves = jax.tree_util.tree_leaves(grads)
    gnorm = jnp.sqrt(sum(jnp.sum(g * g) for g in leaves))
    scale = jnp.minimum(1.0, clip / jnp.maximum(gnorm, 1e-9))
    g = jax.tree_util.tree_map(lambda x: x * scale, grads)
    m = jax.tree_util.tree_map(lambda a, b: b1 * a + (1 - b1) * b, m, g)
    v = jax.tree_util.tree_map(lambda a, b: b2 * a + (1 - b2) * b * b, v, g)
    params = jax.tree_util.tree_map(
        lambda p, a, b: p - lr * ((a / c1) / (jnp.sqrt(b / c2) + eps) + wd * p), params, m, v)
    return params, m, v, g


def adamw_step(opt: dict, step: int, params, m, v, grads):
    hyper = jnp.asarray([lr_at(opt, step), opt["b1"], opt["b2"], opt["eps"],
                         opt["weight_decay"], opt["clip_norm"],
                         1 - opt["b1"] ** step, 1 - opt["b2"] ** step], jnp.float32)
    return _adamw(params, m, v, grads, hyper)


# ------------------------------------------------------------------- serving


@functools.partial(jax.jit, static_argnums=(0, 3, 4))
def served_logits(cfg_items, params, seq, first: int, lowp: bool):
    """Logits (n, V) at positions ``first`` .. end of one sequence ``seq``
    (prompt followed by served tokens but the last)."""
    cfg = dict(cfg_items)
    h = hidden(cfg, params, seq, lowp)
    return logits(cfg, params, h[first:], lowp)
