"""Shared neural layers: norms, RoPE, GQA attention (train/prefill/decode),
and dense FFNs. Pure functions over parameter pytrees; no framework.

Conventions:
  x:      (B, T, d_model) activations, compute dtype bf16 by default
  params: nested dicts of jnp arrays
  cache:  {"k": (B, S, Hkv, Dh), "v": (B, S, Hkv, Dh)} per attention layer,
          stacked over the layer groups as (G, B, S, Hkv, Dh)
Softmax/norm statistics are computed in fp32 regardless of compute dtype.
"""
from __future__ import annotations

import dataclasses
import functools
import math
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig
from repro.runtime.tracing import scope

Params = Dict[str, Any]


# ---------------------------------------------------------------- init utils


def _dense_init(rng, in_dim: int, out_dim: int, dtype) -> jax.Array:
    scale = 1.0 / math.sqrt(in_dim)
    return (jax.random.normal(rng, (in_dim, out_dim), jnp.float32) * scale).astype(dtype)


def _embed_init(rng, vocab: int, dim: int, dtype) -> jax.Array:
    return (jax.random.normal(rng, (vocab, dim), jnp.float32) * 0.02).astype(dtype)


# --------------------------------------------------------------------- norms


def init_norm(cfg: ArchConfig, dim: int, dtype=jnp.float32) -> Params:
    p = {"scale": jnp.ones((dim,), dtype)}
    if cfg.norm == "layernorm":
        p["bias"] = jnp.zeros((dim,), dtype)
    return p


def apply_norm(cfg: ArchConfig, p: Params, x: jax.Array, eps: float = 1e-6) -> jax.Array:
    xf = x.astype(jnp.float32)
    if cfg.norm == "layernorm":
        mu = jnp.mean(xf, axis=-1, keepdims=True)
        var = jnp.var(xf, axis=-1, keepdims=True)
        y = (xf - mu) * jax.lax.rsqrt(var + eps)
        y = y * p["scale"].astype(jnp.float32) + p["bias"].astype(jnp.float32)
    else:  # rmsnorm
        ms = jnp.mean(jnp.square(xf), axis=-1, keepdims=True)
        y = xf * jax.lax.rsqrt(ms + eps) * p["scale"].astype(jnp.float32)
    return y.astype(x.dtype)


def rms_head_norm(x: jax.Array, scale: jax.Array, eps: float = 1e-6) -> jax.Array:
    """Per-head RMS norm over the head dim (Qwen3 qk_norm)."""
    xf = x.astype(jnp.float32)
    ms = jnp.mean(jnp.square(xf), axis=-1, keepdims=True)
    return (xf * jax.lax.rsqrt(ms + eps) * scale.astype(jnp.float32)).astype(x.dtype)


# ---------------------------------------------------------------------- RoPE


def rope_freqs(head_dim: int, theta: float) -> jax.Array:
    return 1.0 / (theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim))


def apply_rope(x: jax.Array, positions: jax.Array, theta: float) -> jax.Array:
    """x: (B, T, H, Dh); positions: (B, T) or (T,)."""
    dh = x.shape[-1]
    freqs = rope_freqs(dh, theta)  # (Dh/2,)
    if positions.ndim == 1:
        positions = positions[None, :]
    angles = positions[..., None].astype(jnp.float32) * freqs  # (B, T, Dh/2)
    cos = jnp.cos(angles)[:, :, None, :]
    sin = jnp.sin(angles)[:, :, None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


# ----------------------------------------------------------------- attention


def init_attention(cfg: ArchConfig, rng, dtype=jnp.bfloat16) -> Params:
    r = jax.random.split(rng, 5)
    p: Params = {
        "wq": _dense_init(r[0], cfg.d_model, cfg.q_dim, dtype),
        "wk": _dense_init(r[1], cfg.d_model, cfg.kv_dim, dtype),
        "wv": _dense_init(r[2], cfg.d_model, cfg.kv_dim, dtype),
        "wo": _dense_init(r[3], cfg.q_dim, cfg.d_model, dtype),
    }
    if cfg.qkv_bias:
        p["bq"] = jnp.zeros((cfg.q_dim,), dtype)
        p["bk"] = jnp.zeros((cfg.kv_dim,), dtype)
        p["bv"] = jnp.zeros((cfg.kv_dim,), dtype)
    if cfg.qk_norm:
        p["q_norm"] = jnp.ones((cfg.head_dim,), jnp.float32)
        p["k_norm"] = jnp.ones((cfg.head_dim,), jnp.float32)
    return p


def _project_qkv(cfg: ArchConfig, p: Params, x: jax.Array, positions: jax.Array):
    B, T, _ = x.shape
    q = x @ p["wq"]
    k = x @ p["wk"]
    v = x @ p["wv"]
    if cfg.qkv_bias:
        q, k, v = q + p["bq"], k + p["bk"], v + p["bv"]
    q = q.reshape(B, T, cfg.n_heads, cfg.head_dim)
    k = k.reshape(B, T, cfg.n_kv_heads, cfg.head_dim)
    v = v.reshape(B, T, cfg.n_kv_heads, cfg.head_dim)
    if cfg.qk_norm:
        q = rms_head_norm(q, p["q_norm"])
        k = rms_head_norm(k, p["k_norm"])
    if cfg.pos == "rope":
        q = apply_rope(q, positions, cfg.rope_theta)
        k = apply_rope(k, positions, cfg.rope_theta)
    return q, k, v


def _sdpa(q, k, v, *, causal: bool, q_offset: int | jax.Array = 0,
          kv_len: Optional[jax.Array] = None) -> jax.Array:
    """Reference scaled-dot-product attention with GQA.

    q: (B, Tq, Hq, Dh); k, v: (B, Tk, Hkv, Dh). fp32 softmax.
    kv_len: optional (B,) valid-length mask for cached decode.
    """
    B, Tq, Hq, Dh = q.shape
    Tk, Hkv = k.shape[1], k.shape[2]
    group = Hq // Hkv
    qf = q.astype(jnp.float32) / math.sqrt(Dh)
    kf = k.astype(jnp.float32)
    vf = v.astype(jnp.float32)
    qf = qf.reshape(B, Tq, Hkv, group, Dh)
    scores = jnp.einsum("bqhgd,bkhd->bhgqk", qf, kf)
    neg = jnp.asarray(-1e30, jnp.float32)
    if causal:
        off = jnp.asarray(q_offset)
        off = jnp.broadcast_to(off.reshape(-1), (B,))  # per-batch offset
        qpos = jnp.arange(Tq)[None, :] + off[:, None]  # (B, Tq)
        kpos = jnp.arange(Tk)
        mask = qpos[:, :, None] >= kpos[None, None, :]  # (B, Tq, Tk)
        scores = jnp.where(mask[:, None, None], scores, neg)
    if kv_len is not None:
        valid = jnp.arange(Tk)[None, :] < kv_len[:, None]  # (B, Tk)
        scores = jnp.where(valid[:, None, None, None], scores, neg)
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bhgqk,bkhd->bqhgd", probs, vf)
    return out.reshape(B, Tq, Hq, Dh).astype(q.dtype)


def _chunked_attention(
    q, k, v, *, causal: bool, q_offset=None, kv_len=None,
    blk_q: int = 512, blk_k: int = 1024, layer=None,
) -> jax.Array:
    """Flash-style attention in pure jnp: double lax.scan with online
    softmax, fp32 accumulators, O(blk_q * blk_k) live scores. This is the
    memory- and FLOP-shape the Pallas kernel has on TPU, expressed portably —
    the dry-run lowers this, so compile-time memory analysis reflects the
    production tiling. Wrapped in remat(nothing_saveable): the backward
    recomputes tiles exactly like the flash backward kernel.

    With ``layer``, k and v are stacked caches (G, B, Tk, Hkv, D) and each
    key block is sliced from group ``layer`` of the stack inside the loop,
    so no copy of the layer's cache is made. The loop (scope ``kv_blocks``)
    then stops after the last block a query of the chunk can see, by
    ``kv_len`` and, when causal, the diagonal: every score of a later block
    is masked, so it would leave m, l and acc bit for bit as they are.

    With one query a row (decode), the (b, h)-batched products would have
    XLA lay the whole cache out anew for them, a copy in and one out on
    every step. So each key row (position, kv head) is read as stored and
    multiplied with every query head, and a score row keeps its own head's
    entries: Hkv times the FLOPs, which decode hardly has."""
    B, Tq, Hq, D = q.shape
    Tk, Hkv = k.shape[-3], k.shape[-2]
    G = Hq // Hkv
    bq = min(blk_q, Tq)
    bk = min(blk_k, Tk)
    assert Tq % bq == 0 and Tk % bk == 0
    nq, nk = Tq // bq, Tk // bk
    scale = 1.0 / math.sqrt(D)
    if q_offset is None:
        q_offset = jnp.zeros((B,), jnp.int32)
    q_offset = jnp.broadcast_to(jnp.asarray(q_offset, jnp.int32).reshape(-1), (B,))

    qf = (q.astype(jnp.float32) * scale).reshape(B, nq, bq, Hkv, G, D)
    if layer is None:
        kf = k.astype(jnp.float32).reshape(B, nk, bk, Hkv, D)
        vf = v.astype(jnp.float32).reshape(B, nk, bk, Hkv, D)

        def key_block(args):
            return args
    else:
        def key_block(ki):
            def blk(c):
                at = (layer, 0, ki * bk, 0, 0)
                return jax.lax.dynamic_slice(c, at, (1, B, bk, Hkv, D))[0].astype(jnp.float32)
            return ki, blk(k), blk(v)

    if bq == 1:
        # Scores (B, Hkv, G, 1, bk * Hkv): column n is key row (n // Hkv, n % Hkv).
        def key_pos(ki):
            return ki * bk + jnp.arange(bk * Hkv) // Hkv

        own = jnp.arange(Hkv)[:, None] == jnp.arange(bk * Hkv)[None, :] % Hkv
        own = own[None, :, None, None, :]

        def scores(q_blk, k_blk):
            s = jnp.einsum("bcd,bnd->bcn", q_blk.reshape(B, Hkv * G, D),
                           k_blk.reshape(B, bk * Hkv, D))
            return jnp.where(own, s.reshape(B, Hkv, G, 1, bk * Hkv), -1e30)

        def mix(p, v_blk):
            pv = jnp.einsum("bcn,bnd->bcd", p.reshape(B, Hkv * G, bk * Hkv),
                            v_blk.reshape(B, bk * Hkv, D))
            return pv.reshape(B, Hkv, G, 1, D)
    else:
        def key_pos(ki):
            return ki * bk + jnp.arange(bk)

        def scores(q_blk, k_blk):
            return jnp.einsum("bqhgd,bkhd->bhgqk", q_blk, k_blk)

        def mix(p, v_blk):
            return jnp.einsum("bhgqk,bkhd->bhgqd", p, v_blk)

    def q_chunk(qi, q_blk):
        # q_blk: (B, bq, Hkv, G, D)
        qpos = q_offset[:, None] + qi * bq + jnp.arange(bq)[None, :]  # (B,bq)

        def k_chunk(carry, args):
            ki, k_blk, v_blk = key_block(args)
            m, l, acc = carry
            s = scores(q_blk, k_blk)
            kpos = key_pos(ki)  # the key position of each score column
            neg = jnp.asarray(-1e30, jnp.float32)
            if causal:
                msk = qpos[:, :, None] >= kpos[None, None, :]  # (B,bq,bk)
                s = jnp.where(msk[:, None, None], s, neg)      # (B,1,1,bq,bk)
            if kv_len is not None:
                valid = kpos[None, :] < kv_len[:, None]  # (B,bk)
                s = jnp.where(valid[:, None, None, None, :], s, neg)
            m_new = jnp.maximum(m, jnp.max(s, axis=-1))
            p = jnp.exp(s - m_new[..., None])
            alpha = jnp.exp(m - m_new)
            l_new = alpha * l + jnp.sum(p, axis=-1)
            acc_new = acc * alpha[..., None] + mix(p, v_blk)
            return (m_new, l_new, acc_new), None

        m0 = jnp.full((B, Hkv, G, bq), -1e30, jnp.float32)
        l0 = jnp.zeros((B, Hkv, G, bq), jnp.float32)
        a0 = jnp.zeros((B, Hkv, G, bq, D), jnp.float32)
        if layer is None:
            (m, l, acc), _ = jax.lax.scan(
                k_chunk, (m0, l0, a0), (jnp.arange(nk), kf.swapaxes(0, 1), vf.swapaxes(0, 1)))
        else:
            end = jnp.int32(Tk)                  # one past the last key the chunk sees
            if kv_len is not None:
                end = jnp.minimum(end, jnp.max(kv_len))
            if causal:
                end = jnp.minimum(end, jnp.max(q_offset) + (qi + 1) * bq)
            with scope("kv_blocks"):
                m, l, acc = jax.lax.fori_loop(0, (end + bk - 1) // bk,
                                              lambda ki, c: k_chunk(c, ki)[0], (m0, l0, a0))
        out = acc / jnp.maximum(l, 1e-30)[..., None]          # (B,Hkv,G,bq,D)
        return out.transpose(0, 3, 1, 2, 4)                   # (B,bq,Hkv,G,D)

    outs = jax.lax.map(lambda args: q_chunk(*args),
                       (jnp.arange(nq), qf.swapaxes(0, 1)))   # (nq,B,bq,Hkv,G,D)
    out = outs.swapaxes(0, 1).reshape(B, Tq, Hq, D)
    return out.astype(q.dtype)


@functools.lru_cache(maxsize=None)
def _chunked_remat(causal: bool, has_kvlen: bool, blk_q: int, blk_k: int):
    """Static-config wrapper (jax.checkpoint traces kwargs, so bools must be
    closed over, not passed)."""

    def f(q, k, v, q_offset, kv_len):
        return _chunked_attention(
            q, k, v, causal=causal, q_offset=q_offset,
            kv_len=kv_len if has_kvlen else None, blk_q=blk_q, blk_k=blk_k,
        )

    return jax.checkpoint(
        f, policy=jax.checkpoint_policies.nothing_saveable, prevent_cse=False
    )


def chunked_attention(q, k, v, *, causal, q_offset=None, kv_len=None,
                      blk_q=512, blk_k=1024, layer=None):
    """Tiled attention; with ``layer``, over group ``layer`` of the stacked
    caches k, v (a cached step: nothing to differentiate, so no remat)."""
    if layer is not None:
        return _chunked_attention(q, k, v, causal=causal, q_offset=q_offset,
                                  kv_len=kv_len, blk_q=blk_q, blk_k=blk_k, layer=layer)
    B = q.shape[0]
    qo = (jnp.zeros((B,), jnp.int32) if q_offset is None
          else jnp.broadcast_to(jnp.asarray(q_offset, jnp.int32).reshape(-1), (B,)))
    kl = (jnp.zeros((B,), jnp.int32) if kv_len is None
          else jnp.asarray(kv_len, jnp.int32))
    f = _chunked_remat(bool(causal), kv_len is not None, blk_q, blk_k)
    return f(q, k, v, qo, kl)


CHUNKED_ATTN_THRESHOLD = 1024  # use tiled path at/above this many kv tokens


def _write_tokens(stack: jax.Array, new: jax.Array, layer: jax.Array,
                  cache_pos: jax.Array) -> jax.Array:
    """Write the T new rows ``new`` (B, T, Hkv, Dh) into layer ``layer`` of
    the stacked cache (G, B, S, Hkv, Dh) at slot ``cache_pos`` (scalar) or
    ``cache_pos[b]`` (per row), in place."""
    if jnp.ndim(cache_pos) == 0:
        return jax.lax.dynamic_update_slice(stack, new[None], (layer, 0, cache_pos, 0, 0))
    B, T = new.shape[:2]
    slots = cache_pos[:, None] + jnp.arange(T)[None, :]
    return stack.at[layer, jnp.arange(B)[:, None], slots].set(new)


@scope("attention")
def attention(
    cfg: ArchConfig,
    p: Params,
    x: jax.Array,
    *,
    positions: Optional[jax.Array] = None,
    cache: Optional[Params] = None,
    cache_pos: Optional[jax.Array] = None,
    layer: Optional[jax.Array] = None,
    learned_pos_table: Optional[jax.Array] = None,
) -> Tuple[jax.Array, Optional[Params]]:
    """Full attention: training when cache is None; else a cached step of T
    tokens (prefill or decode) at slot cache_pos ((B,) or scalar).

    The cache is the whole stack, {"k", "v"}: (G, B, S, Hkv, Dh), carried
    through the layer scan. The step writes only its T new K/V rows into
    layer ``layer`` of it, in place (scope ``kv_update``), then attends over
    that layer's slice of the updated stack, and returns the stack."""
    B, T, _ = x.shape
    if positions is None:
        if cache is None:
            positions = jnp.arange(T)[None, :].repeat(B, 0)
        else:
            cp = jnp.broadcast_to(
                jnp.asarray(cache_pos, jnp.int32).reshape(-1), (B,)
            )
            positions = cp[:, None] + jnp.arange(T)[None, :]
    q, k, v = _project_qkv(cfg, p, x, positions)

    if cache is None:
        if cfg.use_flash:
            from repro.kernels import ops as kops

            out = kops.flash_attention(q, k, v, causal=True)
        elif T >= CHUNKED_ATTN_THRESHOLD:
            out = chunked_attention(q, k, v, causal=True)
        else:
            out = _sdpa(q, k, v, causal=True)
        new_cache = None
    else:
        cache_pos = jnp.asarray(cache_pos, jnp.int32)
        idx = jnp.broadcast_to(cache_pos.reshape(-1), (B,))
        with scope("kv_update"):
            new_cache = {"k": _write_tokens(cache["k"], k, layer, cache_pos),
                         "v": _write_tokens(cache["v"], v, layer, cache_pos)}
        # Causal over the cache: query t (global position idx+t) sees keys
        # [0, idx+t]; kv_len hides never-written slots.
        if new_cache["k"].shape[2] >= CHUNKED_ATTN_THRESHOLD:
            out = chunked_attention(
                q, new_cache["k"], new_cache["v"], causal=True, q_offset=idx,
                kv_len=idx + T, blk_q=min(512, T), blk_k=1024, layer=layer,
            )
        else:
            k_cache, v_cache = (jax.lax.dynamic_index_in_dim(new_cache[n], layer, keepdims=False)
                                for n in ("k", "v"))
            out = _sdpa(q, k_cache, v_cache, causal=True, q_offset=idx, kv_len=idx + T)

    y = out.reshape(B, T, cfg.q_dim) @ p["wo"]
    return y, new_cache


def init_attn_cache(cfg: ArchConfig, batch: int, max_len: int, dtype=jnp.bfloat16) -> Params:
    shape = (batch, max_len, cfg.n_kv_heads, cfg.head_dim)
    return {"k": jnp.zeros(shape, dtype), "v": jnp.zeros(shape, dtype)}


# ----------------------------------------------------------------------- FFN


def init_ffn(cfg: ArchConfig, rng, dtype=jnp.bfloat16) -> Params:
    r = jax.random.split(rng, 3)
    if cfg.activation == "swiglu":
        return {
            "w_gate": _dense_init(r[0], cfg.d_model, cfg.d_ff, dtype),
            "w_up": _dense_init(r[1], cfg.d_model, cfg.d_ff, dtype),
            "w_down": _dense_init(r[2], cfg.d_ff, cfg.d_model, dtype),
        }
    return {
        "w_up": _dense_init(r[0], cfg.d_model, cfg.d_ff, dtype),
        "b_up": jnp.zeros((cfg.d_ff,), dtype),
        "w_down": _dense_init(r[1], cfg.d_ff, cfg.d_model, dtype),
        "b_down": jnp.zeros((cfg.d_model,), dtype),
    }


@scope("ffn")
def apply_ffn(cfg: ArchConfig, p: Params, x: jax.Array) -> jax.Array:
    if cfg.activation == "swiglu":
        return (jax.nn.silu(x @ p["w_gate"]) * (x @ p["w_up"])) @ p["w_down"]
    h = jax.nn.gelu(x @ p["w_up"] + p["b_up"])
    return h @ p["w_down"] + p["b_down"]


# ----------------------------------------------------------------- embedding


def embed_lookup(table: jax.Array, tokens: jax.Array) -> jax.Array:
    """table[tokens] with an explicit f32 scatter-add backward.

    Two reasons this is not a plain gather: (1) fp32 gradient accumulation
    into the (large, shared) embedding table regardless of compute dtype;
    (2) the autodiff transpose-of-gather emits a copy-rooted scatter
    reduction whose bf16 all-reduce XLA:CPU's AllReducePromotion pass cannot
    clone (hard CHECK crash) — the explicit formulation lowers cleanly on
    every backend and shards identically (vocab-parallel)."""
    return _embed_lookup(tuple(table.shape), jnp.dtype(table.dtype).name,
                         table, tokens)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1))
def _embed_lookup(shape, dtype_name, table, tokens):
    return table[tokens]


def _embed_lookup_fwd(shape, dtype_name, table, tokens):
    return table[tokens], tokens


def _embed_lookup_bwd(shape, dtype_name, tokens, dout):
    flat_tok = tokens.reshape(-1)
    flat_dout = dout.reshape(-1, shape[-1]).astype(jnp.float32)
    dtable = jnp.zeros(shape, jnp.float32).at[flat_tok].add(flat_dout)
    return dtable.astype(dtype_name), None


_embed_lookup.defvjp(_embed_lookup_fwd, _embed_lookup_bwd)


def init_embedding(cfg: ArchConfig, rng, dtype=jnp.bfloat16) -> Params:
    r = jax.random.split(rng, 3)
    p: Params = {"tok": _embed_init(r[0], cfg.vocab_size, cfg.d_model, dtype)}
    if not cfg.tie_embeddings:
        p["head"] = _dense_init(r[1], cfg.d_model, cfg.vocab_size, dtype)
    if cfg.pos == "learned":
        p["pos"] = _embed_init(r[2], cfg.max_seq_len, cfg.d_model, dtype)
    return p
