"""Flash-decoding for TPU (Pallas): single-token attention against a long
KV cache, split-K style.

The sequence axis of the cache is split across the inner grid dimension;
each split produces a partial (acc, m, l) in fp32, written per split, and
the splits are merged with a logsumexp combine in the jit'd wrapper (the
merge is O(splits * D) — negligible). This mirrors flash-decoding on GPU but
tiles for VMEM: each split streams blk_s cache rows through VMEM while the
(H, D) query block stays resident.

The cache is read in its own (B, S, H, D) layout: every block's last two
dims are the full (H, D), which Mosaic accepts at any head count. Scores
and the weighted sum are elementwise products reduced on the VPU (one
query row per head leaves the MXU nothing to fill). ``kv_len`` rides in
SMEM through scalar prefetch.

Layouts: q (B, H, D); k, v (B, S, H, D); kv_len (B,) valid lengths.
Outputs: acc (B, nsplit, H, D) fp32, m/l (B, nsplit, H, 1) fp32.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30
_SQ = pl.squeezed


def _decode_kernel(kvlen_ref, q_ref, k_ref, v_ref, acc_ref, m_ref, l_ref,
                   *, scale, blk_s):
    b = pl.program_id(0)
    si = pl.program_id(1)
    q = q_ref[...].astype(jnp.float32) * scale         # (H, D)
    k = k_ref[...].astype(jnp.float32)                 # (blk_s, H, D)
    v = v_ref[...].astype(jnp.float32)

    s = jnp.sum(q[None] * k, axis=-1, keepdims=True)   # (blk_s, H, 1)
    kpos = si * blk_s + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
    valid = kpos < kvlen_ref[b]
    s = jnp.where(valid, s, NEG_INF)
    m = jnp.max(s, axis=0)                             # (H, 1)
    # All-masked splits: exp(NEG_INF - NEG_INF) would be 1; force p to 0.
    safe_m = jnp.maximum(m, NEG_INF / 2)
    p = jnp.where(valid, jnp.exp(s - safe_m[None]), 0.0)
    acc_ref[...] = jnp.sum(p * v, axis=0)              # (H, D)
    m_ref[...] = m
    l_ref[...] = jnp.sum(p, axis=0)


def decode_attention_splits(q, k, v, kv_len, *, scale=None, blk_s=512,
                            interpret=False):
    """Partial-attention pass. Returns (acc, m, l) per split."""
    B, H, D = q.shape
    S = k.shape[1]
    assert k.shape == (B, S, H, D) and v.shape == (B, S, H, D)
    blk_s = min(blk_s, S)
    assert S % blk_s == 0
    nsplit = S // blk_s
    scale = scale if scale is not None else 1.0 / math.sqrt(D)

    kernel = functools.partial(_decode_kernel, scale=scale, blk_s=blk_s)
    stat = pl.BlockSpec((_SQ, _SQ, H, 1), lambda b, si, kl: (b, si, 0, 0))
    acc, m, l = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(B, nsplit),
            in_specs=[
                pl.BlockSpec((_SQ, H, D), lambda b, si, kl: (b, 0, 0)),
                pl.BlockSpec((_SQ, blk_s, H, D), lambda b, si, kl: (b, si, 0, 0)),
                pl.BlockSpec((_SQ, blk_s, H, D), lambda b, si, kl: (b, si, 0, 0)),
            ],
            out_specs=[
                pl.BlockSpec((_SQ, _SQ, H, D), lambda b, si, kl: (b, si, 0, 0)),
                stat,
                stat,
            ],
        ),
        out_shape=[
            jax.ShapeDtypeStruct((B, nsplit, H, D), jnp.float32),
            jax.ShapeDtypeStruct((B, nsplit, H, 1), jnp.float32),
            jax.ShapeDtypeStruct((B, nsplit, H, 1), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel"),
        ),
        interpret=interpret,
        name="decode_attention_splits",
    )(kv_len.astype(jnp.int32), q, k, v)
    return acc, m, l


def combine_splits(acc, m, l, out_dtype):
    """Logsumexp merge of split partials:
    (B,ns,H,D), (B,ns,H,1) x2 -> (B,H,D)."""
    m_glob = jnp.max(m, axis=1, keepdims=True)                # (B,1,H,1)
    w = jnp.exp(m - m_glob)                                   # (B,ns,H,1)
    l_glob = jnp.sum(l * w, axis=1)                           # (B,H,1)
    o = jnp.sum(acc * w, axis=1)                              # (B,H,D)
    return (o / jnp.maximum(l_glob, 1e-30)).astype(out_dtype)
