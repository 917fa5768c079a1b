"""Causal flash attention for TPU (Pallas): forward + backward kernels.

TPU adaptation notes (vs the CUDA flash-attention algorithm):
- Tiling is chosen for the MXU (128x128 systolic array) and VMEM residency:
  q/k blocks default to 128 rows, head_dim rides along in full (<= 128).
- Heads sit OUTSIDE the last two dims of every block: Mosaic needs a
  block's last two dims divisible by (8, 128) or equal to the array's, so
  the kernels see (B, H, T, D) with batch and head squeezed and each block
  is a (rows, D) tile. Per-row statistics (lse, delta) are (B, H, T, 1).
- Matmuls take the inputs' dtype and accumulate in f32
  (``preferred_element_type``); the softmax runs in f32.
- The streaming softmax state (m, l, acc) lives in VMEM scratch and is
  carried across the *innermost grid dimension* (k blocks), declared
  "arbitrary" so Mosaic keeps it sequential; batch/head/q-block dims are
  "parallel". This replaces the CUDA warp-level accumulation.
- Causal skipping: k blocks strictly above the diagonal are skipped via
  pl.when, saving ~half the FLOPs at long sequence.
- GQA is handled by the ops.py wrapper (kv head repeat / group-sum for
  gradients) so the kernels stay MHA-shaped — one fewer index map level in
  VMEM addressing.

Layouts: q, k, v, o are (B, H, T, D); lse is (B, H, T, 1) fp32.
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
_NT = (((1,), (1,)), ((), ()))   # a @ b.T
_TN = (((0,), (0,)), ((), ()))   # a.T @ b


def _dot(a, b, dims=(((1,), (0,)), ((), ()))):
    return jax.lax.dot_general(a, b, dims, preferred_element_type=jnp.float32)


def _scores(q, k, qi, ki, *, scale, causal, blk_q, blk_k):
    s = _dot(q, k, _NT) * scale                                # (bq, bk)
    if causal:
        qpos = qi * blk_q + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
        kpos = ki * blk_k + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        s = jnp.where(qpos >= kpos, s, NEG_INF)
    return s


def _runs(qi, ki, *, causal, blk_q, blk_k):
    """False only for k blocks strictly above the causal diagonal."""
    return (ki * blk_k <= qi * blk_q + blk_q - 1) if causal else (ki >= 0)


# ------------------------------------------------------------------ forward


def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, m_scr, l_scr, acc_scr,
                *, scale, causal, blk_q, blk_k, nk):
    qi = pl.program_id(2)
    ki = pl.program_id(3)

    @pl.when(ki == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    @pl.when(_runs(qi, ki, causal=causal, blk_q=blk_q, blk_k=blk_k))
    def _body():
        v = v_ref[...]
        s = _scores(q_ref[...], k_ref[...], qi, ki, scale=scale,
                    causal=causal, blk_q=blk_q, blk_k=blk_k)
        m_prev = m_scr[...]                                    # (bq, 1)
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m_prev - m_new)
        l_scr[...] = alpha * l_scr[...] + jnp.sum(p, axis=-1, keepdims=True)
        acc_scr[...] = acc_scr[...] * alpha + _dot(p.astype(v.dtype), v)
        m_scr[...] = m_new

    @pl.when(ki == nk - 1)
    def _done():
        l = jnp.maximum(l_scr[...], 1e-30)
        o_ref[...] = (acc_scr[...] / l).astype(o_ref.dtype)
        lse_ref[...] = m_scr[...] + jnp.log(l)


def flash_attention_fwd(q, k, v, *, causal=True, scale=None,
                        blk_q=128, blk_k=128, interpret=False):
    B, H, Tq, D = q.shape
    Tk = k.shape[2]
    assert k.shape == (B, H, Tk, D) and v.shape == (B, H, Tk, D), "MHA-shaped"
    blk_q = min(blk_q, Tq)
    blk_k = min(blk_k, Tk)
    assert Tq % blk_q == 0 and Tk % blk_k == 0
    scale = scale if scale is not None else 1.0 / math.sqrt(D)
    nq, nk = Tq // blk_q, Tk // blk_k

    kernel = functools.partial(
        _fwd_kernel, scale=scale, causal=causal, blk_q=blk_q, blk_k=blk_k, nk=nk
    )
    qspec = pl.BlockSpec((_SQ, _SQ, blk_q, D), lambda b, h, qi, ki: (b, h, qi, 0))
    kspec = pl.BlockSpec((_SQ, _SQ, blk_k, D), lambda b, h, qi, ki: (b, h, ki, 0))
    stat = pl.BlockSpec((_SQ, _SQ, blk_q, 1), lambda b, h, qi, ki: (b, h, qi, 0))
    o, lse = pl.pallas_call(
        kernel,
        grid=(B, H, nq, nk),
        in_specs=[qspec, kspec, kspec],
        out_specs=[qspec, stat],
        out_shape=[
            jax.ShapeDtypeStruct((B, H, Tq, D), q.dtype),
            jax.ShapeDtypeStruct((B, H, Tq, 1), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((blk_q, 1), jnp.float32),
            pltpu.VMEM((blk_q, 1), jnp.float32),
            pltpu.VMEM((blk_q, D), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
        name="flash_attention_fwd",
    )(q, k, v)
    return o, lse


# ----------------------------------------------------------------- backward


def _dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref, dq_scr,
               *, scale, causal, blk_q, blk_k, nk):
    qi = pl.program_id(2)
    ki = pl.program_id(3)

    @pl.when(ki == 0)
    def _init():
        dq_scr[...] = jnp.zeros_like(dq_scr)

    @pl.when(_runs(qi, ki, causal=causal, blk_q=blk_q, blk_k=blk_k))
    def _body():
        k = k_ref[...]
        s = _scores(q_ref[...], k, qi, ki, scale=scale,
                    causal=causal, blk_q=blk_q, blk_k=blk_k)
        p = jnp.exp(s - lse_ref[...])
        dp = _dot(do_ref[...], v_ref[...], _NT)
        ds = p * (dp - delta_ref[...]) * scale
        dq_scr[...] += _dot(ds.astype(k.dtype), k)

    @pl.when(ki == nk - 1)
    def _done():
        dq_ref[...] = dq_scr[...].astype(dq_ref.dtype)


def _dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                dk_ref, dv_ref, dk_scr, dv_scr,
                *, scale, causal, blk_q, blk_k, nq):
    ki = pl.program_id(2)
    qi = pl.program_id(3)

    @pl.when(qi == 0)
    def _init():
        dk_scr[...] = jnp.zeros_like(dk_scr)
        dv_scr[...] = jnp.zeros_like(dv_scr)

    @pl.when(_runs(qi, ki, causal=causal, blk_q=blk_q, blk_k=blk_k))
    def _body():
        q, do = q_ref[...], do_ref[...]
        s = _scores(q, k_ref[...], qi, ki, scale=scale,
                    causal=causal, blk_q=blk_q, blk_k=blk_k)
        p = jnp.exp(s - lse_ref[...])                          # (bq, bk)
        dv_scr[...] += _dot(p.astype(do.dtype), do, _TN)
        dp = _dot(do, v_ref[...], _NT)
        ds = p * (dp - delta_ref[...]) * scale
        dk_scr[...] += _dot(ds.astype(q.dtype), q, _TN)

    @pl.when(qi == nq - 1)
    def _done():
        dk_ref[...] = dk_scr[...].astype(dk_ref.dtype)
        dv_ref[...] = dv_scr[...].astype(dv_ref.dtype)


def flash_attention_bwd(q, k, v, o, lse, do, *, causal=True, scale=None,
                        blk_q=128, blk_k=128, interpret=False):
    B, H, Tq, D = q.shape
    Tk = k.shape[2]
    blk_q = min(blk_q, Tq)
    blk_k = min(blk_k, Tk)
    scale = scale if scale is not None else 1.0 / math.sqrt(D)
    nq, nk = Tq // blk_q, Tk // blk_k
    params = pltpu.CompilerParams(
        dimension_semantics=("parallel", "parallel", "parallel", "arbitrary"),
    )

    # delta = rowsum(dO * O): cheap, done outside the kernels.
    delta = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32), axis=-1,
                    keepdims=True)                             # (B, H, Tq, 1)

    qspec = pl.BlockSpec((_SQ, _SQ, blk_q, D), lambda b, h, qi, ki: (b, h, qi, 0))
    kspec = pl.BlockSpec((_SQ, _SQ, blk_k, D), lambda b, h, qi, ki: (b, h, ki, 0))
    statq = pl.BlockSpec((_SQ, _SQ, blk_q, 1), lambda b, h, qi, ki: (b, h, qi, 0))
    dq = pl.pallas_call(
        functools.partial(_dq_kernel, scale=scale, causal=causal,
                          blk_q=blk_q, blk_k=blk_k, nk=nk),
        grid=(B, H, nq, nk),
        in_specs=[qspec, kspec, kspec, qspec, statq, statq],
        out_specs=qspec,
        out_shape=jax.ShapeDtypeStruct((B, H, Tq, D), q.dtype),
        scratch_shapes=[pltpu.VMEM((blk_q, D), jnp.float32)],
        compiler_params=params,
        interpret=interpret,
        name="flash_attention_dq",
    )(q, k, v, do, lse, delta)

    qspec2 = pl.BlockSpec((_SQ, _SQ, blk_q, D), lambda b, h, ki, qi: (b, h, qi, 0))
    kspec2 = pl.BlockSpec((_SQ, _SQ, blk_k, D), lambda b, h, ki, qi: (b, h, ki, 0))
    statq2 = pl.BlockSpec((_SQ, _SQ, blk_q, 1), lambda b, h, ki, qi: (b, h, qi, 0))
    dk, dv = pl.pallas_call(
        functools.partial(_dkv_kernel, scale=scale, causal=causal,
                          blk_q=blk_q, blk_k=blk_k, nq=nq),
        grid=(B, H, nk, nq),
        in_specs=[qspec2, kspec2, kspec2, qspec2, statq2, statq2],
        out_specs=[kspec2, kspec2],
        out_shape=[
            jax.ShapeDtypeStruct((B, H, Tk, D), k.dtype),
            jax.ShapeDtypeStruct((B, H, Tk, D), v.dtype),
        ],
        scratch_shapes=[
            pltpu.VMEM((blk_k, D), jnp.float32),
            pltpu.VMEM((blk_k, D), jnp.float32),
        ],
        compiler_params=params,
        interpret=interpret,
        name="flash_attention_dkv",
    )(q, k, v, do, lse, delta)
    return dq, dk, dv
