"""Jit'd public wrappers for the Pallas kernels.

- ``flash_attention``: custom_vjp (Pallas fwd + Pallas bwd), GQA handled
  here (kv-head repeat going in, group-sum for dk/dv coming out).
- ``decode_attention``: split-K partials + logsumexp combine.
- ``rmsnorm``: fused forward (training uses the ref path's autodiff).

On the CPU host platform (this container, and any unit test) the kernels
run with interpret=True; on TPU they compile through Mosaic. The dry-run
lowers the FLOP-equivalent ref path instead (kernels are TPU-targeted).
"""
from __future__ import annotations

import functools
import jax
import jax.numpy as jnp

from repro.kernels import decode_attention as _dec
from repro.kernels import flash_attention as _fa
from repro.kernels import ref as _ref
from repro.kernels import rmsnorm as _rms


def _interpret() -> bool:
    return jax.default_backend() == "cpu"


def _repeat_kv(k, group, axis):
    if group == 1:
        return k
    return jnp.repeat(k, group, axis=axis)


def _heads_major(x):
    """(B, T, H, D) <-> (B, H, T, D): the flash kernels tile (rows, D) per
    head."""
    return x.transpose(0, 2, 1, 3)


def _mha_inputs(q, k, v):
    group = q.shape[2] // k.shape[2]
    return (_heads_major(q), _repeat_kv(_heads_major(k), group, 1),
            _repeat_kv(_heads_major(v), group, 1))


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def flash_attention(q, k, v, causal=True, blk_q=128, blk_k=128):
    """q: (B,T,Hq,D); k, v: (B,T,Hkv,D). Causal flash attention."""
    o, _ = _flash_fwd(q, k, v, causal, blk_q, blk_k)
    return o


def _flash_fwd(q, k, v, causal, blk_q, blk_k):
    o, lse = _fa.flash_attention_fwd(
        *_mha_inputs(q, k, v), causal=causal, blk_q=blk_q, blk_k=blk_k,
        interpret=_interpret(),
    )
    return _heads_major(o), (q, k, v, o, lse)


def _flash_bwd(causal, blk_q, blk_k, res, do):
    q, k, v, o, lse = res
    dq, dk, dv = _fa.flash_attention_bwd(
        *_mha_inputs(q, k, v), o, lse, _heads_major(do), causal=causal,
        blk_q=blk_q, blk_k=blk_k, interpret=_interpret(),
    )
    B, Hq, T, D = dk.shape
    group = Hq // k.shape[2]
    # Group-sum the repeated kv heads back to Hkv, then back to (B,T,H,D).
    dk = _heads_major(dk.reshape(B, Hq // group, group, T, D).sum(axis=2))
    dv = _heads_major(dv.reshape(B, Hq // group, group, T, D).sum(axis=2))
    return _heads_major(dq), dk.astype(k.dtype), dv.astype(v.dtype)


flash_attention.defvjp(_flash_fwd, _flash_bwd)


def decode_attention(q, k, v, kv_len, *, blk_s: int = 512):
    """q: (B,Hq,D) single token; k, v: (B,S,Hkv,D); kv_len: (B,)."""
    group = q.shape[1] // k.shape[2]
    kr, vr = _repeat_kv(k, group, 2), _repeat_kv(v, group, 2)
    acc, m, l = _dec.decode_attention_splits(
        q, kr, vr, kv_len, blk_s=blk_s, interpret=_interpret()
    )
    return _dec.combine_splits(acc, m, l, q.dtype)


def rmsnorm(x, scale, eps: float = 1e-6):
    return _rms.rmsnorm(x, scale, eps=eps, interpret=_interpret())


# Re-export oracles for tests/benchmarks.
ref = _ref
