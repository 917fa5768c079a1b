"""Fused RMSNorm (Pallas): one HBM round-trip instead of separate
square/mean/rsqrt/mul kernels. Rows are tiled over the grid; the feature
dim stays resident in VMEM (d_model <= a few K fits comfortably)."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _rmsnorm_kernel(x_ref, scale_ref, o_ref, *, eps):
    x = x_ref[...].astype(jnp.float32)                 # (blk_rows, d)
    ms = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    y = x * jax.lax.rsqrt(ms + eps) * scale_ref[...].astype(jnp.float32)[None]
    o_ref[...] = y.astype(o_ref.dtype)


def rmsnorm(x, scale, *, eps: float = 1e-6, blk_rows: int = 256,
            interpret=False):
    """x: (..., d); scale: (d,)."""
    orig_shape = x.shape
    d = x.shape[-1]
    rows = int(x.size // d)
    x2 = x.reshape(rows, d)
    blk = min(blk_rows, rows)
    # Pad rows to a block multiple.
    pad = (-rows) % blk
    if pad:
        x2 = jnp.concatenate([x2, jnp.zeros((pad, d), x2.dtype)], axis=0)
    n = x2.shape[0] // blk
    out = pl.pallas_call(
        functools.partial(_rmsnorm_kernel, eps=eps),
        grid=(n,),
        in_specs=[
            pl.BlockSpec((blk, d), lambda i: (i, 0)),
            pl.BlockSpec((d,), lambda i: (0,)),
        ],
        out_specs=pl.BlockSpec((blk, d), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct(x2.shape, x.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",),
        ),
        interpret=interpret,
        name="rmsnorm",
    )(x2, scale)
    if pad:
        out = out[:rows]
    return out.reshape(orig_shape)
