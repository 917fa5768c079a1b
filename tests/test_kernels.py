"""Pallas kernel validation: interpret-mode execution against the pure-jnp
oracles in kernels/ref.py, swept over shapes, dtypes, GQA groups, and block
sizes (assignment requirement)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref

ATOL = {jnp.float32: 2e-5, jnp.bfloat16: 2e-2}


def _qkv(B, T, Hq, Hkv, D, dtype, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    q = jax.random.normal(ks[0], (B, T, Hq, D), jnp.float32).astype(dtype)
    k = jax.random.normal(ks[1], (B, T, Hkv, D), jnp.float32).astype(dtype)
    v = jax.random.normal(ks[2], (B, T, Hkv, D), jnp.float32).astype(dtype)
    return q, k, v


@pytest.mark.parametrize(
    "B,T,Hq,Hkv,D,dtype",
    [
        (1, 128, 2, 2, 64, jnp.float32),
        (2, 256, 4, 2, 64, jnp.float32),     # GQA group 2
        (1, 256, 4, 1, 128, jnp.float32),    # MQA
        (2, 128, 2, 2, 128, jnp.bfloat16),
        (1, 512, 8, 2, 64, jnp.bfloat16),
    ],
)
def test_flash_attention_forward(B, T, Hq, Hkv, D, dtype):
    q, k, v = _qkv(B, T, Hq, Hkv, D, dtype)
    out = ops.flash_attention(q, k, v, True)
    want = ref.attention(q, k, v, causal=True)
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(want, np.float32),
        atol=ATOL[dtype], rtol=1e-2,
    )


@pytest.mark.parametrize("blk", [64, 128])
def test_flash_attention_block_sizes(blk):
    q, k, v = _qkv(1, 256, 2, 2, 64, jnp.float32)
    out = ops.flash_attention(q, k, v, True, blk, blk)
    want = ref.attention(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want), atol=2e-5, rtol=1e-3)


def test_flash_attention_non_causal():
    q, k, v = _qkv(1, 128, 2, 2, 64, jnp.float32)
    out = ops.flash_attention(q, k, v, False)
    want = ref.attention(q, k, v, causal=False)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want), atol=2e-5, rtol=1e-3)


@pytest.mark.parametrize(
    "B,T,Hq,Hkv,D",
    [
        (1, 128, 2, 2, 64),
        (2, 128, 4, 2, 64),   # GQA: dk/dv group-summed
    ],
)
def test_flash_attention_grads_match_ref(B, T, Hq, Hkv, D):
    q, k, v = _qkv(B, T, Hq, Hkv, D, jnp.float32, seed=3)

    def f_kernel(q, k, v):
        return jnp.sum(ops.flash_attention(q, k, v, True) ** 2)

    def f_ref(q, k, v):
        return jnp.sum(ref.attention(q, k, v, causal=True).astype(jnp.float32) ** 2)

    g_kernel = jax.grad(f_kernel, argnums=(0, 1, 2))(q, k, v)
    g_ref = jax.grad(f_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b, name in zip(g_kernel, g_ref, "qkv"):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), atol=5e-4, rtol=1e-2,
            err_msg=f"d{name} mismatch",
        )


@pytest.mark.parametrize(
    "B,S,Hq,Hkv,D,blk_s,dtype",
    [
        (2, 1024, 4, 4, 64, 256, jnp.float32),
        (2, 1024, 8, 2, 64, 512, jnp.float32),   # GQA
        (1, 2048, 4, 4, 128, 512, jnp.bfloat16),
    ],
)
def test_decode_attention(B, S, Hq, Hkv, D, blk_s, dtype):
    ks = jax.random.split(jax.random.PRNGKey(7), 3)
    q = jax.random.normal(ks[0], (B, Hq, D), jnp.float32).astype(dtype)
    k = jax.random.normal(ks[1], (B, S, Hkv, D), jnp.float32).astype(dtype)
    v = jax.random.normal(ks[2], (B, S, Hkv, D), jnp.float32).astype(dtype)
    kv_len = jnp.asarray([S // 3, S][:B].copy() if B > 1 else [S // 2], jnp.int32)
    out = ops.decode_attention(q, k, v, kv_len, blk_s=blk_s)
    want = ref.decode_attention(q, k, v, kv_len)
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(want, np.float32),
        atol=ATOL[dtype], rtol=1e-2,
    )


@pytest.mark.parametrize(
    "shape,dtype",
    [((4, 128, 256), jnp.float32), ((3, 100, 512), jnp.bfloat16), ((1000, 64), jnp.float32)],
)
def test_rmsnorm(shape, dtype):
    x = jax.random.normal(jax.random.PRNGKey(9), shape, jnp.float32).astype(dtype)
    scale = jax.random.normal(jax.random.PRNGKey(10), (shape[-1],), jnp.float32)
    out = ops.rmsnorm(x, scale)
    want = ref.rmsnorm(x, scale)
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(want, np.float32),
        atol=ATOL[dtype], rtol=1e-2,
    )


# ---------------------------------------------------------------------------
# Pure-jnp scan-flash (the dry-run / training tiled path) vs dense oracle.
# ---------------------------------------------------------------------------


@pytest.mark.parametrize(
    "B,Tq,Tk,Hq,Hkv,D,offset",
    [
        (2, 2048, 2048, 4, 2, 32, None),         # training shape
        (2, 1, 2048, 4, 4, 32, (1000, 1500)),    # decode against cache
        (2, 1, 2048, 8, 2, 32, (1000, 1500)),    # decode, 4 query heads a kv head
        (1, 1024, 2048, 4, 2, 32, (512,)),       # chunked prefill w/ offset
    ],
)
def test_chunked_attention_matches_sdpa(B, Tq, Tk, Hq, Hkv, D, offset):
    from repro.models import layers as L

    ks = jax.random.split(jax.random.PRNGKey(11), 3)
    q = jax.random.normal(ks[0], (B, Tq, Hq, D), jnp.float32)
    k = jax.random.normal(ks[1], (B, Tk, Hkv, D), jnp.float32)
    v = jax.random.normal(ks[2], (B, Tk, Hkv, D), jnp.float32)
    q_offset = None if offset is None else jnp.asarray(list(offset) * (B // len(offset)) or list(offset), jnp.int32)[:B]
    kv_len = None if offset is None else q_offset + Tq
    out = L.chunked_attention(q, k, v, causal=True, q_offset=q_offset,
                              kv_len=kv_len, blk_q=256, blk_k=512)
    want = L._sdpa(q, k, v, causal=True,
                   q_offset=q_offset if q_offset is not None else 0,
                   kv_len=kv_len)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want), atol=3e-5, rtol=1e-2)


@pytest.mark.parametrize("Tq", [1, 256])
def test_chunked_attention_reads_one_layer_of_a_stack(Tq):
    """With ``layer``, the tiled path reads its blocks straight from the
    stacked caches and gives what it gives on that layer's own cache."""
    from repro.models import layers as L

    G_, B, Tk, Hq, Hkv, D = 3, 2, 2048, 8, 2, 32
    ks = jax.random.split(jax.random.PRNGKey(13), 3)
    q = jax.random.normal(ks[0], (B, Tq, Hq, D), jnp.float32)
    k = jax.random.normal(ks[1], (G_, B, Tk, Hkv, D), jnp.float32)
    v = jax.random.normal(ks[2], (G_, B, Tk, Hkv, D), jnp.float32)
    q_offset = jnp.asarray([700, 1500], jnp.int32)
    kw = dict(causal=True, q_offset=q_offset, kv_len=q_offset + Tq, blk_q=256, blk_k=512)
    out = L.chunked_attention(q, k, v, layer=jnp.int32(1), **kw)
    want = L.chunked_attention(q, k[1], v[1], **kw)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want), atol=3e-5, rtol=1e-4)


# A cached call: (Tq, q_offset a row, blk_q, blk_k). Decode against rows of
# different fill, and prefill chunks at the start and further on.
CACHED = {
    "decode": (1, (700, 1500), 512, 512),
    "prefill": (512, (0, 0), 256, 512),
    "prefill-diagonal": (512, (0, 0), 256, 128),
    "prefill-later": (512, (1024, 1024), 256, 256),
}


def _stacked_cache_call(Tq, offset, Tk=4096, G_=3, layer=1):
    B, Hq, Hkv, D = 2, 8, 2, 32
    ks = jax.random.split(jax.random.PRNGKey(17), 3)
    q = jax.random.normal(ks[0], (B, Tq, Hq, D), jnp.float32)
    k = jax.random.normal(ks[1], (G_, B, Tk, Hkv, D), jnp.float32)
    v = jax.random.normal(ks[2], (G_, B, Tk, Hkv, D), jnp.float32)
    q_offset = jnp.asarray(offset, jnp.int32)
    return q, k, v, q_offset, q_offset + Tq, jnp.int32(layer)


@pytest.mark.parametrize("case", ["decode", "prefill"])
def test_chunked_attention_never_reads_a_dead_cache_block(case):
    """The cached path's loop stops at the live length: every whole key and
    value block past it, and every other layer of the stack, holds NaN, which
    the value product (0 * NaN) would carry into the result."""
    from repro.models import layers as L

    Tq, offset, bq, bk = CACHED[case]
    q, k, v, q_offset, kv_len, layer = _stacked_cache_call(Tq, offset)
    live = -(-int(kv_len.max()) // bk) * bk
    dead = np.zeros(k.shape, bool)
    dead[:, :, live:] = True
    dead[np.arange(k.shape[0]) != int(layer)] = True
    k, v = (jnp.where(dead, jnp.nan, c) for c in (k, v))
    out = L.chunked_attention(q, k, v, causal=True, q_offset=q_offset, kv_len=kv_len,
                              blk_q=bq, blk_k=bk, layer=layer)
    assert np.isfinite(np.asarray(out)).all()
    n = int(kv_len.max())
    want = L._sdpa(q, k[layer, :, :n], v[layer, :, :n], causal=True,
                   q_offset=q_offset, kv_len=kv_len)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want), atol=3e-5, rtol=1e-2)


@pytest.mark.parametrize("case", sorted(CACHED))
def test_chunked_attention_stops_at_the_live_length_exactly(case):
    """The bounded loop gives, bit for bit, what the loop over every key
    block (the path without ``layer``) gives on the layer's own cache."""
    from repro.models import layers as L

    Tq, offset, bq, bk = CACHED[case]
    q, k, v, q_offset, kv_len, layer = _stacked_cache_call(Tq, offset)
    kw = dict(causal=True, q_offset=q_offset, kv_len=kv_len, blk_q=bq, blk_k=bk)
    out = L.chunked_attention(q, k, v, layer=layer, **kw)
    want = L.chunked_attention(q, k[layer], v[layer], **kw)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(want))


def test_chunked_attention_grads_match():
    from repro.models import layers as L

    ks = jax.random.split(jax.random.PRNGKey(12), 3)
    q = jax.random.normal(ks[0], (1, 1024, 4, 32), jnp.float32)
    k = jax.random.normal(ks[1], (1, 1024, 2, 32), jnp.float32)
    v = jax.random.normal(ks[2], (1, 1024, 2, 32), jnp.float32)

    f1 = lambda q, k, v: jnp.sum(L.chunked_attention(q, k, v, causal=True) ** 2)
    f2 = lambda q, k, v: jnp.sum(L._sdpa(q, k, v, causal=True) ** 2)
    g1 = jax.grad(f1, argnums=(0, 1, 2))(q, k, v)
    g2 = jax.grad(f2, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g1, g2):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=5e-4, rtol=1e-2)
