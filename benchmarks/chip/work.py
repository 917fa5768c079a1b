"""Work the algorithms require, computed from shapes: floating-point
operations and bytes that must cross HBM. Each function counts what the
mathematics needs, never what an implementation happens to do: recomputed
activations, padding, upcasts and cache slots that hold no token are not
work. A multiply-add is 2 FLOPs.

Configurations are the dictionaries of ``configs/<name>.json`` (the
published Hugging Face keys).
"""
from __future__ import annotations

DTYPE_BYTES = {"bfloat16": 2, "float16": 2, "float32": 4}


def _dims(cfg: dict):
    d = cfg["hidden_size"]
    q = cfg["num_attention_heads"] * cfg["head_dim"]
    kv = cfg["num_key_value_heads"] * cfg["head_dim"]
    return d, q, kv, cfg["intermediate_size"], cfg["vocab_size"], cfg["num_hidden_layers"]


def layer_matmul_params(cfg: dict) -> int:
    """Weights of one decoder layer's matrix products: q, k, v and o
    projections and the SwiGLU MLP's gate, up and down."""
    d, q, kv, ff, _, _ = _dims(cfg)
    return d * (q + 2 * kv) + q * d + 3 * d * ff


def matmul_params(cfg: dict) -> int:
    """Weights that take part in a matrix product for each token: every
    layer's, and the embedding once as the (tied) output head."""
    d, _, _, _, vocab, layers = _dims(cfg)
    # Tied or not, one vocab x d matrix multiplies (the output head); the
    # embedding lookup is no product.
    return layers * layer_matmul_params(cfg) + vocab * d


def n_params(cfg: dict) -> int:
    """Parameters as the model's published count reckons them: the tied
    embedding once, each layer's projections and MLP and its two RMSNorm
    scales. The per-head q/k norm scales (256 a layer) and the final norm
    (2,048) are left out of it, as they are of the 1.7 B / 1.4 B
    non-embedding figures."""
    d, _, _, _, vocab, layers = _dims(cfg)
    emb = vocab * d * (1 if cfg["tie_word_embeddings"] else 2)
    return emb + layers * (layer_matmul_params(cfg) + 2 * d)


def weight_bytes(cfg: dict) -> int:
    """Bytes of the weights as served, in the configuration's dtype."""
    return n_params(cfg) * DTYPE_BYTES[cfg["torch_dtype"]]


def kv_bytes_per_token(cfg: dict) -> int:
    """Cache bytes one token holds over all layers: its key and value."""
    _, _, kv, _, _, layers = _dims(cfg)
    return layers * 2 * kv * DTYPE_BYTES[cfg["torch_dtype"]]


def attention_flops(cfg: dict, pairs: int) -> int:
    """Forward FLOPs of the two attention products (q.k and p.v) over all
    layers, for ``pairs`` (query, key) pairs that the mask keeps."""
    _, q, _, _, _, layers = _dims(cfg)
    return layers * 2 * 2 * q * pairs


def causal_pairs(seq_len: int) -> int:
    """(query, key) pairs a causal mask keeps in one sequence."""
    return seq_len * (seq_len + 1) // 2


def train_step_flops(cfg: dict, batch: int, seq_len: int) -> int:
    """Model FLOPs of one training step: forward and backward (3x the
    forward) of every matrix product, with causal attention over each
    sequence. Recomputation is not counted."""
    fwd = (2 * matmul_params(cfg) * batch * seq_len
           + attention_flops(cfg, batch * causal_pairs(seq_len)))
    return 3 * fwd


def train_flops_per_token(cfg: dict, seq_len: int) -> float:
    return train_step_flops(cfg, 1, seq_len) / seq_len


def decode_step_work(cfg: dict, kv_lens) -> tuple[int, int]:
    """(FLOPs, HBM bytes) one decode step requires, for sequences whose
    live cache, the new token included, holds ``kv_lens`` tokens. Bytes
    are the weights once and the live cache only: a slot that holds no
    token is no work, whatever the cache's capacity."""
    kv_lens = [int(n) for n in kv_lens]
    flops = (2 * matmul_params(cfg) * len(kv_lens)
             + attention_flops(cfg, sum(kv_lens)))
    nbytes = weight_bytes(cfg) + kv_bytes_per_token(cfg) * sum(kv_lens)
    return flops, nbytes


def least_time_s(flops: float, nbytes: float, peaks: dict) -> tuple[float, str]:
    """The least time a chip with ``peaks`` needs, and which bound sets it."""
    t_c = flops / peaks["bf16_flops"]
    t_m = nbytes / peaks["hbm_bytes_per_s"]
    return (t_c, "compute") if t_c >= t_m else (t_m, "memory")


# ------------------------------------------------- the repository's kernels
# (B, T, H, D) layouts as in ``repro.kernels``; ``itemsize`` of the stored
# activations (2 for bf16).


def flash_forward_work(B, T, Hq, Hkv, D, causal=True, itemsize=2):
    """Flash attention forward: the two products over the kept pairs; reads
    q, k, v once and writes o."""
    pairs = causal_pairs(T) if causal else T * T
    flops = 2 * 2 * B * Hq * D * pairs
    nbytes = itemsize * B * T * D * (2 * Hq + 2 * Hkv)
    return flops, nbytes


def flash_backward_work(B, T, Hq, Hkv, D, causal=True, itemsize=2):
    """Flash attention backward: dv, dp, dq and dk are four products of the
    forward's size (the recomputed s is not counted); reads q, k, v, o, do
    and writes dq, dk, dv."""
    pairs = causal_pairs(T) if causal else T * T
    flops = 4 * 2 * B * Hq * D * pairs
    nbytes = itemsize * B * T * D * (3 * Hq + 2 * Hkv) + itemsize * B * T * D * (Hq + 2 * Hkv)
    return flops, nbytes


def decode_attention_work(kv_lens, Hq, Hkv, D, itemsize=2):
    """One query per sequence against its live cache: two products over
    ``kv_lens`` keys; reads the live keys and values and q, writes o."""
    n = sum(int(x) for x in kv_lens)
    flops = 2 * 2 * Hq * D * n
    nbytes = itemsize * (2 * Hkv * D * n + 2 * len(kv_lens) * Hq * D)
    return flops, nbytes


def rmsnorm_work(rows, dim, itemsize=2):
    """RMSNorm of ``rows`` vectors: square, sum, scale and multiply by the
    weight (4 FLOPs an element); reads x and the f32 scale, writes y."""
    return 4 * rows * dim, itemsize * 2 * rows * dim + 4 * dim
