"""Inputs the benchmark makes from ``--seed``: Qwen3 weights, the
packed training rows, and the serving rounds and prompts (the one generator
that every traffic file's parameters drive).

``params`` draws the weights in the pytree layout the program serves
(``{"embed", "stack", "final_norm"}``, layers stacked on a leading axis) and
by the recipe the program's own initialiser follows, so that the plain
reference can start from exactly the weights that ``Trainer`` draws for
itself from the same seed. ``train_rows`` is a copy of the program's
synthetic packed-document generator, for the same reason. A CPU test holds
both equal to the program's (``tests/chip_bench``); neither imports it.
"""
from __future__ import annotations

import functools
import hashlib
import math
import statistics

import jax
import jax.numpy as jnp
import numpy as np


def seed32(seed: int) -> int:
    """A 31-bit seed for ``jax.random.PRNGKey`` and the data hash, mixed from
    a seed of any size (seeds may exceed 32 signed bits)."""
    return int.from_bytes(hashlib.sha256(str(int(seed)).encode()).digest()[:4], "little") >> 1


def _normal(key, shape, scale, dtype):
    return (jax.random.normal(key, shape, jnp.float32) * scale).astype(dtype)


ARCH_KEYS = ("hidden_size", "intermediate_size", "num_attention_heads", "num_key_value_heads",
             "head_dim", "vocab_size", "num_hidden_layers", "rms_norm_eps", "rope_theta")


def arch_key(cfg: dict) -> tuple:
    """The architecture's keys of ``cfg``, hashable (a static argument)."""
    return tuple((k, cfg[k]) for k in ARCH_KEYS)


def params(cfg: dict, key, dtype=jnp.bfloat16) -> dict:
    """Weights for ``cfg`` from the PRNG ``key``."""
    d, V, L = cfg["hidden_size"], cfg["vocab_size"], cfg["num_hidden_layers"]
    hd = cfg["head_dim"]
    q, kv, ff = cfg["num_attention_heads"] * hd, cfg["num_key_value_heads"] * hd, cfg["intermediate_size"]
    r = jax.random.split(key, 3)
    tok = _normal(jax.random.split(r[0], 3)[0], (V, d), 0.02, dtype)

    def dense(key, n_in, n_out):
        return _normal(key, (n_in, n_out), 1.0 / math.sqrt(n_in), dtype)

    def layer(key):
        rb = jax.random.split(jax.random.split(key, 1)[0], 4)
        ra = jax.random.split(rb[0], 5)
        rf = jax.random.split(rb[1], 3)
        return {"b0": {
            "norm1": {"scale": jnp.ones((d,), jnp.float32)},
            "mixer": {"wq": dense(ra[0], d, q), "wk": dense(ra[1], d, kv),
                      "wv": dense(ra[2], d, kv), "wo": dense(ra[3], q, d),
                      "q_norm": jnp.ones((hd,), jnp.float32),
                      "k_norm": jnp.ones((hd,), jnp.float32)},
            "norm2": {"scale": jnp.ones((d,), jnp.float32)},
            "ffn": {"w_gate": dense(rf[0], d, ff), "w_up": dense(rf[1], d, ff),
                    "w_down": dense(rf[2], ff, d)},
        }}

    layers = [layer(k) for k in jax.random.split(r[1], L)]
    return {"embed": {"tok": tok},
            "stack": jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *layers),
            "final_norm": {"scale": jnp.ones((d,), jnp.float32)}}


@functools.lru_cache(maxsize=None)
def _params_fn(cfg_key: tuple, dtype_name: str):
    cfg = dict(cfg_key)
    return jax.jit(lambda key: params(cfg, key, jnp.dtype(dtype_name)))


def make_params(cfg: dict, seed: int, dtype=jnp.bfloat16) -> dict:
    """Weights for ``cfg`` from ``seed`` (already ``seed32``-mixed), made on
    the device in one jitted call whose program does not depend on the seed."""
    return _params_fn(arch_key(cfg), jnp.dtype(dtype).name)(jax.random.PRNGKey(seed))


def _hash2d(a: np.ndarray, b: np.ndarray, seed: int) -> np.ndarray:
    x = (a.astype(np.uint64) * np.uint64(0x9E3779B97F4A7C15)
         + b.astype(np.uint64) * np.uint64(0xBF58476D1CE4E5B9) + np.uint64(seed))
    x ^= x >> np.uint64(30)
    x *= np.uint64(0xBF58476D1CE4E5B9)
    x ^= x >> np.uint64(27)
    x *= np.uint64(0x94D049BB133111EB)
    x ^= x >> np.uint64(31)
    return x


def train_rows(step: int, *, batch: int, seq_len: int, vocab: int, seed: int,
               mean_doc_len: int, eos_id: int = 0) -> dict:
    """The global batch of training ``step``: hashed tokens cut into
    documents of geometric length (mean ``mean_doc_len``) by EOS, with the
    loss masked where the label is EOS."""
    ex = np.arange(batch, dtype=np.uint64)
    pos = np.arange(seq_len + 1, dtype=np.uint64)
    gidx = ex[:, None] * np.uint64(1_000_003) + np.uint64(step)
    h = _hash2d(gidx.repeat(seq_len + 1, 1), pos[None, :].repeat(batch, 0), seed)
    tokens = (h % np.uint64(max(vocab - 1, 1))).astype(np.int64) + 1
    tokens = np.where((h % np.uint64(mean_doc_len)) == 0, eos_id, tokens)
    lbl = tokens[:, 1:]
    return {"tokens": tokens[:, :-1].astype(np.int32), "labels": lbl.astype(np.int32),
            "loss_mask": (lbl != eos_id).astype(np.float32)}


def prompts(round_idx: int, *, batch: int, prompt_len: int, vocab: int, seed: int) -> np.ndarray:
    """The ``batch`` prompts of serving round ``round_idx``: token ids drawn
    uniformly from [1, vocab), a different set of rows every round."""
    rng = np.random.default_rng([seed, round_idx])
    return rng.integers(1, vocab, size=(batch, prompt_len), dtype=np.int32)


def lognormal_quantiles(median: float, sigma: float, n: int) -> list:
    """``n`` stratified quantiles, at (i + 1/2) / n, of a log-normal
    distribution: a fixed set of sizes that follows its shape."""
    nd = statistics.NormalDist()
    return [median * math.exp(sigma * nd.inv_cdf((i + 0.5) / n)) for i in range(n)]


def serve_rounds(traffic: dict, seed: int) -> list:
    """One cycle of a serving traffic's rounds, ``[(prompt_len, out_lens)]``.

    A round is one batch of ``batch`` requests that share a prompt length,
    as a server that batches requests by prompt-length bucket sends them.
    The cycle's prompt lengths are ``rounds`` stratified quantiles of the
    traffic's log-normal prompt length, each rounded up to the smallest of
    ``buckets`` that holds it (the longest cut to the last), shortest first.
    Each round's requests get ``batch`` stratified quantiles of the
    log-normal output length (the tokens served, the prefill's first
    included), capped at ``max``, in an order drawn from the seed. Every
    seed gets the same sizes in the same rounds: only which request gets
    which length, and the tokens, change."""
    pl, ol, B = traffic["prompt_len"], traffic["output_len"], traffic["batch"]
    buckets = sorted(pl["buckets"])
    lens = [next((b for b in buckets if b >= q), buckets[-1])
            for q in lognormal_quantiles(pl["median"], pl["sigma"], traffic["rounds"])]
    outs = np.array([min(ol["max"], max(1, round(q)))
                     for q in lognormal_quantiles(ol["median"], ol["sigma"], B)])
    rng = np.random.default_rng([seed, 3])
    return [(p, rng.permutation(outs)) for p in sorted(lens)]
