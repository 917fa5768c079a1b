"""A configuration file (published Hugging Face keys) as the program's
``ArchConfig``, and the leaf-by-leaf norms the comparisons read."""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

DTYPES = {"bfloat16": jnp.bfloat16, "float32": jnp.float32}


def arch_config(name: str, cfg: dict):
    """The program's architecture for a dense Qwen3 configuration."""
    from repro.configs.base import ArchConfig

    if cfg["model_type"] != "qwen3":
        raise ValueError(f"{name}: no mapping for model_type {cfg['model_type']!r}")
    # the program's norms take eps 1e-6 and its gated MLP SiLU, with no key for either
    if cfg["rms_norm_eps"] != 1e-6 or cfg["hidden_act"] != "silu":
        raise ValueError(f"{name}: the program runs rms_norm_eps 1e-6 and hidden_act silu only")
    return ArchConfig(
        name=name, family="dense", n_layers=cfg["num_hidden_layers"],
        d_model=cfg["hidden_size"], n_heads=cfg["num_attention_heads"],
        n_kv_heads=cfg["num_key_value_heads"], d_ff=cfg["intermediate_size"],
        vocab_size=cfg["vocab_size"], head_dim=cfg["head_dim"], qk_norm=True,
        qkv_bias=cfg["attention_bias"], activation="swiglu", norm="rmsnorm", pos="rope",
        rope_theta=float(cfg["rope_theta"]), tie_embeddings=cfg["tie_word_embeddings"],
        max_seq_len=cfg["max_position_embeddings"])


def _key(path) -> str:
    return "/".join(str(getattr(k, "key", getattr(k, "name", k))) for k in path)


def _one_norm(path, d):
    d = d.astype(jnp.float32)
    if _key(path).startswith("stack"):
        return jnp.sqrt(jnp.sum(d * d, axis=tuple(range(1, d.ndim))))
    return jnp.sqrt(jnp.sum(d * d))[None]


@jax.jit
def _norms(tree):
    return jax.tree_util.tree_map_with_path(_one_norm, tree)


@jax.jit
def _diff_norms(tree, base):
    return jax.tree_util.tree_map_with_path(
        lambda p, x, b: _one_norm(p, x.astype(jnp.float32) - b.astype(jnp.float32)), tree, base)


def leaf_norms(tree, base=None, scale: float = 1.0) -> dict:
    """{"<path>#<layer>": norm} of ``tree`` (minus ``base``), times ``scale``;
    the stacked layers (leading axis of ``stack/...``) one by one."""
    norms = _norms(tree) if base is None else _diff_norms(tree, base)
    out = {}
    for path, v in jax.tree_util.tree_leaves_with_path(jax.device_get(norms)):
        for i, n in enumerate(np.asarray(v, np.float64)):
            out[f"{_key(path)}#{i}"] = float(n) * scale
    return out
