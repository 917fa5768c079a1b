"""Decoder stack: periodic layer groups scanned with stacked parameters.

Heterogeneous architectures (jamba's mamba/attn interleave, xlstm's
mlstm/slstm mix, MoE-every-other-layer) are handled by finding the smallest
repeating *period* of (block_kind, is_moe) signatures: parameters are
stacked over period repetitions and the repetitions are driven by
``lax.scan`` (small HLO, fast 512-device compiles), while the sublayers
inside one period are unrolled in the scan body. Dense homogeneous stacks
reduce to period=1, i.e. classic scan-over-layers.

Block structure:
  attn:   x += Attn(norm(x));  x += FFN/MoE(norm(x))    (if d_ff > 0)
  mamba:  x += Mamba(norm(x)); x += FFN/MoE(norm(x))    (if d_ff > 0)
  mlstm:  x += mLSTM(norm(x))          (integrated up/down projections)
  slstm:  x += sLSTM(norm(x))          (integrated 4/3 FFN)
"""
from __future__ import annotations

import functools
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig
from repro.models import layers as L
from repro.models import moe as M
from repro.models import ssm as S

Params = Dict[str, Any]

AUX_KEYS = ("moe_load_balance", "moe_router_z")


def period_signature(cfg: ArchConfig) -> List[Tuple[str, bool]]:
    sig = list(zip(cfg.block_types(), cfg.moe_layer_mask()))
    n = len(sig)
    for p in range(1, n + 1):
        if n % p == 0 and sig == sig[:p] * (n // p):
            return sig[:p]
    return sig


def n_groups(cfg: ArchConfig) -> int:
    return cfg.n_layers // len(period_signature(cfg))


# ------------------------------------------------------------------- blocks


def init_block(cfg: ArchConfig, kind: str, is_moe: bool, rng, dtype) -> Params:
    r = jax.random.split(rng, 4)
    p: Params = {"norm1": L.init_norm(cfg, cfg.d_model)}
    if kind == "attn":
        p["mixer"] = L.init_attention(cfg, r[0], dtype)
    elif kind == "mamba":
        p["mixer"] = S.init_mamba(cfg, r[0], dtype)
    elif kind == "mlstm":
        p["mixer"] = S.init_mlstm(cfg, r[0], dtype)
    elif kind == "slstm":
        p["mixer"] = S.init_slstm(cfg, r[0], dtype)
    else:
        raise ValueError(kind)
    if cfg.d_ff > 0 and kind in ("attn", "mamba"):
        p["norm2"] = L.init_norm(cfg, cfg.d_model)
        p["ffn"] = M.init_moe(cfg, r[1], dtype) if is_moe else L.init_ffn(cfg, r[1], dtype)
    return p


_RECURRENT = {"mamba": S.apply_mamba, "mlstm": S.apply_mlstm, "slstm": S.apply_slstm}


def apply_block(
    cfg: ArchConfig,
    kind: str,
    is_moe: bool,
    p: Params,
    x: jax.Array,
    *,
    positions: Optional[jax.Array],
    cache: Optional[Params],
    cache_pos: Optional[jax.Array],
    layer: Optional[jax.Array] = None,
) -> Tuple[jax.Array, Dict[str, jax.Array], Optional[Params]]:
    """One block. ``cache``, when given, is this block's cache stacked over
    the layer groups; the block updates group ``layer`` of it and returns the
    stack: attention writes only its new tokens' K/V, a recurrent block its
    whole (small) state."""
    aux = {k: jnp.zeros((), jnp.float32) for k in AUX_KEYS}
    h = L.apply_norm(cfg, p["norm1"], x)
    new_cache = cache
    if kind == "attn":
        y, new_cache = L.attention(
            cfg, p["mixer"], h, positions=positions, cache=cache, cache_pos=cache_pos,
            layer=layer,
        )
    elif kind in _RECURRENT:
        state = None if cache is None else jax.tree.map(
            lambda a: jax.lax.dynamic_index_in_dim(a, layer, keepdims=False), cache)
        y, state = _RECURRENT[kind](cfg, p["mixer"], h, state=state)
        if cache is not None:
            new_cache = jax.tree.map(
                lambda a, s: jax.lax.dynamic_update_index_in_dim(a, s, layer, 0), cache, state)
    else:
        raise ValueError(kind)
    x = x + y
    if cfg.d_ff > 0 and kind in ("attn", "mamba"):
        h2 = L.apply_norm(cfg, p["norm2"], x)
        if is_moe:
            y2, moe_aux = M.apply_moe(cfg, p["ffn"], h2)
            aux = {k: aux[k] + moe_aux.get(k, 0.0) for k in AUX_KEYS}
        else:
            y2 = L.apply_ffn(cfg, p["ffn"], h2)
        x = x + y2
    return x, aux, new_cache


def init_block_cache(cfg: ArchConfig, kind: str, batch: int, max_len: int, dtype) -> Params:
    if kind == "attn":
        return L.init_attn_cache(cfg, batch, max_len, dtype)
    if kind == "mamba":
        return S.init_mamba_state(cfg, batch, dtype)
    if kind == "mlstm":
        return S.init_mlstm_state(cfg, batch)
    if kind == "slstm":
        return S.init_slstm_state(cfg, batch)
    raise ValueError(kind)


# -------------------------------------------------------------------- stack


def init_stack(cfg: ArchConfig, rng, dtype) -> Params:
    sig = period_signature(cfg)
    G = n_groups(cfg)

    def init_group(key):
        ks = jax.random.split(key, len(sig))
        return {
            f"b{j}": init_block(cfg, kind, is_moe, ks[j], dtype)
            for j, (kind, is_moe) in enumerate(sig)
        }

    keys = jax.random.split(rng, G)
    groups = [init_group(k) for k in keys]
    return jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *groups)


def init_stack_cache(cfg: ArchConfig, batch: int, max_len: int, dtype=jnp.bfloat16) -> Params:
    sig = period_signature(cfg)
    G = n_groups(cfg)
    one = {
        f"b{j}": init_block_cache(cfg, kind, batch, max_len, dtype)
        for j, (kind, is_moe) in enumerate(sig)
    }
    return jax.tree_util.tree_map(lambda a: jnp.stack([a] * G), one)


def apply_stack(
    cfg: ArchConfig,
    stack_params: Params,
    x: jax.Array,
    *,
    positions: Optional[jax.Array] = None,
    caches: Optional[Params] = None,
    cache_pos: Optional[jax.Array] = None,
    train: bool = False,
    gather_fn=None,
) -> Tuple[jax.Array, Dict[str, jax.Array], Optional[Params]]:
    """Scan the layer groups over ``stack_params``.

    caches (optional): the stacked per-group caches (``init_stack_cache``).
    They ride in the scan's carry, not its xs/ys, so each block updates its
    group's part of the one stacked buffer in place and no step moves a
    whole cache; the updated stack is returned as ``new_caches``.

    gather_fn (optional): FSDP weight streaming — applied to each group's
    parameter subtree INSIDE the scan body, so only one layer-group of full
    weights is live at a time (ZeRO-3). Its autodiff transpose produces the
    per-group reduce-scatter of gradients for free."""
    sig = period_signature(cfg)

    def group(gp, x, aux, gc, layer):
        if gather_fn is not None:
            gp = gather_fn(gp)
        for j, (kind, is_moe) in enumerate(sig):
            x, a, c = apply_block(
                cfg, kind, is_moe, gp[f"b{j}"], x,
                positions=positions, cache=None if gc is None else gc[f"b{j}"],
                cache_pos=cache_pos, layer=layer,
            )
            aux = {k: aux[k] + a[k] for k in AUX_KEYS}
            if gc is not None:
                gc = {**gc, f"b{j}": c}
        return x, aux, gc

    aux0 = {k: jnp.zeros((), jnp.float32) for k in AUX_KEYS}
    if caches is not None:
        def cached_body(carry, xs):
            x, aux, gc = carry
            layer, gp = xs
            return group(gp, x, aux, gc, layer), None

        G = n_groups(cfg)
        (x, aux, new_caches), _ = jax.lax.scan(
            cached_body, (x, aux0, caches), (jnp.arange(G), stack_params))
        return x, aux, new_caches

    def group_body(carry, gp):
        x, aux = carry
        x, aux, _ = group(gp, x, aux, None, None)
        return (x, aux), None

    if train and cfg.remat != "none":
        policy = (
            jax.checkpoint_policies.checkpoint_dots
            if cfg.remat == "dots"
            else jax.checkpoint_policies.nothing_saveable
        )
        group_body = jax.checkpoint(group_body, policy=policy, prevent_cse=False)

    (x, aux), _ = jax.lax.scan(group_body, (x, aux0), stack_params)
    return x, aux, None
