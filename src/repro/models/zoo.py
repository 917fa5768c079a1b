"""Public model API: ``build(config) -> Model`` with init / loss / forward /
prefill / decode_step — everything the runtime, dry-run and benchmarks use.

Batch conventions (see ``launch.dryrun.input_specs`` for the dry-run
stand-ins):
  train:   {"tokens": (B,T) i32, "labels": (B,T) i32}            (token archs)
           {"embeddings": (B,T,d) bf16, "labels": (B,T) i32}     (frontend archs)
  prefill: {"tokens"| "embeddings"}                  -> (last_logits, cache)
  decode:  {"tokens": (B,1)}, cache                  -> (logits,     cache)

The modality frontend for [audio]/[vlm] archs is a STUB per the assignment:
precomputed frame/patch embeddings enter where token embeddings would.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig
from repro.models import layers as L
from repro.models import transformer as T
from repro.runtime.tracing import scope

Params = Dict[str, Any]


@dataclasses.dataclass(frozen=True)
class Model:
    cfg: ArchConfig
    dtype: Any = jnp.bfloat16

    # ------------------------------------------------------------------ init

    def init(self, rng) -> Params:
        r = jax.random.split(rng, 3)
        return {
            "embed": L.init_embedding(self.cfg, r[0], self.dtype),
            "stack": T.init_stack(self.cfg, r[1], self.dtype),
            "final_norm": L.init_norm(self.cfg, self.cfg.d_model),
        }

    # ----------------------------------------------------------- embeddings

    @scope("embed")
    def _embed(self, params: Params, batch: Dict[str, jax.Array],
               pos_offset: jax.Array | int = 0) -> jax.Array:
        if "embeddings" in batch:
            h = batch["embeddings"].astype(self.dtype)
        else:
            h = L.embed_lookup(params["embed"]["tok"], batch["tokens"])
        if self.cfg.pos == "learned":
            B, Tn = h.shape[:2]
            idx = jnp.arange(Tn) + pos_offset
            h = h + L.embed_lookup(params["embed"]["pos"], idx)[None]
        return h

    @scope("head")
    def _head(self, params: Params, h: jax.Array) -> jax.Array:
        """The final norm, then the vocabulary projection."""
        h = L.apply_norm(self.cfg, params["final_norm"], h)
        if self.cfg.tie_embeddings:
            return h @ params["embed"]["tok"].T
        return h @ params["embed"]["head"]

    # -------------------------------------------------------------- forward

    def forward(self, params: Params, batch: Dict[str, jax.Array],
                train: bool = False, gather_fn=None) -> Tuple[jax.Array, Dict[str, jax.Array]]:
        h = self._embed(params, batch)
        h, aux, _ = T.apply_stack(self.cfg, params["stack"], h, train=train,
                                  gather_fn=gather_fn)
        logits = self._head(params, h)
        return logits, aux

    def loss(self, params: Params, batch: Dict[str, jax.Array],
             gather_fn=None) -> Tuple[jax.Array, Dict]:
        logits, aux = self.forward(params, batch, train=True, gather_fn=gather_fn)
        labels = batch["labels"]
        with scope("head"):
            lf = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
            nll = -jnp.take_along_axis(lf, labels[..., None], axis=-1)[..., 0]
            mask = batch.get("loss_mask")
            if mask is None:
                ce = jnp.mean(nll)
            else:
                ce = jnp.sum(nll * mask) / jnp.maximum(jnp.sum(mask), 1.0)
        total = ce + sum(aux.values())
        metrics = {"ce": ce, **aux}
        return total, metrics

    # -------------------------------------------------------------- serving

    def init_cache(self, batch_size: int, max_len: int) -> Params:
        return {
            "layers": T.init_stack_cache(self.cfg, batch_size, max_len, self.dtype),
            "pos": jnp.zeros((), jnp.int32),
        }

    def prefill(self, params: Params, batch: Dict[str, jax.Array],
                max_len: int) -> Tuple[jax.Array, Params]:
        """Parallel prompt pass that also populates decode caches: attention
        layers write prompt K/V into cache slots [0, T); recurrent layers
        fold the prompt into their carried state through their chunked
        forms. Every mixer supports multi-token cached steps, so this is one
        fused forward (cache given, cache_pos=0), not T sequential steps."""
        B = (batch.get("tokens", batch.get("embeddings"))).shape[0]
        h = self._embed(params, batch)
        cache = self.init_cache(B, max_len)
        h, aux, new_layers = T.apply_stack(
            self.cfg, params["stack"], h,
            positions=None,
            caches=cache["layers"], cache_pos=jnp.zeros((), jnp.int32),
            train=False,
        )
        logits = self._head(params, h[:, -1:])[:, 0]
        Tn = (batch.get("tokens", batch.get("embeddings"))).shape[1]
        return logits, {"layers": new_layers, "pos": jnp.asarray(Tn, jnp.int32)}

    def decode_step(self, params: Params, cache: Params,
                    batch: Dict[str, jax.Array]) -> Tuple[jax.Array, Params]:
        """One token for every sequence in the batch."""
        pos = cache["pos"]
        h = self._embed(params, batch, pos_offset=pos)
        h, _, new_layers = T.apply_stack(
            self.cfg, params["stack"], h,
            positions=None, caches=cache["layers"], cache_pos=pos, train=False,
        )
        logits = self._head(params, h[:, -1:])[:, 0]
        return logits, {"layers": new_layers, "pos": pos + h.shape[1]}


def build(cfg: ArchConfig, dtype=jnp.bfloat16) -> Model:
    return Model(cfg, dtype)
