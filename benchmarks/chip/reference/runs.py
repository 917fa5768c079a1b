"""The reference's side of each comparison, from the seed alone: the
weights and rows are made again here (``inputs``), never taken from the
program. ``lowp=True`` runs the control; ``fault`` plants one of the faults
a training cell can have, in the reference put in the program's place."""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.chip import arch, inputs
from benchmarks.chip.reference import qwen3


def _rows(traffic: dict, cfg: dict, seed: int, step: int) -> dict:
    return inputs.train_rows(step, batch=traffic["global_batch"], seq_len=traffic["seq_len"],
                             vocab=cfg["vocab_size"], seed=seed,
                             mean_doc_len=traffic["mean_doc_len"])


def _keep(rows: dict, replicas: int, fault: str | None):
    """Rows and replica count the step sees under ``fault``: "half" keeps the
    first half of each replica's rows (the mean is over those), "solo" keeps
    replica 0's rows alone (no exchange between replicas)."""
    if fault is None:
        return rows, replicas
    B = rows["tokens"].shape[0]
    per = B // replicas
    if fault == "half":
        idx = np.concatenate([np.arange(r * per, r * per + per // 2) for r in range(replicas)])
        return {k: v[idx] for k, v in rows.items()}, replicas
    if fault == "solo":
        return {k: v[:per] for k, v in rows.items()}, 1
    raise ValueError(fault)


def train(cfg: dict, opt: dict, traffic: dict, seed: int, replicas: int, steps: int,
          lowp: bool = False, fault: str | None = None) -> dict:
    """``steps`` AdamW steps from the seed's weights: each step's loss, the
    first gradient as the update used it, and each leaf's change after the
    last step (leaf norms, layer by layer). The moments wait on the host
    while a step's gradient is computed, so that the chip holds only the
    weights, the gradient and one row's activations."""
    key = inputs.arch_key(cfg)
    dtype = arch.DTYPES[cfg["torch_dtype"]]
    p = jax.tree_util.tree_map(lambda x: x.astype(jnp.float32), inputs.make_params(cfg, seed, dtype))
    m = v = jax.tree_util.tree_map(lambda x: np.zeros(x.shape, np.float32), p)
    losses, grad = [], None
    for i in range(steps):
        rows, reps = _keep(_rows(traffic, cfg, seed, i), replicas, fault)
        loss, g = qwen3.loss_and_grad(key, p, jax.tree_util.tree_map(jnp.asarray, rows), reps, lowp)
        losses.append(float(loss))
        p, m, v, g_used = qwen3.adamw_step(opt, i + 1, p, m, v, g)
        m, v = jax.device_get((m, v))
        if i == 0:
            grad = arch.leaf_norms(g_used)
        del g, g_used
    del m, v
    change = arch.leaf_norms(p, inputs.make_params(cfg, seed, dtype))
    return {"losses": losses, "grad": grad, "change": change}


def serve_gaps(cfg: dict, seed: int, requests, lowp: bool = False) -> list:
    """For each (prompt, served tokens) request, the widest gap by which the
    chosen token's logit lies below the float32 reference's best over the
    served positions. The chosen token is the served one, or with ``lowp``
    the one the float8 reference puts first (the control)."""
    key = inputs.arch_key(cfg)
    params = inputs.make_params(cfg, seed, arch.DTYPES[cfg["torch_dtype"]])
    gaps = []
    for prompt, served in requests:
        seq = jnp.asarray(np.concatenate([prompt, served[:-1]]), jnp.int32)
        first = len(prompt) - 1
        ref = qwen3.served_logits(key, params, seq, first, False)
        if lowp:
            chosen = jnp.argmax(qwen3.served_logits(key, params, seq, first, True), axis=-1)
        else:
            chosen = jnp.asarray(served, jnp.int32)
        gap = jnp.max(ref, axis=-1) - jnp.take_along_axis(ref, chosen[:, None], axis=-1)[:, 0]
        gaps.append(float(jnp.max(gap)))
    return gaps
