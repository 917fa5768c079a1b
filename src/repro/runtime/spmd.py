"""SPMD step builders: the data plane of the framework.

Train step = ``shard_map`` MANUAL over the data-parallel axes ("pod","data")
x AUTO over "model" (TP/EP stays with the SPMD partitioner). Manual DP is
what makes the paper's technique first-class in-graph:

  1. FSDP gather:   per-leaf ``all_gather`` over "data" on the leaf's FSDP
                    dim (just-in-time weights; ZeRO-3).
  2. local grad:    each DP replica differentiates its OWN microbatch loss —
                    per-replica gradients exist as real values, not just as
                    HLO internals.
  3. Fast Raft vote: each replica votes "finite & in-bounds". The vote
                    scalar is FUSED into the same psum as the non-FSDP
                    gradient leaves (zero extra rounds — the fast track);
                    FSDP leaves ride ``psum_scatter`` in the same phase.
                    ``track="classic"`` instead runs the two-round
                    gather-to-leader + broadcast baseline.
  4. quorum gate:   the optimizer update applies only on a ceil(3M/4)
                    commit; otherwise every replica rolls the step back —
                    the tentative-slot semantics of the paper, in XLA.
  5. sharded AdamW: optimizer state lives and updates in FSDP+TP shards.

Cross-pod gradient reduction can optionally ride int8 + error feedback
(compress_pod=True) — the DCN hop is the narrow one.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Callable, Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.core.collective import classic_track_commit, fast_quorum_size
from repro.optim import adamw, compression
from repro.runtime import sharding as shd
from repro.runtime.tracing import scope

Params = Any


class TrainState(NamedTuple):
    params: Params
    opt: adamw.OptState
    ef_residual: Optional[Params]  # error-feedback (compress_pod only)


def _dp_size(mesh: Mesh) -> int:
    n = 1
    for a in shd.batch_axes(mesh):
        n *= mesh.shape[a]
    return n


def _gather_params(params, specs):
    def one(p, spec):
        d = shd.fsdp_dim(spec)
        if d is None:
            return p
        return jax.lax.all_gather(p, "data", axis=d, tiled=True)

    return jax.tree_util.tree_map(one, params, specs,
                                  is_leaf=lambda x: isinstance(x, P))


def make_train_state(model, opt_cfg: adamw.AdamWConfig, rng,
                     compress_pod: bool = False) -> TrainState:
    params = model.init(rng)
    opt = adamw.init(opt_cfg, params)
    ef = compression.init_residual(params) if compress_pod else None
    return TrainState(params, opt, ef)


def state_specs(model, opt_cfg: adamw.AdamWConfig, mesh: Mesh,
                compress_pod: bool = False):
    p_tpl = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    p_specs = shd.tree_param_specs(p_tpl, mesh)
    m_specs = p_specs
    master_specs = p_specs if opt_cfg.master_weights else None
    opt_specs = adamw.OptState(m=m_specs, v=m_specs, master=master_specs,
                               step=P())
    ef_specs = p_specs if compress_pod else None
    return TrainState(p_specs, opt_specs, ef_specs)


def build_train_step(
    model,
    opt_cfg: adamw.AdamWConfig,
    mesh: Mesh,
    track: str = "fast",
    compress_pod: bool = False,
    vote_max_norm: float = 1e4,
    donate: bool = True,
    fsdp_stream: bool = True,
) -> Tuple[Callable, TrainState, Any]:
    """Returns (jitted step_fn, state_shardings, batch_sharding_fn).

    step_fn: (TrainState, batch) -> (TrainState, metrics)

    fsdp_stream=True (default): layer-group weights are all-gathered INSIDE
    the stack scan (ZeRO-3 streaming — one group of full weights live at a
    time; gradient reduce-scatter per group comes from the gather's autodiff
    transpose). False = gather the whole tree upfront (the naive baseline
    kept for the §Perf comparison; does not fit HBM for the largest archs).

    Consensus gating granularity (see DESIGN.md): per-replica exclusion via
    the fast vote applies to pre-reduction quantities (loss and the
    non-streamed leaves); streamed-stack gradients are reduced inside
    autodiff, so a poisoned replica there is caught by the global finiteness
    check -> the step rolls back (tentative-slot semantics) and repeated
    rollbacks escalate to control-plane exclusion of the host.
    """
    dp_axes = shd.batch_axes(mesh)
    M = _dp_size(mesh)
    fq = fast_quorum_size(M)
    specs = state_specs(model, opt_cfg, mesh, compress_pod)
    p_specs = specs.params

    def make_gather_fn(stack_specs):
        """Per-group FSDP gather: specs are for STACKED leaves (leading group
        dim); inside the scan body that dim is gone, so the gather axis
        shifts down by one. After the gather the TP placement is re-PINNED
        with an explicit constraint — without it the SPMD partitioner loses
        the model-axis sharding of scan-carried weights and replicates them
        (12x FLOPs + per-group weight gathers; see EXPERIMENTS.md §Perf)."""

        def gather_group(gp):
            def one(p, spec):
                sub = P(*spec[1:])  # drop the stacked group dim
                d = shd.fsdp_dim(sub)
                if d is not None:
                    p = jax.lax.all_gather(p, "data", axis=d, tiled=True)
                pin = shd.strip_axis(sub, "data")
                if any(e is not None for e in pin):
                    p = jax.lax.with_sharding_constraint(
                        p, NamedSharding(mesh, pin)
                    )
                return p

            return jax.tree_util.tree_map(one, gp, stack_specs)

        return gather_group

    def step(state: TrainState, batch) -> Tuple[TrainState, Dict[str, jax.Array]]:
        params = state.params

        with scope("train/fwd_bwd"):
            if fsdp_stream:
                rest = {k: v for k, v in params.items() if k != "stack"}
                rest_specs = {k: p_specs[k] for k in rest}
                rest_full = _gather_params(rest, rest_specs)
                gather_fn = make_gather_fn(p_specs["stack"])

                def loss_fn(diff):
                    rf, local_stack = diff
                    p = dict(rf)
                    p["stack"] = local_stack
                    return model.loss(p, batch, gather_fn=gather_fn)

                (loss, metrics), (g_rest, g_stack) = jax.value_and_grad(
                    loss_fn, has_aux=True
                )((rest_full, params["stack"]))
                # g_stack is ALREADY reduce-scattered+summed over "data" (gather
                # transpose); g_rest is per-replica and full-shaped.
                grads = dict(g_rest)
                grads["stack"] = g_stack
                prereduction = {k: g_rest[k] for k in g_rest}
            else:
                full_params = _gather_params(params, p_specs)

                def loss_fn(fp):
                    return model.loss(fp, batch)

                (loss, metrics), grads = jax.value_and_grad(loss_fn, has_aux=True)(
                    full_params
                )
                prereduction = grads

        # --- Fast Raft vote: this replica's local signals.
        with scope("train/vote"):
            finite = jnp.isfinite(loss)
            sq = jnp.asarray(0.0, jnp.float32)
            for g in jax.tree_util.tree_leaves(prereduction):
                finite = jnp.logical_and(finite, jnp.all(jnp.isfinite(g)))
                sq = sq + jnp.sum(jnp.square(g.astype(jnp.float32)))
            vote = jnp.logical_and(finite, jnp.sqrt(sq) < vote_max_norm).astype(jnp.float32)

            if track == "classic":
                # Baseline: two dedicated vote rounds before the reduction.
                n_yes, committed = classic_track_commit(vote, dp_axes)
                # classic commits on majority; hold it to the same fast quorum
                # for an apples-to-apples gate.
                committed = n_yes >= jnp.asarray(fq, n_yes.dtype)

        # --- Gradient reduction phase.
        flat, treedef = jax.tree_util.tree_flatten_with_path(grads)
        spec_flat = [
            shd.param_spec(shd.path_str(path), g.shape, mesh) for path, g in flat
        ]

        def already_reduced(path, spec) -> bool:
            # Streamed-stack FSDP leaves: the all_gather transpose already
            # reduce-scattered them over "data". Stack leaves WITHOUT an FSDP
            # dim (norm scales, gate biases) stay per-replica and join the
            # fused psum like any other plain leaf.
            return (
                fsdp_stream
                and shd.path_str(path).startswith("stack")
                and shd.fsdp_dim(spec) is not None
            )

        # Per-replica Fast Raft gate on every PRE-reduction leaf: a replica
        # that voted 0 contributes exactly nothing to the committed update.
        with scope("train/vote"):
            flat = [
                (path, g if already_reduced(path, s)
                 else (jnp.nan_to_num(g.astype(jnp.float32)) * vote).astype(g.dtype))
                for (path, g), s in zip(flat, spec_flat)
            ]

        with scope("train/reduce"):
            fsdp_items = [(i, shd.fsdp_dim(s)) for i, s in enumerate(spec_flat)]
            reduced: list = [None] * len(flat)

            # Non-FSDP, per-replica leaves + the vote ride ONE fused psum (the
            # fast track).
            plain_idx = [i for i, d in fsdp_items if d is None]
            plain = tuple(flat[i][1] for i in plain_idx)
            if track == "fast":
                out = jax.lax.psum((*plain, vote), dp_axes)
                *plain_out, n_yes = out
                committed = n_yes >= jnp.asarray(fq, n_yes.dtype)
            else:
                plain_out = list(jax.lax.psum(plain, dp_axes)) if plain else []
            for i, g in zip(plain_idx, plain_out):
                reduced[i] = g

            # FSDP leaves: reduce_scatter over "data" (unless the streaming
            # gather transpose already did it), then the cross-pod hop
            # (optionally int8 + error feedback on the DCN link).
            ef_leaves = (
                jax.tree_util.tree_flatten_with_path(state.ef_residual)[0]
                if state.ef_residual is not None else None
            )
            new_ef_flat: Dict[int, jax.Array] = {}
            for i, d in fsdp_items:
                path, g = flat[i]
                if d is None:
                    continue  # handled in the fused psum above
                pre_done = already_reduced(path, spec_flat[i])
                if (not pre_done) and "data" in dp_axes and mesh.shape["data"] > 1:
                    g = jax.lax.psum_scatter(g, "data", scatter_dimension=d, tiled=True)
                if "pod" in dp_axes:
                    if compress_pod and ef_leaves is not None:
                        gf = g.astype(jnp.float32) + ef_leaves[i][1]
                        scale = jnp.maximum(jnp.max(jnp.abs(gf)), 1e-12) / 127.0
                        q = jnp.clip(jnp.round(gf / scale), -127, 127).astype(jnp.int8)
                        new_ef_flat[i] = gf - q.astype(jnp.float32) * scale
                        qs = jax.lax.all_gather(q, "pod")          # int8 on DCN
                        ss = jax.lax.all_gather(scale, "pod")
                        g = jnp.sum(
                            qs.astype(jnp.float32) * ss.reshape((-1,) + (1,) * g.ndim),
                            axis=0,
                        ).astype(g.dtype)
                    else:
                        g = jax.lax.psum(g, "pod")
                reduced[i] = g
            if state.ef_residual is not None:
                old_flat, ef_def = jax.tree_util.tree_flatten(state.ef_residual)
                new_ef = jax.tree_util.tree_unflatten(
                    ef_def,
                    [new_ef_flat.get(i, old_flat[i]) for i in range(len(old_flat))],
                )
            else:
                new_ef = None

            grads_r = jax.tree_util.tree_unflatten(
                treedef, reduced
            )
            denom = jnp.maximum(n_yes, 1.0)
            grads_r = jax.tree_util.tree_map(lambda g: g / denom.astype(g.dtype), grads_r)

        # Global rollback condition: quorum AND post-reduction finiteness
        # (catches poisoned contributions inside the streamed reductions).
        with scope("train/clip"):
            all_finite = jnp.asarray(True)
            for g in jax.tree_util.tree_leaves(grads_r):
                all_finite = jnp.logical_and(all_finite, jnp.all(jnp.isfinite(g)))
            committed = jnp.logical_and(committed, all_finite)

            # Global grad norm for clipping (scalar psum over FSDP shards).
            local_sq = jnp.asarray(0.0, jnp.float32)
            repl_sq = jnp.asarray(0.0, jnp.float32)
            flat_r = jax.tree_util.tree_flatten_with_path(grads_r)[0]
            for (path, g), s in zip(flat_r, spec_flat):
                gs = jnp.sum(jnp.square(g.astype(jnp.float32)))
                if shd.fsdp_dim(s) is None:
                    repl_sq = repl_sq + gs
                else:
                    local_sq = local_sq + gs
            grad_norm = jnp.sqrt(repl_sq + jax.lax.psum(local_sq, ("data",) if "data" in dp_axes else dp_axes))

        # --- Sharded AdamW on local shards; quorum-gated apply.
        with scope("train/adamw"):
            new_params, new_opt = adamw.update(
                opt_cfg, grads_r, state.opt, params, grad_norm=grad_norm
            )
        c = committed.astype(jnp.float32)

        def gate(new, old):
            return jax.tree_util.tree_map(
                lambda a, b: (a.astype(jnp.float32) * c
                              + b.astype(jnp.float32) * (1 - c)).astype(a.dtype),
                new, old,
            )

        with scope("train/gate"):
            params_out = gate(new_params, params)
            opt_out = adamw.OptState(
                m=gate(new_opt.m, state.opt.m),
                v=gate(new_opt.v, state.opt.v),
                master=gate(new_opt.master, state.opt.master)
                if state.opt.master is not None else None,
                step=state.opt.step + committed.astype(jnp.int32),
            )

        out_metrics = {
            "loss": jax.lax.psum(jnp.nan_to_num(loss) * vote, dp_axes) / denom,
            "grad_norm": grad_norm,
            "n_yes": n_yes,
            "committed": committed.astype(jnp.float32),
            "step": opt_out.step.astype(jnp.float32),
            **{k: jax.lax.psum(jnp.nan_to_num(v) * vote, dp_axes) / denom
               for k, v in metrics.items()},
        }
        return TrainState(params_out, opt_out, new_ef), out_metrics

    # ---- wrap: shard_map manual over DP, auto over model.
    manual = tuple(dp_axes)
    state_manual = jax.tree_util.tree_map(
        lambda s: shd.manual_only(s, manual), specs,
        is_leaf=lambda x: isinstance(x, P),
    )
    batch_spec = P(manual if len(manual) > 1 else manual[0] if manual else None)

    def batch_specs_of(batch):
        return {
            k: P(*( [batch_spec[0]] + [None] * (v.ndim - 1) )) for k, v in batch.items()
        }

    def wrapped(state, batch):
        bs = batch_specs_of(batch)
        f = jax.shard_map(
            step,
            mesh=mesh,
            in_specs=(state_manual, bs),
            out_specs=(state_manual, P()),
            axis_names=set(manual),
            check_vma=False,
        )
        return f(state, batch)

    metrics_sharding = None
    state_shardings = shd.named(mesh, specs)
    jitted = jax.jit(
        wrapped,
        donate_argnums=(0,) if donate else (),
    )

    def shard_batch_spec(batch_tpl):
        return {
            k: NamedSharding(mesh, shd.batch_spec(k, v.shape, mesh))
            for k, v in batch_tpl.items()
        }

    return jitted, state_shardings, shard_batch_spec


# ------------------------------------------------------------------ serving


def build_serve_fns(model, mesh: Mesh, max_len: int):
    """(prefill_fn, decode_fn) jitted with mesh shardings; decode donates the
    cache (in-place KV update)."""

    def prefill(params, batch):
        return model.prefill(params, batch, max_len)

    def decode(params, cache, batch):
        return model.decode_step(params, cache, batch)

    p_tpl = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    # Inference: TP-only shardings (weights replicated over the data axis —
    # no per-step FSDP gathers on the decode path).
    p_specs = shd.tree_param_specs(p_tpl, mesh, fsdp=False)
    p_shard = shd.named(mesh, p_specs)

    prefill_fn = jax.jit(prefill, in_shardings=(p_shard, None))
    decode_fn = jax.jit(decode, in_shardings=(p_shard, None, None),
                        donate_argnums=(1,))
    return prefill_fn, decode_fn
