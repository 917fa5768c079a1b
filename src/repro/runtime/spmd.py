"""SPMD step builders: the data plane of the framework.

Train step = ``shard_map`` MANUAL over the data-parallel axes ("pod","data")
x AUTO over "model" (TP/EP stays with the SPMD partitioner). Manual DP is
what makes the paper's technique first-class in-graph:

  1. FSDP gather:   per-leaf ``all_gather`` over "data" on the leaf's FSDP
                    dim (just-in-time weights; ZeRO-3), inside the stack
                    scan for the layer groups.
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

The barrier's pieces live in ``core/collective.py`` (DESIGN.md §14).
"""
from __future__ import annotations

from typing import Any, Callable, Dict, NamedTuple, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.core import collective
from repro.optim import adamw
from repro.runtime import sharding as shd
from repro.runtime.tracing import scope

Params = Any


class TrainState(NamedTuple):
    params: Params
    opt: adamw.OptState


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


def make_train_state(model, opt_cfg: adamw.AdamWConfig, rng) -> TrainState:
    params = model.init(rng)
    return TrainState(params, adamw.init(opt_cfg, params))


def state_specs(model, opt_cfg: adamw.AdamWConfig, mesh: Mesh):
    p_tpl = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    p_specs = shd.tree_param_specs(p_tpl, mesh)
    master_specs = p_specs if opt_cfg.master_weights else None
    opt_specs = adamw.OptState(m=p_specs, v=p_specs, master=master_specs,
                               step=P())
    return TrainState(p_specs, opt_specs)


def build_train_step(
    model,
    opt_cfg: adamw.AdamWConfig,
    mesh: Mesh,
    track: str = "fast",
    donate: bool = True,
) -> Tuple[Callable, TrainState, Any]:
    """Returns (jitted step_fn, state_shardings, batch_sharding_fn).

    step_fn: (TrainState, batch) -> (TrainState, metrics)

    Layer-group weights are all-gathered INSIDE the stack scan (ZeRO-3
    streaming: one group of full weights live at a time); the gather's
    autodiff transpose hands their gradients back reduce-scattered.

    Consensus gating granularity (DESIGN.md §14): per-replica exclusion via
    the fast vote applies to pre-reduction quantities (loss and the
    non-streamed leaves); streamed-stack gradients are reduced inside
    autodiff, so a poisoned replica there is caught by the global finiteness
    check and the step rolls back (tentative-slot semantics).
    """
    dp_axes = shd.batch_axes(mesh)
    fq = collective.fast_quorum_size(_dp_size(mesh))
    specs = state_specs(model, opt_cfg, mesh)
    p_specs = specs.params

    def make_gather_fn(stack_specs):
        """Per-group FSDP gather: specs are for STACKED leaves (leading group
        dim); inside the scan body that dim is gone, so the gather axis
        shifts down by one. After the gather the TP placement is re-PINNED
        with an explicit constraint — without it the SPMD partitioner loses
        the model-axis sharding of scan-carried weights and replicates them
        (12x FLOPs + per-group weight gathers)."""

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

        # --- Fast Raft vote: this replica's local signals.
        with scope("train/vote"):
            vote = collective.replica_vote(loss, jax.tree_util.tree_leaves(g_rest))
            if track == "classic":
                # Baseline: two dedicated vote rounds before the reduction.
                n_yes, committed = collective.classic_track_commit(vote, dp_axes, fq)

        # --- Gradient reduction phase.
        flat_p, treedef = jax.tree_util.tree_flatten_with_path(grads)
        paths = [shd.path_str(path) for path, _ in flat_p]
        flat = [g for _, g in flat_p]
        spec_flat = [shd.param_spec(path, g.shape, mesh) for path, g in zip(paths, flat)]
        fsdp = [shd.fsdp_dim(s) for s in spec_flat]
        # Streamed-stack FSDP leaves: the all_gather transpose already
        # reduce-scattered them over "data". Stack leaves WITHOUT an FSDP
        # dim (norm scales, gate biases) stay per-replica and join the
        # fused psum like any other plain leaf.
        streamed = [path.startswith("stack") and d is not None
                    for path, d in zip(paths, fsdp)]

        # Per-replica Fast Raft gate on every PRE-reduction leaf: a replica
        # that voted 0 contributes exactly nothing to the committed update.
        with scope("train/vote"):
            flat = [g if st else collective.gate_contribution(g, vote)
                    for g, st in zip(flat, streamed)]

        with scope("train/reduce"):
            # Non-FSDP, per-replica leaves (+ the vote on the fast track)
            # ride ONE psum.
            plain_idx = [i for i, d in enumerate(fsdp) if d is None]
            plain = [flat[i] for i in plain_idx]
            if track == "fast":
                plain_out, n_yes, committed = collective.fast_track_psum(
                    plain, vote, dp_axes, fq)
            else:
                plain_out = jax.lax.psum(tuple(plain), dp_axes) if plain else ()
            reduced = list(flat)
            for i, g in zip(plain_idx, plain_out):
                reduced[i] = g

            # FSDP leaves: reduce_scatter over "data" (unless the streaming
            # gather transpose already did it), then the cross-pod hop.
            for i, d in enumerate(fsdp):
                if d is None:
                    continue
                g = reduced[i]
                if not streamed[i] and "data" in dp_axes and mesh.shape["data"] > 1:
                    g = jax.lax.psum_scatter(g, "data", scatter_dimension=d, tiled=True)
                if "pod" in dp_axes:
                    g = jax.lax.psum(g, "pod")
                reduced[i] = g

            denom = jnp.maximum(n_yes, 1.0)
            reduced = [g / denom.astype(g.dtype) for g in reduced]

        # Global rollback condition: quorum AND post-reduction finiteness
        # (catches poisoned contributions inside the streamed reductions).
        with scope("train/clip"):
            all_finite = jnp.asarray(True)
            for g in reduced:
                all_finite = jnp.logical_and(all_finite, jnp.all(jnp.isfinite(g)))
            committed = jnp.logical_and(committed, all_finite)
            # A poisoned replica's NaN reaches the streamed leaves through the
            # reduction. The step then rolls back, but the update it discards
            # must be finite: the gate's blend keeps a NaN (NaN * 0 is NaN).
            reduced = [jnp.nan_to_num(g) if st else g
                       for g, st in zip(reduced, streamed)]

            # Global grad norm for clipping (scalar psum over FSDP shards).
            local_sq = jnp.asarray(0.0, jnp.float32)
            repl_sq = jnp.asarray(0.0, jnp.float32)
            for g, d in zip(reduced, fsdp):
                gs = jnp.sum(jnp.square(g.astype(jnp.float32)))
                if d is None:
                    repl_sq = repl_sq + gs
                else:
                    local_sq = local_sq + gs
            grad_norm = jnp.sqrt(repl_sq + jax.lax.psum(local_sq, ("data",) if "data" in dp_axes else dp_axes))

        # --- Sharded AdamW on local shards; quorum-gated apply.
        with scope("train/adamw"):
            new_params, new_opt = adamw.update(
                opt_cfg, jax.tree_util.tree_unflatten(treedef, reduced), state.opt,
                params, grad_norm=grad_norm
            )

        c = committed.astype(jnp.float32)
        with scope("train/gate"):
            params_out, m_out, v_out, master_out = collective.apply_if_committed(
                c,
                (new_params, new_opt.m, new_opt.v, new_opt.master),
                (params, state.opt.m, state.opt.v, state.opt.master),
            )
            opt_out = adamw.OptState(
                m=m_out, v=v_out, master=master_out,
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
        return TrainState(params_out, opt_out), out_metrics

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
