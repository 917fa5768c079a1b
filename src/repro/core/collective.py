"""In-graph Fast Raft: the TPU-native mapping of the paper's two tracks.

Inside a compiled SPMD step there is no point-to-point RPC; the unit of a
"message round" is a collective. We map:

  fast track    -> ONE ``lax.psum`` of votes over the replica axes;
                   commit iff n_yes >= ceil(3M/4)              (1 round)
  classic track -> ``lax.all_gather`` of votes (leader observes) followed by
                   a leader-decides broadcast ``lax.psum``     (2 rounds)
  piggybacking  -> the vote word is reduced IN THE SAME ``psum`` call as the
                   gradients, so consensus costs ZERO extra collective
                   rounds (beyond-paper optimization; see EXPERIMENTS.md
                   §Perf for the HLO evidence)

Used by the training runtime as the per-step commit barrier: each
data-parallel replica votes "my microbatch gradient is finite and in
bounds"; the optimizer update applies only on a fast-quorum commit,
otherwise the step is skipped (the in-graph analogue of a tentative log slot
being rolled back) and the pathological replica's contribution is excluded.

All functions here must be called inside ``shard_map`` (they use named
axes). ``axis_names`` lists the replica axes, e.g. ("pod", "data").
"""
from __future__ import annotations

import math
from functools import partial
from typing import Any, Sequence, Tuple

import jax
import jax.numpy as jnp
from jax import lax


def _axis_size(axis_names: Sequence[str]) -> int:
    m = 1
    for a in axis_names:
        m *= lax.axis_size(a)
    return m


def fast_quorum_size(m: int) -> int:
    return math.ceil(3 * m / 4)


def majority_size(m: int) -> int:
    return m // 2 + 1


# ---------------------------------------------------------------------------
# Track primitives
# ---------------------------------------------------------------------------


def fast_track_commit(
    vote: jax.Array, axis_names: Sequence[str]
) -> Tuple[jax.Array, jax.Array]:
    """One collective round: psum the votes, commit on a ceil(3M/4) quorum.

    Args:
      vote: scalar in {0., 1.} — this replica's vote.
    Returns:
      (n_yes, committed): replicated scalars.
    """
    m = _axis_size(axis_names)
    n_yes = lax.psum(vote, axis_names)
    committed = n_yes >= jnp.asarray(fast_quorum_size(m), dtype=n_yes.dtype)
    return n_yes, committed


def classic_track_commit(
    vote: jax.Array, axis_names: Sequence[str]
) -> Tuple[jax.Array, jax.Array]:
    """Two collective rounds, structurally mirroring leader-mediated Raft:
    round 1 gathers every vote to the leader; round 2 broadcasts the
    leader's verdict. (Each round is a real collective in the lowered HLO —
    this is the baseline the fast track is measured against.)
    """
    m = _axis_size(axis_names)
    # Round 1: gather votes (the leader — replica 0 — observes the tally).
    votes = vote.reshape(1)
    for a in reversed(axis_names):
        votes = lax.all_gather(votes, a, tiled=True)
    n_yes = jnp.sum(votes)
    decision = (n_yes >= jnp.asarray(majority_size(m), votes.dtype)).astype(votes.dtype)
    # Round 2: only the leader's verdict counts; broadcast it.
    is_leader = jnp.asarray(1.0, votes.dtype)
    for a in axis_names:
        is_leader = is_leader * (lax.axis_index(a) == 0).astype(votes.dtype)
    committed = lax.psum(decision * is_leader, axis_names) > 0
    return n_yes, committed


def voted_psum(
    tree: Any, vote: jax.Array, axis_names: Sequence[str]
) -> Tuple[Any, jax.Array, jax.Array]:
    """Gradient all-reduce with the Fast Raft vote piggybacked.

    The vote scalar rides in the SAME psum call as the gradient leaves, so
    XLA emits one fused all-reduce group — consensus adds zero collective
    rounds. Returns (summed_tree, n_yes, committed).
    """
    m = _axis_size(axis_names)
    leaves, treedef = jax.tree_util.tree_flatten(tree)
    # Pack every leaf AND the vote into one flat f32 buffer so the lowered
    # HLO contains exactly one all-reduce op by construction — tuple psum
    # lowers to one all-reduce per operand and not every backend's combiner
    # pass re-merges them.
    flat = jnp.concatenate(
        [l.astype(jnp.float32).ravel() for l in leaves] + [vote.astype(jnp.float32).reshape(1)]
    )
    summed = lax.psum(flat, axis_names)
    summed_leaves = []
    off = 0
    for l in leaves:
        n = l.size
        summed_leaves.append(summed[off : off + n].reshape(l.shape).astype(l.dtype))
        off += n
    n_yes = summed[off]
    committed = n_yes >= jnp.asarray(fast_quorum_size(m), dtype=n_yes.dtype)
    return jax.tree_util.tree_unflatten(treedef, summed_leaves), n_yes, committed


def masked_update(committed: jax.Array, new_tree: Any, old_tree: Any) -> Any:
    """Apply `new` only when the quorum committed — the in-graph analogue of
    rolling back a tentative slot."""
    def sel(n, o):
        return jnp.where(committed, n, o)

    return jax.tree_util.tree_map(sel, new_tree, old_tree)


# ---------------------------------------------------------------------------
# Step-level consensus barrier used by the Trainer
# ---------------------------------------------------------------------------


def gradient_vote(grads: Any, max_norm: float = 1e4) -> jax.Array:
    """This replica's vote: gradients are finite and in bounds."""
    leaves = jax.tree_util.tree_leaves(grads)
    finite = jnp.asarray(True)
    sq = jnp.asarray(0.0, jnp.float32)
    for g in leaves:
        finite = jnp.logical_and(finite, jnp.all(jnp.isfinite(g)))
        sq = sq + jnp.sum(jnp.square(g.astype(jnp.float32)))
    ok = jnp.logical_and(finite, jnp.sqrt(sq) < max_norm)
    return ok.astype(jnp.float32)


def consensus_gradient_sync(
    grads: Any,
    axis_names: Sequence[str],
    track: str = "fast",
    max_norm: float = 1e4,
) -> Tuple[Any, jax.Array, jax.Array]:
    """All-reduce gradients under a Fast Raft commit barrier.

    track:
      "fast"    — vote piggybacked on the gradient psum (1 fused round).
      "classic" — separate gather + broadcast vote rounds, then the gradient
                  psum (3 collective rounds total; the Raft baseline).

    Pathological replicas are excluded from the mean: each leaf is
    pre-multiplied by the local vote, and the sum is normalized by n_yes —
    so a diverging replica cannot poison a committed step.
    Returns (mean_grads, n_yes, committed).
    """
    vote = gradient_vote(grads, max_norm)
    # nan_to_num before gating: NaN * 0 would still be NaN, and a replica
    # votes 0 exactly when it holds non-finite values.
    gated = jax.tree_util.tree_map(
        lambda g: (jnp.nan_to_num(g.astype(jnp.float32)) * vote).astype(g.dtype),
        grads,
    )
    if track == "fast":
        summed, n_yes, committed = voted_psum(gated, vote, axis_names)
    elif track == "classic":
        n_yes, committed = classic_track_commit(vote, axis_names)
        summed = lax.psum(gated, axis_names)
    else:
        raise ValueError(f"unknown track {track!r}")
    denom = jnp.maximum(n_yes, 1.0)
    mean = jax.tree_util.tree_map(lambda g: (g / denom.astype(g.dtype)), summed)
    return mean, n_yes, committed
