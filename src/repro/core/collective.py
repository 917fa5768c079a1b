"""The in-graph commit barrier: Fast Raft's two tracks as collectives.

Inside a compiled SPMD step there is no point-to-point RPC; the unit of a
message round is a collective. ``runtime/spmd.build_train_step`` runs the
barrier once a step, over the data-parallel replica axes (DESIGN.md §14):

  vote          each replica votes 1 when its loss and its pre-reduction
                gradient leaves are finite and their norm is under
                ``VOTE_MAX_NORM`` (``replica_vote``), and gates every
                pre-reduction leaf by that vote (``gate_contribution``), so a
                replica that votes 0 contributes nothing.
  fast track    the vote rides the gradient ``psum`` itself: one tuple
                ``psum`` of the plain leaves and the vote, commit iff
                n_yes >= ceil(3M/4) (``fast_track_psum``; no round of its own).
  classic track the leader-mediated baseline: an ``all_gather`` of the votes
                to the leader, then a ``psum`` broadcast of its verdict
                (``classic_track_commit``; two rounds before the reduction).
  gate          the optimizer's new state applies only on a commit; otherwise
                every replica keeps the old state, the in-graph analogue of a
                tentative log slot rolled back (``apply_if_committed``).

Both tracks commit on the same fast quorum (``fast_quorum_size``). The two
tracks must be called inside ``shard_map`` (they use named axes);
``axis_names`` lists the replica axes, e.g. ("pod", "data").
"""
from __future__ import annotations

import math
from typing import Any, Sequence, Tuple

import jax
import jax.numpy as jnp
from jax import lax

# A replica whose pre-reduction gradient norm reaches this votes no.
VOTE_MAX_NORM = 1e4


def fast_quorum_size(m: int) -> int:
    return math.ceil(3 * m / 4)


def replica_vote(loss: jax.Array, leaves: Sequence[jax.Array]) -> jax.Array:
    """This replica's vote, 1. or 0.: ``loss`` and ``leaves`` are finite and
    the leaves' norm is under ``VOTE_MAX_NORM``."""
    finite = jnp.isfinite(loss)
    sq = jnp.asarray(0.0, jnp.float32)
    for g in leaves:
        finite = jnp.logical_and(finite, jnp.all(jnp.isfinite(g)))
        sq = sq + jnp.sum(jnp.square(g.astype(jnp.float32)))
    return jnp.logical_and(finite, jnp.sqrt(sq) < VOTE_MAX_NORM).astype(jnp.float32)


def gate_contribution(g: jax.Array, vote: jax.Array) -> jax.Array:
    """A pre-reduction leaf as this replica contributes it: itself on a yes
    vote, zeros on a no. ``nan_to_num`` first, since NaN * 0 is NaN and a
    replica votes no exactly when it may hold non-finite values."""
    return (jnp.nan_to_num(g.astype(jnp.float32)) * vote).astype(g.dtype)


def fast_track_psum(
    leaves: Sequence[jax.Array], vote: jax.Array, axis_names: Sequence[str], quorum: int
) -> Tuple[list, jax.Array, jax.Array]:
    """The gradient all-reduce with the vote in the same ``psum``.

    Returns (summed leaves, n_yes, committed), committed iff n_yes >= quorum.
    """
    *sums, n_yes = lax.psum((*leaves, vote), axis_names)
    committed = n_yes >= jnp.asarray(quorum, n_yes.dtype)
    return sums, n_yes, committed


def classic_track_commit(
    vote: jax.Array, axis_names: Sequence[str], quorum: int
) -> Tuple[jax.Array, jax.Array]:
    """Two collective rounds, as leader-mediated Raft: round 1 gathers every
    vote to the leader (replica 0), which commits iff n_yes >= quorum; round
    2 broadcasts the leader's verdict. Returns (n_yes, committed)."""
    votes = vote.reshape(1)
    for a in reversed(axis_names):
        votes = lax.all_gather(votes, a, tiled=True)
    n_yes = jnp.sum(votes)
    decision = (n_yes >= jnp.asarray(quorum, votes.dtype)).astype(votes.dtype)
    is_leader = jnp.asarray(1.0, votes.dtype)
    for a in axis_names:
        is_leader = is_leader * (lax.axis_index(a) == 0).astype(votes.dtype)
    committed = lax.psum(decision * is_leader, axis_names) > 0
    return n_yes, committed


def apply_if_committed(c: jax.Array, new: Any, old: Any) -> Any:
    """``new`` where the quorum committed (``c`` 1.), else ``old`` (``c``
    0.), leaf by leaf, as an f32 blend ``new * c + old * (1 - c)``. ``new``
    must be finite: a NaN in it survives the blend even when ``c`` is 0."""
    return jax.tree_util.tree_map(
        lambda a, b: (a.astype(jnp.float32) * c
                      + b.astype(jnp.float32) * (1 - c)).astype(a.dtype),
        new, old,
    )
