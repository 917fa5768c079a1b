"""Multi-device in-graph consensus checks. Run in a SUBPROCESS by
test_collective.py with XLA_FLAGS=--xla_force_host_platform_device_count=8
(never set globally — see dryrun.py note in DESIGN.md)."""
import os

os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.core.collective import (
    classic_track_commit,
    consensus_gradient_sync,
    fast_track_commit,
    masked_update,
    voted_psum,
)
from repro.launch.mesh import make_mesh

assert len(jax.devices()) == 8, jax.devices()
mesh = make_mesh((8,), ("data",))


def run_votes(fn, votes):
    f = jax.shard_map(
        lambda v: fn(v[0], ("data",)),
        mesh=mesh,
        in_specs=P("data"),
        out_specs=(P(), P()),
        check_vma=False,
    )
    return jax.jit(f)(jnp.asarray(votes, jnp.float32))


# fast quorum for M=8 is ceil(24/4)=6
n_yes, committed = run_votes(fast_track_commit, [1, 1, 1, 1, 1, 1, 0, 0])
assert int(n_yes) == 6 and bool(committed), (n_yes, committed)
n_yes, committed = run_votes(fast_track_commit, [1, 1, 1, 1, 1, 0, 0, 0])
assert int(n_yes) == 5 and not bool(committed)

# classic track commits on simple majority (5 of 8)
n_yes, committed = run_votes(classic_track_commit, [1, 1, 1, 1, 1, 0, 0, 0])
assert int(n_yes) == 5 and bool(committed)
n_yes, committed = run_votes(classic_track_commit, [1, 1, 1, 1, 0, 0, 0, 0])
assert not bool(committed)

# voted_psum: sum + quorum in one fused round
def vp(x, v):
    tree, n_yes, committed = voted_psum({"g": x[0]}, v[0], ("data",))
    return tree["g"], n_yes, committed

f = jax.shard_map(vp, mesh=mesh, in_specs=(P("data"), P("data")), out_specs=(P(), P(), P()), check_vma=False)
g, n_yes, committed = jax.jit(f)(
    jnp.arange(8, dtype=jnp.float32), jnp.ones(8, jnp.float32)
)
assert float(g) == 28.0 and int(n_yes) == 8 and bool(committed)

# HLO evidence for the piggyback claim: ONE all-reduce for grads+vote.
lowered = jax.jit(f).lower(
    jax.ShapeDtypeStruct((8,), jnp.float32), jax.ShapeDtypeStruct((8,), jnp.float32)
)
hlo = lowered.compile().as_text()
n_allreduce = hlo.count("all-reduce-start(") + hlo.count(" all-reduce(")
assert n_allreduce <= 1, f"expected fused single all-reduce, got {n_allreduce}"

# consensus_gradient_sync end-to-end: a poisoned replica is excluded.
def sync(g):
    grads = {"w": g}
    mean, n_yes, committed = consensus_gradient_sync(grads, ("data",), track="fast")
    return mean["w"], n_yes, committed

f2 = jax.shard_map(sync, mesh=mesh, in_specs=P("data"), out_specs=(P(), P(), P()), check_vma=False)
g = jnp.ones((8, 4), jnp.float32)
g = g.at[3].set(jnp.nan)  # replica 3 diverged
mean, n_yes, committed = jax.jit(f2)(g)
assert int(n_yes) == 7 and bool(committed)
assert np.allclose(np.asarray(mean), 1.0), mean  # NaN replica excluded from mean

# masked_update rolls back on failed quorum
g = g.at[1:6].set(jnp.nan)  # 5 replicas diverged -> 3 yes votes < fq(8)=6
mean, n_yes, committed = jax.jit(f2)(g)
assert int(n_yes) == 3 and not bool(committed)
new = masked_update(committed, {"p": jnp.ones(3)}, {"p": jnp.zeros(3)})
assert np.allclose(np.asarray(new["p"]), 0.0)

print("COLLECTIVE-OK")
