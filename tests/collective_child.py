"""Multi-device checks of the in-graph commit barrier. Run in a SUBPROCESS by
test_collective.py (never set XLA_FLAGS globally — see dryrun.py note in
DESIGN.md).

  python tests/collective_child.py               the barrier's functions, 8 devices
  python tests/collective_child.py fast|classic  the train step on a (4, 1) mesh,
                                                 0, 1 and 2 of its replicas poisoned
"""
import os
import re
import sys

os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402
from jax.sharding import PartitionSpec as P  # noqa: E402

from repro.core import collective  # noqa: E402
from repro.launch.mesh import make_mesh  # noqa: E402

assert len(jax.devices()) == 8, jax.devices()


def check_functions():
    mesh = make_mesh((8,), ("data",))
    fq = collective.fast_quorum_size(8)
    assert fq == 6

    def run(fn, *args):
        f = jax.shard_map(fn, mesh=mesh, in_specs=P("data"), out_specs=P(),
                          check_vma=False)
        return jax.jit(f)(*args)

    def fast(votes):
        def one(x, v):
            sums, n_yes, committed = collective.fast_track_psum([x[0]], v[0], ("data",), fq)
            return sums[0], n_yes, committed
        return run(one, jnp.arange(8, dtype=jnp.float32), jnp.asarray(votes, jnp.float32))

    # The fast track sums the leaves and the votes in one psum and commits
    # on ceil(3M/4): 6 of 8, not 5.
    g, n_yes, committed = fast([1, 1, 1, 1, 1, 1, 0, 0])
    assert float(g) == 28.0 and int(n_yes) == 6 and bool(committed), (g, n_yes, committed)
    _, n_yes, committed = fast([1, 1, 1, 1, 1, 0, 0, 0])
    assert int(n_yes) == 5 and not bool(committed), (n_yes, committed)

    # The classic track commits on the quorum it is given.
    def classic(votes, quorum):
        return run(lambda v: collective.classic_track_commit(v[0], ("data",), quorum),
                   jnp.asarray(votes, jnp.float32))

    assert [bool(classic([1, 1, 1, 1, 1, 0, 0, 0], q)[1]) for q in (5, 6)] == [True, False]
    n_yes, committed = classic([1, 1, 1, 1, 1, 1, 0, 0], fq)
    assert int(n_yes) == 6 and bool(committed)

    # A replica with a NaN votes no, and its gated contribution is zero.
    def vote_and_gate(g):
        v = collective.replica_vote(jnp.sum(g[0]), [g[0]])
        return v.reshape(1), jax.lax.psum(collective.gate_contribution(g[0], v), "data")

    g = jnp.ones((8, 4), jnp.float32).at[3, 1].set(jnp.nan)
    f = jax.shard_map(vote_and_gate, mesh=mesh, in_specs=P("data"),
                      out_specs=(P("data"), P()), check_vma=False)
    votes, total = jax.jit(f)(g)
    assert np.asarray(votes).tolist() == [1, 1, 1, 0, 1, 1, 1, 1], votes
    np.testing.assert_array_equal(np.asarray(total), np.full(4, 7.0))

    # The gate keeps the old state bit for bit when c is 0.
    old = {"p": jnp.asarray([1.5, -2.0, 3.25])}
    new = {"p": jnp.asarray([9.0, 9.0, 9.0])}
    for c, want in ((0.0, old), (1.0, new)):
        out = collective.apply_if_committed(jnp.float32(c), new, old)
        assert np.asarray(out["p"]).tobytes() == np.asarray(want["p"]).tobytes()


def vote_all_reduces(hlo: str):
    """Each all-reduce that carries the vote, as the shapes it reduces."""
    op_name = dict(re.findall(r"^\s*(?:ROOT )?%(\S+) = .*?op_name=\"([^\"]*)\"", hlo, re.M))
    found = []
    for shapes, operands in re.findall(
            r"^\s*(?:ROOT )?%\S+ = (.*?) all-reduce(?:-start)?\(([^)]*)\)", hlo, re.M):
        names = re.findall(r"%([\w.-]+)", operands)
        if any(op_name.get(n, "").endswith("train/vote/convert_element_type") for n in names):
            found.append(re.findall(r"\w+\[[\d,]*\]", shapes))
    return found


def check_step(track: str):
    from repro.configs import registry
    from repro.models import zoo
    from repro.optim.adamw import AdamWConfig
    from repro.runtime import spmd

    mesh = make_mesh((4, 1), ("data", "model"), jax.devices()[:4])
    model = zoo.build(registry.get("qwen3-1.7b", reduced=True), dtype=jnp.float32)
    opt = AdamWConfig(warmup_steps=1, total_steps=10)
    step_fn, shardings, batch_fn = spmd.build_train_step(model, opt, mesh, track=track,
                                                         donate=False)
    state = jax.jit(lambda r: spmd.make_train_state(model, opt, r),
                    out_shardings=shardings)(jax.random.PRNGKey(0))
    rng = np.random.RandomState(0)
    raw = {"tokens": rng.randint(0, 256, (4, 16)).astype(np.int32),
           "labels": rng.randint(0, 256, (4, 16)).astype(np.int32),
           "loss_mask": np.ones((4, 16), np.float32)}  # one row a replica

    if track == "fast":
        # The vote shares one all-reduce with gradient leaves.
        hlo = step_fn.lower(state, jax.device_put(raw, batch_fn(raw))).compile().as_text()
        carriers = vote_all_reduces(hlo)
        assert len(carriers) == 1, carriers
        assert "f32[]" in carriers[0] and any(s != "f32[]" for s in carriers[0]), carriers

    def leaves(s):
        return [np.asarray(x) for x in jax.tree_util.tree_leaves(
            (s.params, s.opt.m, s.opt.v, s.opt.master, s.opt.step))]

    before = leaves(state)
    for poisoned in (0, 1, 2):
        rows = dict(raw, loss_mask=raw["loss_mask"].copy())
        rows["loss_mask"][:poisoned] = np.nan
        new, m = step_fn(state, jax.device_put(rows, batch_fn(rows)))
        assert float(m["n_yes"]) == 4 - poisoned, (poisoned, m)
        # A poisoned replica's NaN reaches the streamed stack gradients
        # through the reduction, so even 3 of 4 yes votes roll back.
        committed = float(m["committed"])
        assert committed == (1.0 if poisoned == 0 else 0.0), (poisoned, m)
        after = leaves(new)
        if committed == 0.0:
            assert all(a.tobytes() == b.tobytes() for a, b in zip(before, after)), poisoned
        else:
            assert int(new.opt.step) == int(state.opt.step) + 1
            assert all(np.isfinite(a).all() for a in after)


if __name__ == "__main__":
    if len(sys.argv) > 1:
        check_step(sys.argv[1])
    else:
        check_functions()
    print("COLLECTIVE-OK")
