"""Substrate tests: data pipeline determinism/resharding, optimizer,
checkpoint 2-phase commit + elastic restore, control plane services,
trainer integration (train -> crash -> restore -> resume)."""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.checkpoint.manager import CheckpointManager
from repro.configs import registry
from repro.data.pipeline import DataConfig, Prefetcher, ShardLease, SyntheticLM
from repro.optim import adamw
from repro.optim.adamw import AdamWConfig
from repro.runtime.controlplane import ControlPlane
from repro.runtime.trainer import Trainer, TrainerConfig


# ------------------------------------------------------------------- data


def test_data_deterministic_and_reshardable():
    cfg = DataConfig(vocab_size=1000, seq_len=32, global_batch=8, seed=7)
    whole = SyntheticLM(cfg, shard_id=0, n_shards=1).batch_at(5)
    halves = [SyntheticLM(cfg, shard_id=i, n_shards=2).batch_at(5) for i in range(2)]
    rejoined = np.concatenate([h["tokens"] for h in halves], axis=0)
    np.testing.assert_array_equal(whole["tokens"], rejoined)
    # Same (step, shard) always yields identical data.
    again = SyntheticLM(cfg, shard_id=0, n_shards=1).batch_at(5)
    np.testing.assert_array_equal(whole["labels"], again["labels"])


def test_data_prefetcher_order():
    cfg = DataConfig(vocab_size=100, seq_len=8, global_batch=2)
    direct = [SyntheticLM(cfg).batch_at(i)["tokens"] for i in range(4)]
    pre = Prefetcher(SyntheticLM(cfg), depth=2)
    for i in range(4):
        np.testing.assert_array_equal(next(pre)["tokens"], direct[i])


def test_shard_lease_rebalance_minimal_moves():
    lease = ShardLease.balanced(["h0", "h1", "h2"], 6)
    new = lease.rebalance(["h0", "h2"])  # h1 died
    assert set(new.owners.values()) <= {"h0", "h2"}
    moved = sum(1 for s in lease.owners if lease.owners[s] != new.owners[s])
    assert moved == 2  # only h1's shards moved


# ------------------------------------------------------------------ optim


def test_adamw_converges_quadratic():
    cfg = AdamWConfig(lr=0.1, warmup_steps=0, total_steps=200, weight_decay=0.0,
                      clip_norm=10.0)
    params = {"w": jnp.asarray([3.0, -2.0])}
    state = adamw.init(cfg, params)
    for _ in range(200):
        grads = {"w": 2 * params["w"]}
        params, state = adamw.update(cfg, grads, state, params)
    assert float(jnp.max(jnp.abs(params["w"]))) < 1e-2


def test_adamw_clips_global_norm():
    cfg = AdamWConfig(lr=1e-3, clip_norm=1.0, warmup_steps=0, total_steps=10)
    params = {"w": jnp.zeros(4)}
    state = adamw.init(cfg, params)
    g = {"w": jnp.full((4,), 100.0)}
    p1, _ = adamw.update(cfg, g, state, params)
    g2 = {"w": jnp.full((4,), 1e6)}
    p2, _ = adamw.update(cfg, g2, state, params)
    # After clipping, wildly different magnitudes give the same step.
    np.testing.assert_allclose(np.asarray(p1["w"]), np.asarray(p2["w"]), rtol=1e-5)


# -------------------------------------------------------------- checkpoint


def test_checkpoint_roundtrip_and_gc(tmp_path):
    mgr = CheckpointManager(str(tmp_path), keep_last=2)
    tree = {"w": jnp.arange(6.0).reshape(2, 3), "b": jnp.ones(3)}
    for step in (1, 2, 3):
        mgr.save(step, {"state": jax.tree_util.tree_map(lambda x: x * step, tree)},
                 async_=False)
    assert mgr.committed_steps() == [2, 3]  # GC kept last 2
    step, out = mgr.restore({"state": tree})
    assert step == 3
    np.testing.assert_allclose(np.asarray(out["state"]["w"]), np.asarray(tree["w"]) * 3)


def test_checkpoint_roundtrip_bf16(tmp_path):
    """.npy has no bf16 type: the raw records must come back as bf16."""
    mgr = CheckpointManager(str(tmp_path))
    tree = {"w": (jnp.arange(6.0) / 7).astype(jnp.bfloat16)}
    mgr.save(1, {"state": tree}, async_=False)
    _, out = mgr.restore({"state": tree})
    assert out["state"]["w"].dtype == jnp.bfloat16
    np.testing.assert_array_equal(np.asarray(out["state"]["w"], np.float32),
                                  np.asarray(tree["w"], np.float32))


def test_checkpoint_uncommitted_invisible(tmp_path):
    """If the consensus commit fails, the checkpoint must not exist."""
    mgr = CheckpointManager(str(tmp_path), commit_fn=lambda rec: False)
    mgr.save(5, {"state": {"w": jnp.ones(2)}}, async_=False)
    assert mgr.latest_step() is None
    with pytest.raises(FileNotFoundError):
        mgr.restore({"state": {"w": jnp.ones(2)}})


def test_checkpoint_commit_through_fastraft(tmp_path):
    cp = ControlPlane(n_nodes=3, seed=42)
    mgr = CheckpointManager(str(tmp_path), commit_fn=cp.checkpoint_commit_fn())
    mgr.save(7, {"state": {"w": jnp.ones(2)}}, async_=False)
    assert mgr.latest_step() == 7
    assert any(c.startswith("ckpt:7:") for c in cp.applied)


# ------------------------------------------------------------ controlplane


def test_controlplane_leases_and_stragglers():
    cp = ControlPlane(n_nodes=3, seed=1)
    lease = cp.assign_leases(["h0", "h1"], n_shards=4)
    assert lease.shards_of("h0") == [0, 2]
    lease2 = cp.rebalance_leases(["h1"])
    assert set(lease2.owners.values()) == {"h1"}
    for _ in range(3):
        cp.report_straggler("h9", step=1)
    assert "h9" in cp.excluded
    # All records traveled the fast track (proposed via a non-leader).
    assert cp.metrics().counters.get("fast_proposals", 0) >= 3


# ---------------------------------------------------------------- trainer


@pytest.mark.slow  # end-to-end Trainer: multi-step XLA compile + train
def test_trainer_loss_decreases():
    cfg = TrainerConfig(
        arch=registry.get("qwen3-1.7b", reduced=True),
        steps=8, global_batch=4, seq_len=32,
        opt=AdamWConfig(lr=1e-3, warmup_steps=2, total_steps=8),
    )
    logs = Trainer(cfg).train()
    assert logs[-1]["loss"] < logs[0]["loss"]
    assert all(l["committed"] == 1.0 for l in logs)


@pytest.mark.slow  # end-to-end Trainer: multi-step XLA compile + train
def test_trainer_checkpoint_restart_resumes(tmp_path):
    """Train 6 steps w/ ckpt@3, 'crash', build a NEW trainer, resume: the
    resumed run must land on the same final step count and a consistent
    loss trajectory (deterministic data by step index)."""
    common = dict(
        arch=registry.get("qwen3-1.7b", reduced=True),
        global_batch=4, seq_len=32,
        opt=AdamWConfig(lr=1e-3, warmup_steps=2, total_steps=6),
        ckpt_dir=str(tmp_path), ckpt_every=3,
    )
    full = Trainer(TrainerConfig(steps=6, **common)).train()

    # Fresh directory: crash after 3 steps (simulated by steps=3).
    crash_dir = str(tmp_path / "crashy")
    common["ckpt_dir"] = crash_dir
    Trainer(TrainerConfig(steps=3, **common)).train()
    resumed_trainer = Trainer(TrainerConfig(steps=6, **common))
    resumed = resumed_trainer.train()
    assert resumed[0]["data_step"] == 3  # resumed from the committed step
    np.testing.assert_allclose(resumed[-1]["loss"], full[-1]["loss"], rtol=1e-4)


@pytest.mark.slow  # end-to-end Trainer: multi-step XLA compile + train
def test_trainer_consensus_checkpoint_integration(tmp_path):
    cp = ControlPlane(n_nodes=3, seed=9)
    cfg = TrainerConfig(
        arch=registry.get("granite-moe-1b-a400m", reduced=True),
        steps=4, global_batch=4, seq_len=16,
        opt=AdamWConfig(lr=1e-3, warmup_steps=1, total_steps=4),
        ckpt_dir=str(tmp_path), ckpt_every=2,
    )
    logs = Trainer(cfg, control=cp).train()
    assert len(logs) == 4
    assert any(c.startswith("ckpt:") for c in cp.applied)
    assert any(c.startswith("lease:") for c in cp.applied)


@pytest.mark.slow  # end-to-end Trainer: multi-step XLA compile + train
def test_trainer_classic_track_also_works():
    cfg = TrainerConfig(
        arch=registry.get("qwen3-1.7b", reduced=True),
        steps=3, global_batch=4, seq_len=16, track="classic",
        opt=AdamWConfig(lr=1e-3, warmup_steps=1, total_steps=3),
    )
    logs = Trainer(cfg).train()
    assert all(l["committed"] == 1.0 for l in logs)
