"""Trainer: the fault-tolerant end-to-end training loop.

Wires together: model zoo + sharded SPMD train step (with the in-graph Fast
Raft commit barrier) + deterministic data pipeline under consensus-committed
shard leases + AdamW + consensus-committed checkpoints + straggler
reporting. ``train()`` is restartable: on (re)entry it restores the newest
COMMITTED checkpoint and resumes from its step with the data pipeline
re-addressed — crash-at-any-point leaves the fleet one committed checkpoint
behind, never torn.
"""
from __future__ import annotations

import dataclasses
import time
from typing import Any, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh

from repro.checkpoint.manager import CheckpointManager
from repro.configs.base import ArchConfig
from repro.data.pipeline import DataConfig, Prefetcher, SyntheticLM
from repro.launch.mesh import make_host_mesh
from repro.models import zoo
from repro.optim import adamw
from repro.runtime import sharding as shd
from repro.runtime import spmd
from repro.runtime import tracing
from repro.runtime.controlplane import ControlPlane


@dataclasses.dataclass
class TrainerConfig:
    arch: ArchConfig
    steps: int = 50
    opt: adamw.AdamWConfig = dataclasses.field(default_factory=adamw.AdamWConfig)
    global_batch: int = 8
    seq_len: int = 64
    seed: int = 0
    track: str = "fast"            # fast | classic (in-graph consensus)
    compress_pod: bool = False
    ckpt_dir: Optional[str] = None
    ckpt_every: int = 0            # 0 = only final
    keep_last: int = 3
    straggler_ms: float = 1e9      # step-time threshold for reports
    dtype: Any = jnp.float32       # fp32 on CPU test runs; bf16 on TPU


class Trainer:
    def __init__(
        self,
        cfg: TrainerConfig,
        mesh: Optional[Mesh] = None,
        control: Optional[ControlPlane] = None,
        host_id: str = "host0",
    ):
        self.cfg = cfg
        self.mesh = mesh or make_host_mesh()
        self.control = control
        self.host_id = host_id
        self.model = zoo.build(cfg.arch, dtype=cfg.dtype)
        self.state: Optional[spmd.TrainState] = None  # after train()
        self.step_fn, self.state_shardings, self.batch_shard_fn = spmd.build_train_step(
            self.model, cfg.opt, self.mesh, track=cfg.track,
            compress_pod=cfg.compress_pod,
        )
        self.ckpt = (
            CheckpointManager(
                cfg.ckpt_dir,
                commit_fn=control.checkpoint_commit_fn() if control else None,
                keep_last=cfg.keep_last,
            )
            if cfg.ckpt_dir
            else None
        )
        vocab = cfg.arch.vocab_size
        self.data_cfg = DataConfig(
            vocab_size=vocab, seq_len=cfg.seq_len, global_batch=cfg.global_batch,
            seed=cfg.seed,
            emit_embeddings=cfg.arch.d_model if cfg.arch.frontend else 0,
        )
        if control is not None:
            control.assign_leases([host_id], n_shards=1)

    # ----------------------------------------------------------------- state

    def init_state(self) -> spmd.TrainState:
        with self.mesh:
            state = jax.jit(
                lambda rng: spmd.make_train_state(
                    self.model, self.cfg.opt, rng, self.cfg.compress_pod
                ),
                out_shardings=self.state_shardings,
            )(jax.random.PRNGKey(self.cfg.seed))
        return state

    def restore_or_init(self) -> (int, spmd.TrainState):
        if self.ckpt is not None and self.ckpt.latest_step() is not None:
            tpl = jax.eval_shape(
                lambda rng: spmd.make_train_state(
                    self.model, self.cfg.opt, rng, self.cfg.compress_pod
                ),
                jax.random.PRNGKey(0),
            )
            step, trees = self.ckpt.restore(
                {"state": tpl}, shardings={"state": self.state_shardings}
            )
            return step, trees["state"]
        return 0, self.init_state()

    # ----------------------------------------------------------------- train

    def train(self) -> List[Dict[str, float]]:
        cfg = self.cfg
        with tracing.span("train.init"):
            start_step, state = self.restore_or_init()
        data = SyntheticLM(self.data_cfg, shard_id=0, n_shards=1,
                           start_step=start_step)
        it = Prefetcher(data, depth=2)
        logs: List[Dict[str, float]] = []
        with self.mesh:
            for i in range(start_step, cfg.steps):
                with tracing.step_span(i):
                    t0 = time.perf_counter()
                    with tracing.span("train.data"):
                        raw = next(it)
                    with tracing.span("train.place"):
                        batch = self.place_batch(raw)
                    with tracing.span("train.dispatch"):
                        state, metrics = self.step_fn(state, batch)
                    with tracing.span("train.sync"):
                        m = {k: float(v) for k, v in metrics.items()}
                    m["wall_ms"] = (time.perf_counter() - t0) * 1e3
                    m["data_step"] = i
                    logs.append(m)
                    if self.control is not None and m["wall_ms"] > cfg.straggler_ms:
                        with tracing.span("train.straggler"):
                            self.control.report_straggler(self.host_id, i)
                    if self.ckpt and cfg.ckpt_every and (i + 1) % cfg.ckpt_every == 0:
                        with tracing.span("train.ckpt", step=i + 1):
                            self.ckpt.save(i + 1, {"state": state})
            if self.ckpt:
                with tracing.span("train.ckpt", step=cfg.steps):
                    self.ckpt.save(cfg.steps, {"state": state}, async_=False)
                    self.ckpt.wait()
        self.state = state
        return logs

    def place_batch(self, raw: Dict[str, np.ndarray]) -> Dict[str, jax.Array]:
        """Host batch -> device arrays, each split over the mesh's batch
        axes as ``batch_shard_fn`` lays it out (one slice per replica)."""
        batch = {}
        for k, v in raw.items():
            if k == "embeddings":
                batch[k] = np.asarray(v, self.cfg.dtype)
            elif k == "loss_mask":
                batch[k] = np.asarray(v, np.float32)
            else:
                batch[k] = np.asarray(v)
        if self.cfg.arch.frontend is not None:
            batch.pop("tokens", None)
        return jax.device_put(batch, self.batch_shard_fn(batch))
