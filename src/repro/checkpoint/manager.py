"""Checkpointing with consensus-committed manifests.

Durability protocol (2-phase, the paper's technique on the control path):
  1. Every host writes its parameter/optimizer shards to
     ``<dir>/step_N/...npy`` plus ``manifest.json.tmp``.
  2. The manifest digest is proposed as a Fast Raft log entry
     (``ckpt:<step>:<digest>``). Only when the entry COMMITS is the manifest
     renamed to ``manifest.json`` — a checkpoint either exists for the whole
     fleet or not at all, and restart-after-failover always agrees on the
     newest committed step (no torn checkpoints after partial pod loss).

Elastic restore: arrays are loaded as host numpy and re-device_put with the
CURRENT mesh's shardings, so the restore mesh may differ from the save mesh
(elastic scaling after node failure).

The async writer runs off the step path; ``wait()`` joins it (called before
the next save or at exit).
"""
from __future__ import annotations

import hashlib
import json
import os
import shutil
import threading
from typing import Any, Callable, Dict, List, Optional, Tuple

import jax
import numpy as np

Params = Any


class SnapshotStore:
    """Durable storage for consensus log-compaction snapshots.

    One JSON file per node, written atomically (tmp + rename) so a crash
    mid-write leaves the previous snapshot intact — the same torn-write
    guarantee the manifest path below gives model checkpoints. Wire it to a
    cluster as each node's ``snapshot_sink``; ``load`` rebuilds the
    :class:`repro.core.types.Snapshot` for cold-start restores.

    What persists is the state machine's OPAQUE reduced state plus the
    client-retry dedup filter (see ``repro.core.statemachine``), not the
    entry list — a KV snapshot on disk is O(live keys) exactly like it is
    on the wire. State must be JSON-serializable (the StateMachine
    contract). Legacy entry-list files load as LogListMachine state, whose
    wire shape they already match.
    """

    def __init__(self, directory: str):
        self.dir = directory
        os.makedirs(directory, exist_ok=True)

    def _path(self, node_id: str) -> str:
        return os.path.join(self.dir, f"consensus_snap_{node_id}.json")

    def save(self, node_id: str, snapshot) -> None:
        payload = {
            "last_index": snapshot.last_index,
            "last_term": snapshot.last_term,
            "members": list(snapshot.members),
            "state": snapshot.state,
            "dedup": snapshot.dedup,
            "version": 2,
        }
        # v2: the full ClusterConfig (voters / learners / joint old_voters)
        # persists next to the legacy flat member list, so a host restored
        # from the checkpoint volume rejoins with exact quorum semantics —
        # a learner must not come back believing it is a voter.
        if snapshot.config is not None:
            payload["config"] = snapshot.config.to_wire()
        # Delta provenance (RaftConfig.delta_snapshots): which base the
        # snapshot's state was reconstructed against, when it arrived as a
        # delta stream. Written only when set so pre-delta files are
        # byte-stable.
        if getattr(snapshot, "delta_base", -1) >= 0:
            payload["delta_base"] = snapshot.delta_base
        tmp = self._path(node_id) + ".tmp"
        with open(tmp, "w") as f:
            json.dump(payload, f)
        os.replace(tmp, self._path(node_id))

    def load(self, node_id: str):
        from repro.core.statemachine import DedupTable
        from repro.core.types import ClusterConfig, EntryId, Snapshot

        path = self._path(node_id)
        if not os.path.exists(path):
            return None
        with open(path) as f:
            payload = json.load(f)
        # Legacy (pre-state-machine) files carry "entries" — the same wire
        # shape LogListMachine state uses — and no dedup filter. Rebuild the
        # filter from the entry ids so client-retry dedup (and the _seq
        # floor) survives a legacy restore instead of silently vanishing.
        state = payload.get("state", payload.get("entries"))
        dedup = payload.get("dedup")
        if dedup is None and isinstance(state, list):
            table = DedupTable()
            for d in state:
                if isinstance(d, dict) and "origin" in d and "seq" in d:
                    table.add(EntryId(d["origin"], d["seq"]))
            dedup = table.state()
        cfg = payload.get("config")  # absent in v1 files: all-voter legacy
        return Snapshot(
            last_index=payload["last_index"],
            last_term=payload["last_term"],
            state=state,
            members=tuple(payload["members"]),
            dedup=dedup,
            config=None if cfg is None else ClusterConfig.from_wire(cfg),
            delta_base=payload.get("delta_base", -1),
        )

    def latest_index(self, node_id: str) -> int:
        snap = self.load(node_id)
        return snap.last_index if snap is not None else 0

    # Raft hard state (term, voted_for, next client seq) — must be durable
    # independently of snapshots: votes change every election and seqs every
    # submission, while snapshots only appear at compaction. A node restored
    # without these could double-vote in a term it voted in, or reuse
    # EntryIds and have fresh commands swallowed as retries.

    def _hard_state_path(self, node_id: str) -> str:
        return os.path.join(self.dir, f"consensus_hard_{node_id}.json")

    def save_hard_state(
        self,
        node_id: str,
        term: int,
        voted_for,
        seq: int,
        floor_index: int = 0,
        floor_term: int = 0,
    ) -> None:
        tmp = self._hard_state_path(node_id) + ".tmp"
        with open(tmp, "w") as f:
            json.dump(
                {
                    "term": term,
                    "voted_for": voted_for,
                    "seq": seq,
                    # Acked-log floor: the store keeps no log, so a restored
                    # node needs this to refuse electing candidates missing
                    # entries it acknowledged before the crash.
                    "floor_index": floor_index,
                    "floor_term": floor_term,
                },
                f,
            )
        os.replace(tmp, self._hard_state_path(node_id))

    def load_hard_state(self, node_id: str):
        """Returns (term, voted_for, seq, floor_index, floor_term) or None.
        Files written before the ack floor existed load with a zero floor."""
        path = self._hard_state_path(node_id)
        if not os.path.exists(path):
            return None
        with open(path) as f:
            payload = json.load(f)
        return (
            payload["term"],
            payload["voted_for"],
            payload["seq"],
            payload.get("floor_index", 0),
            payload.get("floor_term", 0),
        )


def _flatten_with_paths(tree: Params) -> List[Tuple[str, np.ndarray]]:
    flat = jax.tree_util.tree_flatten_with_path(tree)[0]
    out = []
    for path, leaf in flat:
        key = "/".join(str(getattr(k, "key", getattr(k, "idx", k))) for k in path)
        out.append((key, np.asarray(leaf)))
    return out


class CheckpointManager:
    def __init__(
        self,
        directory: str,
        commit_fn: Optional[Callable[[str], bool]] = None,
        keep_last: int = 3,
    ):
        """commit_fn: proposes the manifest record through the control plane
        and returns True once committed. None = local-only commit (tests)."""
        self.dir = directory
        self.commit_fn = commit_fn
        self.keep_last = keep_last
        self._thread: Optional[threading.Thread] = None
        self._error: Optional[BaseException] = None
        os.makedirs(directory, exist_ok=True)

    # ------------------------------------------------------------------ save

    def save(self, step: int, trees: Dict[str, Params], async_: bool = True) -> None:
        self.wait()
        # Materialize on host BEFORE going async (donated buffers may die).
        host_trees = {
            name: _flatten_with_paths(tree) for name, tree in trees.items()
        }

        def work():
            try:
                self._write(step, host_trees)
            except BaseException as e:  # surfaced by wait()
                self._error = e

        if async_:
            self._thread = threading.Thread(target=work, daemon=True)
            self._thread.start()
        else:
            work()
            self._raise_if_failed()

    def _write(self, step: int, host_trees) -> None:
        d = os.path.join(self.dir, f"step_{step:08d}")
        os.makedirs(d, exist_ok=True)
        index = {}
        digest = hashlib.sha256()
        for name, leaves in host_trees.items():
            for key, arr in leaves:
                fname = f"{name}__{key.replace('/', '__')}.npy"
                np.save(os.path.join(d, fname), arr)
                index[f"{name}/{key}"] = {
                    "file": fname, "shape": list(arr.shape), "dtype": str(arr.dtype),
                }
                digest.update(fname.encode())
                digest.update(str(arr.shape).encode())
        manifest = {"step": step, "index": index, "digest": digest.hexdigest()}
        tmp = os.path.join(d, "manifest.json.tmp")
        with open(tmp, "w") as f:
            json.dump(manifest, f)
        # 2-phase commit through the control plane.
        record = f"ckpt:{step}:{manifest['digest']}"
        committed = True if self.commit_fn is None else self.commit_fn(record)
        if committed:
            os.replace(tmp, os.path.join(d, "manifest.json"))
            self._gc()
        # Uncommitted checkpoints keep only the .tmp manifest and are
        # invisible to restore() — exactly the torn-checkpoint guarantee.

    def _gc(self) -> None:
        steps = self.committed_steps()
        for s in steps[: -self.keep_last] if self.keep_last else []:
            shutil.rmtree(os.path.join(self.dir, f"step_{s:08d}"), ignore_errors=True)

    def wait(self) -> None:
        if self._thread is not None:
            self._thread.join()
            self._thread = None
        self._raise_if_failed()

    def _raise_if_failed(self):
        if self._error is not None:
            e, self._error = self._error, None
            raise e

    # --------------------------------------------------------------- restore

    def committed_steps(self) -> List[int]:
        steps = []
        if not os.path.isdir(self.dir):
            return steps
        for name in os.listdir(self.dir):
            if name.startswith("step_") and os.path.exists(
                os.path.join(self.dir, name, "manifest.json")
            ):
                steps.append(int(name[5:]))
        return sorted(steps)

    def latest_step(self) -> Optional[int]:
        steps = self.committed_steps()
        return steps[-1] if steps else None

    def restore(
        self,
        templates: Dict[str, Params],
        step: Optional[int] = None,
        shardings: Optional[Dict[str, Any]] = None,
    ) -> Tuple[int, Dict[str, Params]]:
        """Load into the structure of ``templates``; optionally device_put
        with per-tree shardings (elastic re-shard)."""
        self.wait()
        step = step if step is not None else self.latest_step()
        if step is None:
            raise FileNotFoundError(f"no committed checkpoint in {self.dir}")
        d = os.path.join(self.dir, f"step_{step:08d}")
        with open(os.path.join(d, "manifest.json")) as f:
            manifest = json.load(f)
        out: Dict[str, Params] = {}
        for name, template in templates.items():
            flat, treedef = jax.tree_util.tree_flatten_with_path(template)
            leaves = []
            for path, leaf in flat:
                key = "/".join(
                    str(getattr(k, "key", getattr(k, "idx", k))) for k in path
                )
                entry = manifest["index"][f"{name}/{key}"]
                arr = np.load(os.path.join(d, entry["file"]))
                if arr.dtype.kind == "V":  # .npy keeps bf16 as raw 2-byte records
                    arr = arr.view(np.dtype(entry["dtype"]))
                assert list(arr.shape) == list(leaf.shape), (key, arr.shape, leaf.shape)
                leaves.append(arr.astype(leaf.dtype))
            tree = jax.tree_util.tree_unflatten(
                jax.tree_util.tree_structure(template), leaves
            )
            if shardings is not None and name in shardings and shardings[name] is not None:
                tree = jax.device_put(tree, shardings[name])
            out[name] = tree
        return step, out
