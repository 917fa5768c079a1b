"""Device time per train step of the ops under the step's ``train/adamw``
and ``train/gate`` scopes (spmd.build_train_step): the sharded AdamW update
and the quorum gate's blend of the new state with the old."""
from benchmarks.chip import scopes


def read(run):
    a = scopes.for_run(run, "train")
    return a.ms("train/adamw", "train/gate") if a else None
