"""Device time per train step of the ops under the step's ``train/vote``
scope (spmd.build_train_step): the Fast Raft vote's finiteness and norm pass
over the pre-reduction gradient leaves, and their masking by the vote. An
operation XLA fuses across scopes counts under its own (its root's) scope."""
from benchmarks.chip import scopes


def read(run):
    a = scopes.for_run(run, "train")
    return a.ms("train/vote") if a else None
