"""Device time of one training step: the mean run of the heaviest program in
the window (the compiled step, spmd.build_train_step), on device 0."""
import statistics


def read(run):
    runs = run.summary.heaviest_module_runs()
    return 1e3 * statistics.mean(runs) if runs else None
