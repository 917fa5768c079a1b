"""Device time of one decode step (build_serve_fns decode -> zoo.decode_step,
attention over the cache), mean over the window's steps."""
import statistics


def read(run):
    runs = run.summary.module_runs(r"^jit_decode\b")
    return 1e3 * statistics.mean(runs) if runs else None
