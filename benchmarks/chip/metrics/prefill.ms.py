"""Device time of one prefill (build_serve_fns prefill -> zoo.prefill), mean
over the window's rounds."""
import statistics


def read(run):
    runs = run.summary.module_runs(r"^jit_prefill\b")
    return 1e3 * statistics.mean(runs) if runs else None
