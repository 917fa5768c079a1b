"""Whole decode step's share of the chip's peak: the least time each step
needs at the published peaks (the larger of its FLOPs over peak FLOP/s and
its bytes over peak HBM bandwidth, where the bytes are the weights and only
the live part of the cache of the requests still being served), summed over
the traced decode steps, over those steps' device time. In decode the byte
bound is the one that binds."""
from benchmarks.chip import peaks, work


def read(run):
    runs = run.summary.module_runs(r"^jit_decode\b")
    # the trace covers the window's first decode steps
    steps = (run.facts.get("decode_steps") or [])[:len(runs)]
    if not runs or not steps:
        return None
    pk = peaks.peaks(run.devices[0].device_kind)
    least = [work.least_time_s(*work.decode_step_work(run.cell.config, [kv] * live), pk)[0]
             for kv, live in steps]
    return 100.0 * (sum(least) / len(least)) / (sum(runs[:len(steps)]) / len(steps))
