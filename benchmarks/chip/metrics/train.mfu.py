"""Model FLOPs utilization of the train step: the model FLOPs one step
requires (forward and backward of every product, causal attention;
recomputation not counted), over the step's mean device time (the compiled
step's runs in the traced window) times the chips times each chip's bf16
peak. The trainer loop's idle time between steps is not in it
(``train.device_idle_pct`` reads that)."""
import statistics

from benchmarks.chip import peaks, work


def read(run):
    runs = run.summary.heaviest_module_runs()
    if not runs:
        return None
    tf = run.cell.traffic
    flops = work.train_step_flops(run.cell.config, tf["global_batch"], tf["seq_len"])
    peak = peaks.peaks(run.devices[0].device_kind)["bf16_flops"]
    return 100.0 * flops / (statistics.mean(runs) * len(run.devices) * peak)
