"""Time per step in which a collective runs and no other operation does on
that chip: the exchange the step waits for, mean over the chips."""


def read(run):
    s = run.summary
    n = len(s.heaviest_module_runs())            # steps in the traced window
    per = [s.collective(d) for d in range(len(s.ops))]
    if not n or not any(c for c, _ in per):
        return None
    return 1e3 * sum(e for _, e in per) / len(per) / n
