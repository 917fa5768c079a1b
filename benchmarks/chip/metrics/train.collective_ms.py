"""Time per step in which a collective runs (the fused psum that carries the
Fast Raft vote, the FSDP gathers and reduce-scatters), mean over the chips."""


def read(run):
    s = run.summary
    n = len(s.heaviest_module_runs())            # steps in the traced window
    per = [s.collective(d)[0] for d in range(len(s.ops))]
    if not n or not any(per):
        return None
    return 1e3 * sum(per) / len(per) / n
