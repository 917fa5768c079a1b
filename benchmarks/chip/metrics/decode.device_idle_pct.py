"""Share of the serving window in which no operation ran on the device: the
closed loop's host work, the per-step token fetch and the prompt upload."""


def read(run):
    s = run.summary
    return 100.0 * s.idle_share() if s.window_s > 0 else None
