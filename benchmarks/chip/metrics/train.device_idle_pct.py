"""Share of the training window in which no operation ran on the device,
mean over the chips used: the trainer loop's host work (data, place_batch,
the per-step float() sync) that the device waits for."""


def read(run):
    s = run.summary
    return 100.0 * s.idle_share() if s.window_s > 0 else None
