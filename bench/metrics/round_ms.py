"""Wall time of the window over all rounds of all jobs in it (host clock)."""


def read(run):
    return run.window_s * 1e3 / run.rounds
