"""Device busy time per round, over the traced jobs (profiler trace)."""


def read(run):
    if run.trace is None or not run.traced_rounds:
        return None
    return run.trace.busy_s * 1e3 / run.traced_rounds
