"""Process start to the window's start: JAX's start, the data made on the
device, and one warm-up job (with its compiles, where the cache lacks them)."""


def read(run):
    return run.setup_s
