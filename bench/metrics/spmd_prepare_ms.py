"""Per job, the SPMD join's preparation: the summed ``spmd.trace``,
``spmd.lower`` and ``spmd.compile`` spans of the Session tracer (STEP's own
Python in building the program, JAX's tracing and lowering, the compile or
the persistent-cache fetch)."""

from bench import program

STAGES = ("spmd.trace", "spmd.lower", "spmd.compile")


def read(run):
    if not program.spans(run, ("spmd.compile",)):
        return None             # a program without the staged join
    return program.span_ms_per_job(run, STAGES)
