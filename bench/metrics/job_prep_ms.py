"""Per job, the time JAX spent tracing, lowering, compiling and fetching
programs from the persistent cache (jax.monitoring duration events)."""

EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
          "/jax/core/compile/jaxpr_to_mlir_module_duration",
          "/jax/core/compile/backend_compile_duration",
          "/jax/compilation_cache/cache_retrieval_time_sec")


def read(run):
    total = sum(j.seconds.get(e, 0.0) for j in run.jobs for e in EVENTS)
    return total * 1e3 / len(run.jobs)
