"""Per job, the compiles that the persistent compilation cache did not
serve: requests that used the cache less hits (jax.monitoring counters)."""

REQUESTS = "/jax/compilation_cache/compile_requests_use_cache"
HITS = "/jax/compilation_cache/cache_hits"


def read(run):
    misses = sum(j.counts.get(REQUESTS, 0) - j.counts.get(HITS, 0) for j in run.jobs)
    return misses / len(run.jobs)
