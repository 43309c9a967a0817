"""The paper's four applications (logreg / kmeans / nmf / pagerank).

Each exposes ``fit_reference`` (single-thread oracle) and ``fit`` — one
backend-agnostic ``thread_proc`` over the `step.Session` facade that runs on
either substrate: ``backend="host"`` (the paper's Pthreads-style DThread +
DSM + accumulator programming model) or ``backend="spmd"`` (one STEP thread
per mesh position via shard_map, the production path)."""

from repro.analytics import kmeans, logreg, nmf, pagerank

__all__ = ["kmeans", "logreg", "nmf", "pagerank"]
