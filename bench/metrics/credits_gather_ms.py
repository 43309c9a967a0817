"""Per traced round, the device time of PageRank's gather: the ops traced
under ``jax.named_scope("pagerank.gather")``, found by the compiled
program's scope map (``bench/program.py``)."""

from bench import program


def read(run):
    return program.scope_ms_per_round(run, "pagerank.gather")
