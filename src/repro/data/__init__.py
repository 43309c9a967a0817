from repro.data.pipeline import LMDataPipeline, Prefetcher, partition_rows, shard_batch
from repro.data.synthetic import (
    SyntheticLM,
    kmeans_dataset,
    lm_batch,
    logreg_dataset,
    nmf_dataset,
    powerlaw_graph,
    rmat_graph,
)

__all__ = [
    "LMDataPipeline", "Prefetcher", "partition_rows", "shard_batch",
    "SyntheticLM", "kmeans_dataset", "lm_batch", "logreg_dataset",
    "nmf_dataset", "powerlaw_graph", "rmat_graph",
]
