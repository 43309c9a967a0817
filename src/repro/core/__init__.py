"""STEP's primary contribution as composable JAX modules.

- :mod:`repro.core.session` - the Table-1 facade: Session / SharedRef / backends
- :mod:`repro.core.dsm` - GlobalStore distributed shared memory (fine/coarse)
- :mod:`repro.core.shards` - consistent-hash sharded store beneath the DSM
- :mod:`repro.core.tiers` - step.tiers: pluggable cold tiers (host mem / disk)
- :mod:`repro.core.accumulator` - DAddAccumulator (SPMD + host forms)
- :mod:`repro.core.cache` - directory-based write-invalidate DSM cache
- :mod:`repro.core.sync` - DBarrier / DSemaphore / SSP clock
- :mod:`repro.core.threads` - DThread pool + shard_map SPMD adapter
- :mod:`repro.core.addressing` - the 64-bit DSM address space
- :mod:`repro.core.telemetry` - step.trace: spans/counters/histograms + export
- :mod:`repro.core.compat` - mesh / shard_map helpers with the repo's defaults

Most programs need only :class:`~repro.core.session.Session`: it owns the
store, cache, thread pool, sync controller and accumulator registry, and the
same workload code runs on the host or SPMD backend.
"""

from repro.core import telemetry
from repro.core.accumulator import AccumMode, DAddAccumulator, accumulate, accumulate_scatter, accumulate_tree
from repro.core.addressing import AddressAllocator, make_address, ring_hash, split_address, watcher_node
from repro.core.cache import DSMCache, CacheStats
from repro.core.compat import axis_size, cost_analysis, make_mesh, shard_map
from repro.core.dsm import GlobalStore, PackSpec, pack_spec, pack_tree, unpack_tree
from repro.core.session import Backend, HostBackend, Session, SharedRef, SpmdBackend, WorkerCtx
from repro.core.shards import (
    HashRing,
    MigrationWindow,
    OwnerHandle,
    Shard,
    ShardedStore,
    ShardMigration,
)
from repro.core.sparse import (
    blocked_topk_accumulate,
    blocked_topk_sparsify,
    densify,
    sparse_beneficial,
    sparse_beneficial_batch,
    topk_sparsify,
)
from repro.core.sync import DBarrier, DSemaphore, SSPClock
from repro.core.tiers import ColdTier, DiskTier, HostMemTier
from repro.core.telemetry import NULL_TRACER, Tracer, as_tracer
from repro.core.threads import DThread, DThreadPool, ThreadState, spmd_threads

__all__ = [
    "AccumMode", "DAddAccumulator", "accumulate", "accumulate_scatter", "accumulate_tree",
    "AddressAllocator", "make_address", "ring_hash", "split_address", "watcher_node",
    "DSMCache", "CacheStats",
    "axis_size", "cost_analysis", "make_mesh", "shard_map",
    "GlobalStore", "PackSpec", "pack_spec", "pack_tree", "unpack_tree",
    "Backend", "HostBackend", "Session", "SharedRef", "SpmdBackend", "WorkerCtx",
    "HashRing", "MigrationWindow", "OwnerHandle", "Shard", "ShardedStore", "ShardMigration",
    "ColdTier", "DiskTier", "HostMemTier",
    "blocked_topk_accumulate", "blocked_topk_sparsify", "densify",
    "sparse_beneficial", "sparse_beneficial_batch", "topk_sparsify",
    "DBarrier", "DSemaphore", "SSPClock",
    "telemetry", "Tracer", "NULL_TRACER", "as_tracer",
    "DThread", "DThreadPool", "ThreadState", "spmd_threads",
]
