"""Distributed shared memory (DSM) — STEP §4.1/§5.1 adapted to a JAX mesh.

The paper keeps globally shared data in an in-memory key-value store; every
thread in the cluster addresses it through a 64-bit ``object_id ++ field_id``
address.  On a TPU pod the analogous substrate is a set of named, *sharded*
``jax.Array``s living across the mesh: the NamedSharding plays the role the KV
store's hash ring played, ICI collectives play the network.

Three STEP concepts are kept first-class:

* **shared variables / arrays / objects** — ``def_global`` / ``new_array`` /
  ``new_object`` mirror ``DefGlobal`` / ``NewArray`` / ``NewObj``.  Objects are
  pytrees of fields under one ``object_id``.
* **fine- vs coarse-grained DSM** (§5.1) — a *layout policy*.  ``coarse`` packs
  pytree leaves into 128-element-aligned flat *packages* (``pack_tree``), so a
  collective over the packed buffer moves few large aligned blocks; ``fine``
  leaves every leaf as its own transfer.
* **host/device split** — between barriers the store owns the arrays (the KV
  store's role); inside a jitted step, state is threaded functionally and the
  store is only consulted for packing metadata.

Since the ``step.shards`` subsystem landed, :class:`GlobalStore` is a thin
facade over :class:`repro.core.shards.ShardedStore`: the namespace is
partitioned over a consistent-hash ring of S shards (``shards=1`` by default,
behaviour-identical to the seed's flat store), each shard owning its entries,
epoch generations, watcher directory and its own lock.  See
:mod:`repro.core.shards` for the ring, the per-shard locking discipline and
elastic rebalancing.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.addressing import TPU_PACKAGE_ELEMS, align_up
from repro.core.shards import (  # noqa: F401  (re-exported, public surface)
    GlobalEntry,
    HashRing,
    MigrationWindow,
    OwnerHandle,
    Shard,
    ShardedStore,
    ShardMigration,
    _nbytes,
)
from repro.core.tiers import (  # noqa: F401  (re-exported, public surface)
    ColdTier,
    DiskTier,
    HostMemTier,
)


class GlobalStore(ShardedStore):
    """The DSM: a named global address space of (optionally sharded) arrays.

    A thin facade over :class:`~repro.core.shards.ShardedStore` — the Table-1
    store API (``def_global`` / ``new_array`` / ``new_object`` / ``get`` /
    ``set`` / ``mget`` / ``inc`` / ``delete``) routed through the consistent-
    hash ring.  ``shards=1`` (the default) is the paper's single-store setup;
    ``shards=S`` partitions the namespace so operations on names owned by
    different shards never contend on a common lock.
    """


# ---------------------------------------------------------------------------
# Coarse-grained packing: fuse a pytree into package-aligned flat buffers.
# This is the TPU realisation of the paper's 32-word packages: collectives over
# the packed representation move one large lane-aligned block instead of one
# (latency-bound) transfer per leaf.
# ---------------------------------------------------------------------------


@dataclass
class PackSpec:
    """Metadata to unpack a fused buffer back into the original pytree."""

    treedef: Any
    shapes: list
    dtypes: list
    offsets: list  # start offset of each leaf in the packed buffer (elements)
    sizes: list    # padded size of each leaf (elements)
    total: int

    @property
    def padding_waste(self) -> int:
        return self.total - sum(int(np.prod(s, dtype=np.int64)) for s in self.shapes)


def pack_spec(tree, *, package: int = TPU_PACKAGE_ELEMS, dtype=jnp.float32) -> PackSpec:
    leaves, treedef = jax.tree.flatten(tree)
    shapes, dtypes, offsets, sizes = [], [], [], []
    off = 0
    for l in leaves:
        shapes.append(tuple(l.shape))
        dtypes.append(jnp.dtype(l.dtype))
        size = align_up(max(1, int(np.prod(l.shape, dtype=np.int64))), package)
        offsets.append(off)
        sizes.append(size)
        off += size
    return PackSpec(treedef, shapes, dtypes, offsets, sizes, off)


def pack_tree(tree, spec: PackSpec, *, dtype=jnp.float32):
    """Fuse all leaves into one package-aligned flat buffer (coarse DSM)."""
    leaves = jax.tree.leaves(tree)
    parts = []
    for l, size in zip(leaves, spec.sizes):
        flat = jnp.ravel(l).astype(dtype)
        parts.append(jnp.pad(flat, (0, size - flat.size)))
    return jnp.concatenate(parts) if parts else jnp.zeros((0,), dtype)


def unpack_tree(buf, spec: PackSpec):
    """Inverse of :func:`pack_tree`."""
    leaves = []
    for shape, dt, off, size in zip(spec.shapes, spec.dtypes, spec.offsets, spec.sizes):
        n = int(np.prod(shape, dtype=np.int64))
        leaves.append(jax.lax.dynamic_slice_in_dim(buf, off, n).astype(dt).reshape(shape))
    return jax.tree.unflatten(spec.treedef, leaves)
