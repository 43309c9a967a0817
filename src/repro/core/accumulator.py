"""DAddAccumulator — STEP §4.4/§5.2, in both its host form and its SPMD form.

The paper's accumulator: N threads each split a local V-vector into M chunks;
chunk *i* goes to node *i*, which reduces its chunk locally and writes it into
the output shared array.  Total wire traffic drops from ``(2N+1)·V`` (send all
vectors to one node, reduce, send the result back) to ``(N+1)·V``.

On a TPU mesh that schedule *is* reduce-scatter: ``psum_scatter`` leaves shard
*i* of the sum on device *i* (each device "owns" its chunk, exactly the
watcher-node role), and an optional ``all_gather`` republishes the full vector.
The naive baseline corresponds to an ``all_gather`` of whole vectors followed
by a local reduction (what a driver-aggregation system does).

Two layers:

* **SPMD functions** (``accumulate`` / ``accumulate_scatter``) — used inside
  ``shard_map`` by the production training path, the analytics apps and the
  ZeRO-1 optimizer.  Modes: ``gather_all`` (strawman), ``reduce_scatter``
  (paper), ``hierarchical`` (paper §4.5 node-local-combine → cross-pod),
  ``sparse`` (top-k pairs), ``auto`` (paper's rule, lossless by construction).
* **DAddAccumulator** — the host-side class with the paper's exact API
  (``Accumulate(local, len)`` blocking until all N threads contribute), used by
  the Pthreads-style thread pool.  It *accounts traffic per mode* so the
  ``(2N+1)·V → (N+1)·V`` claim is assertable in tests.

Sparse parity contract (both layers): a contribution keeps the *same*
blocked top-k selection (:func:`~repro.core.sparse.block_layout`).  The SPMD
collective compresses it with :func:`~repro.core.sparse.blocked_topk_sparsify`
(Pallas ``topk_compress`` kernel, interpret mode off-TPU) and sums the
scattered pairs; the host class sums the round in one fused sparsify→
scatter-add launch (:func:`~repro.core.sparse.blocked_topk_accumulate`),
bit-exact with that chain.  Wire traffic is ``2 · pair_capacity(V, k)``
elements per contribution plus the ``V``-element republish — the static
length of the pair arrays, never a dense sum with sparse accounting.
Compression is lossy iff some block's nnz exceeds its per-block quota;
``auto`` only selects pairs when they are lossless AND cheaper, so it never
changes results.
"""

from __future__ import annotations

import threading
import time
from enum import Enum
from typing import Optional

import jax
import jax.numpy as jnp

from repro.check import checker as stepcheck
from repro.core import telemetry
from repro.core.addressing import align_up
from repro.core.compat import axis_size as compat_axis_size
from repro.core.sparse import (
    DEFAULT_BLOCK,
    blocked_topk_accumulate,
    blocked_topk_sparsify,
    default_auto_k,
    densify,
    pair_capacity,
    sparse_beneficial,
    sparse_beneficial_batch,
)


class AccumMode(str, Enum):
    GATHER_ALL = "gather_all"          # (2N+1)V-class strawman
    REDUCE_SCATTER = "reduce_scatter"  # (N+1)V-class, the paper's accumulator
    HIERARCHICAL = "hierarchical"      # §4.5: combine per node, then across
    SPARSE = "sparse"                  # (index,value) pairs
    AUTO = "auto"                      # paper's auto rule


# ---------------------------------------------------------------------------
# SPMD layer (inside shard_map: `axis` names are mesh axes)
# ---------------------------------------------------------------------------


def _axis_size(axis) -> int:
    return compat_axis_size(axis)


def _pad_to(x: jax.Array, multiple: int) -> jax.Array:
    n = x.shape[0]
    target = align_up(n, multiple)
    return jnp.pad(x, [(0, target - n)] + [(0, 0)] * (x.ndim - 1))


def accumulate_scatter(x: jax.Array, axis) -> jax.Array:
    """Reduce-scatter: return this device's owned chunk of the global sum.

    This is the paper's "node i receives chunk i and reduces locally" —
    the primitive behind ZeRO-1 (the owner then updates its optimizer shard).
    """
    n_dev = _axis_size(axis)
    xp = _pad_to(x, n_dev)
    return jax.lax.psum_scatter(xp, axis, scatter_dimension=0, tiled=True)


def _gather_chunks(chunk: jax.Array, axis, orig_len: int) -> jax.Array:
    full = jax.lax.all_gather(chunk, axis, axis=0, tiled=True)
    return full[:orig_len] if full.shape[0] != orig_len else full


def accumulate(
    x: jax.Array,
    axis,
    mode: AccumMode | str = AccumMode.REDUCE_SCATTER,
    *,
    inner_axis=None,
    outer_axis=None,
    k: Optional[int] = None,
    with_branch: bool = False,
) -> jax.Array:
    """Sum `x` over mesh axis(es); every device receives the full result.

    Must be called inside ``shard_map`` (or under a mesh context with manual
    axes).  `x` is the per-device local vector (leading dim = vector length).

    ``with_branch=True`` (``auto`` mode only) additionally returns the
    globally-agreed branch decision as a traced bool — the hook the SPMD
    session uses to carry a device-side "sparse branch taken" counter out of
    the program, so wire accounting can settle to the branch actually taken.

    The ops are traced under ``jax.named_scope("accumulate.<mode>")`` (and
    ``auto``'s decision under ``accumulate.auto_decide``), which names them
    in a device profile.
    """
    mode = AccumMode(mode)
    if with_branch and mode != AccumMode.AUTO:
        raise ValueError("with_branch reports the auto rule's runtime "
                         f"decision; mode {mode.value!r} has no branch")
    with jax.named_scope(f"accumulate.{mode.value}"):
        return _accumulate(x, axis, mode, inner_axis, outer_axis, k, with_branch)


def _accumulate(x, axis, mode: AccumMode, inner_axis, outer_axis, k, with_branch):
    n = x.shape[0]

    if mode == AccumMode.GATHER_ALL:
        # strawman: everyone receives every vector, reduces locally.
        allv = jax.lax.all_gather(x, axis, axis=0)          # (N, V)
        return jnp.sum(allv, axis=0)

    if mode == AccumMode.REDUCE_SCATTER:
        chunk = accumulate_scatter(x, axis)
        return _gather_chunks(chunk, axis, n)

    if mode == AccumMode.HIERARCHICAL:
        # paper §4.5: one combine inside the node (pod), then across nodes.
        inner = inner_axis if inner_axis is not None else axis
        outer = outer_axis
        chunk = accumulate_scatter(x, inner)                 # intra-pod RS
        if outer is not None:
            chunk = jax.lax.psum(chunk, outer)               # cross-pod on 1/N of data
        return _gather_chunks(chunk, inner, n)               # intra-pod AG

    if mode == AccumMode.SPARSE:
        if k is None:
            raise ValueError("sparse mode needs a top-k budget k")
        pairs = blocked_topk_sparsify(x, k)     # Pallas kernel (interpret off-TPU)
        all_idx = jax.lax.all_gather(pairs.idx, axis, axis=0)   # (N, P) ints
        all_val = jax.lax.all_gather(pairs.vals, axis, axis=0)  # (N, P)
        return densify(all_idx, all_val, n)

    if mode == AccumMode.AUTO:
        if k is None:
            k = default_auto_k(n)
        # the paper's rule must agree across devices: decide on the *global*
        # benefit (all_gather of one scalar nnz flag).
        with jax.named_scope("accumulate.auto_decide"):
            my_ok = sparse_beneficial(x, k)
            all_ok = jax.lax.all_gather(my_ok, axis)
            use_sparse = jnp.all(all_ok)
        dense_fn = lambda v: accumulate(v, axis, AccumMode.REDUCE_SCATTER)
        sparse_fn = lambda v: accumulate(v, axis, AccumMode.SPARSE, k=k)
        total = jax.lax.cond(use_sparse, sparse_fn, dense_fn, x)
        return (total, use_sparse) if with_branch else total

    raise ValueError(f"unknown accumulator mode: {mode}")


def accumulate_tree(tree, axis, mode=AccumMode.REDUCE_SCATTER, **kw):
    """Accumulate every leaf of a pytree (each flattened to 1-D and restored)."""

    def one(leaf):
        flat = leaf.reshape(-1)
        out = accumulate(flat, axis, mode, **kw)
        return out.reshape(leaf.shape)

    return jax.tree.map(one, tree)


# ---------------------------------------------------------------------------
# Host layer: the paper's class API with per-mode traffic accounting
# ---------------------------------------------------------------------------


class DAddAccumulator:
    """Paper-faithful blocking accumulator for the host thread pool.

    ``Accumulate(tid, local_vec)`` blocks until all N threads have contributed,
    then the sum is written into the output shared array in the
    :class:`~repro.core.dsm.GlobalStore`.  Traffic is accounted per the paper's
    cost model so unit tests can assert (N+1)·V vs (2N+1)·V.

    ``mode=SPARSE`` needs a top-k budget ``k``: the round's contributions are
    stacked and summed by one fused blocked-top-k sparsify→scatter-add launch
    (:func:`~repro.core.sparse.blocked_topk_accumulate`, the same selection
    the SPMD collective's pairs use), and traffic is ``Σ_threads 2·pairs + V``
    with ``pairs = pair_capacity(V, k)``.  ``mode=AUTO`` buffers the round,
    applies the paper's benefit rule to every contribution (lossless AND
    cheaper), and takes the pairs path only when all threads agree —
    mirroring the SPMD collective's globally-agreed branch.  All contributions in a round must have the same
    shape; a ragged contribution raises ``ValueError``, aborts the barrier
    (parked peers get ``BrokenBarrierError``) and poisons the accumulator —
    subsequent rounds raise ``RuntimeError`` instead of publishing.
    """

    def __init__(self, store, output_name: str, n_threads: int, n_nodes: int,
                 mode: AccumMode | str = AccumMode.REDUCE_SCATTER, *,
                 k: Optional[int] = None, block: int = DEFAULT_BLOCK,
                 tracer=None, checker=None):
        self.store = store
        self.tracer = tracer if tracer is not None else telemetry.NULL_TRACER
        self.checker = checker if checker is not None else stepcheck.NULL_CHECKER
        self.output_name = output_name
        self.n = n_threads
        self.m = max(1, n_nodes)
        self.mode = AccumMode(mode)
        if self.mode == AccumMode.SPARSE and k is None:
            raise ValueError("sparse mode needs a top-k budget k")
        self.k = k                  # AUTO with k=None defaults per round (~V/4)
        self.block = block
        self._owner = None          # memoised (ring_version, shard) of output
        self._lock = threading.Lock()
        self._vecs: list = []           # buffered contributions (SPARSE/AUTO)
        self._partial = None            # running sum (fixed dense modes)
        self._count = 0
        self._round_len: Optional[int] = None
        self._round_shape: Optional[tuple] = None
        self._barrier = threading.Barrier(n_threads)
        self._broken = False        # poisoned by an aborted round
        self.bytes_transferred = 0  # wire-traffic in vector *elements*
        self.rounds = 0
        self.last_mode: Optional[AccumMode] = None  # branch taken last round
        self.last_pair_counts: list = []  # per-thread pairs shipped last round

    # modes that can never take the pairs branch keep a running sum — O(V)
    # peak memory per round; SPARSE/AUTO must buffer the N contributions
    # (compression/benefit is per contribution, decided when the round closes)
    _DENSE_MODES = (AccumMode.GATHER_ALL, AccumMode.REDUCE_SCATTER,
                    AccumMode.HIERARCHICAL)

    def _account_dense(self, vec_len: int) -> None:
        if self.mode == AccumMode.GATHER_ALL:
            # every thread ships V to the root; root ships V back to each: (2N+1)V
            self.bytes_transferred += (2 * self.n + 1) * vec_len
        else:
            # each thread ships its V once (chunked to owners); owners write V
            self.bytes_transferred += (self.n + 1) * vec_len

    def _abort_round(self) -> None:
        self._broken = True
        self._barrier.abort()

    def _reset_round(self) -> None:
        self._vecs = []
        self._partial = None
        self._count = 0
        self._round_len = None
        self._round_shape = None

    def _reduce_round(self) -> None:
        """Runs under the lock when the round's last contribution arrives.
        Armed, the reduce is one ``accumulate.round`` span carrying the
        branch taken."""
        trc = self.tracer
        if not (telemetry.TRACING and trc.enabled):
            self._reduce(None)
            self._reset_round()
            return
        wire_before = self.bytes_transferred
        vec_len = self._round_len
        with trc.span("accumulate-round", "accumulate.round") as span:
            mode = self._reduce(trc)
            wire = self.bytes_transferred - wire_before
            span.args = {"mode": mode.value, "vec_len": vec_len, "threads": self.n,
                         "pairs": sum(self.last_pair_counts), "wire_elements": wire}
        path = "fused" if mode == AccumMode.SPARSE else "dense"
        trc.count(f"accum.kernel_path.{path}")
        trc.count("accumulate.rounds")
        trc.count("accumulate.wire_elements", wire)
        self._reset_round()

    def _reduce(self, trc) -> AccumMode:
        """Sum the round into the output; returns the branch taken.  ``trc``:
        the armed tracer, else None."""
        tracing = trc is not None
        vec_len, shape = self._round_len, self._round_shape
        if self.mode in self._DENSE_MODES:
            total = self._partial
            self.last_pair_counts = []
            self._account_dense(vec_len)
            mode = self.mode
        else:
            k = self.k if self.k is not None else default_auto_k(vec_len)
            # compression works on flat vectors (scalars and matrices ride
            # along flattened, mirroring the SPMD ctx's rank normalisation)
            flats = [v.reshape(-1) for v in self._vecs]
            mode = self.mode
            span = trc.span if tracing else telemetry.null_span
            if mode == AccumMode.AUTO:
                # pairs only when every contribution is losslessly
                # compressible AND cheaper — the same globally-agreed branch
                # as the collective.  One jitted call decides the whole round
                # (the N contributions are same-shape by the ragged check):
                # a single device sync instead of N small ones per round,
                # which waits for the work that made the contributions too:
                # armed, that wait is the `accumulate.sync` span.
                with span("accumulate-round", "accumulate.sync"):
                    all_ok = bool(sparse_beneficial_batch(flats, k, self.block))
                mode = AccumMode.SPARSE if all_ok else AccumMode.REDUCE_SCATTER
            if mode == AccumMode.SPARSE:
                tc = time.perf_counter() if tracing else 0.0
                # one fused sparsify→scatter-add launch over the stacked
                # round — no pair arrays or dense intermediates; the logical
                # pair count is the static capacity (under jit num_pairs
                # always equals pair_capacity).  Armed, the launch is the
                # `accumulate.fused` span, which waits for the sum.
                with span("accumulate-round", "accumulate.fused"):
                    total = blocked_topk_accumulate(
                        jnp.stack(flats), k, self.block).reshape(shape)
                    if tracing:
                        total.block_until_ready()
                self.last_pair_counts = (
                    [pair_capacity(vec_len, k, self.block)] * self.n)
                if tracing:
                    trc.observe("accumulate.compress",
                                (time.perf_counter() - tc) * 1e6)
                self.bytes_transferred += (
                    sum(2 * c for c in self.last_pair_counts) + vec_len)
            else:
                total = flats[0]
                for f in flats[1:]:
                    total = total + f
                total = total.reshape(shape)
                self.last_pair_counts = []
                self._account_dense(vec_len)
        self.last_mode = mode
        self._store_output(total)
        self.rounds += 1
        return mode

    def _store_output(self, total) -> None:
        """Publish the round sum, with the output's owner shard memoised.

        The output name never changes, so its ring owner is stable between
        rebalances — pass the cached :class:`~repro.core.shards.OwnerHandle`
        to skip the blake2b + bisect on every round (refreshed lazily when
        ``add_shard``/``remove_shard`` bumps the ring version)."""
        store = self.store
        if hasattr(store, "owner_handle"):
            handle = self._owner
            if handle is None or handle.version != store.ring_version:
                handle = store.owner_handle(self.output_name)
                self._owner = handle
            store.set(self.output_name, total, owner=handle)
        else:
            store.set(self.output_name, total)

    def accumulate(self, local_vec) -> None:
        """Paper's ``Accumulate`` — synchronization point across all N threads.

        With an armed tracer, each call records one per-thread span (category
        ``accumulate-round``, name ``accumulate``, entry→barrier release) plus
        a ``barrier-wait`` span for the time parked on the round barrier; the
        round-closing thread additionally records the ``accumulate.round``
        reduce span from :meth:`_reduce_round`."""
        ck = self.checker
        if stepcheck.CHECKING and ck.enabled:
            # publish this thread's clock into the round edge; the collective
            # output write is recorded at the publish-time epoch after the
            # round barrier releases, so peers' post-join clocks dominate it
            token = ck.acc_begin(self)
            self._accumulate_traced(local_vec)
            ck.acc_done(self, self.output_name, token)
            return
        self._accumulate_traced(local_vec)

    def _accumulate_traced(self, local_vec) -> None:
        trc = self.tracer
        if telemetry.TRACING and trc.enabled:
            t0 = time.perf_counter()
            self._accumulate(local_vec, trc)
            trc.wait_span("accumulate-round", "accumulate", t0)
        else:
            self._accumulate(local_vec, None)

    def _accumulate(self, local_vec, trc) -> None:
        local_vec = jnp.asarray(local_vec)
        with self._lock:
            if self._broken:
                # the barrier was aborted by an earlier error; without this
                # guard a later round would publish its sum to the store and
                # THEN raise BrokenBarrierError in every thread
                raise RuntimeError(
                    "DAddAccumulator is unusable after an aborted round — "
                    "create a fresh accumulator")
            if self._count == 0:
                self._round_shape = local_vec.shape
                self._round_len = int(local_vec.size)
            elif local_vec.shape != self._round_shape:
                # release threads already parked on the barrier, drop the
                # poisoned round, then surface
                self._abort_round()
                shape = self._round_shape
                self._reset_round()
                raise ValueError(
                    f"ragged accumulate contribution: round opened with shape "
                    f"{shape}, got {local_vec.shape} — all threads must "
                    "contribute identically-shaped vectors")
            if self.mode in self._DENSE_MODES:
                self._partial = (local_vec if self._partial is None
                                 else self._partial + local_vec)
            else:
                self._vecs.append(local_vec)
            self._count += 1
            if self._count == self.n:
                try:
                    self._reduce_round()
                except BaseException:
                    # never strand the N-1 threads parked on the barrier
                    self._abort_round()
                    self._reset_round()
                    raise
        if trc is not None:
            tb = time.perf_counter()
            self._barrier.wait()
            trc.wait_span("barrier-wait", "accumulate.barrier", tb)
        else:
            self._barrier.wait()

    # paper-cased alias
    Accumulate = accumulate
