"""step.Session — the paper's Table 1 as ONE facade over DSM, threads and sync.

STEP's pitch is a single coherent interface: DSM manipulation (DefGlobal /
NewArray / NewObj / Get / Set / Inc / Accumulate), cluster & thread management
(create / start / join / fail), and synchronization (barrier / semaphore /
SSP clock).  This module is that interface.  A :class:`Session` owns the
:class:`~repro.core.dsm.GlobalStore`, the directory-based DSM cache, the sync
controller and the accumulator registry; shared data is declared through it
and handled via typed :class:`SharedRef` handles instead of string-keyed store
access at call sites.

Workloads are written once against the facade::

    sess = Session(backend="host", n_nodes=2, threads_per_node=2)
    grad = sess.new_array("grad", (d,))

    def thread_proc(ctx, xs, ys):          # ctx: tid / guard / iterate
        def step(theta):                   # one synchronous round
            total = grad.accumulate(local_grad(theta, xs, ys))
            return theta + lr * total
        return ctx.iterate(step, jnp.zeros((d,)), iters)

    thetas = sess.run(thread_proc, data=(x, y))

and execute unchanged on either substrate, selected at construction:

* ``backend="host"`` — :class:`HostBackend`: the paper's programming model.
  ``DThreadPool`` threads, blocking ``DAddAccumulator`` rounds, reads served
  through the write-invalidate DSM cache, barrier-based release.
* ``backend="spmd"`` — :class:`SpmdBackend`: one STEP thread per mesh position
  via ``shard_map``.  ``SharedRef.accumulate`` lowers to the reduce-scatter /
  all-gather collective schedule, ``SharedRef.get``/``set`` become the
  per-trace replicated value, and barriers are implicit in the collectives.

The bulk-synchronous contract shared by both backends: within ``thread_proc``,
``ref.set(v)`` must be called with a value that is identical across threads
(all threads re-derive the update from the accumulated total), which is what
makes the host path's N redundant writes and the SPMD path's replicated
update the same program.

Iteration is a framework primitive, not a Python loop: ``ctx.iterate(step,
carry, iters)`` (and the indexed ``ctx.fori``) runs one *logical* loop with
two lowerings — a plain ``ctx.guard()``-per-round loop on the host backend,
and a single ``lax.scan`` on the SPMD backend, so the lowered program (and
compile time) is O(1) in ``iters`` instead of O(iters) unrolled HLO.  The
shared-value dict is threaded through the scan carry, which is what keeps
``SharedRef.get/set/accumulate`` legal inside the step body.
"""

from __future__ import annotations

import itertools
import threading
import warnings
import weakref
from dataclasses import dataclass, field
from typing import Any, Callable, Dict, List, Optional, Protocol, Sequence, runtime_checkable

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro import obs as stepobs
from repro.check import checker as stepcheck
from repro.core import telemetry
from repro.core.accumulator import AccumMode, DAddAccumulator, accumulate as spmd_accumulate
from repro.core.cache import CacheStats, DSMCache
from repro.core.compat import make_mesh, shard_map
from repro.core.dsm import GlobalStore
from repro.core.sparse import default_auto_k, pair_capacity
from repro.core.sync import DBarrier, DSemaphore, SSPClock
from repro.core.threads import DThreadPool, ThreadState
from repro.data.pipeline import partition_rows
from repro.utils.hlo import op_scopes

_SESSION_IDS = itertools.count(1)


# ---------------------------------------------------------------------------
# Handles
# ---------------------------------------------------------------------------


class SharedRef:
    """Typed handle to one piece of shared data in a session's DSM.

    Table 1's access verbs live here: ``get``/``set``/``inc``/``accumulate``.
    Outside a worker they hit the store directly; inside ``Session.spawn`` they
    are routed through the active backend (cache-validated reads and blocking
    accumulator rounds on the host; traced replicated values and collectives
    under SPMD).
    """

    __slots__ = ("_session", "name", "_hcache")

    def __init__(self, session: "Session", name: str):
        self._session = session
        self.name = name
        self._hcache = None  # memoised OwnerHandle, refreshed on ring bumps

    def _owner(self):
        """This name's memoised :class:`~repro.core.shards.OwnerHandle`.

        Resolved lazily and refreshed (by atomic reference swap — handles are
        immutable, so concurrent readers see either the old or the new handle,
        never a torn one) whenever ``add_shard``/``remove_shard`` bumped the
        ring version.  Every hot ``get``/``set``/``inc`` through this ref then
        skips the per-op blake2b + bisect in the store."""
        store = self._session.store
        handle = self._hcache
        if handle is None or handle.version != store.ring_version:
            handle = store.owner_handle(self.name)
            self._hcache = handle
        return handle

    def get(self):
        """``Get`` — current value (cache-validated inside host workers)."""
        return self._session._read(self.name, owner=self._owner())

    def set(self, value) -> None:
        """``Set`` — write-through + invalidate.  Inside a worker this is the
        bulk-synchronous collective write: every thread passes the identical
        re-derived value."""
        self._session._write(self.name, value, owner=self._owner())

    def inc(self, amount=1):
        """``Inc`` — atomic increment; bypasses the cache layer (§5.1).

        N threads calling ``inc(a)`` advance the value by ``N·a`` on both
        backends.  The *return value's* intermediate is backend-specific:
        the host returns each thread's own post-increment snapshot (atomic
        RMW order), SPMD returns the replicated round total — treat the
        return as "some current value", not a unique ticket."""
        return self._session._inc(self.name, amount, owner=self._owner())

    def accumulate(self, local, *, mode: Optional[AccumMode | str] = None,
                   k: Optional[int] = None):
        """``Accumulate`` — contribute this thread's vector, return the global
        sum.  A synchronization point across all threads (§4.4)."""
        return self._session._accumulate(self.name, local, mode, k)

    def delete(self) -> None:
        """``DelArray`` / ``DelObj`` — also purges cache replicas and
        directory records so a re-declared name can never serve the
        deleted-era value."""
        self._session.delete(self.name)

    @property
    def address(self) -> int:
        """64-bit DSM address (``object_id ++ field_id``)."""
        return self._session.store.address(self.name)

    @property
    def epoch(self) -> int:
        return self._session.store.epoch(self.name)

    @property
    def shard(self) -> int:
        """Owning shard id under the store's consistent-hash ring."""
        return self._session.store.shard_of(self.name)

    def __repr__(self) -> str:  # pragma: no cover - debug aid
        return f"SharedRef({self.name!r}, addr=0x{self.address:x})"

    # paper-cased aliases
    Get = get
    Set = set
    Inc = inc
    Accumulate = accumulate


# ---------------------------------------------------------------------------
# Worker contexts (what thread_proc sees)
# ---------------------------------------------------------------------------


class WorkerCtx:
    """One STEP thread's view of the session: identity, sync, ref-op routing,
    and the iteration engine.

    Subclasses plug in the transport (``read``/``write``/``inc``/
    ``accumulate``) and the physical lowering of :meth:`fori`; everything a
    ``thread_proc`` calls is declared here, so workload code is written once
    against this contract and runs on either backend.
    """

    def __init__(self, session: "Session", tid, n_threads: int, node_id):
        self._session = session
        self.tid = tid
        self.n_threads = n_threads
        self.node_id = node_id

    # -- sync ----------------------------------------------------------------

    def guard(self) -> None:
        """Checkpoint boundary: raise inside threads whose node was failed.
        A no-op where node failure is handled below this layer."""
        return None

    def barrier(self, timeout: Optional[float] = None) -> bool:
        return True

    # -- tracing -------------------------------------------------------------

    def span(self, name: str, **args):
        """A user-labelled span — the hook the analytics apps use to mark one
        algorithm round.  On the host backend, with tracing armed, a tracer
        span (category ``app-round``) on this thread's timeline that also
        lands in an active ``jax.profiler`` trace.  Under SPMD the step body
        is traced once into a device program, where per-round host
        timestamps would lie about device execution: there it is
        ``jax.named_scope(name)``, which labels the device ops traced inside
        it (the ``op_name`` of their metadata), armed or not.  ``args`` go
        to the tracer span only.  This base context marks nothing."""
        return telemetry.NULL_SPAN

    # -- iteration engine ----------------------------------------------------

    def iterate(self, step: Callable, carry, iters: int):
        """Run ``carry = step(carry)`` for ``iters`` synchronous rounds.

        The canonical per-thread loop: one *logical* construct with two
        physical lowerings (a guarded Python loop on the host backend, one
        ``lax.scan`` under SPMD — O(1) lowered program size in ``iters``).
        ``SharedRef.get/set/accumulate`` are legal inside ``step``; the carry
        must be a pytree of fixed shape/dtype across rounds (or ``None``).
        """
        return self.fori(lambda i, c: step(c), carry, iters)

    def fori(self, step: Callable, carry, iters: int):
        """Indexed variant: ``carry = step(i, carry)`` for i in [0, iters)."""
        raise NotImplementedError

    # -- ref-op routing (transport is backend-specific; `owner` is the ref's
    # memoised OwnerHandle, meaningful only on store-backed transports) -------

    def read(self, name: str, owner=None):
        raise NotImplementedError

    def write(self, name: str, value, owner=None) -> None:
        raise NotImplementedError

    def inc(self, name: str, amount, owner=None):
        raise NotImplementedError

    def accumulate(self, name: str, local, mode: AccumMode, k: Optional[int]):
        raise NotImplementedError


class HostWorkerCtx(WorkerCtx):
    """One DThread's view: cache-validated reads, blocking accumulator rounds,
    and a plain ``guard()``-per-round iteration loop."""

    def __init__(self, session: "Session", backend: "HostBackend", tid: int):
        super().__init__(session, tid, backend.n_threads,
                         tid // backend.pool.threads_per_node)
        self._backend = backend

    def guard(self) -> None:
        """Raise inside threads whose node was failed (checkpoint boundary)."""
        self._backend.pool.checkpoint_guard(self.tid)

    def barrier(self, timeout: Optional[float] = None) -> bool:
        return self._backend.run_barrier.enter(timeout)

    def span(self, name: str, **args):
        trc = self._session.tracer
        if telemetry.TRACING and trc.enabled:
            return trc.span("app-round", name, **args)
        return telemetry.NULL_SPAN

    # -- iteration: the paper's programming model, round by round ------------

    def fori(self, step: Callable, carry, iters: int):
        for i in range(int(iters)):
            self.guard()
            carry = step(i, carry)
        return carry

    # -- ref-op routing ------------------------------------------------------

    def read(self, name: str, owner=None):
        return self._session._cached_read(self.node_id, name, owner=owner)

    def write(self, name: str, value, owner=None) -> None:
        self._session._cached_write(self.node_id, name, value, owner=owner)

    def inc(self, name: str, amount, owner=None):
        # atomicity comes from the owning shard's lock inside store.inc —
        # increments to names on different shards proceed concurrently
        return self._session.cache.atomic_inc(name, amount, owner=owner)

    def accumulate(self, name: str, local, mode: AccumMode, k: Optional[int]):
        accu = self._backend.accumulator(self._session, name, mode, k)
        accu.accumulate(local)
        return self.read(name)


class SpmdWorkerCtx(WorkerCtx):
    """The traced per-mesh-position view: shared refs are replicated values
    threaded through the trace; barriers are the collectives themselves."""

    def __init__(self, session: "Session", backend: "SpmdBackend", tid,
                 values: Dict[str, Any]):
        super().__init__(session, tid, backend.n_threads, tid)
        self._backend = backend
        self.values = values
        self._accum_repeat = 1  # trip-count multiplier for traffic accounting
        # AUTO branch slots: one per auto-accumulate call site, carrying the
        # *device-side* count of rounds that took the sparse branch plus the
        # static per-round costs of either branch.  `join` settles the
        # trace-time dense upper bound against these counts (ROADMAP item).
        self._auto_slots: List[Dict[str, Any]] = []

    def span(self, name: str, **args):
        return jax.named_scope(name)

    # -- iteration: one lax.scan, O(1) lowered size in `iters` ---------------

    def fori(self, step: Callable, carry, iters: int):
        iters = int(iters)
        if iters <= 0:
            return carry
        # The shared-value dict rides in the scan carry: ref.get/set/accumulate
        # inside `step` read and write the scanned copy, so shared state
        # advances per round exactly as it does on the host backend.
        values0 = jax.tree.map(jnp.asarray, dict(self.values))
        carry0 = jax.tree.map(jnp.asarray, carry)
        slot_meta: List[Dict[str, Any]] = []

        def body(state, i):
            inner_carry, values = state
            outer_values, self.values = self.values, dict(values)
            outer_repeat = self._accum_repeat
            self._accum_repeat = outer_repeat * iters  # nested loops compose
            base = len(self._auto_slots)
            try:
                new_carry = step(i, inner_carry)
                new_values = dict(self.values)
            finally:
                self.values = outer_values
                self._accum_repeat = outer_repeat
            # AUTO branch counters born inside the body ride the scan's
            # stacked outputs; summed below they report how many of the
            # `iters` executions of each call site took the sparse branch
            born = self._auto_slots[base:]
            del self._auto_slots[base:]
            slot_meta[:] = [{k: v for k, v in s.items() if k != "count"}
                            for s in born]
            return (new_carry, new_values), tuple(s["count"] for s in born)

        (carry, values), counts = jax.lax.scan(body, (carry0, values0),
                                               jnp.arange(iters))
        for meta, per_iter in zip(slot_meta, counts):
            self._auto_slots.append(dict(meta, count=jnp.sum(per_iter)))
        self.values.clear()
        self.values.update(values)
        return carry

    # -- ref-op routing (replicated traced values: `owner` has no transport
    # to shortcut and is ignored) --------------------------------------------

    def read(self, name: str, owner=None):
        return self.values[name]

    def write(self, name: str, value, owner=None) -> None:
        self.values[name] = jax.tree.map(jnp.asarray, value)

    def inc(self, name: str, amount, owner=None):
        # `Inc` is per-thread: N threads calling inc(a) must advance the value
        # by N·a, exactly as N atomic increments do on the host backend.  The
        # replicated value is written once per trace, so the per-thread amounts
        # are psum'd over the mesh axis and applied in one replicated update.
        total = jax.lax.psum(jnp.asarray(amount), self._backend.axis)
        self.values[name] = jnp.asarray(self.values[name]) + total
        return self.values[name]

    def accumulate(self, name: str, local, mode: AccumMode, k: Optional[int]):
        vec = local if local.ndim else local[None]   # collectives want rank>=1
        shard = self._session.store.shard_of(name)
        if mode == AccumMode.AUTO:
            # the collective's lax.cond branch is a runtime decision: record a
            # device-side counter (0/1 this execution; ctx.fori sums it across
            # scan rounds) so join() can settle the trace-time dense bound to
            # the branch actually taken, matching host accounting.
            total, took_sparse = spmd_accumulate(vec, self._backend.axis, mode,
                                                 k=k, with_branch=True)
            vec_len = int(local.size)
            k_eff = k if k is not None else default_auto_k(vec_len)
            n = self.n_threads
            self._auto_slots.append({
                "count": took_sparse.astype(jnp.int32),
                "per_sparse": 2 * pair_capacity(vec_len, k_eff) * n + vec_len,
                "per_dense": (n + 1) * vec_len,
                "rounds": self._accum_repeat,
                "shard": shard,
            })
        else:
            total = spmd_accumulate(vec, self._backend.axis, mode, k=k)
        if not local.ndim:
            total = total[0]
        self.values[name] = total
        self._backend.stats.account(mode, self.n_threads, int(local.size), k,
                                    repeat=self._accum_repeat, shard=shard)
        return total


def _warn_at_caller(message: str, category) -> None:
    """Warn with the first stack frame *outside this module* as the location,
    so run/join/lower entry paths all attribute to the user's call site."""
    import sys
    level, frame = 2, sys._getframe(1)
    while frame is not None and frame.f_globals.get("__name__") == __name__:
        frame = frame.f_back
        level += 1
    warnings.warn(message, category, stacklevel=level)


# ---------------------------------------------------------------------------
# Backends
# ---------------------------------------------------------------------------


@runtime_checkable
class Backend(Protocol):
    """Execution substrate behind a Session: place threads, run them, account
    accumulator traffic.  Two implementations ship: :class:`HostBackend` and
    :class:`SpmdBackend`."""

    kind: str

    @property
    def n_threads(self) -> int: ...

    @property
    def n_nodes(self) -> int: ...

    def spawn(self, session: "Session", thread_proc: Callable,
              data: Sequence, broadcast: Sequence) -> None: ...

    def join(self, session: "Session", timeout: Optional[float]) -> List[Any]: ...

    def wire_traffic(self) -> int: ...


class HostBackend:
    """Today's paper-faithful path: DThreadPool + blocking DAddAccumulator
    (whose SPARSE/AUTO pairs rounds reduce in one fused sparsify→scatter-add
    kernel launch)."""

    kind = "host"

    def __init__(self, n_nodes: int = 2, threads_per_node: int = 2):
        self.pool = DThreadPool(n_nodes, threads_per_node)
        self.run_barrier = DBarrier(self.pool.n_threads)
        self._accumulators: Dict[tuple, DAddAccumulator] = {}
        self._lock = threading.Lock()

    @property
    def n_threads(self) -> int:
        return self.pool.n_threads

    @property
    def n_nodes(self) -> int:
        return self.pool.n_nodes

    def accumulator(self, session: "Session", name: str,
                    mode: Optional[AccumMode] = None,
                    k: Optional[int] = None) -> DAddAccumulator:
        """Registry: one accumulator per (output ref, mode, k budget), created
        on first use — so per-call mode/budget switches behave the same as on
        the SPMD path.  ``mode=None`` resolves to the ref's sole existing
        accumulator (the common case for post-run inspection), else the
        session default; ``k=None`` resolves to the ref's declared
        ``sparse_k`` budget."""
        with self._lock:
            if mode is None:
                existing = [a for (n, _, _), a in self._accumulators.items()
                            if n == name]
                if len(existing) == 1:
                    return existing[0]
                mode = session.accum_mode
            mode = AccumMode(mode)
            if k is None:
                k = session.sparse_k(name)
            key = (name, mode, k)
            accu = self._accumulators.get(key)
            if accu is None and k is None:
                # budget-less inspection of a ref that accumulated with a
                # per-call k: resolve to the sole (name, mode) accumulator
                # instead of constructing a fresh zero-traffic one (which for
                # SPARSE would even be unconstructible without a budget)
                matches = [a for (n, m, _), a in self._accumulators.items()
                           if n == name and m == mode]
                if len(matches) == 1:
                    return matches[0]
            if accu is None:
                accu = DAddAccumulator(session.store, name, self.n_threads,
                                       self.n_nodes, mode, k=k,
                                       tracer=session.tracer,
                                       checker=session.checker)
                self._accumulators[key] = accu
            return accu

    def spawn(self, session: "Session", thread_proc: Callable,
              data: Sequence, broadcast: Sequence) -> None:
        n = self.n_threads

        def entry(tid: int, _param):
            lo_hi = [partition_rows(a.shape[0], tid, n) for a in data]
            shards = [a[lo:hi] for a, (lo, hi) in zip(data, lo_hi)]
            ctx = HostWorkerCtx(session, self, tid)
            if telemetry.TRACING and session.tracer.enabled:
                # spans from this OS thread land on (node, tid) timelines
                session.tracer.bind_thread(tid, ctx.node_id)
            ck = session.checker
            if stepcheck.CHECKING and ck.enabled:
                # the worker's vector clock starts from the driver's spawn
                # snapshot (the spawn happens-before edge)
                ck.bind_thread(tid, ctx.node_id)
            session._tls.ctx = ctx
            try:
                return thread_proc(ctx, *shards, *broadcast)
            finally:
                session._tls.ctx = None

        self.pool.create_threads(entry)
        self.pool.start_all()

    def join(self, session: "Session", timeout: Optional[float] = None) -> List[Any]:
        self.pool.join_all(timeout)
        # a thread_proc that raised must not dissolve into a None result —
        # surface the first failure (LOST threads are the FT layer's business)
        failed = [t for t in self.pool.threads if t.state is ThreadState.FAILED]
        if failed:
            raise RuntimeError(
                f"{len(failed)} session thread(s) failed; first: tid "
                f"{failed[0].tid} on node {failed[0].node_id}") from failed[0].error
        return [t.result for t in self.pool.threads]

    def wire_traffic(self) -> int:
        with self._lock:
            return sum(a.bytes_transferred for a in self._accumulators.values())


@dataclass
class SpmdTraffic:
    """Per-call traffic accounting for the SPMD accumulator, mirroring the
    host accumulator's cost model.  Accounting happens at trace time, where
    the data is unknown: ``sparse`` is costed at its top-k budget, and
    ``auto`` provisionally at the dense figure — then settled at ``join``
    time against the device-side branch counter each auto call site threads
    through the program (see :meth:`settle_auto`), so ``wire_traffic()``
    reports the branch actually taken, as the host does.

    ``by_shard`` attributes each call site's traffic to the shard owning the
    output ref — the per-shard half of ``Session.shard_stats()``."""

    bytes_transferred: int = 0
    rounds: int = 0
    by_shard: Dict[int, int] = field(default_factory=dict)

    def _charge(self, amount: int, shard: Optional[int]) -> None:
        self.bytes_transferred += amount
        if shard is not None:
            self.by_shard[shard] = self.by_shard.get(shard, 0) + amount

    def settle_auto(self, slot: Dict[str, Any], sparse_rounds: int) -> None:
        """Replace one auto call site's trace-time dense upper bound with the
        cost of the branches actually taken: ``sparse_rounds`` of its
        ``rounds`` executions took the pairs path, the rest went dense."""
        actual = (sparse_rounds * slot["per_sparse"]
                  + (slot["rounds"] - sparse_rounds) * slot["per_dense"])
        self._charge(actual - slot["rounds"] * slot["per_dense"],
                     slot.get("shard"))

    def account(self, mode: AccumMode, n: int, vec_len: int, k: Optional[int],
                *, repeat: int = 1, shard: Optional[int] = None) -> None:
        """Charge one accumulate call site.  ``vec_len`` is the total element
        count of the local contribution (scalars cost 1, like the host
        accumulator).  ``repeat`` multiplies by the trip count when the call
        site sits inside ``ctx.iterate`` — the scan body is traced once but
        executes ``iters`` rounds.

        ``sparse`` is costed from the pair arrays actually shipped: every
        device all-gathers ``pair_capacity(V, k)`` static (index, value)
        pairs, and the densified result is the ``V``-element republish — the
        same ``Σ 2·pairs + V`` figure the host accumulator derives from its
        per-thread :class:`~repro.core.sparse.SparsePairs`, so
        ``wire_traffic()`` agrees across backends for a sparse round."""
        if mode == AccumMode.GATHER_ALL:
            per_round = (2 * n + 1) * vec_len
        elif mode == AccumMode.SPARSE:
            per_round = 2 * pair_capacity(vec_len, k) * n + vec_len
        else:  # REDUCE_SCATTER / HIERARCHICAL / AUTO (dense, settled at join)
            per_round = (n + 1) * vec_len
        self._charge(per_round * repeat, shard)
        self.rounds += repeat


class SpmdBackend:
    """The production path: one STEP thread per mesh position via shard_map.

    ``spawn`` records the program; ``join`` traces ``thread_proc`` once, runs
    it over the mesh, and writes final shared values back into the session's
    store so the driver-side ``ref.get()`` sees the result exactly as it does
    on the host backend.  Iteration written with ``ctx.iterate`` lowers to one
    ``lax.scan`` (O(1) program size in the trip count); a raw Python loop in
    ``thread_proc`` still works but unrolls into the jitted step.
    """

    kind = "spmd"

    def __init__(self, mesh=None, axis: str = "data", n_threads: Optional[int] = None):
        if mesh is None:
            mesh = make_mesh((n_threads or len(jax.devices()),), (axis,))
        if axis not in mesh.axis_names:
            raise ValueError(f"mesh has axes {mesh.axis_names}, no {axis!r}")
        self.mesh = mesh
        self.axis = axis
        self.stats = SpmdTraffic()
        self._pending = None

    @property
    def n_threads(self) -> int:
        return int(self.mesh.shape[self.axis])

    @property
    def n_nodes(self) -> int:
        return self.n_threads

    def spawn(self, session: "Session", thread_proc: Callable,
              data: Sequence, broadcast: Sequence) -> None:
        if self._pending is not None:
            raise RuntimeError("SPMD backend already has a spawned program; join() it first")
        self._pending = (thread_proc, tuple(data), tuple(broadcast))

    def _compile(self, session: "Session", thread_proc: Callable,
                 data: Sequence, broadcast: Sequence):
        """Build the jitted shard_map program for one spawn.

        Returns ``(f, data, names, auto_box)`` — the compiled callable, the
        (possibly trimmed) data arrays, the shared names captured in the
        trace, and the static metadata of every AUTO branch-counter slot (the
        traced counts themselves come out as the program's third output).
        """
        n = self.n_threads
        # shard_map splits evenly: trim ragged rows (the host backend gives the
        # remainder to low tids instead; parity holds whenever n divides rows).
        dropped = [int(a.shape[0] % n) for a in data]
        if any(dropped):
            _warn_at_caller(
                f"SpmdBackend: dropping {sum(dropped)} ragged row(s) "
                f"({dropped} per data array) so shard_map splits "
                f"evenly across {n} threads; pad or trim row counts to a "
                "multiple of n_threads for host/SPMD parity",
                UserWarning)
        data = tuple(a[: (a.shape[0] // n) * n] for a in data)
        names = session.store.names()
        shared0 = {m: session.store.get(m) for m in names}
        auto_box: List[Dict[str, Any]] = []

        def body(*args):
            tid = jax.lax.axis_index(self.axis)
            ctx = SpmdWorkerCtx(session, self, tid, dict(shared0))
            session._tls.ctx = ctx
            try:
                result = thread_proc(ctx, *args)
            finally:
                session._tls.ctx = None
            # the AUTO branch counters leave the program as a third output;
            # their static cost metadata rides out-of-band through auto_box
            auto_box[:] = [{k: v for k, v in s.items() if k != "count"}
                           for s in ctx._auto_slots]
            counts = tuple(s["count"] for s in ctx._auto_slots)
            # stack every leaf along the mesh axis so out_specs is uniform
            return jax.tree.map(lambda x: jnp.asarray(x)[None],
                                (result, ctx.values, counts))

        in_specs = tuple(P(self.axis) for _ in data) + tuple(P() for _ in broadcast)
        f = jax.jit(shard_map(body, mesh=self.mesh, in_specs=in_specs,
                              out_specs=P(self.axis), check_vma=False))
        return f, data, names, auto_box

    def lower(self, session: "Session", thread_proc: Callable,
              data: Sequence, broadcast: Sequence):
        """Trace + lower ``thread_proc`` without running it: the hook for
        compile-cost inspection (``lowered.as_text()`` / ``.compile()``)."""
        f, data, _, _ = self._compile(session, thread_proc, data, broadcast)
        # accounting fires at trace time: inspection must not charge the
        # session's wire-traffic figures, so trace against throwaway stats
        stats, self.stats = self.stats, SpmdTraffic()
        try:
            return f.lower(*data, *broadcast)
        finally:
            self.stats = stats

    def join(self, session: "Session", timeout: Optional[float] = None) -> List[Any]:
        """Run the spawned program in JAX's ahead-of-time stages — trace,
        lower, compile (where the persistent cache is looked up), run — and
        write the shared values back.  Armed, each stage is a span of
        category ``spmd``: ``spmd.run`` then waits for the outputs, so it
        spans the device work, and ``spmd.compile`` carries the compiled
        program's ``hlo_scopes`` (HLO op name -> ``op_name`` scope)."""
        if self._pending is None:
            return []
        thread_proc, data, broadcast = self._pending
        self._pending = None
        n = self.n_threads
        trc = session.tracer
        tracing = telemetry.TRACING and trc.enabled
        span = trc.span if tracing else telemetry.null_span
        job = {"session": session.id, "threads": n}
        with span("spmd", "spmd.trace", **job):
            f, data, names, auto_box = self._compile(session, thread_proc, data, broadcast)
            args = (*data, *broadcast)
            traced = f.trace(*args)
        with span("spmd", "spmd.lower", **job):
            lowered = traced.lower()
        with span("spmd", "spmd.compile", **job) as stage:
            compiled = lowered.compile()
            if tracing:
                stage.args["hlo_scopes"] = op_scopes(compiled.as_text())
        with span("spmd", "spmd.run", **job):
            stacked_result, stacked_shared, stacked_counts = compiled(*args)
            if tracing:
                jax.block_until_ready((stacked_result, stacked_shared, stacked_counts))
        with span("spmd", "spmd.writeback", **job):
            # settle every AUTO call site's trace-time dense bound against the
            # branch counter the device actually accumulated (globally agreed,
            # so replica 0's count is everyone's count)
            for meta, counts in zip(auto_box, stacked_counts):
                self.stats.settle_auto(meta, int(jax.device_get(counts)[0]))
            for m in names:
                session.store.set(m, jax.tree.map(lambda x: x[0], stacked_shared[m]))
            out = [jax.tree.map(lambda x, i=i: x[i], stacked_result) for i in range(n)]
        if tracing:
            trc.count("spmd.joins")
        return out

    def wire_traffic(self) -> int:
        return self.stats.bytes_transferred


# ---------------------------------------------------------------------------
# The facade
# ---------------------------------------------------------------------------


class Session:
    """Table 1 as one object: DSM + cluster/thread management + sync.

    Parameters
    ----------
    backend:
        ``"host"`` | ``"spmd"`` | a :class:`Backend` instance.
    n_nodes / threads_per_node:
        Host-backend cluster shape (ignored for SPMD).
    mesh / axis:
        SPMD mesh (defaults to one thread per visible device on ``axis``).
    accum_mode:
        Default :class:`AccumMode` for ``SharedRef.accumulate``.
    store:
        Optionally adopt an existing :class:`GlobalStore` (FT recovery rolls
        a new session onto the surviving store this way).
    shards:
        Number of consistent-hash shards in a freshly built store (ignored
        when adopting ``store``).  ``1`` is the paper's single flat store;
        larger counts let workers touching different shards read/write/inc
        concurrently — there is no session-global cache lock.
    cold_tier / cold_budget:
        ``step.tiers`` knobs for a freshly built store (ignored when
        adopting ``store``): ``cold_tier`` is ``None`` (default, pure
        in-memory), ``"host"`` (pinned host-memory numpy tier), ``"disk"``
        (pickled spill files), or any
        :class:`~repro.core.tiers.ColdTier` instance; ``cold_budget`` caps
        per-shard hot bytes — beyond it, least-recently-used entries demote
        to the cold tier and promote back (epoch-preserving) on access.
    trace:
        ``step.trace`` arming: ``True`` arms a fresh
        :class:`~repro.core.telemetry.Tracer`, an existing tracer is adopted
        as-is (how FT recovery re-arms a replacement session), and the
        default ``None`` leaves tracing *off* — a disabled tracer whose hot
        paths cost one attribute check and allocate nothing.  Inspect via
        ``session.tracer`` / :meth:`metrics`; export with
        ``session.tracer.export(path)``.
    check:
        ``step.check`` arming, same contract as ``trace``: ``True`` arms a
        fresh :class:`~repro.check.Checker` (happens-before race detection,
        lock-order sanitizing, and a spawn-time lint that rejects
        structurally broken programs with
        :class:`~repro.check.CheckError`), an existing checker is adopted
        as-is, and the default ``None`` leaves checking off at one-branch
        hot-path cost.  Inspect via ``session.checker`` / :meth:`findings`;
        export with ``session.checker.export(path)``.
    record:
        ``step.obs`` flight-recorder arming, same contract again: ``True``
        arms a fresh :class:`~repro.obs.FlightRecorder` (a bounded ring of
        recent events, cheap enough to leave on always — the tracer runs in
        *record-only* mode unless ``trace`` armed it fully), an existing
        recorder is adopted as-is (FT recovery re-attaches the dead
        session's recorder), and the default ``None`` leaves recording off.
        Inspect via ``session.recorder``; pair with :meth:`watchdog` for
        anomaly detection and :meth:`openmetrics` for scrape text.  Call
        ``session.recorder.close()`` when done with an armed recorder so
        the module-level tracing flag drops back.
    """

    def __init__(self, backend: Backend | str = "host", *,
                 n_nodes: int = 2, threads_per_node: int = 2,
                 mesh=None, axis: str = "data",
                 store: Optional[GlobalStore] = None,
                 granularity: str = "coarse",
                 shards: int = 1,
                 cold_tier=None,
                 cold_budget: Optional[int] = None,
                 accum_mode: AccumMode | str = AccumMode.REDUCE_SCATTER,
                 cache_capacity: int = 1024,
                 trace: "telemetry.Tracer | bool | None" = None,
                 check: "stepcheck.Checker | bool | None" = None,
                 record: "stepobs.FlightRecorder | bool | None" = None):
        if isinstance(backend, str):
            if backend == "host":
                backend = HostBackend(n_nodes, threads_per_node)
            elif backend == "spmd":
                backend = SpmdBackend(mesh=mesh, axis=axis)
            else:
                raise ValueError(f"backend must be host|spmd, got {backend!r}")
        self.backend = backend
        #: process-unique, carried by the session's job spans (``session``)
        self.id = next(_SESSION_IDS)
        # step.trace: trace=True arms a fresh tracer; a Tracer instance is
        # adopted as-is (FT recovery re-arms the failed session's tracer);
        # the default is a *disabled* tracer — hot paths see a false
        # `tracer.enabled` behind the module flag and allocate nothing.
        self.tracer = telemetry.as_tracer(trace)
        # step.check mirrors the arming contract: check=True arms a fresh
        # checker; a Checker instance is adopted as-is (FT recovery re-arms
        # the failed session's checker); default is disabled, one branch.
        self.checker = stepcheck.as_checker(check)
        # step.obs: record=True arms the flight recorder — a bounded ring of
        # recent events behind the same tracer; when `trace` didn't arm full
        # tracing the tracer runs record-only (hists/counters accumulate,
        # only slow/lifecycle events materialise, memory stays O(capacity)).
        self.recorder = stepobs.as_recorder(record)
        self.recorder.attach(self.tracer)
        # sync primitives handed out by this session, for the watchdog's
        # live in-flight-wait scan (weak: a dropped barrier unregisters
        # itself; nothing here extends primitive lifetime)
        self._watch_prims: "weakref.WeakSet" = weakref.WeakSet()
        # step.tiers: cold_tier ("host" | "disk" | a ColdTier instance) and
        # cold_budget (per-shard hot bytes before LRU demotion kicks in) are
        # store-construction options — like `shards`, they are ignored when
        # an existing store is adopted (FT recovery keeps its tiering as-is)
        self.store = store if store is not None else GlobalStore(
            granularity=granularity, shards=shards,
            cold_tier=cold_tier, cold_budget=cold_budget)
        self.store.tracer = self.tracer
        self.store.checker = self.checker
        self.accum_mode = AccumMode(accum_mode)
        self.cache = DSMCache(self.store, n_nodes=backend.n_nodes,
                              capacity=cache_capacity)
        self.cache.tracer = self.tracer
        self.cache.checker = self.checker
        if backend.kind == "host":
            backend.run_barrier.tracer = self.tracer
            backend.run_barrier.checker = self.checker
            self._watch_prims.add(backend.run_barrier)
        self._sparse_k: Dict[str, int] = {}  # per-ref default top-k budgets
        self._tls = threading.local()

    # -- Table 1: DSM manipulation --------------------------------------------

    def def_global(self, name: str, value, *, spec=None,
                   sparse_k: Optional[int] = None) -> SharedRef:
        """``DefGlobal`` — declare + initialise a shared variable.

        ``sparse_k`` sets the ref's default top-k budget: any
        ``ref.accumulate(..., mode="sparse"|"auto")`` without an explicit
        ``k`` compresses with this budget on either backend."""
        self.store.def_global(name, value, spec=spec)
        self._set_sparse_k(name, sparse_k,
                           size=None if sparse_k is None
                           else int(jnp.asarray(value).size))
        return SharedRef(self, name)

    def new_array(self, name: str, shape, dtype=jnp.float32, *, spec=None,
                  sparse_k: Optional[int] = None) -> SharedRef:
        """``NewArray`` — allocate a zeroed shared array.  ``sparse_k`` is the
        ref's default top-k budget for sparse/auto accumulates."""
        self.store.new_array(name, shape, dtype, spec=spec)
        self._set_sparse_k(name, sparse_k,
                           size=None if sparse_k is None
                           else int(np.prod(shape, dtype=np.int64)) if shape
                           else 1)
        return SharedRef(self, name)

    def _set_sparse_k(self, name: str, sparse_k: Optional[int],
                      size: Optional[int] = None) -> None:
        self._sparse_k.pop(name, None)  # re-declared names drop the old budget
        if sparse_k is not None:
            if sparse_k < 1:
                raise ValueError(f"sparse_k must be >= 1, got {sparse_k}")
            self._sparse_k[name] = int(sparse_k)
            ck = self.checker
            if stepcheck.CHECKING and ck.enabled and size is not None:
                # declaration-time lint: a budget the blocked pair layout
                # cannot ship is silently lossier than asked
                ck.lint_sparse_budget(name, size, int(sparse_k))

    def sparse_k(self, name: str) -> Optional[int]:
        """The ref's declared default top-k budget (None if unset)."""
        return self._sparse_k.get(name)

    def new_object(self, name: str, fields: Dict[str, Any], *, specs=None) -> SharedRef:
        """``NewObj`` — a shared pytree of fields under one object_id."""
        self.store.new_object(name, fields, specs=specs)
        return SharedRef(self, name)

    def ref(self, name: str) -> SharedRef:
        """Handle to an already-declared name."""
        if name not in self.store.names():
            raise KeyError(name)
        return SharedRef(self, name)

    def names(self) -> List[str]:
        return self.store.names()

    def delete(self, name: str) -> None:
        """``DelArray`` / ``DelObj`` + coherence teardown: every node's cache
        replica and every directory record of the name is purged, so a later
        re-declaration under the same name starts with no stale state.

        The teardown is the store's delete hook (the cache registered
        :meth:`DSMCache.drop` at construction), fired under the owning
        shard's lock — a concurrent worker read of the same name either
        completes before the delete or misses afterwards, never re-populates
        a deleted-era replica."""
        ck = self.checker
        if stepcheck.CHECKING and ck.enabled:
            # advisory directory peek (no lock): a delete while nodes still
            # hold replicas is legal but worth a lint warning — a concurrent
            # reader of the deleted era may be mid-flight
            holders = set(self.store.shard_for(name).directory.get(name, ()))
            if holders:
                ck.check_delete(name, holders)
        self.store.delete(name)
        self._sparse_k.pop(name, None)

    # -- Table 1: cluster & thread management ---------------------------------

    def spawn(self, thread_proc: Callable, *, data: Sequence = (),
              broadcast: Sequence = ()) -> None:
        """Create + start one STEP thread per backend slot.

        ``thread_proc(ctx, *data_shards, *broadcast)`` receives this thread's
        contiguous row-partition of each array in ``data`` and every array in
        ``broadcast`` whole (replicated).
        """
        data = tuple(jnp.asarray(a) for a in data)
        broadcast = tuple(jnp.asarray(b) for b in broadcast)
        ck = self.checker
        if stepcheck.CHECKING and ck.enabled:
            # lint dry run FIRST: a strict checker raises CheckError here —
            # a structurally broken program is rejected before any thread
            # (or any SPMD trace) exists
            ck.lint_spawn(self, thread_proc, data, broadcast)
            ck.on_spawn(self.backend.n_threads)
        self.backend.spawn(self, thread_proc, data, broadcast)

    def join(self, timeout: Optional[float] = None) -> List[Any]:
        """Join all threads; returns per-tid results."""
        try:
            return self.backend.join(self, timeout)
        finally:
            ck = self.checker
            if stepcheck.CHECKING and ck.enabled:
                # the join happens-before edge: the driver's clock absorbs
                # every worker's; the lock sanitizer's wait-for state resets
                ck.after_join()

    def run(self, thread_proc: Callable, *, data: Sequence = (),
            broadcast: Sequence = (), timeout: Optional[float] = None) -> List[Any]:
        """``spawn`` + ``join``.  Armed, one ``session.run`` span (category
        ``lifecycle``) holds both and carries the session's :attr:`id`, as
        the stages of an SPMD join do."""
        trc = self.tracer
        span = trc.span if telemetry.TRACING and trc.enabled else telemetry.null_span
        with span("lifecycle", "session.run", session=self.id):
            self.spawn(thread_proc, data=data, broadcast=broadcast)
            return self.join(timeout)

    def lower(self, thread_proc: Callable, *, data: Sequence = (),
              broadcast: Sequence = ()):
        """Trace + lower ``thread_proc`` without executing it (SPMD backend).

        Returns the ``jax.stages.Lowered`` for the program ``join`` would run:
        inspect ``.as_text()`` for lowered size (the ``ctx.iterate`` scan path
        is O(1) in ``iters``) or ``.compile()`` for compile cost.
        """
        if self.backend.kind != "spmd":
            raise RuntimeError("Session.lower inspects the traced SPMD program; "
                               "the host backend does not trace thread_proc")
        data = tuple(jnp.asarray(a) for a in data)
        broadcast = tuple(jnp.asarray(b) for b in broadcast)
        if telemetry.TRACING and self.tracer.enabled:
            with self.tracer.span("spmd", "session.lower"):
                return self.backend.lower(self, thread_proc, data, broadcast)
        return self.backend.lower(self, thread_proc, data, broadcast)

    def kill_node(self, node_id: int) -> List[int]:
        """Simulate a node failure (host backend); returns lost tids."""
        if self.backend.kind != "host":
            raise RuntimeError("node-failure simulation needs the host backend; "
                               "SPMD recovery goes through ft.elastic_restore")
        return self.backend.pool.kill_node(node_id)

    def healthy_nodes(self) -> List[int]:
        if self.backend.kind != "host":
            return list(range(self.backend.n_nodes))
        return self.backend.pool.healthy_nodes()

    def thread_states(self) -> Dict[int, Any]:
        if self.backend.kind != "host":
            return {}
        return self.backend.pool.states()

    # -- Table 1: synchronization ---------------------------------------------

    def barrier(self, count: Optional[int] = None) -> DBarrier:
        """A counter barrier sized to the session's threads by default.
        Carries the session's tracer: every ``enter`` records a per-thread
        entry→release ``barrier-wait`` span when tracing is armed."""
        b = DBarrier(count or self.backend.n_threads)
        b.tracer = self.tracer
        b.checker = self.checker
        self._watch_prims.add(b)
        return b

    def semaphore(self, count: int = 1) -> DSemaphore:
        s = DSemaphore(count)
        s.tracer = self.tracer
        s.checker = self.checker
        self._watch_prims.add(s)
        return s

    def ssp_clock(self, staleness: int = 0, n_workers: Optional[int] = None) -> SSPClock:
        c = SSPClock(n_workers or self.backend.n_threads, staleness=staleness)
        c.tracer = self.tracer
        c.checker = self.checker
        return c

    # -- accumulator registry / stats -----------------------------------------

    def accumulator(self, name: str, mode: Optional[AccumMode | str] = None):
        """The accumulator behind ``ref.accumulate`` (host backend)."""
        if self.backend.kind != "host":
            return self.backend.stats
        return self.backend.accumulator(self, name,
                                        AccumMode(mode) if mode else None)

    def wire_traffic(self) -> int:
        """Total accumulator wire traffic, in vector elements (paper §5.2)."""
        return self.backend.wire_traffic()

    def findings(self) -> List[Any]:
        """Findings recorded by this session's checker (see ``step.check``):
        race/lock/lint :class:`~repro.check.Finding` rows.  Empty unless the
        session was built with ``check=True`` (or an armed checker)."""
        return self.checker.findings()

    def stats(self) -> Dict[str, Any]:
        """Deprecated view: the original raw-counter triple.  Kept intact for
        existing callers; new code should use :meth:`metrics`, which returns
        the canonical normalized key set plus the tracer snapshot."""
        _warn_at_caller("Session.stats() is deprecated; use Session.metrics() "
                        "for the canonical normalized snapshot",
                        DeprecationWarning)
        # frozen key set: tier/migration counters added later live only in
        # metrics() — this view keeps the pre-tiers shape for old callers
        legacy = ("get", "set", "inc", "bytes_get", "bytes_set",
                  "transfers", "migrated_in", "migrated_out")
        raw = self.store.stats
        return {"store": {k: raw.get(k, 0) for k in legacy},
                "cache": self.cache.stats,
                "wire_traffic": self.wire_traffic()}

    def metrics(self) -> Dict[str, Any]:
        """The unified observability snapshot (supersedes :meth:`stats` /
        :meth:`shard_stats` without breaking them).  Key set pinned by
        :data:`repro.core.telemetry.SESSION_METRIC_KEYS`:

        * ``backend`` — ``"host"`` | ``"spmd"``
        * ``store`` — canonical store counters
          (:data:`~repro.core.telemetry.STORE_METRIC_KEYS`)
        * ``cache`` — canonical coherence counters
          (:data:`~repro.core.telemetry.CACHE_METRIC_KEYS`)
        * ``wire_traffic`` — accumulator elements, host/SPMD comparable
        * ``shards`` — per-shard ``{store, cache, wire_traffic}`` rows with
          the same canonical shapes
        * ``tiers`` — hot/cold tier occupancy + hit/promotion/demotion
          counters (:meth:`ShardedStore.tier_stats`), with a ``migration``
          sub-dict of lifetime rebalance-window totals
          (:meth:`ShardedStore.migration_totals`)
        * ``trace`` — :meth:`Tracer.snapshot` (span counts, counters,
          latency histograms); ``{"enabled": False, ...}`` when unarmed
        """
        shard_rows = {
            sid: {"store": telemetry.normalize_store_stats(row["store"]),
                  "cache": row["cache"].as_dict(),
                  "wire_traffic": row["wire_traffic"]}
            for sid, row in self._shard_rows().items()}
        return {"backend": self.backend.kind,
                "store": telemetry.normalize_store_stats(self.store.stats),
                "cache": self.cache.stats.as_dict(),
                "wire_traffic": self.wire_traffic(),
                "shards": shard_rows,
                "tiers": {**self.store.tier_stats(),
                          "migration": self.store.migration_totals()},
                "trace": self.tracer.snapshot()}

    def openmetrics(self, *, prefix: str = "step",
                    anomalies: Optional[Sequence[Any]] = None) -> str:
        """:meth:`metrics` rendered as OpenMetrics/Prometheus exposition
        text (``step.obs``'s scrape surface).  Pass ``watchdog.anomalies``
        to include the anomaly counters on the same page."""
        return stepobs.openmetrics(self.metrics(), prefix=prefix,
                                   anomalies=anomalies)

    def watchdog(self, **kwargs) -> "stepobs.Watchdog":
        """A :class:`~repro.obs.Watchdog` over this session (not started —
        call ``.start()`` for the daemon thread or drive ``poll_once()``
        yourself).  Detects stalled migration windows, barrier/semaphore
        waits beyond a p99-derived SLO, tier thrash, shard lock-wait
        outliers, and (via ``watch_heartbeats``) dead nodes; each anomaly
        carries a flight-recorder dump when :attr:`recorder` is armed."""
        return stepobs.Watchdog(self, **kwargs)

    def shard_stats(self) -> Dict[int, Dict[str, Any]]:
        """Per-shard view of the session, keyed by shard id: the store's op
        counters (+ entry count + migration counts), the cache's coherence
        counters, and accumulator wire traffic attributed to the shard owning
        each output ref.  Deprecated view — raw counter shapes; the
        normalized per-shard rows live in ``metrics()["shards"]``."""
        _warn_at_caller("Session.shard_stats() is deprecated; use "
                        "Session.metrics()['shards'] for the canonical "
                        "normalized per-shard rows", DeprecationWarning)
        return self._shard_rows()

    def _shard_rows(self) -> Dict[int, Dict[str, Any]]:
        cache_rows = self.cache.shard_stats()
        out: Dict[int, Dict[str, Any]] = {
            sid: {"store": row, "cache": cache_rows.get(sid, CacheStats()),
                  "wire_traffic": 0}
            for sid, row in self.store.shard_stats().items()}
        if self.backend.kind == "host":
            for (name, _, _), accu in self.backend._accumulators.items():
                sid = self.store.shard_of(name)
                if sid in out:
                    out[sid]["wire_traffic"] += accu.bytes_transferred
        else:
            for sid, elems in self.backend.stats.by_shard.items():
                if sid in out:
                    out[sid]["wire_traffic"] += elems
        return out

    # -- ref-op dispatch (driver vs active worker ctx) ------------------------

    def _ctx(self):
        return getattr(self._tls, "ctx", None)

    def _read(self, name: str, owner=None):
        ctx = self._ctx()
        value = (self.store.get(name, owner=owner) if ctx is None
                 else ctx.read(name, owner=owner))
        ck = self.checker
        if stepcheck.CHECKING and ck.enabled and (
                ctx is None or type(ctx) is HostWorkerCtx):
            # race detection sees host/driver accesses only: SPMD refs are
            # traced replicated values (ordered by the collective schedule)
            # and the lint dry run's shadow ctx must stay invisible
            ck.on_access(name, "read", value)
        return value

    def _write(self, name: str, value, owner=None) -> None:
        ctx = self._ctx()
        if ctx is None:
            self.store.set(name, value, owner=owner)
        else:
            ctx.write(name, value, owner=owner)
        ck = self.checker
        if stepcheck.CHECKING and ck.enabled and (
                ctx is None or type(ctx) is HostWorkerCtx):
            ck.on_access(name, "write", value)

    def _inc(self, name: str, amount, owner=None):
        ctx = self._ctx()
        result = (self.store.inc(name, amount, owner=owner) if ctx is None
                  else ctx.inc(name, amount, owner=owner))
        ck = self.checker
        if stepcheck.CHECKING and ck.enabled and (
                ctx is None or type(ctx) is HostWorkerCtx):
            # inc is atomic under the owning shard's lock: inc-inc pairs
            # commute and are never racy; inc vs set/get still is
            ck.on_access(name, "inc", result)
        return result

    def _accumulate(self, name: str, local, mode, k):
        ctx = self._ctx()
        if ctx is None:
            raise RuntimeError(
                "SharedRef.accumulate is a collective across the session's "
                "threads — call it inside a thread_proc run by Session.spawn")
        if k is None:
            k = self._sparse_k.get(name)  # the ref's declared default budget
        return ctx.accumulate(name, jnp.asarray(local),
                              AccumMode(mode) if mode is not None else self.accum_mode, k)

    def _cached_read(self, node_id: int, name: str, owner=None):
        # locking lives in the cache/store layer: the owning shard's lock,
        # not a session-global one — reads of names on different shards
        # proceed concurrently
        return self.cache.read(node_id, name, owner=owner)

    def _cached_write(self, node_id: int, name: str, value, owner=None) -> None:
        self.cache.write(node_id, name, value, owner=owner)

    # paper-cased aliases (Table 1)
    DefGlobal = def_global
    NewArray = new_array
    NewObj = new_object

    def __repr__(self) -> str:  # pragma: no cover - debug aid
        return (f"Session(backend={self.backend.kind}, "
                f"threads={self.backend.n_threads}, names={self.names()})")

