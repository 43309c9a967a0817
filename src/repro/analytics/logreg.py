"""Logistic regression — the paper's worked example (§4.5) on the Session facade.

``fit`` is a line-by-line port of the paper's ``slave_proc``: every working
thread keeps a local ``theta``, computes the gradient over its partition
(``LoadTrainPoint``), pushes it through the shared accumulator (a
synchronisation point), and applies the accumulated global gradient from DSM.
The *same* ``thread_proc`` runs on either substrate — ``backend="host"``
(DThreadPool + DAddAccumulator) or ``backend="spmd"`` (one STEP thread per
mesh position via shard_map) — selected at ``Session`` construction.

Rows come dense, an ``(n, d)`` array, or as fixed-width ELL rows, a tuple
``(idx, val)`` of ``(n, w)`` feature indices and values (``w`` active
features a row, as in hashed click logs), with ``n_features`` given: the
margin is then a gather ``Σ_j θ[idx_j]·val_j`` and the gradient a
scatter-add into a length-``n_features`` vector, so a mini-batch's gradient
is as sparse as its rows.  ``batch=`` takes round ``i``'s rows
``[i·batch, (i+1)·batch)`` of each thread's partition.

``fit_threads`` / ``fit_spmd`` remain as deprecation shims over ``fit``.
"""

from __future__ import annotations

from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import AccumMode, Session
from repro.core.dsm import GlobalStore
from repro.core.session import SpmdBackend, deprecated_entry


def _sigmoid(z):
    return 1.0 / (1.0 + jnp.exp(-z))


def _margin(theta, rows):
    """θᵀx_p for every row: a matmul over dense rows, a gather over ELL rows."""
    if isinstance(rows, tuple):
        idx, val = rows
        return jnp.sum(theta[idx] * val, axis=1)
    return rows @ theta


@partial(jax.jit, static_argnames=("batch",))
def _local_grad(theta, rows, y, i=0, batch: Optional[int] = None):
    """δ = Σ_p (y_p − σ(θᵀx_p))·x_p over this thread's mini-batch: its rows
    ``[i·batch, (i+1)·batch)``, or all of them when ``batch`` is None."""
    if batch is not None:
        take = lambda a: jax.lax.dynamic_slice_in_dim(a, i * batch, batch)
        rows, y = jax.tree.map(take, rows), take(y)
    with jax.named_scope("logreg.margin"):
        residual = y - _sigmoid(_margin(theta, rows))
    with jax.named_scope("logreg.grad"):
        if isinstance(rows, tuple):
            idx, val = rows
            return jnp.zeros_like(theta).at[idx].add(residual[:, None] * val)
        return residual @ rows


def _as_rows(x):
    """Dense rows as one array, ELL rows as an ``(idx, val)`` tuple."""
    if isinstance(x, tuple):
        idx, val = (jnp.asarray(a) for a in x)
        if idx.ndim != 2 or idx.shape != val.shape:
            raise ValueError("ELL rows are (idx, val) of one (rows, width) shape, "
                             f"got {idx.shape} and {val.shape}")
        return idx, val
    return jnp.asarray(x)


def loss(theta, x, y):
    z = _margin(jnp.asarray(theta), _as_rows(x))
    p = np.clip(np.asarray(_sigmoid(z)), 1e-7, 1 - 1e-7)
    return float(-np.mean(y * np.log(p) + (1 - y) * np.log(1 - p)))


def fit_reference(x, y, iters: int = 10, lr: float = 1e-3):
    """Single-thread oracle (same algorithm, no distribution)."""
    theta = jnp.zeros((x.shape[1],), jnp.float32)
    for _ in range(iters):
        theta = theta + lr * _local_grad(theta, jnp.asarray(x), jnp.asarray(y))
    return np.asarray(theta)


def fit(x, y, *, iters: int = 10, lr: float = 1e-3,
        mode: Optional[AccumMode | str] = None, k: Optional[int] = None,
        n_features: Optional[int] = None, batch: Optional[int] = None,
        session: Optional[Session] = None, backend: str = "host",
        n_nodes: int = 2, threads_per_node: int = 2, mesh=None):
    """Paper §4.5 through the Table-1 facade; backend-agnostic.

    ``x`` is dense rows ``(n, d)`` or ELL rows ``(idx, val)`` with
    ``n_features`` features.  ``batch`` is the rows each thread takes a
    round: round ``i`` reads rows ``[i·batch, (i+1)·batch)`` of its
    partition, so ``iters · batch`` rows must fit the smallest partition;
    ``None`` reads the whole partition every round.

    ``mode="sparse"``/``"auto"`` compress the gradient to top-``k`` (index,
    value) pairs through the shared Pallas dispatch — ``k`` becomes the grad
    ref's declared budget (``new_array(..., sparse_k=k)``), so per-round calls
    need no explicit ``k``.  Returns ``(theta, session)`` — the session
    exposes the store, cache and accumulator traffic for inspection.
    """
    rows = _as_rows(x)
    ell = isinstance(rows, tuple)
    if ell and n_features is None:
        raise ValueError("ELL rows need n_features")
    if ell and not 0 <= int(jnp.min(rows[0])) <= int(jnp.max(rows[0])) < n_features:
        # a gather clamps and a scatter drops what lies outside: refuse it
        raise ValueError(f"ELL feature indices must lie in [0, {n_features})")
    d = n_features if ell else rows.shape[1]
    sess = session or Session(backend=backend, n_nodes=n_nodes,
                              threads_per_node=threads_per_node, mesh=mesh)
    n_rows = jnp.shape(y)[0]
    if batch is not None and iters * batch > n_rows // sess.backend.n_threads:
        raise ValueError(f"{iters} rounds of {batch} rows need {iters * batch} rows "
                         f"a thread; the smallest of {sess.backend.n_threads} "
                         f"partitions of {n_rows} rows has {n_rows // sess.backend.n_threads}")
    grad = sess.new_array("grad", (d,), sparse_k=k)

    def thread_proc(ctx, *cols):
        *xs, ys = cols
        xs = tuple(xs) if ell else xs[0]

        def step(i, theta):                           # one synchronous round
            with ctx.span("logreg.round"):            # app-round marker (host)
                local = _local_grad(theta, xs, ys, i, batch=batch)  # lines 14–21
                total = grad.accumulate(local, mode=mode)  # line 22 (sync point)
                return theta + lr * total             # lines 23–24
        # local theta (paper line 10) is the carry; host: guarded loop,
        # SPMD: one lax.scan — O(1) lowered program size in `iters`.
        return ctx.fori(step, jnp.zeros((d,), jnp.float32), iters)

    data = (*rows, jnp.asarray(y)) if ell else (rows, jnp.asarray(y))
    thetas = sess.run(thread_proc, data=data)
    return np.asarray(thetas[0]), sess


def fit_ssp(x, y, *, n_workers: int = 4, staleness: int = 1, iters: int = 10,
            lr: float = 1e-3):
    """Asynchronous SGD under Stale Synchronous Parallel (paper §7 / Petuum).

    Workers update the shared theta in DSM without a barrier — ``theta.inc``
    is the atomic Table-1 increment — and the SSP clock only blocks a worker
    that runs more than ``staleness`` iterations ahead of the slowest.  With
    ``staleness=0`` this degenerates to fully synchronous execution.
    """
    sess = Session(backend="host", n_nodes=n_workers, threads_per_node=1)
    d = x.shape[1]
    theta = sess.def_global("theta", jnp.zeros((d,), jnp.float32))
    clock = sess.ssp_clock(staleness)

    def worker(ctx, xs, ys):
        def step(_):
            with ctx.span("logreg.ssp_round"):
                g = _local_grad(theta.get(), xs, ys)   # possibly stale replica
                theta.inc(lr * g)                      # atomic DSM update
                clock.tick(ctx.tid)
                clock.wait(ctx.tid)                    # bounded staleness
            return _
        ctx.iterate(step, None, iters)             # host-only: clock is a
                                                   # Python-side effect

    sess.run(worker, data=(jnp.asarray(x), jnp.asarray(y)), timeout=60)
    return np.asarray(theta.get()), clock


# ---------------------------------------------------------------------------
# Deprecated pre-Session entry points
# ---------------------------------------------------------------------------


def fit_threads(x, y, *, n_nodes: int = 2, threads_per_node: int = 2,
                iters: int = 10, lr: float = 1e-3,
                mode: AccumMode | str = AccumMode.REDUCE_SCATTER,
                store: Optional[GlobalStore] = None):
    """Deprecated shim: ``fit(backend="host")`` with the old return tuple."""
    deprecated_entry("logreg.fit_threads", 'logreg.fit(backend="host")')
    sess = Session(backend="host", n_nodes=n_nodes,
                   threads_per_node=threads_per_node, store=store,
                   accum_mode=mode)
    theta, sess = fit(x, y, iters=iters, lr=lr, mode=mode, session=sess)
    return theta, sess.store, sess.accumulator("grad")


def fit_spmd(x, y, mesh, *, iters: int = 10, lr: float = 1e-3,
             mode: AccumMode | str = AccumMode.REDUCE_SCATTER, k: int = 0):
    """Deprecated shim: ``fit(backend="spmd")``."""
    deprecated_entry("logreg.fit_spmd", 'logreg.fit(backend="spmd")')
    sess = Session(backend=SpmdBackend(mesh=mesh))
    theta, _ = fit(x, y, iters=iters, lr=lr, mode=mode, k=k or None, session=sess)
    return theta
