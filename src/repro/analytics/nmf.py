"""NMF (paper §6.6) on the Session facade: R ≈ P·Q, globally shared Q.

Multiplicative updates (Lee–Seung).  With rows partitioned across threads,
P's update is thread-local; Q's update needs two global reductions —
numer = PᵀR (k×m) and gram = PᵀP (k×k) — which is precisely an accumulator
workload (the paper keeps the factorized matrices in DSM).  One
``thread_proc`` serves both the host and SPMD backends.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import AccumMode, Session

_EPS = 1e-9


@jax.jit
def _update_p(p, q, r):
    """P ← P ⊙ (RQᵀ) / (PQQᵀ)."""
    return p * (r @ q.T) / (p @ (q @ q.T) + _EPS)


@jax.jit
def _q_partials(p, r):
    return p.T @ r, p.T @ p            # numer (k,m), gram (k,k)


def frob_loss(r, p, q) -> float:
    return float(np.linalg.norm(np.asarray(r) - np.asarray(p) @ np.asarray(q)) ** 2 / r.shape[0])


def fit_reference(r, k: int, iters: int = 10, seed: int = 0):
    rng = np.random.default_rng(seed)
    p = jnp.asarray(np.abs(rng.normal(size=(r.shape[0], k))).astype(np.float32))
    q = jnp.asarray(np.abs(rng.normal(size=(k, r.shape[1]))).astype(np.float32))
    rj = jnp.asarray(r)
    for _ in range(iters):
        p = _update_p(p, q, rj)
        numer, gram = _q_partials(p, rj)
        q = q * numer / (gram @ q + _EPS)
    return np.asarray(p), np.asarray(q)


def fit(r, k: int, *, iters: int = 10, seed: int = 0,
        mode: Optional[AccumMode | str] = None,
        session: Optional[Session] = None, backend: str = "host",
        n_nodes: int = 2, threads_per_node: int = 2, mesh=None):
    """Lee–Seung updates through the Table-1 facade; backend-agnostic.

    Returns ``(p, q, session)``.
    """
    sess = session or Session(backend=backend, n_nodes=n_nodes,
                              threads_per_node=threads_per_node, mesh=mesh)
    rng = np.random.default_rng(seed)
    n, m = r.shape
    # same init stream as fit_reference (P then Q) so trajectories match exactly
    p_full0 = np.abs(rng.normal(size=(n, k))).astype(np.float32)
    q0 = np.abs(rng.normal(size=(k, m))).astype(np.float32)
    Q = sess.def_global("Q", jnp.asarray(q0))
    q_partials = sess.new_array("q_partials", (k * m + k * k,))

    def thread_proc(ctx, r_loc, p_loc):
        def step(p):                        # thread-local P rides in the carry
            with ctx.span("nmf.round"):
                q = Q.get()
                p = _update_p(p, q, r_loc)
                numer, gram = _q_partials(p, r_loc)
                flat = q_partials.accumulate(
                    jnp.concatenate([numer.reshape(-1), gram.reshape(-1)]), mode=mode)
                numer_g = flat[: k * m].reshape(k, m)
                gram_g = flat[k * m:].reshape(k, k)
                Q.set(q * numer_g / (gram_g @ q + _EPS))
            return p
        return ctx.iterate(step, p_loc, iters)

    ps = sess.run(thread_proc, data=(jnp.asarray(r), jnp.asarray(p_full0)))
    p_full = np.concatenate([np.asarray(p) for p in ps], axis=0)
    return p_full, np.asarray(Q.get()), sess
