"""Logistic regression on ELL rows with mini-batch rounds: the same result
as dense rows, and the host accumulator's AUTO rule taking the pairs path
exactly when each thread's batch fits every block's quota."""

import numpy as np
import pytest

from repro.analytics import logreg
from repro.core import Session
from repro.core.sparse import default_auto_k, pair_capacity

N_THREADS = 4


def ell_rows(n, width, n_features, seed):
    """Random ELL rows with values, and the same rows dense."""
    rng = np.random.default_rng(seed)
    idx = rng.integers(0, n_features, size=(n, width)).astype(np.int32)
    val = rng.normal(size=(n, width)).astype(np.float32)
    y = (rng.random(n) < 0.3).astype(np.float32)
    x = np.zeros((n, n_features), np.float32)
    np.add.at(x, (np.arange(n)[:, None], idx), val)
    return idx, val, y, x


def traced_fit(rows, y, n_features, batch, iters):
    sess = Session(backend="host", n_nodes=2, threads_per_node=2, trace=True)
    try:
        theta, _ = logreg.fit(rows, y, n_features=n_features, batch=batch, iters=iters,
                              lr=0.1, mode="auto", session=sess)
        counters = sess.tracer.counters()
    finally:
        sess.tracer.disable()
    return theta, sess.wire_traffic(), counters


@pytest.mark.parametrize("batch,iters", [(None, 3), (16, 4)])
@pytest.mark.parametrize("backend", ["host", "spmd"])
def test_ell_rows_equal_dense_rows(backend, batch, iters):
    idx, val, y, x = ell_rows(256, 6, 1536, seed=1)
    kw = dict(batch=batch, iters=iters, lr=0.1, mode="auto", backend=backend)
    ell, ell_sess = logreg.fit((idx, val), y, n_features=1536, **kw)
    dense, dense_sess = logreg.fit(x, y, **kw)
    np.testing.assert_allclose(ell, dense, rtol=1e-5, atol=1e-6)
    assert np.max(np.abs(ell)) > 1e-2
    assert ell_sess.wire_traffic() == dense_sess.wire_traffic()


def test_minibatch_round_is_the_batch_at_the_round_index():
    """Round i of thread t reads rows [i·batch, (i+1)·batch) of its
    partition: one thread, two rounds, against the formula by hand."""
    idx, val, y, x = ell_rows(64, 5, 300, seed=2)
    theta, _ = logreg.fit((idx, val), y, n_features=300, batch=8, iters=2, lr=0.5,
                          backend="host", n_nodes=1, threads_per_node=1)
    want = np.zeros(300)
    for i in range(2):
        xb, yb = x[8 * i: 8 * (i + 1)].astype(np.float64), y[8 * i: 8 * (i + 1)]
        want += 0.5 * (yb - 1 / (1 + np.exp(-xb @ want))) @ xb
    np.testing.assert_allclose(theta, want, rtol=1e-5, atol=1e-6)


def test_sparse_minibatch_takes_the_pairs_path_every_round():
    n_features, batch, iters = 4096, 8, 6       # 4 blocks; 48 entries a thread
    idx, val, y, _ = ell_rows(N_THREADS * batch * iters + 3, 6, n_features, seed=3)
    _, wire, counters = traced_fit((idx, val), y, n_features, batch, iters)
    cap = pair_capacity(n_features, default_auto_k(n_features))
    assert counters["accum.kernel_path.fused"] == iters
    assert "accum.kernel_path.dense" not in counters
    assert wire == iters * (2 * N_THREADS * cap + n_features)


def test_batch_that_overflows_a_block_falls_back_to_dense():
    """2,048 features in two blocks of quota 256: 128 rows of 8 uniform
    features put ~400 distinct features in each block, so the rule takes
    the dense wire."""
    n_features, batch, iters = 2048, 128, 3
    idx, val, y, _ = ell_rows(N_THREADS * batch * iters, 8, n_features, seed=4)
    _, wire, counters = traced_fit((idx, val), y, n_features, batch, iters)
    assert counters["accum.kernel_path.dense"] == iters
    assert "accum.kernel_path.fused" not in counters
    assert wire == iters * (N_THREADS + 1) * n_features


def test_rows_must_fit_the_rounds():
    idx, val, y, _ = ell_rows(100, 4, 64, seed=5)
    with pytest.raises(ValueError, match="smallest of 4 partitions"):
        logreg.fit((idx, val), y, n_features=64, batch=10, iters=3)
    with pytest.raises(ValueError, match="n_features"):
        logreg.fit((idx, val), y)
    with pytest.raises(ValueError, match="one"):
        logreg.fit((idx, val[:, :3]), y, n_features=64)
    with pytest.raises(ValueError, match=r"in \[0, 60\)"):
        logreg.fit((idx, val), y, n_features=60)


def test_loss_reads_ell_rows_as_dense():
    idx, val, y, x = ell_rows(50, 4, 40, seed=6)
    theta = np.random.default_rng(0).normal(size=40).astype(np.float32)
    assert logreg.loss(theta, (idx, val), y) == pytest.approx(logreg.loss(theta, x, y),
                                                              rel=1e-5)
