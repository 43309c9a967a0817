"""DAddAccumulator host layer: correctness + the paper's traffic formulas."""

import threading
import time

import jax.numpy as jnp
import numpy as np
import pytest

from conftest import unfused_reduce
from repro.core import AccumMode, DAddAccumulator, GlobalStore
from repro.core.sparse import DEFAULT_BLOCK, pair_capacity


def run_round(mode, vecs, n_nodes=2, k=None):
    n = len(vecs)
    store = GlobalStore()
    store.new_array("out", (vecs[0].size,))
    acc = DAddAccumulator(store, "out", n, n_nodes, mode, k=k)
    ts = [threading.Thread(target=acc.accumulate, args=(v,)) for v in vecs]
    [t.start() for t in ts]
    [t.join(10) for t in ts]
    return np.asarray(store.get("out")), acc


def test_sum_correct_all_modes():
    vecs = [jnp.full((64,), float(i + 1)) for i in range(4)]
    expect = np.full(64, 1.0 + 2 + 3 + 4)
    for mode in AccumMode:
        # k=V keeps sparse lossless even for fully-dense contributions
        out, _ = run_round(mode, vecs, k=64)
        np.testing.assert_allclose(out, expect)


def test_traffic_formulas():
    """Paper §5.2: (2N+1)·V naive vs (N+1)·V accumulator."""
    V, N = 128, 4
    vecs = [jnp.ones((V,)) for _ in range(N)]
    _, naive = run_round(AccumMode.GATHER_ALL, vecs)
    _, rs = run_round(AccumMode.REDUCE_SCATTER, vecs)
    assert naive.bytes_transferred == (2 * N + 1) * V
    assert rs.bytes_transferred == (N + 1) * V
    assert rs.bytes_transferred < naive.bytes_transferred


def _sparse_vecs(V, N, nnz=3):
    vecs = []
    for i in range(N):
        v = np.zeros(V, np.float32)
        v[i * nnz: (i + 1) * nnz] = float(i + 1)
        vecs.append(jnp.asarray(v))
    return vecs


def test_sparse_traffic_from_actual_pairs():
    """Sparse traffic is Σ_threads 2·pairs + V, with pairs = the static
    capacity of the budget-k compression — never a dense-sum figure."""
    V, N, k = 1024, 4, 8
    vecs = _sparse_vecs(V, N)
    out, sp = run_round(AccumMode.SPARSE, vecs, k=k)
    P = pair_capacity(V, k)
    assert sp.last_pair_counts == [P] * N
    assert sp.bytes_transferred == N * 2 * P + V
    np.testing.assert_allclose(out, np.sum(np.stack(vecs), axis=0))  # lossless


def test_fused_reduce_matches_unfused_bitexact():
    """The pairs round (one sparsify→scatter-add kernel launch) must be
    bit-exact with the compress→densify→add chain, and carry identical pair
    counts + wire accounting — fusion is an implementation detail, never a
    semantics change."""
    V, N, k = 1024, 4, 8
    for vecs in (_sparse_vecs(V, N),                       # lossless round
                 [jnp.asarray(np.random.default_rng(i).normal(size=V)
                              .astype(np.float32)) for i in range(N)]):  # lossy
        out_f, acc_f = run_round(AccumMode.SPARSE, vecs, k=k)
        out_u, counts_u, wire_u = unfused_reduce(jnp.stack(vecs), k, DEFAULT_BLOCK)
        assert np.array_equal(out_f, np.asarray(out_u))
        assert acc_f.last_pair_counts == counts_u
        assert acc_f.bytes_transferred == wire_u


def test_sparse_requires_budget():
    store = GlobalStore()
    store.new_array("out", (8,))
    with pytest.raises(ValueError, match="top-k budget"):
        DAddAccumulator(store, "out", 2, 2, AccumMode.SPARSE)


def test_sparse_is_lossy_beyond_budget():
    """nnz > capacity: the round keeps only the top-k pairs per thread —
    same lossy semantics as the SPMD collective, not a silent dense sum."""
    V, k, N = 256, 4, 2
    rng = np.random.default_rng(0)
    vec = jnp.asarray(rng.normal(size=(V,)), jnp.float32)   # fully dense
    out, acc = run_round(AccumMode.SPARSE, [vec, vec], k=k)
    P = pair_capacity(V, k)
    assert int(np.sum(out != 0)) <= P          # top-k survived, rest dropped
    assert acc.bytes_transferred == N * 2 * P + V
    # the kept entries are the k largest-|x|
    top = np.argsort(-np.abs(np.asarray(vec)))[:P]
    np.testing.assert_allclose(out[top], 2 * np.asarray(vec)[top], rtol=1e-6)


def test_auto_crossover_dense_vs_pairs():
    """AUTO takes the pairs path iff every contribution is losslessly
    compressible AND cheaper; accounting follows the branch actually taken."""
    V, N, k = 1024, 4, 8
    sparse_vecs = _sparse_vecs(V, N)
    out, auto = run_round(AccumMode.AUTO, sparse_vecs, k=k)
    assert auto.last_mode == AccumMode.SPARSE
    assert auto.bytes_transferred == N * 2 * pair_capacity(V, k) + V
    np.testing.assert_allclose(out, np.sum(np.stack(sparse_vecs), axis=0))

    dense_vecs = [jnp.ones((V,)) for _ in range(N)]
    out2, auto2 = run_round(AccumMode.AUTO, dense_vecs, k=k)
    assert auto2.last_mode == AccumMode.REDUCE_SCATTER
    assert auto2.bytes_transferred == (N + 1) * V
    np.testing.assert_allclose(out2, N)

    # one dense thread among sparse ones forces the dense branch (global AND)
    mixed = sparse_vecs[:-1] + [jnp.ones((V,))]
    _, auto3 = run_round(AccumMode.AUTO, mixed, k=k)
    assert auto3.last_mode == AccumMode.REDUCE_SCATTER


def test_auto_defaults_budget_when_unset():
    """AUTO without an explicit k resolves a ~V/4 default per round and still
    crosses over; results are unchanged (auto is lossless by construction)."""
    V, N = 1024, 4
    out, auto = run_round(AccumMode.AUTO, _sparse_vecs(V, N))   # k=None
    assert auto.last_mode == AccumMode.SPARSE
    np.testing.assert_allclose(out, np.sum(np.stack(_sparse_vecs(V, N)), axis=0))
    assert auto.bytes_transferred < (N + 1) * V                 # pairs won


def test_ragged_contribution_is_an_error():
    """All threads must contribute equal-length vectors; a ragged one aborts
    the round instead of mis-accounting vec_len from the last arrival."""
    store = GlobalStore()
    store.new_array("out", (8,))
    acc = DAddAccumulator(store, "out", 2, 2, AccumMode.REDUCE_SCATTER)
    peer_errors = []

    def peer():
        try:
            acc.accumulate(jnp.ones(8))
        except threading.BrokenBarrierError as e:
            peer_errors.append(e)

    t = threading.Thread(target=peer)
    t.start()
    deadline = time.time() + 10
    while acc._count == 0 and time.time() < deadline:
        time.sleep(0.005)           # peer's contribution opens the round
    with pytest.raises(ValueError, match="ragged"):
        acc.accumulate(jnp.ones(4))
    t.join(10)                      # barrier was aborted: peer released
    assert not t.is_alive() and len(peer_errors) == 1
    # the poisoned round was dropped: no partial state, nothing stored
    assert acc._count == 0 and acc._vecs == [] and acc._partial is None
    assert acc.rounds == 0
    np.testing.assert_allclose(np.asarray(store.get("out")), 0.0)
    # and the accumulator is poisoned: a retry must NOT publish to the store
    # against a barrier that stays broken
    with pytest.raises(RuntimeError, match="aborted"):
        acc.accumulate(jnp.ones(8))
    assert acc.rounds == 0
    np.testing.assert_allclose(np.asarray(store.get("out")), 0.0)


def test_same_size_different_shape_is_ragged():
    """(8, 1) vs (8,) has equal size but must not broadcast into a silently
    wrong (8, 8) total — the shape guard catches it."""
    store = GlobalStore()
    store.new_array("out", (8,))
    acc = DAddAccumulator(store, "out", 2, 2, AccumMode.REDUCE_SCATTER)
    peer_errors = []

    def peer():
        try:
            acc.accumulate(jnp.ones((8, 1)))
        except threading.BrokenBarrierError as e:
            peer_errors.append(e)

    t = threading.Thread(target=peer)
    t.start()
    deadline = time.time() + 10
    while acc._count == 0 and time.time() < deadline:
        time.sleep(0.005)
    with pytest.raises(ValueError, match="ragged"):
        acc.accumulate(jnp.ones(8))
    t.join(10)
    assert not t.is_alive() and len(peer_errors) == 1


def test_sparse_auto_scalar_and_matrix_contributions():
    """Scalars and rank>=2 contributions ride the sparse/auto path flattened
    (as the SPMD ctx normalises ranks), with the round shape restored."""
    store = GlobalStore()
    store.def_global("s", 0.0)
    acc = DAddAccumulator(store, "s", 2, 2, AccumMode.AUTO)
    ts = [threading.Thread(target=acc.accumulate, args=(jnp.asarray(v),))
          for v in (2.0, 3.0)]
    [t.start() for t in ts]
    [t.join(10) for t in ts]
    assert float(store.get("s")) == 5.0
    assert acc.last_mode == AccumMode.REDUCE_SCATTER   # 2·cap < 1 never holds

    store.new_array("m", (4, 8))
    accm = DAddAccumulator(store, "m", 2, 2, AccumMode.SPARSE, k=4)
    mat = np.zeros((4, 8), np.float32)
    mat[1, 2] = 5.0
    mat[3, 7] = -1.0
    ts = [threading.Thread(target=accm.accumulate, args=(jnp.asarray(mat),))
          for _ in range(2)]
    [t.start() for t in ts]
    [t.join(10) for t in ts]
    got = np.asarray(store.get("m"))
    assert got.shape == (4, 8)
    np.testing.assert_allclose(got, 2 * mat)           # nnz=2 <= k: lossless


def test_reduce_failure_releases_waiters():
    """An exception inside the round reduction (here: an invalid AUTO budget)
    must abort the barrier instead of stranding the other threads forever."""
    store = GlobalStore()
    store.new_array("out", (8,))
    acc = DAddAccumulator(store, "out", 2, 2, AccumMode.AUTO, k=0)
    peer_errors = []

    def peer():
        try:
            acc.accumulate(jnp.ones(8))
        except threading.BrokenBarrierError as e:
            peer_errors.append(e)

    t = threading.Thread(target=peer)
    t.start()
    deadline = time.time() + 10
    while acc._count == 0 and time.time() < deadline:
        time.sleep(0.005)
    with pytest.raises(ValueError, match="budget"):
        acc.accumulate(jnp.ones(8))   # last arrival runs the failing reduce
    t.join(10)
    assert not t.is_alive() and len(peer_errors) == 1


def test_multi_round():
    V, N = 32, 3
    store = GlobalStore()
    store.new_array("out", (V,))
    acc = DAddAccumulator(store, "out", N, 2, AccumMode.REDUCE_SCATTER)

    def worker():
        for _ in range(3):
            acc.accumulate(jnp.ones((V,)))

    ts = [threading.Thread(target=worker) for _ in range(N)]
    [t.start() for t in ts]
    [t.join(10) for t in ts]
    assert acc.rounds == 3
    np.testing.assert_allclose(np.asarray(store.get("out")), N)


def test_multi_round_sparse_accounting_resets():
    """Pair accounting is per-round: a sparse round followed by another must
    not reuse the previous round's pair list (the old _nnzs reuse bug)."""
    V, N, k = 512, 2, 4
    store = GlobalStore()
    store.new_array("out", (V,))
    acc = DAddAccumulator(store, "out", N, 2, AccumMode.SPARSE, k=k)
    v = np.zeros(V, np.float32)
    v[:2] = 1.0
    vec = jnp.asarray(v)

    def worker():
        for _ in range(3):
            acc.accumulate(vec)

    ts = [threading.Thread(target=worker) for _ in range(N)]
    [t.start() for t in ts]
    [t.join(10) for t in ts]
    P = pair_capacity(V, k)
    assert acc.rounds == 3
    assert acc.bytes_transferred == 3 * (N * 2 * P + V)
    assert acc.last_pair_counts == [P] * N
