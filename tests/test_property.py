"""Hypothesis property tests on system invariants."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

pytest.importorskip("hypothesis")
from hypothesis import given, settings, strategies as st

from repro.core import pack_spec, pack_tree, unpack_tree
from repro.core.sparse import blocked_topk_sparsify, densify, sparse_beneficial
from repro.launch.shardings import sanitize_spec
from jax.sharding import PartitionSpec as P


@settings(max_examples=30, deadline=None)
@given(st.lists(st.tuples(st.integers(1, 8), st.integers(1, 8)), min_size=1, max_size=5),
       st.integers(0, 5))
def test_pack_unpack_roundtrip_2d(shapes, seed):
    rng = np.random.default_rng(seed)
    tree = {f"l{i}": jnp.asarray(rng.normal(size=s), jnp.float32)
            for i, s in enumerate(shapes)}
    spec = pack_spec(tree)
    back = unpack_tree(pack_tree(tree, spec), spec)
    for k in tree:
        np.testing.assert_allclose(np.asarray(back[k]), np.asarray(tree[k]), rtol=1e-6)


@settings(max_examples=30, deadline=None)
@given(st.integers(1, 12), st.integers(0, 5))
def test_sparse_lossless_when_under_budget(nnz, seed):
    """densify(topk(x)) == x whenever nnz(x) <= k (the auto-mode guarantee)."""
    rng = np.random.default_rng(seed)
    v = np.zeros(256, np.float32)
    pos = rng.choice(256, size=nnz, replace=False)
    v[pos] = rng.normal(size=nnz)
    x = jnp.asarray(v)
    k = 16
    idx, vals = blocked_topk_sparsify(x, k)
    np.testing.assert_allclose(np.asarray(densify(idx, vals, 256)), v, rtol=1e-6)


@settings(max_examples=30, deadline=None)
@given(st.integers(1, 64), st.integers(1, 8))
def test_sanitize_spec_always_divides(dim, axis_size):
    class FakeMesh:
        shape = {"data": axis_size}
        axis_names = ("data",)
    spec = sanitize_spec(P("data"), (dim,), FakeMesh())
    if spec[0] is not None:
        assert dim % axis_size == 0


@settings(max_examples=15, deadline=None)
@given(st.integers(0, 5))
def test_accumulator_linearity(seed):
    """accumulate is a linear operator: sum of parts == part of sums."""
    rng = np.random.default_rng(seed)
    a = rng.normal(size=(64,)).astype(np.float32)
    b = rng.normal(size=(64,)).astype(np.float32)
    from repro.core.sparse import blocked_topk_accumulate

    def total(*rows):        # k = 64 fills the one 64-wide block: no selection
        return blocked_topk_accumulate(jnp.stack([jnp.asarray(r) for r in rows]), k=64)

    lhs = total(a + b)
    rhs = total(a) + total(b)
    np.testing.assert_allclose(np.asarray(lhs), np.asarray(rhs), rtol=1e-5)


@settings(max_examples=10, deadline=None)
@given(st.lists(st.text(alphabet="abcdefgh", min_size=1, max_size=8),
                min_size=1, max_size=20, unique=True),
       st.integers(0, 25), st.integers(0, 10 ** 6))
def test_crash_mid_migration_loses_and_duplicates_nothing(keys, steps, seed):
    """step.tiers satellite: kill a session inside an open migration window
    at an arbitrary drain point — session_recovery must complete the handoff
    with every key present exactly once and every value intact."""
    from repro.core import Session
    from repro.ft import session_recovery

    sess = Session(backend="host", n_nodes=2, threads_per_node=1, shards=2)
    vals = {f"hz_{k}": float((seed + i) % 977)
            for i, k in enumerate(keys)}
    for k, v in vals.items():
        sess.store.def_global(k, jnp.full((4,), v))
    sess.store.add_shard(9, drain=False)             # window opens
    sess.store.migrate_step(steps)                   # partial drain
    plan, new_sess = session_recovery(sess, [1])     # crash strikes here
    assert new_sess.store.migration_window is None
    assert sorted(new_sess.store.names()) == sorted(vals)
    for k, v in vals.items():
        np.testing.assert_allclose(np.asarray(new_sess.store.get(k)), v)
