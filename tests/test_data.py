"""Data pipeline: restart exactness + partition properties."""

import numpy as np
import pytest

pytest.importorskip("hypothesis")
from hypothesis import given, strategies as st

from repro.data import LMDataPipeline, lm_batch, partition_rows


def test_stateless_stream():
    b1 = lm_batch(5, 4, 16, 100, seed=7)
    b2 = lm_batch(5, 4, 16, 100, seed=7)
    assert np.array_equal(b1["tokens"], b2["tokens"])
    b3 = lm_batch(6, 4, 16, 100, seed=7)
    assert not np.array_equal(b1["tokens"], b3["tokens"])


def test_pipeline_restart_exact():
    p = LMDataPipeline(4, 8, 100, prefetch=True)
    batches = [p.next() for _ in range(3)]
    p.close()
    p2 = LMDataPipeline(4, 8, 100, prefetch=False, start_step=1)
    s, b = p2.next()
    assert s == 1
    assert np.array_equal(np.asarray(b["tokens"]), np.asarray(batches[1][1]["tokens"]))


def test_labels_are_shifted_tokens():
    b = lm_batch(0, 2, 8, 50, seed=0)
    # tokens/labels come from one (T+1)-stream: labels[t] == tokens[t+1]
    assert np.array_equal(b["tokens"][:, 1:], b["labels"][:, :-1])


@given(st.integers(1, 500), st.integers(1, 16))
def test_partition_rows_cover_disjoint(n_rows, n_threads):
    spans = [partition_rows(n_rows, t, n_threads) for t in range(n_threads)]
    covered = []
    for lo, hi in spans:
        assert 0 <= lo <= hi <= n_rows
        covered.extend(range(lo, hi))
    assert covered == list(range(n_rows))


def test_rmat_graph_shape_range_and_skew():
    from repro.data import rmat_graph
    v, e = 5000, 60000
    edges = rmat_graph(v, e, seed=3)
    assert edges.shape == (e, 2) and edges.dtype == np.int32
    assert edges.min() >= 0 and edges.max() < v
    assert np.array_equal(edges, rmat_graph(v, e, seed=3))
    assert not np.array_equal(edges, rmat_graph(v, e, seed=4))
    # R-MAT is skewed, but no vertex draws anything near zipf's share
    share = np.bincount(edges[:, 1], minlength=v).max() / e
    assert 1.0 / v < share < 0.05
