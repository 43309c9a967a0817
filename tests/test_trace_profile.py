"""Session's spans on the profiler's clock, and the program's named scopes.

Armed, every context-manager span of the tracer is also a
``jax.profiler.TraceAnnotation``: a ``jax.profiler`` trace of an SPMD job
holds ``session.run`` and the five stages of its join, in order and nested.
Disarmed, no annotation is made.  Inside the device program ``ctx.span``
and the apps' scopes are ``jax.named_scope`` scopes, which the compiled
program's ``hlo_scopes`` map shows.
"""

from pathlib import Path

import jax
import numpy as np
import pytest

from repro.analytics import kmeans, logreg, pagerank
from repro.core import Session

STAGES = ("spmd.trace", "spmd.lower", "spmd.compile", "spmd.run", "spmd.writeback")


def _edges(n_vertices=64, n_edges=512, seed=0):
    rng = np.random.default_rng(seed)
    return rng.integers(0, n_vertices, size=(n_edges, 2), dtype=np.int32)


def test_spmd_job_spans_land_in_a_profiler_trace(tmp_path):
    from jax.profiler import ProfileData
    sess = Session(backend="spmd", trace=True)
    try:
        with jax.profiler.trace(str(tmp_path)):
            pagerank.fit(_edges(), 64, iters=2, session=sess)
    finally:
        sess.tracer.disable()
    (pb,) = Path(tmp_path).rglob("*.xplane.pb")
    events = {}
    for plane in ProfileData.from_file(str(pb)).planes:
        for line in plane.lines:
            for e in line.events:
                if e.name == "session.run" or e.name.startswith("spmd."):
                    events[e.name] = (e.start_ns, e.end_ns, dict(e.stats))
    assert set(events) == {"session.run", *STAGES}
    job0, job1, stats = events["session.run"]
    assert int(stats["session"]) == sess.id
    t = job0
    for name in STAGES:
        start, end, stats = events[name]
        assert t <= start <= end <= job1, name
        assert int(stats["session"]) == sess.id
        t = end


def test_disarmed_tracer_makes_no_annotation(monkeypatch):
    made = []

    class Counting:
        def __init__(self, name, **kwargs):
            made.append(name)

        def __enter__(self):
            return self

        def __exit__(self, *exc):
            return None

    monkeypatch.setattr(jax.profiler, "TraceAnnotation", Counting)
    x = np.random.default_rng(0).normal(size=(64, 4)).astype(np.float32)
    for backend in ("spmd", "host"):
        pagerank.fit(_edges(), 64, iters=2, backend=backend, n_nodes=1, threads_per_node=2)
        kmeans.fit(x, 3, iters=2, backend=backend, n_nodes=1, threads_per_node=2)
    assert made == []
    # the same counter sees the armed spans
    sess = Session(backend="host", n_nodes=1, threads_per_node=2, trace=True)
    try:
        pagerank.fit(_edges(), 64, iters=2, session=sess)
    finally:
        sess.tracer.disable()
    assert {"session.run", "accumulate.round", "accumulate.sync", "pagerank.round"} <= set(made)
    assert "accumulate" not in made and "store.get" not in made     # per-op spans


def test_named_scopes_reach_the_compiled_program():
    sess = Session(backend="spmd", trace=True)
    try:
        pagerank.fit(_edges(), 64, iters=2, session=sess)
    finally:
        sess.tracer.disable()
    (compile_span,) = sess.tracer.spans(name="spmd.compile")
    scopes = [s.split("/") for s in compile_span["args"]["hlo_scopes"].values()]
    for scope in ("pagerank.round", "pagerank.gather", "pagerank.scatter",
                  "accumulate.auto", "accumulate.auto_decide", "accumulate.reduce_scatter"):
        assert any(scope in s for s in scopes), scope
    # the round's ops sit inside the round's scope
    assert all("pagerank.round" in s for s in scopes if "pagerank.gather" in s)


def test_kmeans_scopes():
    x = np.random.default_rng(1).normal(size=(64, 4)).astype(np.float32)
    sess = Session(backend="spmd", trace=True)
    try:
        kmeans.fit(x, 3, iters=2, session=sess)
    finally:
        sess.tracer.disable()
    scopes = sess.tracer.spans(name="spmd.compile")[0]["args"]["hlo_scopes"].values()
    for scope in ("kmeans.round", "kmeans.assign", "kmeans.partials"):
        assert any(scope in s.split("/") for s in scopes), scope


def test_host_auto_round_holds_its_sync():
    sess = Session(backend="host", n_nodes=1, threads_per_node=2, trace=True)
    try:
        pagerank.fit(_edges(), 64, iters=3, mode="auto", session=sess)
    finally:
        sess.tracer.disable()
    rounds = sess.tracer.spans(name="accumulate.round")
    syncs = sess.tracer.spans(name="accumulate.sync")
    assert len(rounds) == len(syncs) == 3
    for r, s in zip(rounds, syncs):
        assert r["ts"] <= s["ts"] and s["ts"] + s["dur"] <= r["ts"] + r["dur"]
        assert set(r["args"]) == {"mode", "vec_len", "threads", "pairs", "wire_elements"}
    # a fixed mode takes no decision, so it waits on none
    sess = Session(backend="host", n_nodes=1, threads_per_node=2, trace=True)
    try:
        pagerank.fit(_edges(), 64, iters=2, mode="reduce_scatter", session=sess)
    finally:
        sess.tracer.disable()
    assert len(sess.tracer.spans(name="accumulate.round")) == 2
    assert sess.tracer.spans(name="accumulate.sync") == []


def test_flight_recorder_keeps_the_job_and_its_stages():
    sess = Session(backend="spmd", record=True)
    try:
        pagerank.fit(_edges(), 64, iters=2, session=sess)
        names = [e["name"] for e in sess.tracer.ring_events()]
    finally:
        sess.recorder.close()
    assert [n for n in names if n in STAGES] == list(STAGES)
    assert "session.run" in names


@pytest.mark.parametrize("backend", ["host", "spmd"])
def test_session_ids_are_unique(backend):
    a, b = Session(backend=backend), Session(backend=backend)
    assert a.id != b.id


def _ell(n_rows, n_features, width=6, seed=0):
    rng = np.random.default_rng(seed)
    idx = rng.integers(0, n_features, size=(n_rows, width)).astype(np.int32)
    return (idx, np.ones((n_rows, width), np.float32)), (rng.random(n_rows) < 0.3).astype(
        np.float32)


def test_fused_span_sits_in_every_pairs_round_and_no_dense_one():
    """Sparse rounds (8 rows a thread over 4,096 features) take the pairs
    path, each with its ``accumulate.fused`` span inside the round's;
    overflowing rounds (128 rows a thread over 2,048) go dense, with none."""
    rounds, fused = [], []
    for n_features, batch, iters, width in ((4096, 8, 3, 6), (2048, 128, 2, 8)):
        sess = Session(backend="host", n_nodes=2, threads_per_node=2, trace=True)
        try:
            rows, y = _ell(4 * batch * iters, n_features, width)
            logreg.fit(rows, y, n_features=n_features, batch=batch, iters=iters,
                       mode="auto", session=sess)
        finally:
            sess.tracer.disable()
        rounds += sess.tracer.spans(name="accumulate.round")
        fused += sess.tracer.spans(name="accumulate.fused")
    assert [r["args"]["mode"] for r in rounds] == ["sparse"] * 3 + ["reduce_scatter"] * 2
    assert len(fused) == 3
    for r, f in zip(rounds, fused):
        assert r["ts"] <= f["ts"] and f["ts"] + f["dur"] <= r["ts"] + r["dur"]


def test_disarmed_pairs_round_makes_no_annotation(monkeypatch):
    made = []

    class Counting:
        def __init__(self, name, **kwargs):
            made.append(name)

        def __enter__(self):
            return self

        def __exit__(self, *exc):
            return None

    monkeypatch.setattr(jax.profiler, "TraceAnnotation", Counting)
    rows, y = _ell(4 * 8 * 2, 4096)
    _, sess = logreg.fit(rows, y, n_features=4096, batch=8, iters=2, mode="auto")
    assert sess.tracer.counters() == {} and made == []
    armed = Session(backend="host", n_nodes=2, threads_per_node=2, trace=True)
    try:
        logreg.fit(rows, y, n_features=4096, batch=8, iters=2, mode="auto", session=armed)
    finally:
        armed.tracer.disable()
    assert made.count("accumulate.fused") == 2


def test_logreg_scopes():
    rows, y = _ell(64, 512)
    sess = Session(backend="spmd", trace=True)
    try:
        logreg.fit(rows, y, n_features=512, batch=8, iters=2, session=sess)
    finally:
        sess.tracer.disable()
    scopes = [s.split("/") for s in
              sess.tracer.spans(name="spmd.compile")[0]["args"]["hlo_scopes"].values()]
    for scope in ("logreg.round", "logreg.margin", "logreg.grad"):
        assert any(scope in s for s in scopes), scope
