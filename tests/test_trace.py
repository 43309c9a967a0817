"""step.trace — tracer correctness, export round-trip, stats unification.

The tentpole contract: tracing is a strict no-op by default (no events, no
allocation, nothing armed globally); armed, it records spans/counters/
histograms from every hot path (store ops, barrier waits, accumulator
rounds, sync primitives, SPMD settling) with per-thread attribution; the
Chrome-trace export loads back as plain JSON with all three core span
categories present for a 2-thread logreg host run; and the three legacy
stats shapes stay intact beneath the canonical ``Session.metrics()`` keys.
"""

import json
import threading

import jax.numpy as jnp
import numpy as np
import pytest

from repro.analytics import logreg
from repro.core import Session, telemetry
from repro.core.shards import ShardedStore
from repro.core.telemetry import (
    CACHE_METRIC_KEYS,
    SESSION_METRIC_KEYS,
    STORE_METRIC_KEYS,
    Tracer,
)
from repro.ft import metrics_payload, session_recovery


def _logreg_data(n=64, d=16, seed=0):
    rng = np.random.default_rng(seed)
    x = rng.normal(size=(n, d)).astype(np.float32)
    y = (rng.random(n) > 0.5).astype(np.float32)
    return x, y


# -- no-op by default ---------------------------------------------------------


def test_noop_by_default():
    """A plain Session records nothing, arms nothing, and ctx.span is the
    shared null context manager — the zero-cost guarantee."""
    assert telemetry.armed_count() == 0
    x, y = _logreg_data()
    theta, sess = logreg.fit(x, y, iters=2, n_nodes=1, threads_per_node=2)
    assert not sess.tracer.enabled
    assert telemetry.TRACING is False
    assert telemetry.armed_count() == 0
    snap = sess.tracer.snapshot()
    assert snap["events"] == 0
    assert snap["counters"] == {}
    assert snap["spans_by_category"] == {}
    # metrics() still works against a disabled tracer
    m = sess.metrics()
    assert m["trace"]["enabled"] is False


def test_arm_disarm_scoping():
    t1, t2 = Tracer(enabled=True), Tracer(enabled=True)
    try:
        assert telemetry.TRACING and telemetry.armed_count() == 2
        t1.disable()
        assert telemetry.TRACING and telemetry.armed_count() == 1
        t2.disable()
        assert not telemetry.TRACING and telemetry.armed_count() == 0
    finally:
        telemetry.reset()


# -- the acceptance criterion: export round-trip from a 2-thread logreg run ---


def test_chrome_export_roundtrip_logreg(tmp_path):
    x, y = _logreg_data()
    sess = Session(backend="host", n_nodes=2, threads_per_node=1, trace=True)
    try:
        theta, _ = logreg.fit(x, y, iters=3, session=sess)
        path = sess.tracer.export(str(tmp_path / "trace.json"))
        with open(path) as f:
            trace = json.load(f)          # must round-trip as plain JSON
        events = trace["traceEvents"]
        cats = {e.get("cat") for e in events if e.get("ph") == "X"}
        for required in ("store-op", "barrier-wait", "accumulate-round"):
            assert required in cats, f"missing {required} spans in export"
        # app-round markers from ctx.span land too (host backend)
        assert "app-round" in cats
        # thread metadata: both STEP threads named on their node timelines
        names = {(e["pid"], e["tid"]) for e in events
                 if e.get("ph") == "M" and e["name"] == "thread_name"}
        assert {(0, 0), (1, 1)} <= names
        # every X event carries the Chrome-trace complete-event fields
        for e in events:
            if e.get("ph") == "X":
                assert {"name", "cat", "ts", "dur", "pid", "tid"} <= set(e)
    finally:
        sess.tracer.disable()


# -- span correctness under concurrency ---------------------------------------


def test_accumulate_span_counts_and_thread_attribution():
    """N threads x R rounds => exactly N*R per-thread 'accumulate' spans, R
    reduce spans, and per-thread spans that never overlap on a timeline."""
    N_NODES, TPN, R = 2, 2, 3
    N = N_NODES * TPN
    sess = Session(backend="host", n_nodes=N_NODES, threads_per_node=TPN,
                   trace=True)
    try:
        ref = sess.new_array("v", (32,))

        def proc(ctx, xs):
            def step(c):
                return c + ref.accumulate(xs.sum(axis=0)).sum()
            return ctx.iterate(step, jnp.float32(0), R)

        sess.run(proc, data=(jnp.ones((N * 2, 32)),))
        per_thread = sess.tracer.spans("accumulate-round", "accumulate")
        assert len(per_thread) == N * R
        reduces = sess.tracer.spans("accumulate-round", "accumulate.round")
        assert len(reduces) == R
        assert all(r["args"]["threads"] == N for r in reduces)
        # attribution: spans landed on N distinct (node, tid) timelines, R each
        by_tid = {}
        for e in per_thread:
            by_tid.setdefault((e["pid"], e["tid"]), []).append(e)
        assert len(by_tid) == N
        for timeline in by_tid.values():
            assert len(timeline) == R
            timeline.sort(key=lambda e: e["ts"])
            for a, b in zip(timeline, timeline[1:]):
                # a thread's rounds are sequential: no span starts before the
                # previous one on the same timeline ended
                assert b["ts"] >= a["ts"] + a["dur"] - 1e-3
        # each accumulate span brackets its barrier wait on the same thread
        waits = sess.tracer.spans("barrier-wait", "accumulate.barrier")
        assert len(waits) == N * R
        counters = sess.tracer.counters()
        assert counters["accumulate.rounds"] == R
        assert counters["accumulate.wire_elements"] == sess.wire_traffic()
    finally:
        sess.tracer.disable()


def test_barrier_semaphore_ssp_instrumentation():
    sess = Session(backend="host", n_nodes=2, threads_per_node=2, trace=True)
    try:
        bar = sess.barrier()
        sem = sess.semaphore(1)
        clock = sess.ssp_clock(staleness=0, n_workers=4)

        def proc(ctx, xs):
            sem.acquire()
            sem.release()
            ctx.barrier()          # backend run barrier (tracer attached)
            bar.enter()            # session-factory barrier
            clock.tick(ctx.tid)
            clock.wait(ctx.tid)
            return None

        sess.run(proc, data=(jnp.ones((4, 4)),))
        snap = sess.tracer.snapshot()
        # two traced barriers x 4 threads
        assert snap["ops"]["barrier.wait"]["count"] == 8
        assert len(sess.tracer.spans("barrier-wait", "barrier.wait")) == 8
        assert snap["ops"]["semaphore.queue_depth"]["count"] == 4
        assert snap["ops"]["semaphore.queue_depth"]["max"] >= 1
        assert len(sess.tracer.spans("sync", "semaphore.acquire")) == 4
        skew = snap["ops"]["ssp.skew"]
        assert skew["count"] == 4 and skew["max"] <= 1  # staleness=0 bound+1
    finally:
        sess.tracer.disable()


def test_store_op_shard_attribution_and_lock_wait():
    store = ShardedStore(shards=4)
    trc = Tracer(enabled=True)
    store.tracer = trc
    try:
        for i in range(32):
            store.def_global(f"n{i}", float(i))
            store.get(f"n{i}")
            store.inc(f"n{i}", 1.0)
        store.mget([f"n{i}" for i in range(32)])
        snap = trc.snapshot()
        assert snap["ops"]["store.get"]["count"] == 32
        assert snap["ops"]["store.inc"]["count"] == 32
        assert snap["ops"]["store.mget"]["count"] == 1
        # per-shard histograms: the 32 names spread over all 4 shard rows
        per_shard = snap["ops_by_shard"]["store.get"]
        assert set(per_shard) == set(store.shard_ids())
        assert sum(row["count"] for row in per_shard.values()) == 32
        # lock waits were measured (traced-acquire path) in microseconds
        assert snap["ops"]["store.lock_wait"]["count"] > 0
        # normalized views agree with the raw counters
        assert store.metrics()["gets"] >= 32
        assert set(store.metrics()) == set(STORE_METRIC_KEYS)
    finally:
        trc.disable()


# -- host <-> SPMD parity through metrics() -----------------------------------


def test_metrics_collective_bytes_parity_host_spmd():
    """The same 1-thread workload reports identical wire_traffic through
    metrics() on both backends; the host tracer's accumulate.wire_elements
    agrees with it, and the SPMD join records its five stages, in order and
    each inside the job's session.run span, all carrying the session's id."""
    V, R = 128, 3
    rows = jnp.ones((2, V))

    def run(backend):
        sess = Session(backend=backend, n_nodes=1, threads_per_node=1,
                       trace=True)
        try:
            out = sess.new_array("o", (V,))

            def proc(ctx, xs):
                def step(c):
                    return c + out.accumulate(xs.sum(axis=0)).sum()
                return ctx.iterate(step, jnp.float32(0), R)

            res = sess.run(proc, data=(rows,))
            m = sess.metrics()
            return np.asarray(res[0]), m, sess.tracer.counters(), sess
        finally:
            sess.tracer.disable()

    r_h, m_h, c_h, _ = run("host")
    r_s, m_s, c_s, sess = run("spmd")
    np.testing.assert_allclose(r_h, r_s, rtol=1e-6)
    assert m_h["wire_traffic"] == m_s["wire_traffic"] == 2 * V * R
    assert c_h["accumulate.wire_elements"] == m_h["wire_traffic"]
    assert c_s["spmd.joins"] == 1
    (job,) = sess.tracer.spans(name="session.run")
    stages = sess.tracer.spans(cat="spmd")
    assert [s["name"] for s in stages] == ["spmd.trace", "spmd.lower", "spmd.compile",
                                           "spmd.run", "spmd.writeback"]
    ends = [job["ts"]] + [s["ts"] + s["dur"] for s in stages]
    for s, prev_end in zip(stages, ends):
        assert prev_end <= s["ts"] and s["ts"] + s["dur"] <= job["ts"] + job["dur"]
    assert {s["args"]["session"] for s in stages + [job]} == {sess.id}


# -- stats unification: pinned key sets, deprecated views intact --------------


def test_metric_key_sets_pinned():
    x, y = _logreg_data()
    theta, sess = logreg.fit(x, y, iters=2, n_nodes=2, threads_per_node=1,
                             backend="host")
    m = sess.metrics()
    assert set(m) == set(SESSION_METRIC_KEYS)
    assert set(m["store"]) == set(STORE_METRIC_KEYS)
    assert set(m["cache"]) == set(CACHE_METRIC_KEYS)
    assert m["backend"] == "host"
    for sid, row in m["shards"].items():
        assert set(row) == {"store", "cache", "wire_traffic"}
        # per-shard store rows add the entry count to the canonical set
        assert set(row["store"]) == set(STORE_METRIC_KEYS) | {"names"}
        assert set(row["cache"]) == set(CACHE_METRIC_KEYS)
    # canonical counters mirror the raw legacy ones
    with pytest.warns(DeprecationWarning, match="Session.stats"):
        raw = sess.stats()
    assert m["store"]["gets"] == raw["store"]["get"]
    assert m["store"]["bytes_written"] == raw["store"]["bytes_set"]
    assert m["cache"]["hits"] == raw["cache"].hits
    assert m["wire_traffic"] == raw["wire_traffic"]


def test_deprecated_stats_shapes_unchanged():
    """The three legacy shapes are frozen: old callers keep working (they
    just see a DeprecationWarning now — step.check PR)."""
    x, y = _logreg_data()
    theta, sess = logreg.fit(x, y, iters=2, n_nodes=2, threads_per_node=1)
    with pytest.warns(DeprecationWarning, match="Session.stats"):
        raw = sess.stats()
    assert set(raw) == {"store", "cache", "wire_traffic"}
    assert set(raw["store"]) == {"get", "set", "inc", "bytes_get", "bytes_set",
                                 "transfers", "migrated_in", "migrated_out"}
    cs = raw["cache"]          # CacheStats object, not a dict
    for attr in ("hits", "misses", "invalidations", "write_messages",
                 "missing_messages", "evictions", "hit_rate"):
        assert hasattr(cs, attr)
    assert cs.as_dict()["hits"] == cs.hits
    with pytest.warns(DeprecationWarning, match="Session.shard_stats"):
        shard_rows = sess.shard_stats()
    for sid, row in shard_rows.items():
        assert set(row) == {"store", "cache", "wire_traffic"}
        assert "get" in row["store"] and "names" in row["store"]


# -- FT integration -----------------------------------------------------------


def test_recovery_rearms_tracer():
    """session_recovery's replacement session adopts the dead session's
    tracer (still armed) and keeps recording into the same timeline."""
    sess = Session(backend="host", n_nodes=2, threads_per_node=1, shards=2,
                   trace=True)
    try:
        ref = sess.new_array("w", (16,))
        sess.run(lambda ctx, xs: ref.accumulate(xs.sum(axis=0)),
                 data=(jnp.ones((2, 16)),))
        before = sess.tracer.snapshot()["events"]
        assert before > 0
        plan, new_sess = session_recovery(sess, [1])
        assert new_sess.tracer is sess.tracer
        assert new_sess.tracer.enabled
        assert new_sess.store.tracer is sess.tracer
        ref2 = new_sess.ref("w")
        new_sess.run(lambda ctx, xs: ref2.accumulate(xs.sum(axis=0)),
                     data=(jnp.ones((1, 16)),))
        assert new_sess.tracer.snapshot()["events"] > before
    finally:
        sess.tracer.disable()


def test_heartbeat_metrics_payload():
    sess = Session(backend="host", n_nodes=1, threads_per_node=2, trace=True)
    try:
        ref = sess.new_array("v", (8,))

        def proc(ctx, xs):
            ref.accumulate(xs.sum(axis=0))
            ctx.barrier()
            return None

        sess.run(proc, data=(jnp.ones((2, 8)),))
        payload = metrics_payload(sess)
        assert payload["trace_enabled"] is True
        assert payload["barrier_wait_us"]["count"] >= 2
        assert payload["barrier_wait_us"]["p99"] >= payload["barrier_wait_us"]["p50"]
        assert payload["op_rates"]["store.set"] > 0
        assert payload["wire_traffic"] == sess.wire_traffic()
    finally:
        sess.tracer.disable()


# -- recorder robustness ------------------------------------------------------


def test_event_cap_drops_counted():
    trc = Tracer(enabled=True, max_events=10)
    try:
        for i in range(25):
            t0 = trc.now()
            trc.add_span("store-op", "store.get", t0, t0)
        snap = trc.snapshot()
        assert snap["events"] == 10
        assert snap["dropped_events"] == 15
        # span *counts* keep the true total even past the event cap
        assert snap["spans_by_category"]["store-op"] == 25
    finally:
        trc.disable()


def test_tracer_thread_safety_counters():
    trc = Tracer(enabled=True)
    try:
        def work():
            for _ in range(500):
                trc.count("x")
                trc.observe("y", 1.0, shard=0)
        ts = [threading.Thread(target=work) for _ in range(8)]
        [t.start() for t in ts]
        [t.join() for t in ts]
        snap = trc.snapshot()
        assert snap["counters"]["x"] == 4000
        assert snap["ops"]["y"]["count"] == 4000
        assert snap["ops_by_shard"]["y"][0]["count"] == 4000
    finally:
        trc.disable()
