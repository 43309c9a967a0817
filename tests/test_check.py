"""step.check — happens-before race detector, lock-order sanitizer, lint.

Tentpole contract: checking is a strict no-op by default (one-branch hot
paths, nothing armed globally); armed via ``Session(check=True)``, the
vector-clock race detector deterministically flags a seeded unsynchronized
RMW with both stack sites yet stays silent on all four analytics apps; the
lock sanitizer catches a node→shard inversion and wait-for cycles across
DBarrier/DSemaphore; and the spawn-time lint rejects structurally broken
programs (barrier arity, ragged accumulate, host sync under SPMD) with
``CheckError`` before any worker thread runs.
"""

import json
import os
import subprocess
import sys
import threading
import time

import jax.numpy as jnp
import numpy as np
import pytest

from repro.analytics import kmeans, logreg, nmf, pagerank
from repro.check import CheckError, Checker, Finding, NULL_CHECKER
from repro.check import checker as stepcheck
from repro.core import Session
from repro.ft import session_recovery


def _session(n_nodes=1, tpn=2, **kw):
    return Session(backend="host", n_nodes=n_nodes, threads_per_node=tpn,
                   check=True, **kw)


# -- no-op by default ---------------------------------------------------------


def test_noop_by_default():
    """A plain Session arms nothing: CHECKING stays False, the null checker
    is shared, and findings() answers (empty) against a disabled checker."""
    assert stepcheck.armed_count() == 0
    sess = Session(backend="host", n_nodes=1, threads_per_node=2)
    assert not sess.checker.enabled
    assert stepcheck.CHECKING is False
    assert stepcheck.armed_count() == 0
    ref = sess.def_global("g", jnp.float32(0))
    sess.run(lambda ctx: ref.set(ref.get() + 1))   # racy — but nobody looks
    assert sess.findings() == []


def test_arm_disarm_scoping():
    c1, c2 = Checker(enabled=True), Checker(enabled=True)
    try:
        assert stepcheck.CHECKING and stepcheck.armed_count() == 2
        c1.disable()
        assert stepcheck.CHECKING and stepcheck.armed_count() == 1
        c2.disable()
        assert not stepcheck.CHECKING and stepcheck.armed_count() == 0
    finally:
        stepcheck.reset()


def test_checker_context_manager():
    with Checker(enabled=True) as ck:
        assert ck.enabled and stepcheck.armed_count() == 1
    assert not ck.enabled and stepcheck.armed_count() == 0


# -- the acceptance race: seeded unsynchronized RMW ---------------------------


def _seeded_rmw_findings():
    sess = _session()
    counter = sess.def_global("counter", jnp.float32(0))

    def proc(ctx):
        for _ in range(4):
            v = counter.get()
            counter.set(v + jnp.float32(ctx.tid + 1))  # distinct per thread
        return None

    sess.run(proc)
    found = sess.findings()
    sess.checker.disable()
    return found


def test_seeded_rmw_race_detected_with_both_sites():
    found = _seeded_rmw_findings()
    kinds = {f.kind for f in found}
    assert "write-write" in kinds
    assert "read-write" in kinds
    for f in found:
        assert f.layer == "race" and f.severity == "error"
        assert f.name == "counter"
        assert len(f.tids) == 2          # both racing threads named
        assert f.sites and all(":" in s for s in f.sites)
        assert "test_check.py" in f.sites[0]
    # the read-write pair reports BOTH stack sites (read line != write line)
    rw = next(f for f in found if f.kind == "read-write")
    assert len(rw.sites) == 2


def test_race_detection_deterministic():
    """Same program, same findings — the detector keys on program structure
    (sites/kinds), not on which interleaving the scheduler happened to pick."""
    a = {(f.kind, f.name, f.sites) for f in _seeded_rmw_findings()}
    b = {(f.kind, f.name, f.sites) for f in _seeded_rmw_findings()}
    assert a == b and a


def test_ww_fixture_two_blind_writers():
    sess = _session()
    ref = sess.def_global("w", jnp.float32(0))

    def proc(ctx):
        ref.set(jnp.float32(ctx.tid + 1))   # differing values, no sync
        return None

    sess.run(proc)
    found = sess.findings()
    sess.checker.disable()
    assert [f.kind for f in found] == ["write-write"]
    assert found[0].tids == (0, 1)


def test_equal_value_writes_are_benign_replication():
    """The §4.5 bulk-synchronous idiom — every thread writes the identical
    reduced value — is unordered but benign; it is counted, not flagged."""
    sess = _session()
    ref = sess.def_global("r", jnp.float32(0))

    def proc(ctx):
        ref.set(jnp.float32(7.0))           # same value from both threads
        return None

    sess.run(proc)
    assert sess.findings() == []
    assert sess.checker.benign_replicated > 0
    sess.checker.disable()


def test_inc_inc_commutes():
    sess = _session()
    ref = sess.def_global("acc", jnp.float32(0))
    sess.run(lambda ctx: ref.inc(jnp.float32(ctx.tid + 1)))
    assert sess.findings() == []            # atomic incs commute by design
    sess.checker.disable()


def test_barrier_creates_happens_before_edge():
    """Writer → barrier → reader is ordered (clean); the identical program
    without the barrier is flagged — the sync edge is what's being tested."""

    def run(with_barrier):
        sess = _session()
        ref = sess.def_global("x", jnp.float32(0))
        bar = sess.barrier()

        def proc(ctx):
            if ctx.tid == 0:
                ref.set(jnp.float32(42.0))
            bar.enter() if with_barrier else None
            out = ref.get() if ctx.tid == 1 else None
            if not with_barrier:
                bar.enter()     # keep barrier arity identical for the lint
            return out

        sess.run(proc)
        found = sess.findings()
        sess.checker.disable()
        return found

    assert run(with_barrier=True) == []
    flagged = run(with_barrier=False)
    assert {f.kind for f in flagged} == {"read-write"}


def test_semaphore_handoff_creates_edge():
    sess = _session()
    ref = sess.def_global("h", jnp.float32(0))
    sem = sess.semaphore(0)                  # starts unavailable

    def proc(ctx):
        if ctx.tid == 0:
            ref.set(jnp.float32(1.0))
            sem.release()                    # hand-off publishes the write
        else:
            sem.acquire()
            ref.get()
        return None

    sess.run(proc)
    assert sess.findings() == []
    sess.checker.disable()


def test_accumulator_round_is_a_barrier_edge():
    sess = _session()
    partial = sess.new_array("p", (8,))
    out = sess.def_global("o", jnp.float32(0))

    def proc(ctx):
        tot = partial.accumulate(jnp.ones(8))
        if ctx.tid == 0:
            out.set(tot.sum())               # only one thread writes post-round
        return None

    sess.run(proc)
    assert sess.findings() == []
    sess.checker.disable()


# -- acceptance: zero findings on the four analytics apps ---------------------


@pytest.mark.parametrize("shards", [1, 8])
def test_apps_clean_under_armed_checker(shards):
    rng = np.random.default_rng(0)
    x = rng.normal(size=(64, 8)).astype(np.float32)
    y = (rng.random(64) > 0.5).astype(np.float32)
    pts = rng.normal(size=(60, 4)).astype(np.float32)
    r = np.abs(rng.normal(size=(24, 16))).astype(np.float32)
    edges = np.stack([rng.integers(0, 20, 60), rng.integers(0, 20, 60)],
                     axis=1).astype(np.int32)
    apps = [
        ("logreg", lambda s: logreg.fit(x, y, iters=3, session=s)),
        ("kmeans", lambda s: kmeans.fit(pts, 3, iters=3, session=s)),
        ("nmf", lambda s: nmf.fit(r, 4, iters=3, session=s)),
        ("pagerank", lambda s: pagerank.fit(edges, 20, iters=3, session=s)),
    ]
    for name, call in apps:
        sess = Session(backend="host", n_nodes=2, threads_per_node=2,
                       shards=shards, check=True)
        call(sess)
        found = sess.findings()
        sess.checker.disable()
        assert found == [], (f"{name} S={shards}: "
                             f"{[f.as_dict() for f in found]}")


# -- lock-order sanitizer -----------------------------------------------------


def test_inverted_node_shard_order_flagged():
    """Regression for the documented shard → node order: taking a shard lock
    while holding a node LRU lock is the inversion the cache layer must
    never perform (eviction cleanup defers for exactly this reason)."""
    ck = Checker(enabled=True)
    try:
        ck.bind_thread(0)
        ck.lock_acquired(("node", 0))
        ck.lock_acquired(("shard", 1))       # inverted!
        ck.lock_released(("shard", 1))
        ck.lock_released(("node", 0))
        kinds = [f.kind for f in ck.findings()]
        assert kinds == ["lock-order-inversion"]
        assert "shard → node" in ck.findings()[0].message
    finally:
        ck.disable()


def test_correct_shard_then_node_order_clean():
    ck = Checker(enabled=True)
    try:
        ck.bind_thread(0)
        ck.lock_acquired(("shard", 3))
        ck.lock_acquired(("node", 0))
        ck.lock_released(("node", 0))
        ck.lock_released(("shard", 3))
        assert ck.findings() == []
    finally:
        ck.disable()


def test_rebalance_shard_pairs_must_be_sorted():
    ck = Checker(enabled=True)
    try:
        ck.bind_thread(0)
        ck.rebalance_begin()
        ck.lock_acquired(("shard", 1))
        ck.lock_acquired(("shard", 2))       # ascending: fine
        ck.lock_released(("shard", 2))
        ck.lock_released(("shard", 1))
        assert ck.findings() == []
        ck.lock_acquired(("shard", 5))
        ck.lock_acquired(("shard", 4))       # descending: deadlock-prone
        ck.lock_released(("shard", 4))
        ck.lock_released(("shard", 5))
        ck.rebalance_end()
        assert [f.kind for f in ck.findings()] == ["rebalance-unsorted"]
    finally:
        ck.disable()


def test_shard_nesting_outside_rebalance_flagged():
    ck = Checker(enabled=True)
    try:
        ck.bind_thread(0)
        ck.lock_acquired(("shard", 0))
        ck.lock_acquired(("shard", 1))       # not in a rebalance
        assert [f.kind for f in ck.findings()] == ["shard-shard-nesting"]
    finally:
        ck.disable()


def test_live_rebalance_passes_sanitizer():
    """A real add_shard migration takes its sorted shard-pair locks under
    the rebalance exemption — armed, it must produce zero lock findings."""
    sess = _session(n_nodes=2, tpn=1, shards=2)
    for i in range(16):
        sess.def_global(f"k{i}", float(i))
    sess.store.add_shard(7)
    assert [f for f in sess.findings() if f.layer == "lock"] == []
    sess.checker.disable()


def test_wait_cycle_semaphore_barrier_deadlock():
    """t0 holds the semaphore and parks on a 2-arrival barrier; t1 parks on
    the semaphore — a cross-primitive wait-for cycle.  Timeouts let both
    threads exit; the checker must have reported the cycle meanwhile."""
    sess = _session()
    sem = sess.semaphore(1)
    bar = sess.barrier(2)

    def proc(ctx):
        if ctx.tid == 0:
            sem.acquire()
            bar.enter(timeout=2.0)           # t1 never arrives
            sem.release()
        else:
            time.sleep(0.2)
            if sem.acquire(timeout=2.0):
                sem.release()
            bar.enter(timeout=2.0)
        return None

    sess.run(proc)
    kinds = {f.kind for f in sess.findings()}
    assert "wait-cycle" in kinds
    cycle = next(f for f in sess.findings() if f.kind == "wait-cycle")
    assert "thread 0" in cycle.message and "thread 1" in cycle.message
    sess.checker.disable()


# -- spawn-time lint ----------------------------------------------------------


def test_lint_rejects_barrier_arity_before_threads_run():
    sess = _session()
    bar = sess.barrier(3)                    # 3 arrivals, only 2 threads
    seen_threads = []

    def proc(ctx):
        seen_threads.append(threading.current_thread())
        bar.enter()
        return None

    with pytest.raises(CheckError, match="arity"):
        sess.run(proc)
    # the only executions were the lint dry-runs on the driver thread:
    # no worker thread ever started, nothing ever parked on the barrier
    assert seen_threads and all(t is threading.main_thread()
                                for t in seen_threads)
    assert [f.kind for f in sess.findings()] == ["barrier-arity"]
    sess.checker.disable()


def test_lint_rejects_ragged_accumulate():
    sess = _session()
    g = sess.new_array("g", (4,))

    def proc(ctx):
        g.accumulate(jnp.ones(4))
        if ctx.tid == 0:
            g.accumulate(jnp.ones(4))        # one thread runs an extra round
        return None

    with pytest.raises(CheckError, match="diverge"):
        sess.run(proc)
    assert [f.kind for f in sess.findings()] == ["ragged-accumulate"]
    sess.checker.disable()


def test_lint_counts_fori_trips():
    """ctx.iterate multiplies reach counts: N rounds in a loop body is a
    matched program, a tid-dependent trip count is ragged."""
    sess = _session()
    g = sess.new_array("g", (4,))

    def ok(ctx):
        return ctx.iterate(lambda c: c + g.accumulate(jnp.ones(4)).sum(),
                           jnp.float32(0), 3)

    sess.run(ok)                             # lints clean, then really runs
    assert sess.findings() == []

    def ragged(ctx):
        return ctx.iterate(lambda c: c + g.accumulate(jnp.ones(4)).sum(),
                           jnp.float32(0), 3 + ctx.tid)

    with pytest.raises(CheckError, match="diverge"):
        sess.run(ragged)
    sess.checker.disable()


def test_lint_rejects_host_sync_under_spmd():
    sess = Session(backend="spmd", check=True)
    bar = sess.barrier()

    def proc(ctx, xs):
        bar.enter()                          # host-only primitive
        return xs.sum()

    with pytest.raises(CheckError, match="SPMD"):
        sess.run(proc, data=(jnp.ones((4, 2)),))
    assert [f.kind for f in sess.findings()] == ["spmd-host-sync"]
    sess.checker.disable()


def test_lint_sparse_budget_warning():
    sess = _session()
    sess.new_array("sp", (16,), sparse_k=100)   # k > pair_capacity(16)
    found = sess.findings()
    assert [f.kind for f in found] == ["sparse-overbudget"]
    assert found[0].severity == "warning"       # advisory, nothing raised
    sess.checker.disable()


def test_delete_with_live_replicas_warns():
    sess = _session(n_nodes=2, tpn=1)
    ref = sess.new_array("d", (4,))
    def proc(ctx):
        ref.get()                               # both nodes cache a replica
        return None

    sess.run(proc)
    sess.delete("d")
    found = [f for f in sess.findings() if f.kind == "delete-live-replicas"]
    assert len(found) == 1 and found[0].severity == "warning"
    assert "node(s) [0, 1]" in found[0].message
    assert "d" not in sess.names()              # the delete still happened
    sess.checker.disable()


def test_strict_false_records_without_raising():
    ck = Checker(enabled=True, strict=False)
    try:
        sess = Session(backend="host", n_nodes=1, threads_per_node=2,
                       check=ck)
        bar = sess.barrier(3)

        def proc(ctx):
            bar.enter(timeout=0.1)           # arity-broken but non-strict
            return None

        sess.run(proc)                       # no CheckError
        kinds = [f.kind for f in sess.findings()]
        assert "barrier-arity" in kinds      # the lint still records it
        # non-strict means the broken program really ran, so the dynamic
        # layer reports the starvation the lint predicted
        assert "starved-barrier" in kinds
    finally:
        ck.disable()


# -- findings model / export --------------------------------------------------


def test_findings_dedupe_and_export_roundtrip(tmp_path):
    found = _seeded_rmw_findings()
    # 4 RMW rounds/thread but structurally-identical findings dedupe by key
    assert len(found) == len({f.key() for f in found})
    ck = Checker(enabled=True)
    try:
        for f in found:
            ck.record(f)
            ck.record(f)                     # duplicate — dropped
        assert len(ck.findings()) == len(found)
        path = ck.export(str(tmp_path / "check.json"))
        with open(path) as fh:
            report = json.load(fh)
        assert report["count"] == len(found)
        assert set(report["by_layer"]) == {"race"}
        assert report["by_severity"]["error"] == len(found)
        for row in report["findings"]:
            assert {"layer", "kind", "severity", "message"} <= set(row)
    finally:
        ck.disable()


def test_finding_cap_counts_drops():
    ck = Checker(enabled=True, max_findings=2)
    try:
        for i in range(5):
            ck.record(Finding("race", "write-write", "error", f"m{i}",
                              name=f"n{i}"))
        assert len(ck.findings()) == 2 and ck.dropped == 3
    finally:
        ck.disable()


def test_null_checker_is_inert():
    assert not NULL_CHECKER.enabled
    NULL_CHECKER.on_access("x", "write", 1.0)   # all hooks are safe no-ops
    assert NULL_CHECKER.findings() == []


# -- integration: FT recovery keeps the armed checker -------------------------


def test_recovery_rearms_checker():
    sess = _session(n_nodes=2, tpn=1, shards=2)
    ref = sess.new_array("w", (8,))

    def proc(ctx):
        ref.accumulate(jnp.ones(8))
        return None

    sess.run(proc)
    plan, new_sess = session_recovery(sess, [1])
    assert new_sess.checker is sess.checker and new_sess.checker.enabled
    ref2 = new_sess.ref("w")

    def proc2(ctx):
        ref2.accumulate(jnp.ones(8))
        return None

    new_sess.run(proc2)
    assert new_sess.findings() == []
    sess.checker.disable()


# -- the example is the documented repro ------------------------------------


def test_race_demo_smoke():
    """examples/race_demo.py runs green: flags the seeded race with both
    sites, stays silent on the synchronized variant."""
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src")
    proc = subprocess.run(
        [sys.executable,
         os.path.join(os.path.dirname(__file__), "..", "examples",
                      "race_demo.py")],
        env=env, capture_output=True, text=True, timeout=300)
    assert proc.returncode == 0, proc.stderr
    assert "race_demo.py:32" in proc.stdout   # read site
    assert "race_demo.py:33" in proc.stdout   # write site
    assert "synchronized program: 0 finding(s)" in proc.stdout
