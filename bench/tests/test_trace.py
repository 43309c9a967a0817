"""bench/trace.py: busy time, per-op time and labelled idle gaps."""

from pathlib import Path

import pytest

from bench import trace

DATA = Path(__file__).parent / "data"


def test_union_and_clip():
    assert trace.union([(5, 7), (0, 2), (1, 3), (6, 9)]) == [(0, 3), (5, 9)]
    assert trace.clip([(0, 3), (5, 9)], 2, 6) == [(2, 3), (5, 6)]


def test_reduce_events_by_hand():
    ms = 1_000_000
    host = [("window", 0, 100 * ms), ("job.prepare", 0, 10 * ms), ("fit", 10 * ms, 80 * ms),
            ("result.fetch", 90 * ms, 10 * ms), ("unrelated", 0, 100 * ms)]
    ops = {"/device:TPU:0": [("scatter", 20 * ms, 30 * ms), ("gather", 40 * ms, 20 * ms),
                             ("scatter", 70 * ms, 10 * ms), ("late", 150 * ms, 10 * ms)]}
    s = trace.reduce_events(ops, host)
    assert s.window_s == pytest.approx(0.1)
    assert s.busy_s == pytest.approx(0.05)              # [20, 60) and [70, 80)
    assert s.op_s == pytest.approx({"scatter": 0.04, "gather": 0.02})
    # gaps [0, 20) in fit, [60, 70) in fit, [80, 100) in result.fetch
    assert s.gaps == [("fit", pytest.approx(0.02)), ("result.fetch", pytest.approx(0.02)),
                      ("fit", pytest.approx(0.01))]
    b = s.breakdown(top=1)
    assert b["device_ops"] == [["scatter", pytest.approx(0.04)]]
    assert len(b["idle_gaps"]) == 1


def test_busy_is_averaged_over_devices():
    host = [("window", 0, 100)]
    ops = {"/device:TPU:0": [("a", 0, 100)], "/device:TPU:1": [("a", 0, 50)]}
    s = trace.reduce_events(ops, host)
    assert s.busy_s == pytest.approx(75e-9)
    assert s.n_devices == 2


def test_no_device_op_is_an_error():
    with pytest.raises(ValueError):
        trace.reduce_events({}, [("window", 0, 10)])


def test_load_reads_a_tpu_layout_trace(tmp_path):
    """The same numbers as by hand, through an .xplane.pb in the layout
    jax.profiler writes on a TPU host (tests/data/tpu_trace.textproto)."""
    from jax.profiler import ProfileData
    text = (DATA / "tpu_trace.textproto").read_text()
    path = tmp_path / "t.xplane.pb"
    path.write_bytes(ProfileData.text_proto_to_serialized_xspace(text))
    s = trace.load(path)
    assert s.n_devices == 1
    assert s.window_s == pytest.approx(0.1)
    assert s.busy_s == pytest.approx(0.05)
    assert s.op_s == pytest.approx({"fusion.1": 0.04, "scatter.2": 0.02})
    assert s.gaps == [("fit", pytest.approx(0.02)), ("result.fetch", pytest.approx(0.02)),
                      ("fit", pytest.approx(0.01))]
