"""bench/program_trace.py: the program's spans label the idle gaps, and the
device self time per scope counts no op twice (tests/data/
tpu_program_trace.textproto, whose numbers its header gives by hand)."""

from pathlib import Path

import pytest

from bench import program, program_trace, trace

DATA = Path(__file__).parent / "data"
# the fixture's ops as an SPMD program's hlo_scopes map them: control flow
# (while.1) is left out of the map, the other program's op (fusion.9) is not in it
SCOPES = {"fusion.1": "jit(body)/while/body/pagerank.round/pagerank.gather/div",
          "fusion.2": "jit(body)/while/body/pagerank.round/pagerank.scatter/scatter-add",
          "copy.3": "jit(body)/copy"}


@pytest.fixture
def trace_file(tmp_path):
    from jax.profiler import ProfileData
    path = tmp_path / "t.xplane.pb"
    path.write_bytes(ProfileData.text_proto_to_serialized_xspace(
        (DATA / "tpu_program_trace.textproto").read_text()))
    return path


def test_gaps_are_labelled_by_the_innermost_span(trace_file):
    s = program_trace.load(trace_file)
    # [0, 4) job.prepare; [6, 32) spmd.trace; [72, 74) spmd.run; [76, 100) fit,
    # after session.run has ended
    assert s.gaps == [("spmd.trace", pytest.approx(0.026)), ("fit", pytest.approx(0.024)),
                      ("job.prepare", pytest.approx(0.004)), ("spmd.run", pytest.approx(0.002))]
    assert s.as_dict()["idle_by_label"][0] == ["spmd.trace", pytest.approx(0.026)]


def test_window_busy_and_ops_stay_the_harness_reading(trace_file):
    """The program's spans move neither the window nor busy time; bench/trace.py
    counts the while with its body, the self times do not."""
    s, base = program_trace.load(trace_file), trace.load(trace_file)
    assert (s.window_s, s.busy_s) == (base.window_s, base.busy_s)
    assert s.busy_s == pytest.approx(0.044)
    (while_s,) = [v for k, v in base.op_s.items() if k.startswith("%while.1 ")]
    assert while_s == pytest.approx(0.040)
    assert {program.instruction(k): v for k, v in s.op_self_s.items()} == \
        pytest.approx({"while.1": 0.002, "fusion.1": 0.020, "fusion.2": 0.018,
                       "copy.3": 0.002, "fusion.9": 0.002})
    assert sum(s.op_self_s.values()) == pytest.approx(s.busy_s)


def test_self_time_per_scope(trace_file):
    s = program_trace.load(trace_file, SCOPES)
    assert s.scope_total_s("pagerank.gather") == pytest.approx(0.020)
    assert s.scope_total_s("pagerank.scatter") == pytest.approx(0.018)
    assert s.scope_total_s("pagerank.round") == pytest.approx(0.038)
    assert s.scope_s == pytest.approx({
        "jit(body)/while/body/pagerank.round/pagerank.gather": 0.020,
        "jit(body)/while/body/pagerank.round/pagerank.scatter": 0.018, "jit(body)": 0.002})
    assert s.scope_total_s("kmeans.assign") == 0.0
    assert program_trace.load(trace_file).scope_s == {}          # no map, no scopes


def test_self_times_nest():
    events = [("outer", 0, 10), ("mid", 1, 6), ("leaf", 2, 2), ("next", 8, 1)]
    assert program_trace.self_times(events) == [("outer", 3), ("mid", 4), ("leaf", 2),
                                                ("next", 1)]


def test_program_span_names_are_one_list():
    for name in ("session.run", "spmd.trace", "spmd.writeback", "accumulate.round",
                 "accumulate.sync", "pagerank.round"):
        assert program_trace.is_program_span(name)
    for name in ("fit", "PjitFunction(body)", "accumulate", "store.get"):
        assert not program_trace.is_program_span(name)


def test_runs_as_a_script(trace_file):
    import json
    import os
    import subprocess
    import sys
    root = Path(__file__).resolve().parents[2]
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    scopes = trace_file.parent / "scopes.json"
    scopes.write_text(json.dumps(SCOPES))
    p = subprocess.run([sys.executable, "bench/program_trace.py", str(trace_file), str(scopes)],
                       cwd=root, env=env, capture_output=True, text=True, timeout=120)
    assert p.returncode == 0, p.stderr
    out = json.loads(p.stdout)
    assert out["idle_gaps"][0] == ["spmd.trace", pytest.approx(0.026)]
    assert out["scope_self_s"][0] == [
        "jit(body)/while/body/pagerank.round/pagerank.gather", pytest.approx(0.020)]
