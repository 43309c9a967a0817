"""Run one benchmark cell once, on the chip, and print its result line.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

In one process:

1. turn on JAX's persistent compilation cache (``$JAX_COMPILATION_CACHE_DIR``,
   else ``<checkout>/.jax_cache``);
2. make the cell's data on the device from ``--seed`` (the app adapter);
3. warm up with one whole job of the cell's own shapes;
4. run jobs back to back, each on a fresh ``Session``, for ``--seconds``:
   whole jobs only, so the window closes at the end of the job nearest the
   deadline (a job starts only if, at the last job's pace, it ends less than
   half a job past the deadline);
5. read the peak device memory, free the program's state, and compare every
   job's result with the adapter's plain reference;
6. print the result line: ``--trace 0`` the cell's end-to-end metrics,
   ``--trace 1`` its per-layer metrics, from a profiler trace of the jobs that
   start in the window's first :data:`TRACE_SECONDS`.

The compared numbers and their limits are the last lines on standard error
and the ``checks`` key, last in the result line.  Exits 2, printing no
result, when JAX finds no TPU or fewer chips than the cell asks for.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()           # set-up is timed from process start

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from dataclasses import dataclass, field  # noqa: E402
from pathlib import Path  # noqa: E402
from typing import Dict, List, Optional  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
# run as a script, bench/ leads sys.path: put the checkout there instead, so
# that the harness imports as the package `bench` and shadows nothing
if sys.path and Path(sys.path[0]).resolve() == ROOT / "bench":
    sys.path[0] = str(ROOT)
for p in (str(ROOT / "src"), str(ROOT)):
    if p not in sys.path:
        sys.path.insert(0, p)

TRACE_SECONDS = 5.0     # the traced run traces the jobs that start this early


@dataclass
class Job:
    rounds: int
    wire: int                                   # accumulator elements over the wire
    counts: Dict[str, int] = field(default_factory=dict)
    seconds: Dict[str, float] = field(default_factory=dict)
    spans: List[dict] = field(default_factory=list)   # Session tracer spans


@dataclass
class Run:
    """What a metric reader reads."""
    cell: object
    peaks: dict
    setup_s: float
    window_s: float
    jobs: List[Job]
    trace: Optional[object] = None              # bench.trace.Summary
    traced_jobs: int = 0

    @property
    def rounds(self) -> int:
        return sum(j.rounds for j in self.jobs)

    @property
    def traced_rounds(self) -> int:
        return sum(j.rounds for j in self.jobs[: self.traced_jobs])


class Monitor:
    """Counts and sums ``jax.monitoring`` events into the current job."""

    def __init__(self):
        import jax
        self.job: Optional[Job] = None
        jax.monitoring.register_event_listener(self._event)
        jax.monitoring.register_event_duration_secs_listener(self._duration)

    def _event(self, name, **_):
        if self.job is not None:
            self.job.counts[name] = self.job.counts.get(name, 0) + 1

    def _duration(self, name, secs, **_):
        if self.job is not None:
            self.job.counts[name] = self.job.counts.get(name, 0) + 1
            self.job.seconds[name] = self.job.seconds.get(name, 0.0) + secs

    def close(self):
        import jax
        jax.monitoring.unregister_event_listener(self._event)
        jax.monitoring.unregister_event_duration_listener(self._duration)


def make_session(traffic: dict, devices, trace: bool):
    from repro.core import Session, SpmdBackend, make_mesh
    armed = True if trace else None
    if traffic["backend"] == "spmd":
        mesh = make_mesh((len(devices),), ("data",), devices=devices)
        return Session(backend=SpmdBackend(mesh=mesh), trace=armed)
    return Session(backend=traffic["backend"], n_nodes=traffic["n_nodes"],
                   threads_per_node=traffic["threads_per_node"], trace=armed)


def run_job(cell, data, seed: int, devices, trace: bool, monitor: Monitor):
    """One job as a user runs it: a fresh Session, the app's ``fit``, the result."""
    import jax
    ann = jax.profiler.TraceAnnotation
    job = Job(rounds=cell.app.rounds(cell.config), wire=0)
    monitor.job = job
    with ann("job.prepare"):
        sess = make_session(cell.traffic, devices, trace)
    with ann("fit"):
        result = cell.app.run_job(data, cell.config, seed, sess)
    with ann("result.fetch"):
        result = result.copy()
        job.wire = int(sess.wire_traffic())
        if trace:
            job.spans = sess.tracer.spans()
    monitor.job = None
    return job, result


def run_window(cell, data, seed, seconds, trace, devices, monitor, trace_dir):
    """Whole jobs back to back for about ``seconds``; returns (window_s,
    jobs, results, traced_jobs)."""
    import jax
    jobs, results = [], []
    traced = 0
    t0 = time.perf_counter()
    deadline = t0 + seconds
    if trace:
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(trace_dir, profiler_options=opts)
        with jax.profiler.TraceAnnotation("window"):
            while True:
                job, result = run_job(cell, data, seed, devices, True, monitor)
                jobs.append(job)
                results.append(result)
                if time.perf_counter() >= t0 + min(seconds, TRACE_SECONDS):
                    break
        jax.profiler.stop_trace()
        traced = len(jobs)
    last_s = 0.0
    while not jobs or time.perf_counter() + last_s / 2 < deadline:
        t_job = time.perf_counter()
        job, result = run_job(cell, data, seed, devices, trace, monitor)
        last_s = time.perf_counter() - t_job
        jobs.append(job)
        results.append(result)
    return time.perf_counter() - t0, jobs, results, traced


def check(cell, results, data, seed) -> dict:
    """Compare every job's result with the plain reference, after the
    program's state is freed (``data`` is emptied).  Returns each number's
    worst value over the jobs beside its limit, and the count of jobs that
    broke a limit."""
    host = cell.app.to_host(data)
    data.clear()
    gc.collect()
    ref = cell.app.reference(host, cell.config, seed)
    limits = cell.config["limits"]
    worst = {name: 0.0 for name in limits}
    failed = 0
    for result in results:
        numbers = cell.app.compare(result, ref, cell.config)
        bad = False
        for name, limit in limits.items():
            value = numbers[name]
            worst[name] = max(worst[name], value) if math.isfinite(value) else math.inf
            bad |= not value <= limit
        failed += bad
    return {"numbers": {n: {"value": worst[n], "limit": limits[n]} for n in limits},
            "failed": failed}


def run_cell(cell, seed: int, seconds: float, trace: bool, devices,
             t_start: float, trace_out: Optional[str] = None) -> dict:
    """Everything after the device check; returns the result line's object.
    ``trace_out``: where to keep a copy of the profiler trace."""
    import jax
    from bench import trace as tracemod
    peaks_table = json.loads((ROOT / "bench" / "peaks.json").read_text())
    kind = devices[0].device_kind
    peaks = peaks_table.get(kind)
    if peaks is None and devices[0].platform == "tpu":
        raise KeyError(f"device kind {kind!r} not in bench/peaks.json")
    monitor = Monitor()
    trace_dir = tempfile.mkdtemp(prefix="bench-trace-") if trace else None
    try:
        data = cell.app.make_data(cell.config, seed)
        run_job(cell, data, seed, devices, False, monitor)          # warm-up
        setup_s = time.perf_counter() - t_start
        window_s, jobs, results, traced = run_window(
            cell, data, seed, seconds, trace, devices, monitor, trace_dir)
        summary = None
        if trace:
            pb = sorted(Path(trace_dir).rglob("*.xplane.pb"))
            summary = tracemod.load(pb[-1])
            if trace_out:
                shutil.copy(pb[-1], trace_out)
        stats = [d.memory_stats() or {} for d in devices]
        memory_peak = max(int(s.get("peak_bytes_in_use", 0)) for s in stats)
        run = Run(cell=cell, peaks=peaks or {}, setup_s=setup_s, window_s=window_s,
                  jobs=jobs, trace=summary, traced_jobs=traced)
        kind_key = "per_layer" if trace else "end_to_end"
        metrics = {}
        for m in cell.metrics[kind_key]:
            value = cell.readers[m["name"]].read(run)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        verdict = check(cell, results, data, seed)
    finally:
        monitor.close()
        if trace_dir:
            shutil.rmtree(trace_dir, ignore_errors=True)
    device = {"platform": devices[0].platform, "kind": kind, "count": len(jax.devices()),
              "memory_peak_bytes": memory_peak}
    out = {"correct": verdict["failed"] == 0 and len(results) > 0,
           "attempted": len(results), "failed": verdict["failed"],
           "metrics": metrics, "device": device}
    if summary is not None:
        device["busy_s"] = summary.busy_s
        device["window_s"] = summary.window_s
        out["breakdown"] = summary.breakdown()
    out["checks"] = verdict["numbers"]
    return out


def enable_compile_cache() -> str:
    """The program's persistent compilation cache (``$JAX_COMPILATION_CACHE_DIR``,
    else ``<checkout>/.jax_cache``), holding every program however fast it
    compiled, so that only a cell's first run in a checkout compiles."""
    import jax
    from repro.utils.compile_cache import enable_compile_cache as enable
    path = enable()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return path


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--trace-out", help="keep a copy of the profiler trace (.xplane.pb) here")
    args = ap.parse_args(argv)

    from bench import registry
    cell = registry.resolve(args.workload)
    import repro.core  # noqa: F401  the system under test: without it there is nothing to run
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < cell.chips:
        print(f"bench: {args.workload} needs {cell.chips} TPU chip(s); JAX found "
              f"{len(devices)} {devices[0].platform} device(s)", file=sys.stderr)
        return 2
    enable_compile_cache()
    out = run_cell(cell, args.seed, args.seconds, bool(args.trace),
                   devices[: cell.chips], T_START, args.trace_out)
    for name, c in out["checks"].items():
        print(f"check {name} {c['value']!r} limit {c['limit']!r}", file=sys.stderr)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
