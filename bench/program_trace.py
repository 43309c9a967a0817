"""Read a ``jax.profiler`` trace with the program's own spans and scopes.

What ``bench/trace.py`` reads (the harness's spans, the device ops), and
besides:

* the program's annotations on the host plane: the Session tracer's
  context-manager spans (``session.run``, the ``spmd.*`` stages of a join,
  ``accumulate.round``/``accumulate.sync``, the apps' host round spans),
  recognised by the prefixes of :data:`PROGRAM_SPANS`.  An idle gap of the
  device is labelled by the innermost harness or program span that holds
  its midpoint;
* each op's self time: its duration less that of the events nested in it on
  the same line (a ``while`` holds the ops of its body);
* the device self time per scope, in which no op is counted twice.  A TPU
  trace carries no op-name stat on its op events (only their device offset
  and duration), so an op's scope comes from the compiled program: the
  ``hlo_scopes`` map (HLO op name -> ``op_name`` scope path) that the
  Session tracer's ``spmd.compile`` span carries.

The window, busy time and per-op time are ``bench/trace.py``'s, from the
harness's spans and the op names alone.

    python3 bench/program_trace.py <trace.xplane.pb> [<hlo_scopes.json>]

prints the summary as JSON; ``bench/run.py --trace-out <file>`` keeps the
trace of a traced run.
"""

from __future__ import annotations

import json
import sys
from dataclasses import dataclass, field
from pathlib import Path
from typing import Dict, List, Optional, Tuple

if __package__ in (None, ""):                   # run as a script
    sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from bench import trace  # noqa: E402
from bench.program import instruction  # noqa: E402

#: name prefixes of the program's annotations on the host plane
PROGRAM_SPANS = ("session.", "spmd.", "accumulate.", "pagerank.", "kmeans.")

Span = Tuple[str, int, int]                     # (name, start_ns, end_ns)


@dataclass
class ProgramSummary:
    window_s: float
    busy_s: float
    gaps: List[Tuple[str, float]]               # (innermost span, seconds), longest first
    scope_s: Dict[str, float] = field(default_factory=dict)    # scope path -> self seconds
    op_self_s: Dict[str, float] = field(default_factory=dict)  # op name -> self seconds

    def scope_total_s(self, scope: str) -> float:
        """Device self time of the ops traced under the named scope ``scope``."""
        return sum(s for path, s in self.scope_s.items() if scope in path.split("/"))

    def as_dict(self, top: int = 20) -> dict:
        by_label: Dict[str, float] = {}
        for name, s in self.gaps:
            by_label[name] = by_label.get(name, 0.0) + s

        def first(d):
            return [[k, v] for k, v in sorted(d.items(), key=lambda kv: -kv[1])[:top]]
        return {"window_s": self.window_s, "busy_s": self.busy_s,
                "idle_by_label": first(by_label),
                "idle_gaps": [[n, s] for n, s in self.gaps[:top]],
                "scope_self_s": first(self.scope_s), "op_self_s": first(self.op_self_s)}


def is_program_span(name: str) -> bool:
    return name.startswith(PROGRAM_SPANS)


def innermost(spans: List[Span], t: int) -> str:
    """The innermost span that holds ``t``: of those around it, the one that
    starts last (and, of those, ends first)."""
    inside = [(s, -e, n) for n, s, e in spans if s <= t < e]
    return max(inside)[2] if inside else "outside"


def self_times(events: List[Tuple[str, int, int]]) -> List[Tuple[str, int]]:
    """``(name, start_ns, duration_ns)`` events of one line -> ``(name,
    self_ns)``: each event's duration less its direct children's, the events
    that start and end inside it."""
    order = sorted(range(len(events)), key=lambda i: (events[i][1], -events[i][2]))
    self_ns = [d for _, _, d in events]
    open_: List[int] = []
    for i in order:
        start = events[i][1]
        while open_ and events[open_[-1]][1] + events[open_[-1]][2] <= start:
            open_.pop()
        if open_:
            self_ns[open_[-1]] -= events[i][2]
        open_.append(i)
    return [(n, self_ns[i]) for i, (n, _, _) in enumerate(events)]


def summarise(device_ops: Dict[str, List[Tuple[str, int, int]]],
              host_spans: List[Tuple[str, int, int]],
              scopes: Optional[Dict[str, str]] = None) -> ProgramSummary:
    """``device_ops``: per device, ``(name, start_ns, duration_ns)``;
    ``host_spans``: harness and program spans, ``(name, start_ns,
    duration_ns)``; ``scopes``: HLO op name -> scope path (``hlo_scopes``)."""
    scopes = scopes or {}
    base = trace.reduce_events(device_ops, [h for h in host_spans if h[0] in trace.HOST_SPANS])
    harness = [(n, s, s + d) for n, s, d in host_spans if n in trace.HOST_SPANS]
    lo, hi = min(s for _, s, _ in harness), max(e for _, _, e in harness)
    spans = [(n, s, s + d) for n, s, d in host_spans
             if n in trace.HOST_SPANS or is_program_span(n)]
    gaps: List[Tuple[str, float]] = []
    scope_s: Dict[str, float] = {}
    op_self_s: Dict[str, float] = {}
    for events in device_ops.values():
        busy = trace.union(trace.clip([(s, s + d) for _, s, d in events], lo, hi))
        edges = [lo] + [t for iv in busy for t in iv] + [hi]
        for g0, g1 in zip(edges[::2], edges[1::2]):
            if g1 > g0:
                gaps.append((innermost(spans, (g0 + g1) // 2), (g1 - g0) * 1e-9))
        inside = [e for e in events if e[1] + e[2] > lo and e[1] < hi]
        for name, ns in self_times(inside):
            op_self_s[name] = op_self_s.get(name, 0.0) + ns * 1e-9
            scope = scopes.get(instruction(name))
            if scope:
                path = scope.rsplit("/", 1)[0] if "/" in scope else scope
                scope_s[path] = scope_s.get(path, 0.0) + ns * 1e-9
    gaps.sort(key=lambda g: -g[1])
    return ProgramSummary(window_s=base.window_s, busy_s=base.busy_s, gaps=gaps,
                          scope_s=scope_s, op_self_s=op_self_s)


def read_events(profile) -> Tuple[Dict[str, list], list]:
    """Device ops, and the harness's and the program's spans, of a
    ``jax.profiler.ProfileData``."""
    device_ops: Dict[str, list] = {}
    host_spans: list = []
    for plane in profile.planes:
        if trace.DEVICE_PLANE.match(plane.name):
            for line in plane.lines:
                if line.name == trace.OPS_LINE:
                    device_ops[plane.name] = [(e.name, int(e.start_ns), int(e.duration_ns))
                                              for e in line.events]
        elif plane.name == trace.HOST_PLANE:
            for line in plane.lines:
                host_spans += [(e.name, int(e.start_ns), int(e.duration_ns))
                               for e in line.events
                               if e.name in trace.HOST_SPANS or is_program_span(e.name)]
    return device_ops, host_spans


def load(path: str, scopes: Optional[Dict[str, str]] = None) -> ProgramSummary:
    """Summarise one ``.xplane.pb`` file; ``scopes`` as for :func:`summarise`."""
    from jax.profiler import ProfileData
    return summarise(*read_events(ProfileData.from_file(str(path))), scopes)


if __name__ == "__main__":
    scope_map = json.loads(Path(sys.argv[2]).read_text()) if len(sys.argv) > 2 else None
    print(json.dumps(load(sys.argv[1], scope_map).as_dict(), indent=1))
