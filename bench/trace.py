"""Reduce a ``jax.profiler`` trace to the numbers the per-layer metrics read.

A trace is read with ``jax.profiler.ProfileData``.  Device operations are the
events of the ``XLA Ops`` line of each ``/device:TPU:<n>`` plane; the
harness's own spans are ``jax.profiler.TraceAnnotation`` events on the host
plane, named in :data:`HOST_SPANS`.  Both sit on the profile's one clock.

* busy time: the union of device-op intervals inside the traced window,
  averaged over the devices;
* device time per op name, summed over the devices;
* idle gaps: the stretches inside the window where no op runs on a device,
  each labelled with the innermost harness span around its midpoint.
"""

from __future__ import annotations

import re
from dataclasses import dataclass, field
from typing import Dict, List, Sequence, Tuple

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE = "XLA Ops"
HOST_PLANE = "/host:CPU"
# the harness's spans around each layer call, outermost first
HOST_SPANS = ("window", "job.prepare", "fit", "result.fetch")

Interval = Tuple[int, int]          # (start_ns, end_ns)


@dataclass
class Summary:
    window_s: float                 # from the first harness span's start to the last's end
    busy_s: float                   # device busy, averaged over devices
    n_devices: int
    op_s: Dict[str, float] = field(default_factory=dict)     # device seconds per op name
    gaps: List[Tuple[str, float]] = field(default_factory=list)  # (label, seconds), longest first

    def kernel_s(self, pattern: str) -> float:
        """Device seconds of ops whose name contains ``pattern``."""
        return sum(s for name, s in self.op_s.items() if pattern in name)

    def breakdown(self, top: int = 10) -> dict:
        ops = sorted(self.op_s.items(), key=lambda kv: -kv[1])[:top]
        return {"device_ops": [[n, s] for n, s in ops],
                "idle_gaps": [[n, s] for n, s in self.gaps[:top]]}


def union(intervals: Sequence[Interval]) -> List[Interval]:
    """Merge overlapping intervals."""
    merged: List[Interval] = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            if e > merged[-1][1]:
                merged[-1] = (merged[-1][0], e)
        else:
            merged.append((s, e))
    return merged


def clip(intervals: Sequence[Interval], lo: int, hi: int) -> List[Interval]:
    return [(max(s, lo), min(e, hi)) for s, e in intervals if e > lo and s < hi]


def reduce_events(device_ops: Dict[str, List[Tuple[str, int, int]]],
                  host_spans: List[Tuple[str, int, int]]) -> Summary:
    """``device_ops``: per device, ``(name, start_ns, duration_ns)``;
    ``host_spans``: the harness's spans, ``(name, start_ns, duration_ns)``."""
    spans = [(n, s, s + d) for n, s, d in host_spans if n in HOST_SPANS]
    if not spans or not device_ops:
        raise ValueError("trace holds no harness span or no device op")
    lo, hi = min(s for _, s, _ in spans), max(e for _, _, e in spans)
    op_s: Dict[str, float] = {}
    busy_ns = 0
    gaps: List[Tuple[str, float]] = []
    for events in device_ops.values():
        busy = union(clip([(s, s + d) for _, s, d in events], lo, hi))
        busy_ns += sum(e - s for s, e in busy)
        for name, s, d in events:
            if s + d > lo and s < hi:
                op_s[name] = op_s.get(name, 0.0) + d * 1e-9
        edges = [lo] + [t for iv in busy for t in iv] + [hi]
        for g0, g1 in zip(edges[::2], edges[1::2]):
            if g1 > g0:
                gaps.append((label(spans, (g0 + g1) // 2), (g1 - g0) * 1e-9))
    gaps.sort(key=lambda g: -g[1])
    return Summary(window_s=(hi - lo) * 1e-9, busy_s=busy_ns * 1e-9 / len(device_ops),
                   n_devices=len(device_ops), op_s=op_s, gaps=gaps)


def label(spans: List[Tuple[str, int, int]], t: int) -> str:
    """The innermost harness span that holds ``t``."""
    inside = [(HOST_SPANS.index(n), n) for n, s, e in spans if s <= t < e]
    return max(inside)[1] if inside else "outside"


def read_events(profile) -> Tuple[Dict[str, list], list]:
    """Device ops and host spans of a ``jax.profiler.ProfileData``."""
    device_ops: Dict[str, list] = {}
    host_spans: list = []
    for plane in profile.planes:
        if DEVICE_PLANE.match(plane.name):
            for line in plane.lines:
                if line.name == OPS_LINE:
                    device_ops[plane.name] = [(e.name, int(e.start_ns), int(e.duration_ns))
                                              for e in line.events]
        elif plane.name == HOST_PLANE:
            for line in plane.lines:
                host_spans += [(e.name, int(e.start_ns), int(e.duration_ns))
                               for e in line.events if e.name in HOST_SPANS]
    return device_ops, host_spans


def load(path: str) -> Summary:
    """Summarise one ``.xplane.pb`` file."""
    from jax.profiler import ProfileData
    return reduce_events(*read_events(ProfileData.from_file(str(path))))
