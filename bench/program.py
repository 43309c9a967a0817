"""What the program's own instrumentation leaves in a run, for the metric
readers.

* the Session tracer's spans of each job (``Job.spans``, recorded when the
  run is traced);
* the named scopes of each SPMD program: the ``spmd.compile`` span carries
  ``hlo_scopes``, the compiled program's HLO op name -> ``op_name`` scope
  path, which names the device ops of the trace (``Summary.op_s``) by the
  ``jax.named_scope`` they were traced under.

A program without these spans or scopes reads None.
"""

from __future__ import annotations

import re
from typing import Dict, Iterable, Optional

# an op of the trace is named by its HLO instruction, alone ("fusion.3") or
# at the head of the instruction's text ("%fusion.3 = f32[...] fusion(...)",
# as on a TPU)
INSTRUCTION = re.compile(r"%?([\w.\-]+)")


def instruction(op: str) -> str:
    """The HLO instruction name of a trace's op name."""
    m = INSTRUCTION.match(op)
    return m.group(1) if m else op


def spans(run, names: Iterable[str]):
    names = set(names)
    return [s for job in run.jobs for s in job.spans if s["name"] in names]


def span_ms_per_job(run, names: Iterable[str]) -> Optional[float]:
    """Per job, the summed duration of the spans named in ``names`` (ms)."""
    found = spans(run, names)
    if not found:
        return None
    return sum(s["dur"] for s in found) * 1e-3 / len(run.jobs)


def mean_span_ms(run, name: str) -> Optional[float]:
    """The mean duration of the spans named ``name`` (ms)."""
    found = spans(run, (name,))
    if not found:
        return None
    return sum(s["dur"] for s in found) * 1e-3 / len(found)


def op_scopes(run) -> Dict[str, str]:
    """HLO op name -> scope path, over the traced jobs' SPMD programs."""
    scopes: Dict[str, str] = {}
    for job in run.jobs[: run.traced_jobs]:
        for s in job.spans:
            if s["name"] == "spmd.compile":
                scopes.update(s.get("args", {}).get("hlo_scopes", {}))
    return scopes


def scope_ms_per_round(run, scope: str) -> Optional[float]:
    """Per traced round, the device time of the ops traced under the named
    scope ``scope`` (ms).  Each op's own events only: the scope map leaves
    out control flow, whose events hold those of its body."""
    if run.trace is None or not run.traced_rounds:
        return None
    scopes = op_scopes(run)
    seconds = [s for op, s in run.trace.op_s.items()
               if scope in scopes.get(instruction(op), "").split("/")]
    if not seconds:
        return None
    return sum(seconds) * 1e3 / run.traced_rounds
