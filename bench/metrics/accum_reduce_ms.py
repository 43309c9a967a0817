"""The host accumulator's reduce per round: the Session tracer's
``accumulate.round`` spans, which the round-closing thread records."""


def read(run):
    spans = [s for j in run.jobs for s in j.spans if s["name"] == "accumulate.round"]
    if not spans:
        return None
    return sum(s["dur"] for s in spans) * 1e-3 / len(spans)
