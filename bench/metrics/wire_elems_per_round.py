"""Accumulator elements on the wire per round: ``Session.wire_traffic()``."""


def read(run):
    return sum(j.wire for j in run.jobs) / run.rounds
