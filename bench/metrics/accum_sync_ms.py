"""Per round, the host accumulator's wait for the AUTO decision: the mean
``accumulate.sync`` span, which sits inside ``accumulate.round`` and blocks
until the round's contributions are computed on the device."""

from bench import program


def read(run):
    return program.mean_span_ms(run, "accumulate.sync")
