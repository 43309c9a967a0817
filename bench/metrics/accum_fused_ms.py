"""Per pairs round, the host accumulator's fused sparsify→scatter-add: the
mean ``accumulate.fused`` span, which sits inside ``accumulate.round`` and
waits for the round's sum."""

from bench import program


def read(run):
    return program.mean_span_ms(run, "accumulate.fused")
