"""Device ms per decode step of collective ops: all-to-all, all-gather,
all-reduce, collective-permute, reduce-scatter (trace)."""

from bench.metrics._scopes import collective_ms


def read(facts):
    return collective_ms(facts, "decode")
