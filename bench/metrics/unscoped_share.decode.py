"""Share of a decode step's device op time in no program scope: copies
XLA inserted and the harness's argmax (trace, %)."""

from bench.metrics._scopes import unscoped_share


def read(facts):
    return unscoped_share(facts, "decode")
