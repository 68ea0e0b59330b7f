"""Share of a prefill batch's device op time in no program scope
(trace, %)."""

from bench.metrics._scopes import unscoped_share


def read(facts):
    return unscoped_share(facts, "prefill")
