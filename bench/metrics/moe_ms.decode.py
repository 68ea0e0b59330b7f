"""Device ms per decode step under the ``moe`` scope: router, routing,
dispatch scan, dispatch, expert exchange, experts, combine, shared
expert (trace)."""

from bench.metrics._scopes import scope_ms


def read(facts):
    return scope_ms(facts, "decode", "moe")
