"""Device ms per prefill batch under the ``moe`` scope (trace)."""

from bench.metrics._scopes import scope_ms


def read(facts):
    return scope_ms(facts, "prefill", "moe")
