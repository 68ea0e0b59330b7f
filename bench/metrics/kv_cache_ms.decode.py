"""Device ms per decode step under the ``kv_cache`` scopes: the row write
into the stacked cache after the layer loop, and attention's KV
duplication and cast (trace)."""

from bench.metrics._scopes import scope_ms


def read(facts):
    return scope_ms(facts, "decode", "kv_cache")
