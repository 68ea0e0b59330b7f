"""Device ms per prefill batch under the ``attn`` scope: norm,
projections, rope, the cache writes, scores and softmax, output
projection (trace)."""

from bench.metrics._scopes import scope_ms


def read(facts):
    return scope_ms(facts, "prefill", "attn")
