"""Device us per call of the cross-chip dispatch scan (``exscan.*``
scopes; one ``scan_with_total`` per MoE layer a step) in decode (trace)."""

from bench.metrics._scopes import exscan_us


def read(facts):
    return exscan_us(facts, "decode")
