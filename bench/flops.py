"""Operations and bytes that each step, kernel and call needs, from the
configuration's shapes.  Only useful work counts: what each layer kind
counts is in ``bench/counts/<kind>.py``, summed here over the model's
layers (its ``LayerSpec`` pattern, repeated), and the output head where
logits are produced."""

from __future__ import annotations

from bench import spec


def layers(m: dict) -> list:
    """Every layer's ``LayerSpec``, bottom up: the program's pattern for
    the configuration, repeated."""
    from repro.models.config import ModelConfig

    cfg = ModelConfig(**m)
    return list(cfg.pattern()) * cfg.n_repeats


def token_flops(m: dict, ctx: int) -> float:
    """One token through every layer, attending over ``ctx`` positions;
    the output head is not included."""
    return sum(spec.counts(s.kind).flops(m, s, ctx) for s in layers(m))


def head_flops(m: dict) -> float:
    return 2 * m["d_model"] * m["vocab"]


def decode_step_flops(m: dict, batch: int, pos: int) -> float:
    """One decode step of ``batch`` tokens at position ``pos``."""
    return batch * (token_flops(m, pos + 1) + head_flops(m))


def prefill_flops(m: dict, batch: int, seq: int) -> float:
    """A prefill of ``batch`` prompts of ``seq`` tokens, logits of the
    last position only: the token at position t attends over t + 1."""
    per_layer = [(spec.counts(s.kind), s) for s in layers(m)]
    tokens = sum(c.flops(m, s, t) for c, s in per_layer
                 for t in range(1, seq + 1))
    return batch * (tokens + head_flops(m))


def state_bytes(m: dict, batch: int, ctx: float) -> float:
    """Cache or state bytes that a decode step of ``batch`` sequences of
    ``ctx`` positions reads, over every layer."""
    return sum(spec.counts(s.kind).state_bytes(m, s, batch, ctx)
               for s in layers(m))


def moe_routing(T: int, K: int, E: int) -> tuple[float, float]:
    """(ops, bytes) of the routing kernel: one count per (token, slot);
    read the (T, K) int32 expert ids, write the (T, K) int32 positions
    and the (E,) int32 counts."""
    return float(T * K), float(4 * (2 * T * K + E))
