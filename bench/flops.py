"""Operations and bytes that each step, kernel and call needs, from the
configuration's shapes.  Only useful work counts: the k routed experts
of each token (not capacity padding nor padded experts), attention over
the context actually held, the output head where logits are produced."""

from __future__ import annotations


def _sizes(m: dict):
    d, H, KV = m["d_model"], m["n_heads"], m["n_kv_heads"]
    hd = m.get("head_dim") or d // H
    f = m.get("d_expert_ff") or m["d_ff"]
    return d, H, KV, hd, f


def token_flops(m: dict, ctx: int) -> float:
    """One token through every layer, attending over ``ctx`` keys; the
    output head is not included."""
    d, H, KV, hd, f = _sizes(m)
    proj = 2 * d * (H * hd + 2 * KV * hd) + 2 * H * hd * d
    attn = 2 * 2 * H * hd * ctx
    router = 2 * d * m["n_experts"]
    experts = m["top_k"] * 3 * 2 * d * f
    shared = m.get("n_shared_experts", 0) * 3 * 2 * d * f
    return m["n_layers"] * (proj + attn + router + experts + shared)


def head_flops(m: dict) -> float:
    return 2 * m["d_model"] * m["vocab"]


def decode_step_flops(m: dict, batch: int, pos: int) -> float:
    """One decode step of ``batch`` tokens at position ``pos``."""
    return batch * (token_flops(m, pos + 1) + head_flops(m))


def prefill_flops(m: dict, batch: int, seq: int) -> float:
    """A prefill of ``batch`` prompts of ``seq`` tokens, logits of the
    last position only."""
    d, H, KV, hd, f = _sizes(m)
    no_attn = token_flops(m, 0)
    attn = m["n_layers"] * 2 * 2 * H * hd * seq * (seq + 1) / 2
    return batch * (seq * no_attn + attn + head_flops(m))


def kv_bytes(m: dict, batch: int, ctx: int, itemsize: int = 2) -> float:
    """Key and value bytes of ``batch`` sequences of ``ctx`` positions."""
    d, H, KV, hd, f = _sizes(m)
    return m["n_layers"] * batch * ctx * 2 * KV * hd * itemsize


def moe_routing(T: int, K: int, E: int) -> tuple[float, float]:
    """(ops, bytes) of the routing kernel: one count per (token, slot);
    read the (T, K) int32 expert ids, write the (T, K) int32 positions
    and the (E,) int32 counts."""
    return float(T * K), float(4 * (2 * T * K + E))
