"""Attention layer (``LayerSpec`` kind ``attn``) with its feed-forward:
projections, scores and values over the context (a sliding window caps
it), and a dense SwiGLU or the MoE layer: router, the k routed experts
of each token (not capacity padding nor padded experts), shared
experts.  A decode step reads the valid keys and values of its cache."""

from __future__ import annotations

from bench.counts import itemsize


def _sizes(m: dict):
    d, H, KV = m["d_model"], m["n_heads"], m["n_kv_heads"]
    hd = m.get("head_dim") or d // H
    return d, H, KV, hd


def flops(m: dict, spec, ctx: int) -> float:
    d, H, KV, hd = _sizes(m)
    if spec.sliding_window:
        ctx = min(ctx, spec.sliding_window)
    proj = 2 * d * (H * hd + 2 * KV * hd) + 2 * H * hd * d
    attn = 2 * 2 * H * hd * ctx
    if not spec.use_moe:
        return proj + attn + 3 * 2 * d * m["d_ff"]
    f = m.get("d_expert_ff") or m["d_ff"]
    router = 2 * d * m["n_experts"]
    experts = m["top_k"] * 3 * 2 * d * f
    shared = m.get("n_shared_experts", 0) * 3 * 2 * d * f
    return proj + attn + router + experts + shared


def state_bytes(m: dict, spec, batch: int, ctx: float) -> float:
    d, H, KV, hd = _sizes(m)
    if spec.sliding_window:
        ctx = min(ctx, spec.sliding_window)
    return batch * ctx * 2 * KV * hd * itemsize(m)
