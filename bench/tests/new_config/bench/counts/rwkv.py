"""RWKV-6 layer (``LayerSpec`` kind ``rwkv``): the time mix's five
d x d projections and decay projection, the per-head state update and
read (k v^T, the decayed sum, r (S + u k v^T)), and the channel mix's
three projections; none depends on the context.  A decode step reads the
layer's float32 state (B, H, 64, 64) and its two token-shift rows."""

from __future__ import annotations

from bench.counts import itemsize

HEAD_DIM = 64


def flops(m: dict, spec, ctx: int) -> float:
    d, f = m["d_model"], m["d_ff"]
    time_mix = 6 * 2 * d * d
    state = 6 * d * HEAD_DIM  # outer product, decay, add, bonus, read
    channel_mix = 2 * d * f + 2 * f * d + 2 * d * d
    return time_mix + state + channel_mix


def state_bytes(m: dict, spec, batch: int, ctx: float) -> float:
    d = m["d_model"]
    return batch * (d * HEAD_DIM * 4 + 2 * d * itemsize(m))
