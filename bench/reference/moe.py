"""Plain float32 forward of the MoE decoder the program serves.

Written from the layer equations, layer by layer, in ``jax.numpy`` with
every matrix product at ``Precision.HIGHEST``: token embedding; per
layer a pre-norm grouped-query attention with rotary positions and a
pre-norm MoE feed-forward (softmax router over the real experts, top-k,
probabilities renormalised over the k, SwiGLU experts, optional shared
SwiGLU expert); final RMS norm and output head.

The dispatch keeps the program's capacity rule, which makes a token's
output depend on the other tokens of its step: the tokens of one step
form a group, split into ``tp`` equal runs (one per expert-parallel
rank, in batch-major order); a (token, slot) is kept iff its position
within its expert on its rank is under ``cap`` and its position among
all the group's ranks is under ``cap * tp``, where
``cap = max(8, int(capacity_factor * n_rank * k / e_pad))``.

``precision="fp8"`` is the control: every matrix product takes its
operands rounded to float8_e4m3fn under a per-tensor scale.
``precision="bf16"`` is the witness: the operands rounded to bfloat16.

Weights are drawn from the seed one layer at a time with
``bench/weights.py``, in the dtype the configuration serves and the
padded shapes of the served layout, then widened to float32, so
nothing the program made is read.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from bench import weights as W
from bench.reference import gap_values, summary  # noqa: F401

HIGHEST = lax.Precision.HIGHEST
FP8 = jnp.float8_e4m3fn
FP8_MAX = 448.0
BLOCK = 256  # rows per expert block in the grouped FFN


def _quant(x):
    s = FP8_MAX / jnp.maximum(jnp.max(jnp.abs(x)), 1e-30)
    return (x * s).astype(FP8).astype(jnp.float32) / s


def _bf16(x):
    return x.astype(jnp.bfloat16).astype(jnp.float32)


def mm(spec, a, b, precision):
    if precision == "fp8":
        a, b = _quant(a), _quant(b)
    elif precision == "bf16":
        a, b = _bf16(a), _bf16(b)
    return jnp.einsum(spec, a, b, precision=HIGHEST)


def rmsnorm(x, scale, eps):
    return x * lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale


def rope(x, positions, theta):
    half = x.shape[-1] // 2
    freqs = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = positions[..., None].astype(jnp.float32) * freqs
    cos, sin = jnp.cos(ang)[:, :, None], jnp.sin(ang)[:, :, None]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


@functools.partial(jax.jit, static_argnames=("cfg", "precision", "chunk"))
def _attention(x, w, cfg, precision, chunk):
    B, T, _ = x.shape
    H, KV, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    xn = rmsnorm(x, w["norm1"], cfg.eps)
    q = mm("btd,dh->bth", xn, w["wq"], precision).reshape(B, T, H, hd)
    k = mm("btd,dh->bth", xn, w["wk"], precision).reshape(B, T, KV, hd)
    v = mm("btd,dh->bth", xn, w["wv"], precision).reshape(B, T, KV, hd)
    pos = jnp.broadcast_to(jnp.arange(T), (B, T))
    q, k = rope(q, pos, cfg.theta), rope(k, pos, cfg.theta)
    q = q.reshape(B, T // chunk, chunk, KV, H // KV, hd)

    def block(args):
        qc, c = args  # (B, C, KV, G, hd)
        s = mm("bckgd,bskd->bkgcs", qc, k, precision) * hd ** -0.5
        pq = c * chunk + jnp.arange(chunk)
        s = jnp.where(jnp.arange(T)[None, :] <= pq[:, None], s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        return mm("bkgcs,bskd->bckgd", p, v, precision)

    out = lax.map(block, (q.swapaxes(0, 1), jnp.arange(T // chunk)))
    out = out.swapaxes(0, 1).reshape(B, T, H * hd)
    return x + mm("bth,hd->btd", out, w["wo"], precision)


@functools.partial(jax.jit, static_argnames=("cfg", "precision"))
def _route(x, w, perm, seg, seg_cap, cfg, precision):
    """Router, top-k and the capacity keep rule on tokens in group
    order.  Returns (xn in group order, expert, weight) per (token,
    slot), weight 0 where the slot is dropped.

    A slot's position within its expert on its rank is its rank among
    the slots of the same (rank, expert) in (token, slot) order, found
    by a stable sort (no long cumulative sums)."""
    d, k, E, tp = x.shape[-1], cfg.top_k, cfg.e_pad, cfg.tp
    xs = rmsnorm(x, w["norm2"], cfg.eps).reshape(-1, d)[perm]
    logits = mm("nd,de->ne", xs, w["router"], precision)
    logits = jnp.where(jnp.arange(E) < cfg.n_experts, logits, -jnp.inf)
    probs = jax.nn.softmax(logits, axis=-1)
    top_p, top_e = lax.top_k(probs, k)
    top_p = top_p / jnp.sum(top_p, -1, keepdims=True)
    flat_e = top_e.reshape(-1)
    seg_f = jnp.repeat(seg, k)
    M, n_seg = flat_e.shape[0], seg_cap.shape[0]
    key = seg_f * E + flat_e
    order = jnp.argsort(key, stable=True)
    sk = key[order]
    starts = jnp.searchsorted(sk, jnp.arange(n_seg * E + 1,
                                             dtype=key.dtype))
    pos = jnp.zeros(M, jnp.int32).at[order].set(
        jnp.arange(M, dtype=jnp.int32) - starts[sk].astype(jnp.int32),
        unique_indices=True)
    counts = jnp.diff(starts).astype(jnp.int32)
    counts = counts.reshape(n_seg // tp, tp, E)  # (group, rank, expert)
    offsets = (jnp.cumsum(counts, 1) - counts).reshape(n_seg, E)
    gpos = offsets[seg_f, flat_e] + pos
    cap = seg_cap[seg_f]
    keep = (pos < cap) & (gpos < cap * tp)
    return xs, flat_e, jnp.where(keep, top_p.reshape(-1), 0.0)


@functools.partial(jax.jit, static_argnames=("cfg", "precision"),
                   donate_argnames=("y",))
def _experts(y, xs, src, block_expert, w, cfg, precision):
    """Grouped SwiGLU over blocks of ``BLOCK`` slots of one expert each;
    adds weight * expert(x) of every slot into its token's row of y."""
    k = cfg.top_k
    M = xs.shape[0] * k
    slot_w = w["slot_w"]

    def body(b, y):
        s = src[b]  # (BLOCK,) slot ids, M = padding
        valid = s < M
        tok = jnp.where(valid, s // k, 0)
        xb = xs[tok]
        e = block_expert[b]
        g = mm("rd,df->rf", xb, w["moe_gate"][e], precision)
        u = mm("rd,df->rf", xb, w["moe_up"][e], precision)
        out = mm("rf,fd->rd", jax.nn.silu(g) * u, w["moe_down"][e],
                 precision)
        wt = jnp.where(valid, slot_w[jnp.where(valid, s, 0)], 0.0)
        # an expert's block holds each token once; padding rows drop
        dst = jnp.where(valid, tok, y.shape[0])
        return y.at[dst].add(out * wt[:, None], mode="drop",
                             unique_indices=True)

    return lax.fori_loop(0, src.shape[0], body, y)


@functools.partial(jax.jit, static_argnames=("cfg", "precision"))
def _finish_moe(x, y, xs, inv_perm, w, cfg, precision):
    if cfg.shared:
        g = mm("nd,df->nf", xs, w["shared_gate"], precision)
        u = mm("nd,df->nf", xs, w["shared_up"], precision)
        y = y + mm("nf,fd->nd", jax.nn.silu(g) * u, w["shared_down"],
                   precision)
    return x + y[inv_perm].reshape(x.shape)


@functools.partial(jax.jit, static_argnums=(1, 2, 3, 4))
def _top_leaf(key, name, shape, dtype, draws):
    return W.top_leaf(key, name, shape, dtype, dict(draws)).astype(
        jnp.float32)


@functools.partial(jax.jit, static_argnums=(2, 3, 4, 5))
def _layer_leaves(key, layer, shapes, dtype, n_experts, draws):
    """One layer's weights in float32; the experts cut to the real
    ones (the padded ones are never routed to)."""
    raw = {k: v.astype(jnp.float32) for k, v in W.layer_leaves(
        key, 0, layer, dict(shapes), dtype, dict(draws)).items()}
    for name in ("moe_gate", "moe_up", "moe_down"):
        raw[name] = raw[name][:n_experts]
    return raw


class _Cfg:
    """Hashable sizes for the jitted layer functions."""

    def __init__(self, m: dict, e_pad: int, tp: int):
        self.n_heads, self.n_kv_heads = m["n_heads"], m["n_kv_heads"]
        self.head_dim = m.get("head_dim") or m["d_model"] // m["n_heads"]
        self.theta = float(m.get("rope_theta", 10_000.0))
        self.eps = float(m.get("norm_eps", 1e-6))
        self.top_k, self.n_experts, self.e_pad = m["top_k"], m[
            "n_experts"], e_pad
        self.cf = float(m.get("capacity_factor", 1.25))
        self.shared = bool(m.get("n_shared_experts", 0))
        self.tp = tp
        self._key = tuple(sorted(vars(self).items()))

    def __hash__(self):
        return hash(self._key)

    def __eq__(self, other):
        return isinstance(other, _Cfg) and self._key == other._key


def group_layout(group_id: np.ndarray, tp: int, cfg: _Cfg):
    """Host-side order of the tokens by (group, batch, position), the
    run (rank) each falls in, and each run's capacity."""
    B, T = group_id.shape
    flat_g = group_id.reshape(-1)
    perm = np.lexsort((np.arange(B * T), flat_g))
    g_sorted = flat_g[perm]
    starts = np.flatnonzero(np.r_[True, g_sorted[1:] != g_sorted[:-1]])
    sizes = np.diff(np.r_[starts, B * T])
    seg = np.empty(B * T, np.int32)
    seg_cap = []
    for start, n in zip(starts, sizes):
        if n % tp:
            raise ValueError(f"a group of {n} tokens does not split over "
                             f"{tp} ranks")
        n0 = n // tp
        cap = max(8, int(cfg.cf * n0 * cfg.top_k / cfg.e_pad))
        for r in range(tp):
            seg[start + r * n0:start + (r + 1) * n0] = len(seg_cap)
            seg_cap.append(cap)
    inv = np.empty_like(perm)
    inv[perm] = np.arange(B * T)
    a = lambda v: jnp.asarray(np.asarray(v, np.int32))  # noqa: E731
    return a(perm), a(seg), a(seg_cap), a(inv)


def _blocks(flat_e: np.ndarray, n_experts: int):
    """Slots sorted by expert, each expert's run padded to whole blocks:
    (src (n_blocks, BLOCK) slot ids with M as padding, block_expert).
    ``n_blocks`` is its bound ceil(M / BLOCK) + n_experts, so that one
    compiled program serves every layer and seed."""
    M = flat_e.shape[0]
    order = np.argsort(flat_e, kind="stable")
    counts = np.bincount(flat_e, minlength=n_experts)[:n_experts]
    n_blocks = -(-M // BLOCK) + n_experts
    src = np.full((n_blocks, BLOCK), M, np.int32)
    block_expert = np.zeros(n_blocks, np.int32)
    b = start = 0
    for e, c in enumerate(counts):
        nb = -(-c // BLOCK)
        run = np.full(nb * BLOCK, M, np.int32)
        run[:c] = order[start:start + c]
        src[b:b + nb] = run.reshape(nb, BLOCK)
        block_expert[b:b + nb] = e
        b, start = b + nb, start + c
    return jnp.asarray(src), jnp.asarray(block_expert)


class Reference:
    """The reference model for one configuration and seed.

    ``model``: the configuration's ``model`` sizes; ``shapes``: the
    served layout, {"top": {name: shape}, "blocks": [{name: per-layer
    shape}]}; ``tp``: ranks the experts are spread over; ``draws``: the
    configuration's ``weights`` block."""

    def __init__(self, model: dict, shapes: dict, seed: int, tp: int = 1,
                 precision: str = "f32", device=None, draws=None):
        self.m, self.shapes = model, shapes
        self.draws = W.frozen(draws)
        self.key = W.seed_key(seed)
        self.precision = precision
        self.dtype = jnp.dtype(model.get("dtype", "bfloat16"))
        self.device = device or jax.devices()[0]
        e_pad = shapes["blocks"][0]["router"][-1]
        self.cfg = _Cfg(model, e_pad, tp)
        if len(shapes["blocks"]) != 1:
            raise ValueError("the reference covers one MoE attention "
                             "layer kind per repeat")

    def _put(self, x):
        return jax.device_put(x, self.device)

    def _top(self, name):
        return _top_leaf(self._put(self.key), name,
                         self.shapes["top"][name], self.dtype, self.draws)

    def _layer(self, layer):
        shapes = tuple(sorted(self.shapes["blocks"][0].items()))
        return _layer_leaves(self._put(self.key), self._put(
            jnp.int32(layer)), shapes, self.dtype, self.cfg.n_experts,
            self.draws)

    def hidden(self, tokens: np.ndarray, group_id: np.ndarray,
               keep_pos: np.ndarray, chunk: int = 256):
        """Final-normed hidden states (B, len(keep_pos), d) of the
        teacher-forced sequences ``tokens`` (B, T), with ``group_id``
        (B, T) naming the step each token was served in."""
        cfg, prec = self.cfg, self.precision
        B, T = tokens.shape
        layout = group_layout(group_id, cfg.tp, cfg)
        perm, seg, seg_cap, inv = map(self._put, layout)
        emb = self._top("tok_embed")
        x = emb[self._put(jnp.asarray(tokens, jnp.int32))]
        del emb
        chunk = max(c for c in range(1, min(chunk, T) + 1) if T % c == 0)
        for layer in range(self.m["n_layers"]):
            w = self._layer(layer)
            x = _attention(x, w, cfg, prec, chunk)
            xs, flat_e, slot_w = _route(x, w, perm, seg, seg_cap, cfg,
                                        prec)
            src, block_expert = _blocks(np.asarray(flat_e),
                                        cfg.n_experts)
            w["slot_w"] = slot_w
            y = _experts(jnp.zeros_like(xs), xs, self._put(src),
                         self._put(block_expert), w, cfg, prec)
            x = _finish_moe(x, y, xs, inv, w, cfg, prec)
        x = x[:, self._put(jnp.asarray(keep_pos, jnp.int32))]
        return rmsnorm(x, self._top("final_norm"), cfg.eps)

    def head(self):
        """Output head (d, V) over the real vocabulary."""
        name = "tok_embed" if self.m.get("tie_embeddings") else "lm_head"
        w = self._top(name)
        w = w.T if name == "tok_embed" else w
        return w[:, :self.m["vocab"]]

    def logits(self, h, head):
        return mm("bpd,dv->bpv", h, head, self.precision)
