"""GQA attention: chunked full-sequence path + cached decode path.

Memory discipline: the (S, S) score matrix is never materialized — the
query axis is processed in ``cfg.attn_chunk`` chunks with ``lax.scan``
(q-chunk scores are (B, KV, G, C, S)).  This is the XLA-expressible
flash-style formulation that both lowers on the CPU dry-run backend and
fuses well on TPU.  GQA is computed in grouped form (no KV repetition).

Variants: RoPE, attention-score softcap (gemma2), sliding window
(gemma2 local layers), non-causal (hubert encoder).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

from repro.models.common import rmsnorm, rope, softcap
from repro.sharding.ctx import constrain

NEG_INF = -1e30


def _grouped_scores(q, k, scale, cap):
    """q: (B,C,KV,G,hd)  k: (B,S,KV,hd)  ->  (B,KV,G,C,S)."""
    s = jnp.einsum("bckgd,bskd->bkgcs", q, k,
                   preferred_element_type=jnp.float32)
    return softcap(s * scale, cap)


def _apply_mask(scores, mask):
    return jnp.where(mask, scores, NEG_INF)


def _attend(scores, v):
    """scores: (B,KV,G,C,S) f32; v: (B,S,KV,hd) -> (B,C,KV,G,hd)."""
    w = jax.nn.softmax(scores, axis=-1)
    return jnp.einsum("bkgcs,bskd->bckgd", w.astype(v.dtype), v)


def attention_core(
    q: jax.Array,  # (B, Sq, H, hd), rope applied
    k: jax.Array,  # (B, Skv, KV, hd), rope applied
    v: jax.Array,  # (B, Skv, KV, hd)
    pos_q: jax.Array,  # (B, Sq) int32
    pos_k: jax.Array,  # (B, Skv) int32
    *,
    causal: bool,
    window: int,
    attn_softcap: float,
    chunk: int,
    kv_len: jax.Array | None = None,  # (B,) valid cache length (decode)
):
    B, Sq, H, hd = q.shape
    KV = k.shape[2]
    G = H // KV
    scale = hd ** -0.5
    qg = q.reshape(B, Sq, KV, G, hd)

    def block(qc, pq):
        # qc: (B, C, KV, G, hd); pq: (B, C)
        scores = _grouped_scores(qc, k, scale, attn_softcap)
        mask = jnp.ones((B, 1, 1, qc.shape[1], k.shape[1]), bool)
        pk = pos_k[:, None, None, None, :]
        pqe = pq[:, None, None, :, None]
        if causal:
            mask &= pk <= pqe
        if window:
            mask &= pk > pqe - window
        if kv_len is not None:
            mask &= pk < kv_len[:, None, None, None, None]
        return _attend(_apply_mask(scores, mask), v)

    if Sq <= chunk:
        out = block(qg, pos_q)
    else:
        assert Sq % chunk == 0, (Sq, chunk)
        n = Sq // chunk
        qs = qg.reshape(B, n, chunk, KV, G, hd).swapaxes(0, 1)
        ps = pos_q.reshape(B, n, chunk).swapaxes(0, 1)
        out = lax.scan(
            lambda _, qp: (None, block(*qp)), None, (qs, ps)
        )[1]  # (n, B, C, KV, G, hd)
        out = out.swapaxes(0, 1).reshape(B, Sq, KV, G, hd)
    return out.reshape(B, Sq, H, hd)


@jax.named_scope("attn")
def attention_block(
    cfg,
    p: dict,
    x: jax.Array,  # (B, S, d)
    positions: jax.Array,  # (B, S)
    *,
    window: int,
    cache: dict | None = None,
    cache_len: jax.Array | None = None,
):
    """Pre-norm attention sub-block.  Returns (residual_out, new_cache).

    Full-sequence mode (cache=None): self-attention over x.
    Decode mode: x is (B, 1, d); cache holds (k, v) of shape
    (B, S_max, KVd, hd) with ``cache_len`` valid entries; kv heads are
    stored duplicated to the TP degree when n_kv < TP (see DESIGN §5).
    """
    B, S, _ = x.shape
    hd = cfg.head_dim_
    with jax.named_scope("qkv"):
        xn = rmsnorm(x, p["norm1"], cfg.norm_eps)
        q = constrain(jnp.einsum("bsd,dh->bsh", xn, p["wq"]),
                      "batch", "seq", "heads").reshape(
            B, S, cfg.n_heads, hd)
        k = constrain(jnp.einsum("bsd,dh->bsh", xn, p["wk"]),
                      "batch", "seq_kv", "kv_heads").reshape(
            B, S, cfg.n_kv_heads, hd)
        v = constrain(jnp.einsum("bsd,dh->bsh", xn, p["wv"]),
                      "batch", "seq_kv", "kv_heads").reshape(
            B, S, cfg.n_kv_heads, hd)
        q = rope(q, positions, cfg.rope_theta)
        k = rope(k, positions, cfg.rope_theta)

    if cache is None:
        with jax.named_scope("attn_core"):
            out = attention_core(
                q, k, v, positions, positions,
                causal=cfg.causal, window=window,
                attn_softcap=cfg.attn_softcap, chunk=cfg.attn_chunk,
            )
        new_cache = None
    else:
        with jax.named_scope("kv_cache"):
            dup = cache["k"].shape[2] // cfg.n_kv_heads
            if dup > 1:
                k = jnp.repeat(k, dup, axis=2)
                v = jnp.repeat(v, dup, axis=2)
            ck = lax.dynamic_update_slice_in_dim(
                cache["k"], k.astype(cache["k"].dtype), cache_len, axis=1)
            cv = lax.dynamic_update_slice_in_dim(
                cache["v"], v.astype(cache["v"].dtype), cache_len, axis=1)
            new_cache = {"k": ck, "v": cv}
        with jax.named_scope("attn_core"):
            S_max = ck.shape[1]
            pos_k = jnp.broadcast_to(jnp.arange(S_max, dtype=jnp.int32),
                                     (B, S_max))
            kv_len = jnp.full((B,), cache_len + S, jnp.int32)
            out = attention_core(
                q, ck, cv, positions, pos_k,
                causal=cfg.causal, window=window,
                attn_softcap=cfg.attn_softcap, chunk=cfg.attn_chunk,
                kv_len=kv_len,
            )
    with jax.named_scope("attn_out"):
        y = constrain(jnp.einsum("bsh,hd->bsd", out.reshape(B, S, -1),
                                 p["wo"]), "batch", "seq", "embed_act")
        return x + y, new_cache
