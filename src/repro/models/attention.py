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
from repro.models.params import LANE
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


def merged_heads(head_dim: int) -> bool:
    """Whether an attention cache leaf stores heads x head_dim merged on
    one axis, ``(B, S_max, KVd * hd)``, rather than ``(B, S_max, KVd,
    hd)``.  A head narrower than the 128 lanes of a TPU tile would
    otherwise be padded to them, so the compiler lays such a leaf out
    sequence-minor, and a one-position write touches a lane of every
    tile; merged, a written position is whole rows."""
    return head_dim % LANE != 0


def cached_attention(
    q: jax.Array,  # (B, 1, H, hd), rope applied
    ck: jax.Array,  # (B, S_max, KV, hd) or merged (B, S_max, KV * hd)
    cv: jax.Array,  # as ck
    k: jax.Array,  # (B, 1, ...) in ck's form: the step's own key at
    v: jax.Array,  # cache dtype, and its value
    cache_len: jax.Array,  # scalar: the position of the step's token
    *,
    window: int,
    attn_softcap: float,
):
    """One query position against the cache, read in place, and the
    step's own key and value.

    The keys and masks are those of writing ``k, v`` at ``cache_len``
    and attending over the written cache (``attention_core`` with
    ``kv_len = cache_len + 1``): cached positions below ``cache_len``
    (and inside the sliding window), then the new key.  One softmax
    runs over both sets; the two value products are summed in float32.
    The cache is never written, so a caller that holds it stacked over
    layers reads its slice without copying it.

    A merged leaf (``merged_heads``) is read over its full width with
    block-diagonal products: each query head sits in its KV head's
    lanes with zeros elsewhere, and keeps its own block of the value
    product.  No view of the leaf per head is taken, which on a TPU
    would copy it."""
    B, _, H, hd = q.shape
    S_max = ck.shape[1]
    scale = hd ** -0.5
    if ck.ndim == 4:
        KV = ck.shape[2]
        qg = q.reshape(B, 1, KV, H // KV, hd)

        def scores_of(c):  # (B, KV, G, 1, S)
            return _grouped_scores(qg, c, scale, attn_softcap)

        def values_of(w, c):  # (B, 1, KV, G, hd)
            return jnp.einsum("bkgcs,bskd->bckgd", w, c,
                              preferred_element_type=jnp.float32)

        def heads(out):
            return out.reshape(B, 1, H, hd)
    else:
        KV = ck.shape[2] // hd
        own = (jnp.arange(H)[:, None] // (H // KV)
               == jnp.arange(KV)[None, :])  # (H, KV)
        qb = (q[:, 0, :, None, :] * own[..., None].astype(q.dtype)
              ).reshape(B, H, KV * hd)

        def scores_of(c):  # (B, H, S)
            s = jnp.einsum("bhj,bsj->bhs", qb, c,
                           preferred_element_type=jnp.float32)
            return softcap(s * scale, attn_softcap)

        def values_of(w, c):  # (B, H, KV * hd)
            return jnp.einsum("bhs,bsj->bhj", w, c,
                              preferred_element_type=jnp.float32)

        def heads(out):
            out = jnp.sum(out.reshape(B, H, KV, hd) * own[..., None],
                          axis=2)
            return out.reshape(B, 1, H, hd)
    pk = jnp.arange(S_max, dtype=jnp.int32)
    mask = pk < cache_len
    if window:
        mask &= pk > cache_len - window
    scores = jnp.concatenate(
        [_apply_mask(scores_of(ck), mask), scores_of(k)], axis=-1)
    w = jax.nn.softmax(scores, axis=-1).astype(cv.dtype)
    out = values_of(w[..., :S_max], cv) + values_of(w[..., S_max:], v)
    return heads(out).astype(cv.dtype)


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
    """Pre-norm attention sub-block.  Returns (residual_out, cache_out).

    Full-sequence mode (cache=None): self-attention over x; cache_out is
    None.  With a cache of (k, v), each (B, S_max, KVd, hd), or (B,
    S_max, KVd * hd) where ``merged_heads(hd)``, with ``cache_len``
    valid entries (kv heads stored duplicated to the TP degree when
    n_kv < TP, see DESIGN §5):

    * prefill (S > 1) writes the S new rows into the cache and attends
      over it; cache_out is the written cache;
    * decode (S == 1) reads the cache without writing it
      (``cached_attention``); cache_out holds only the new rows, (B, 1,
      ...) in the cache's form, for the caller to write at
      ``cache_len``.
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
        merged = cache["k"].ndim == 3
        with jax.named_scope("kv_cache"):
            kvd = cache["k"].shape[2] // (hd if merged else 1)
            dup = kvd // cfg.n_kv_heads
            if dup > 1:
                k = jnp.repeat(k, dup, axis=2)
                v = jnp.repeat(v, dup, axis=2)
            k = k.astype(cache["k"].dtype)
            v = v.astype(cache["v"].dtype)
            if merged:
                k = k.reshape(B, S, kvd * hd)
                v = v.reshape(B, S, kvd * hd)
            if S == 1:
                new_cache = {"k": k, "v": v}
            else:
                ck = lax.dynamic_update_slice_in_dim(
                    cache["k"], k, cache_len, axis=1)
                cv = lax.dynamic_update_slice_in_dim(
                    cache["v"], v, cache_len, axis=1)
                new_cache = {"k": ck, "v": cv}
        with jax.named_scope("attn_core"):
            if S == 1:
                out = cached_attention(
                    q, cache["k"], cache["v"], k, v, cache_len,
                    window=window, attn_softcap=cfg.attn_softcap)
            else:
                S_max = ck.shape[1]
                if merged:  # prefill may view the written leaf per head
                    ck = ck.reshape(B, S_max, kvd, hd)
                    cv = cv.reshape(B, S_max, kvd, hd)
                pos_k = jnp.broadcast_to(
                    jnp.arange(S_max, dtype=jnp.int32), (B, S_max))
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
