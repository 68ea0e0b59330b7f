"""Per-architecture smoke + consistency tests (reduced configs, CPU).

* forward/loss: finite, correct shapes, for all 10 archs
* decode-with-cache == full forward (cache correctness), all decodable
* decode attention over the cache read in place == write-then-attend,
  and a decode step writes only the new rows
* train step decreases loss (integration with optimizer)
* MoE: multi-device (2 data x 4 model) == single-device reference
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh

from helpers import run_with_devices
from repro import configs
from repro.models import params as PD
from repro.models.model import Model


def _mesh1():
    return Mesh(np.array(jax.devices()[:1]).reshape(1, 1), ("data", "model"))


def _batch(cfg, B, S, seed=0):
    rng = np.random.default_rng(seed)
    batch = {}
    if cfg.frontend == "audio":
        batch["embeds"] = jnp.asarray(
            rng.standard_normal((B, S, cfg.d_model)), jnp.float32)
        batch["labels"] = jnp.asarray(rng.integers(0, cfg.vocab, (B, S)))
    else:
        batch["tokens"] = jnp.asarray(
            rng.integers(0, cfg.vocab, (B, S)), jnp.int32)
        batch["labels"] = batch["tokens"]
        if cfg.frontend == "vision":
            batch["prefix"] = jnp.asarray(
                rng.standard_normal((B, cfg.n_prefix, cfg.d_model)),
                jnp.float32)
    return batch


@pytest.mark.parametrize("name", configs.ARCHITECTURES)
def test_smoke_forward_loss(name):
    cfg = configs.get_smoke(name)
    mesh = _mesh1()
    m = Model(cfg, mesh)
    params = m.init_params(jax.random.PRNGKey(0))
    batch = _batch(cfg, 2, 32)
    with jax.set_mesh(mesh):
        loss, metrics = jax.jit(m.loss)(params, batch)
        tokens = batch.get("tokens")
        embeds = batch.get("embeds") if cfg.frontend == "audio" else \
            batch.get("prefix")
        logits, _ = jax.jit(m.forward)(params, tokens, embeds)
    S_out = 32 + (cfg.n_prefix if cfg.frontend == "vision" else 0)
    assert logits.shape == (2, S_out, PD.vocab_padded(cfg))
    assert np.isfinite(float(loss))
    assert np.all(np.isfinite(np.asarray(logits)))


@pytest.mark.parametrize("name", configs.ARCHITECTURES)
def test_decode_matches_forward(name):
    cfg = configs.get_smoke(name, capacity_factor=16.0)
    if cfg.encoder_only:
        pytest.skip("encoder-only: no decode step")
    mesh = _mesh1()
    m = Model(cfg, mesh)
    params = m.init_params(jax.random.PRNGKey(0))
    B, S = 2, 16
    rng = np.random.default_rng(1)
    tokens = jnp.asarray(rng.integers(0, cfg.vocab, (B, S)), jnp.int32)
    with jax.set_mesh(mesh):
        logits_full, _ = jax.jit(m.forward)(params, tokens)
        cache = m.init_cache(B, S)
        step = jax.jit(m.decode_step)
        outs = []
        for t in range(S):
            lg, cache = step(params, cache, tokens[:, t : t + 1], t)
            outs.append(lg)
    logits_dec = jnp.concatenate(outs, axis=1)
    np.testing.assert_allclose(
        np.asarray(logits_dec), np.asarray(logits_full), atol=2e-3, rtol=1e-2)


# (cache_len, kv_dup, window, attn_softcap) over a 16-position cache
_CACHED_CASES = {
    "len0": (0, 1, 0, 0.0),
    "middle": (7, 1, 0, 0.0),
    "last": (15, 1, 0, 0.0),
    "kv_dup2": (7, 2, 0, 0.0),
    "window": (11, 1, 4, 0.0),
    "window_short_cache": (2, 1, 4, 0.0),
    "softcap": (7, 1, 0, 30.0),
    "all": (15, 2, 4, 30.0),
}


# head_dim 8 takes the merged leaf (B, S_max, KV * hd), 128 keeps the
# per-head leaf (B, S_max, KV, hd); the ids of the first keep their case
_LEAF_FORMS = [pytest.param(case, 8, id=case)
               for case in sorted(_CACHED_CASES)] + [
    pytest.param(case, 128, id=f"{case}-hd128")
    for case in sorted(_CACHED_CASES)]


@pytest.mark.parametrize("case,hd", _LEAF_FORMS)
def test_cached_attention_matches_write_then_attend(case, hd):
    """Decode attention over the cache read in place plus the step's own
    key and value equals writing them at ``cache_len`` and attending
    over the written cache, the form prefill keeps, for the cache leaf
    in the form its head width takes."""
    from jax import lax

    from repro.models.attention import (attention_core, cached_attention,
                                        merged_heads)

    cache_len, dup, window, cap = _CACHED_CASES[case]
    B, S_max, H, KV = 2, 16, 8, 2
    rng = np.random.default_rng(cache_len + 10 * dup + window)

    def normal(*shape):
        return jnp.asarray(rng.standard_normal(shape), jnp.float32)

    def leaf(t):  # (B, S, KVd, hd) in the form the cache stores
        return t.reshape(*t.shape[:2], -1) if merged_heads(hd) else t

    q = normal(B, 1, H, hd)
    k = jnp.repeat(normal(B, 1, KV, hd), dup, axis=2)
    v = jnp.repeat(normal(B, 1, KV, hd), dup, axis=2)
    ck, cv = normal(B, S_max, KV * dup, hd), normal(B, S_max, KV * dup, hd)
    n = jnp.int32(cache_len)
    got = jax.jit(lambda *a: cached_attention(
        *a, window=window, attn_softcap=cap))(
        q, leaf(ck), leaf(cv), leaf(k), leaf(v), n)
    wk = lax.dynamic_update_slice_in_dim(ck, k, cache_len, axis=1)
    wv = lax.dynamic_update_slice_in_dim(cv, v, cache_len, axis=1)
    pos_k = jnp.broadcast_to(jnp.arange(S_max, dtype=jnp.int32),
                             (B, S_max))
    want = attention_core(
        q, wk, wv, jnp.full((B, 1), cache_len, jnp.int32), pos_k,
        causal=True, window=window, attn_softcap=cap, chunk=16,
        kv_len=jnp.full((B,), cache_len + 1, jnp.int32))
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=1e-5, rtol=1e-5)


@pytest.mark.parametrize("hd", [16, 128])
def test_cache_logical_axes_name_every_attention_axis(hd):
    """Each attention cache leaf gets one logical name per axis, with
    its sequence axis (2, after layers and batch) named ``cache_seq...``,
    whether the leaf is merged (head_dim 16) or per head (128)."""
    from repro.models.attention import merged_heads

    cfg = configs.get_smoke("granite_3_2b", head_dim=hd)
    m = Model(cfg, _mesh1())
    cache = m.abstract_cache(2, 16)
    assert cache[0]["k"].ndim == (4 if merged_heads(hd) else 5)
    for seq_sharded, kv_shardable in ((False, True), (True, True),
                                      (False, False)):
        axes = m.cache_logical_axes(seq_sharded, kv_shardable)
        for spec, c, ax in zip(cfg.pattern(), cache, axes):
            if spec.kind != "attn":
                continue
            for leaf in ("k", "v"):
                assert len(ax[leaf]) == c[leaf].ndim, (ax[leaf], c[leaf])
                seq = [i for i, a in enumerate(ax[leaf])
                       if a and a.startswith("cache_seq")]
                assert seq == [2], ax[leaf]


def _check_decode_writes_only_the_new_rows(cfg, cache_len, kv_dup):
    mesh = _mesh1()
    m = Model(cfg, mesh)
    params = m.init_params(jax.random.PRNGKey(0))
    B, L = 2, 16
    rng = np.random.default_rng(cache_len)
    tokens = jnp.asarray(rng.integers(0, cfg.vocab, (B, L)), jnp.int32)
    pattern = cfg.pattern()
    cache0 = tuple(
        jax.tree.map(lambda t: jnp.asarray(
            rng.standard_normal(t.shape), t.dtype), c)
        if spec.kind == "attn" else c
        for spec, c in zip(pattern, m.init_cache(B, L, kv_dup)))
    with jax.set_mesh(mesh):
        serve = jax.jit(m.serve_step)
        before = (serve(params, cache0, tokens[:, :cache_len], 0)[1]
                  if cache_len else cache0)
        logits, after = jax.jit(m.decode_step)(
            params, before, tokens[:, cache_len:cache_len + 1], cache_len)
        ref_logits, ref = serve(params, cache0, tokens[:, :cache_len + 1], 0)
    keep = np.arange(L) != cache_len
    n_attn = 0
    for spec, b, a, r in zip(pattern, before, after, ref):
        if spec.kind != "attn":
            continue
        n_attn += 1
        for leaf in ("k", "v"):
            assert a[leaf].shape == b[leaf].shape
            a_, b_, r_ = (np.asarray(t[leaf]) for t in (a, b, r))
            np.testing.assert_array_equal(a_[:, :, keep], b_[:, :, keep])
            np.testing.assert_allclose(a_[:, :, cache_len],
                                       r_[:, :, cache_len],
                                       atol=1e-4, rtol=1e-4)
    assert n_attn
    np.testing.assert_allclose(np.asarray(logits),
                               np.asarray(ref_logits[:, -1:]),
                               atol=2e-3, rtol=1e-2)


@pytest.mark.parametrize("name,cache_len,kv_dup,unroll", [
    ("granite_3_2b", 0, 1, False),
    ("granite_3_2b", 6, 2, False),
    ("gemma2_9b", 11, 1, False),
    ("jamba_1_5_large_398b", 9, 1, False),
    ("jamba_1_5_large_398b", 4, 1, True),
    ("qwen2_moe_a2_7b", 15, 1, False),
])
def test_decode_writes_only_the_new_rows(name, cache_len, kv_dup, unroll):
    """One decode step leaves every attention cache row but the one at
    ``cache_len`` bit-identical, and writes there the key and value a
    prefill of the same tokens writes; its logits match that prefill's
    last position.  The rows the cache holds past ``cache_len`` are
    noise, which the step must not attend to.  ``unroll`` runs the
    layer stack unrolled, as the dry run compiles it.  The smoke sizes'
    heads (12-16 wide) take the merged cache leaf."""
    over = {"sliding_window": 4} if name == "gemma2_9b" else {}
    cfg = configs.get_smoke(name, capacity_factor=16.0,
                            unroll_stack=unroll, **over)
    _check_decode_writes_only_the_new_rows(cfg, cache_len, kv_dup)


@pytest.mark.parametrize("name,hd,cache_len,kv_dup,over", [
    ("granite_3_2b", 128, 6, 2, {}),
    ("gemma2_9b", 256, 11, 1, {"sliding_window": 4}),
])
def test_decode_writes_only_the_new_rows_per_head_leaf(name, hd, cache_len,
                                                       kv_dup, over):
    """The same with heads a multiple of 128 lanes wide, whose cache
    leaf keeps one axis per head (``merged_heads`` is false)."""
    cfg = configs.get_smoke(name, capacity_factor=16.0, head_dim=hd, **over)
    _check_decode_writes_only_the_new_rows(cfg, cache_len, kv_dup)


@pytest.mark.parametrize("name", ["llama3_8b", "qwen2_moe_a2_7b",
                                  "rwkv6_1_6b", "jamba_1_5_large_398b"])
def test_train_step_decreases_loss(name):
    from repro.optim.adamw import adamw_init, adamw_update

    cfg = configs.get_smoke(name)
    mesh = _mesh1()
    m = Model(cfg, mesh)
    params = m.init_params(jax.random.PRNGKey(0))
    batch = _batch(cfg, 2, 32)
    opt = adamw_init(params)

    @jax.jit
    def step(params, opt, batch):
        (loss, metrics), grads = jax.value_and_grad(
            m.loss, has_aux=True)(params, batch)
        params, opt = adamw_update(params, grads, opt, lr=3e-3)
        return params, opt, loss

    with jax.set_mesh(mesh):
        losses = []
        for _ in range(8):
            params, opt, loss = step(params, opt, batch)
            losses.append(float(loss))
    assert all(np.isfinite(l) for l in losses)
    assert losses[-1] < losses[0], losses


def test_param_counts_match_published():
    expect = {
        "jamba_1_5_large_398b": (398e9, 410e9),
        "qwen2_moe_a2_7b": (14e9, 16e9),
        "llama3_8b": (7.9e9, 8.2e9),
        "gemma2_9b": (9.0e9, 9.5e9),
        "rwkv6_1_6b": (1.5e9, 1.8e9),
        "pixtral_12b": (11.8e9, 12.6e9),
    }
    for name, (lo, hi) in expect.items():
        n = PD.count_params(configs.get(name))
        assert lo <= n <= hi, (name, n)
    # active params: jamba publishes 94B
    na = PD.count_params(configs.get("jamba_1_5_large_398b"),
                         active_only=True)
    assert 90e9 <= na <= 98e9, na


_MOE_MULTIDEV = """
import jax, numpy as np, jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from repro import configs
from repro.models.model import Model

cfg = configs.get_smoke("qwen2_moe_a2_7b", capacity_factor=16.0,
                        exscan_algorithm="{alg}")
B, S = 4, 16
rng = np.random.default_rng(3)
tokens = jnp.asarray(rng.integers(0, cfg.vocab, (B, S)), jnp.int32)

# single-device reference
mesh1 = Mesh(np.array(jax.devices()[:1]).reshape(1, 1), ("data", "model"))
m1 = Model(cfg, mesh1)
params = m1.init_params(jax.random.PRNGKey(0))
with jax.set_mesh(mesh1):
    ref, _ = jax.jit(m1.forward)(params, tokens)

# 2 data x 4 model
mesh = Mesh(np.array(jax.devices()).reshape(2, 4), ("data", "model"))
m = Model(cfg, mesh)
with jax.set_mesh(mesh):
    got, _ = jax.jit(m.forward)(params, tokens)
np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                           atol=3e-4, rtol=3e-3)
print("OK moe multidev")
"""


@pytest.mark.parametrize("alg", ["123", "1doubling", "two_op"])
def test_moe_multidevice_matches_reference(alg):
    out = run_with_devices(_MOE_MULTIDEV.format(alg=alg), 8, x64=False)
    assert "OK" in out
