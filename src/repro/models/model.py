"""Model assembly: pattern-unit scan, forward, decode, loss.

The layer stack runs as ``lax.scan`` over pattern repeats (HLO contains
each distinct layer kind once — compile time at 512 devices stays flat
in depth).  Each repeat body is ``jax.checkpoint``-ed (activation
rematerialization), the standard memory/compute trade at scale.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any

import jax
import jax.numpy as jnp
from jax import lax

from repro.models import params as PD
from repro.models import rwkv as rwkv_lib
from repro.models.attention import attention_block, merged_heads
from repro.models.common import rmsnorm, softcap, swiglu
from repro.models.config import ModelConfig
from repro.models.mamba import init_mamba_cache, mamba_block
from repro.models.moe import moe_block
from repro.models.rwkv import rwkv_block
from repro.sharding import rules as rules_lib
from repro.sharding.ctx import constrain, use_mesh_rules


@dataclasses.dataclass(frozen=True)
class Model:
    cfg: ModelConfig
    mesh: Any  # jax.sharding.Mesh — needed by the MoE shard_map

    # ------------------------- params -------------------------

    def init_params(self, key):
        return PD.init_params(self.cfg, key)

    def abstract_params(self):
        return PD.abstract_params(self.cfg)

    def param_shardings(self, rules):
        return PD.param_shardings(self.cfg, self.mesh, rules)

    # ------------------------- layers -------------------------

    def _ffn(self, spec, p, x):
        """Post-attention FFN half of a block. Returns (x, aux)."""
        cfg = self.cfg
        if spec.use_moe:
            return moe_block(cfg, p, x, self.mesh)
        with jax.named_scope("ffn"):
            xn = rmsnorm(x, p["norm2"], cfg.norm_eps)
            y = swiglu(xn, p["w_gate"], p["w_up"], p["w_down"])
            return x + y, jnp.zeros((2,), jnp.float32)

    def _layer(self, spec, p, x, positions, cache=None, cache_len=None):
        cfg = self.cfg
        if spec.kind == "attn":
            x, new_cache = attention_block(
                cfg, p, x, positions, window=spec.sliding_window,
                cache=cache, cache_len=cache_len)
            x, aux = self._ffn(spec, p, x)
        elif spec.kind == "mamba":
            x, new_cache = mamba_block(cfg, p, x, cache=cache)
            x, aux = self._ffn(spec, p, x)
        elif spec.kind == "rwkv":
            x, new_cache = rwkv_block(cfg, p, x, cache=cache,
                                      mesh=self.mesh)
            aux = jnp.zeros((2,), jnp.float32)
        else:
            raise ValueError(spec.kind)
        return x, aux, new_cache

    # ------------------------- forward -------------------------

    def _embed(self, p_top, tokens, prefix_embeds=None):
        """tokens: (B, S_tok) int32 or None; prefix_embeds: (B, n, d) —
        vlm patch embeddings (prepended) or audio frame embeddings (the
        whole input).  Frontends are stubs per the assignment."""
        cfg = self.cfg
        if tokens is None:
            return prefix_embeds  # audio: frame embeddings are the input
        with jax.named_scope("embed"):
            x = jnp.take(p_top["tok_embed"], tokens, axis=0)
            if cfg.frontend == "vision" and prefix_embeds is not None:
                x = jnp.concatenate(
                    [prefix_embeds.astype(x.dtype), x], axis=1)
            return x

    def _stack(self, params, x, positions):
        """Scan the layer stack. Returns (x, aux_sum)."""
        cfg = self.cfg
        pattern = cfg.pattern()

        def body(carry, layer_params):
            h, aux = carry
            for j, spec in enumerate(pattern):
                h, aux_j, _ = self._layer(spec, layer_params[j], h,
                                          positions)
                aux = aux + aux_j
            return (h, aux), None

        if cfg.remat:
            policy = (jax.checkpoint_policies.dots_saveable
                      if cfg.remat_policy == "dots"
                      else jax.checkpoint_policies.nothing_saveable)
            body = jax.checkpoint(body, policy=policy)
        carry = (x, jnp.zeros((2,), jnp.float32))
        with jax.named_scope("layers"):
            if cfg.unroll_stack:
                for r in range(cfg.n_repeats):
                    layer_params = jax.tree.map(lambda t: t[r],
                                                params["blocks"])
                    carry, _ = body(carry, layer_params)
            else:
                carry, _ = lax.scan(body, carry, params["blocks"])
        return carry

    def logits_fn(self, params, x):
        cfg = self.cfg
        with jax.named_scope("head"):
            x = rmsnorm(x, params["top"]["final_norm"], cfg.norm_eps)
            if cfg.tie_embeddings:
                w = params["top"]["tok_embed"].T
            else:
                w = params["top"]["lm_head"]
            logits = jnp.einsum("bsd,dv->bsv", x, w).astype(jnp.float32)
            logits = softcap(logits, cfg.logit_softcap)
            vp = PD.vocab_padded(cfg)
            if vp != cfg.vocab:
                vmask = jnp.arange(vp) < cfg.vocab
                logits = jnp.where(vmask, logits, -1e30)
            return logits

    def forward(self, params, tokens, prefix_embeds=None, positions=None):
        """Full-sequence forward (train / prefill). Returns (logits, aux)."""
        with use_mesh_rules(self.mesh, rules_lib.rules_for(self.cfg)):
            x = self._embed(params["top"], tokens, prefix_embeds)
            x = constrain(x, "batch", "seq", "embed_act")
            B, S, _ = x.shape
            if positions is None:
                positions = jnp.broadcast_to(
                    jnp.arange(S, dtype=jnp.int32), (B, S))
            x, aux = self._stack(params, x, positions)
            return self.logits_fn(params, x), aux

    def loss(self, params, batch):
        """batch: {"tokens" or "embeds", "labels", optional "prefix"}.
        Next-token CE for causal LMs; per-position CE for encoders.

        The CE is vocab-shard-safe: no full-vocab softmax materializes
        off-shard — max/logsumexp/label-pick all reduce over the sharded
        vocab axis locally + one tiny (B, S) cross-shard reduction, and
        shapes stay round (shift via roll + mask, not odd slicing).
        See EXPERIMENTS.md §Perf iteration 0.
        """
        cfg = self.cfg
        tokens = batch.get("tokens")
        prefix = batch.get("embeds") if cfg.frontend == "audio" else \
            batch.get("prefix")
        logits, aux = self.forward(params, tokens, prefix)
        with use_mesh_rules(self.mesh, rules_lib.rules_for(self.cfg)):
            return self._loss_inner(logits, aux, batch)

    def _loss_inner(self, logits, aux, batch):
        cfg = self.cfg
        n_moe = sum(1 for s in cfg.pattern() if s.use_moe) * cfg.n_repeats
        aux = aux / max(n_moe, 1)  # per-MoE-layer means
        logits = constrain(logits, "batch", "seq", "vocab")
        labels = batch["labels"]
        B, S_l = labels.shape
        n_prefix = logits.shape[1] - S_l
        if cfg.causal and not cfg.encoder_only:
            # predict labels[t+1] at position t; last position masked
            labels = jnp.roll(labels, -1, axis=1)
            weights = jnp.concatenate(
                [jnp.ones((B, S_l - 1), jnp.float32),
                 jnp.zeros((B, 1), jnp.float32)], axis=1)
        else:
            weights = jnp.ones((B, S_l), jnp.float32)
        if n_prefix:  # vlm: prefix positions carry no labels
            labels = jnp.concatenate(
                [jnp.zeros((B, n_prefix), labels.dtype), labels], axis=1)
            weights = jnp.concatenate(
                [jnp.zeros((B, n_prefix), jnp.float32), weights], axis=1)
        logits32 = logits.astype(jnp.float32)
        zmax = jnp.max(logits32, axis=-1, keepdims=True)
        lse = jnp.log(jnp.sum(jnp.exp(logits32 - zmax), axis=-1)) + \
            zmax[..., 0]
        vp = logits.shape[-1]
        onehot = jax.nn.one_hot(labels, vp, dtype=jnp.float32)
        label_logit = jnp.sum(logits32 * onehot, axis=-1)
        nll = (lse - label_logit) * weights
        ce = jnp.sum(nll) / jnp.maximum(jnp.sum(weights), 1.0)
        lb_loss = aux[0] * 0.01  # load-balance coefficient
        metrics = {"ce": ce, "load_balance": aux[0], "dropped": aux[1]}
        return ce + lb_loss, metrics

    # ------------------------- decode -------------------------

    def init_cache(self, batch: int, max_len: int, kv_dup: int = 1):
        """Stacked-by-repeat caches, one entry per pattern position."""
        cfg = self.cfg
        dtype = jnp.dtype(cfg.dtype)
        r = cfg.n_repeats
        caches = []
        for spec in cfg.pattern():
            if spec.kind == "attn":
                kvd, hd = cfg.n_kv_heads * kv_dup, cfg.head_dim_
                heads = (kvd * hd,) if merged_heads(hd) else (kvd, hd)
                shape = (r, batch, max_len, *heads)
                c = {"k": jnp.zeros(shape, dtype),
                     "v": jnp.zeros(shape, dtype)}
            elif spec.kind == "mamba":
                c = jax.tree.map(
                    lambda t: jnp.broadcast_to(t, (r, *t.shape)).copy(),
                    init_mamba_cache(cfg, batch, dtype))
            else:
                c = jax.tree.map(
                    lambda t: jnp.broadcast_to(t, (r, *t.shape)).copy(),
                    rwkv_lib.init_rwkv_cache(cfg, batch, dtype))
            caches.append(c)
        return tuple(caches)

    def abstract_cache(self, batch: int, max_len: int, kv_dup: int = 1):
        return jax.eval_shape(
            lambda: self.init_cache(batch, max_len, kv_dup))

    def cache_logical_axes(self, seq_sharded: bool = False,
                           kv_shardable: bool = True):
        """Logical-axis tree matching init_cache's structure.

        seq_sharded: long-context mode — cache seq over the data axis.
        kv_shardable: False when no kv duplication makes the heads dim
        divisible by TP (then seq shards over "model" instead)."""
        cfg = self.cfg
        if seq_sharded:
            seq_ax, b_ax = "cache_seq_shard", None
        elif not kv_shardable:
            seq_ax, b_ax = "cache_seq_tp", "cache_batch"
        else:
            seq_ax, b_ax = "cache_seq", "cache_batch"
        kv_ax = "cache_kv" if kv_shardable else None
        # a merged leaf (init_cache) shards its heads-major last axis
        attn_ax = ("layers", b_ax, seq_ax, kv_ax) + (
            () if merged_heads(cfg.head_dim_) else (None,))
        out = []
        for spec in cfg.pattern():
            if spec.kind == "attn":
                out.append({"k": attn_ax, "v": attn_ax})
            elif spec.kind == "mamba":
                out.append({
                    "conv": ("layers", b_ax, None, "d_inner"),
                    "h": ("layers", b_ax, "d_inner", None),
                })
            else:
                out.append({
                    "shift": ("layers", b_ax, None, None),
                    "cm_shift": ("layers", b_ax, None, None),
                    "state": ("layers", b_ax, "heads", None, None),
                })
        return tuple(out)

    def decode_step(self, params, cache, tokens, cache_len):
        """One-token decode.  tokens: (B, 1) int32; cache_len: scalar.

        Returns (logits (B, 1, V), new_cache)."""
        return self.serve_step(params, cache, tokens, cache_len)

    def serve_step(self, params, cache, tokens, cache_len,
                   prefix_embeds=None, last_only=False):
        """Serving step: decode (S=1) or prefill (S>1) into the cache.

        tokens: (B, S) int32; cache_len: scalar i32 (valid cache length
        before this call).  Returns (logits, new_cache); with
        ``last_only`` logits cover only the final position (prefill
        avoids materializing (B, S, vocab))."""
        cfg = self.cfg
        with use_mesh_rules(self.mesh, rules_lib.rules_for(self.cfg)):
            return self._serve_step_inner(params, cache, tokens, cache_len,
                                          prefix_embeds, last_only)

    def _serve_step_inner(self, params, cache, tokens, cache_len,
                          prefix_embeds, last_only):
        cfg = self.cfg
        x = self._embed(params["top"], tokens, prefix_embeds)
        x = constrain(x, "batch", None, None)
        B, S, _ = x.shape
        positions = cache_len + jnp.broadcast_to(
            jnp.arange(S, dtype=jnp.int32), (B, S))
        pattern = cfg.pattern()

        def body(h, scan_in):
            layer_params, layer_cache = scan_in
            new_caches = []
            for j, spec in enumerate(pattern):
                h, _, nc = self._layer(spec, layer_params[j], h, positions,
                                       cache=layer_cache[j],
                                       cache_len=cache_len)
                new_caches.append(nc)
            return h, tuple(new_caches)

        with jax.named_scope("layers"):
            if cfg.unroll_stack:
                new_caches = []
                for r in range(cfg.n_repeats):
                    lp = jax.tree.map(lambda t: t[r], params["blocks"])
                    lc = jax.tree.map(lambda t: t[r], cache)
                    x, nc = body(x, (lp, lc))
                    new_caches.append(nc)
                new_cache = jax.tree.map(
                    lambda *ts: jnp.stack(ts), *new_caches)
            else:
                x, new_cache = lax.scan(body, x,
                                        (params["blocks"], cache))
            if S == 1:
                # decode: attention layers gave only their new rows, (R,
                # B, 1, ...) in the cache's form; one write per leaf into
                # the stacked cache, in place where the caller donates it
                with jax.named_scope("kv_cache"):
                    new_cache = tuple(
                        jax.tree.map(
                            lambda c, rows: lax.dynamic_update_slice_in_dim(
                                c, rows, cache_len, axis=2), c, nc)
                        if spec.kind == "attn" else nc
                        for spec, c, nc in zip(pattern, cache, new_cache))
        if last_only:
            x = x[:, -1:]
        return self.logits_fn(params, x), new_cache
