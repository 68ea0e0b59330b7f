"""Closed loop of greedy decode at a fixed batch (traffic kind
``decode``).

Set-up draws the weights, prefills the seeded prompts in slices of the
batch into one cache, and runs the first ``warmup_steps`` decode steps.
The window then runs decode steps back to back: each feeds the token
the previous step sampled, at positions ``prompt_len`` to
``max_len - 1``, and then wraps back to ``prompt_len`` (a new cycle of
the same prompts; a recurrent state is put back to what it held after
the prompt).  Each step ends in ``block_until_ready`` of the sampled
token.

Correctness: the newest cycle that finished (finishing the current one
after the window if none did) is run through the configuration's
float32 reference, teacher-forced, with the program's step grouping; the
checks are the gaps between the reference's best logit and the served
token's (``modelcell.compare``).
"""

from __future__ import annotations

import gc
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from bench import flops, modelcell


def prompts(seed: int, batch: int, length: int, vocab: int):
    rng = np.random.default_rng(seed)
    return rng.integers(1, vocab, (batch, length)).astype(np.int32)


def _fns(model, slice_rows):
    def prefill(params, cache, toks, b0):
        part = jax.tree.map(
            lambda c: lax.dynamic_slice_in_dim(c, b0, slice_rows, 1), cache)
        logits, part = model.serve_step(params, part, toks, 0,
                                        last_only=True)
        cache = jax.tree.map(
            lambda c, p: lax.dynamic_update_slice_in_dim(c, p, b0, 1),
            cache, part)
        return jnp.argmax(logits[:, -1], -1).astype(jnp.int32), cache

    def step(params, cache, tok, pos):
        logits, cache = model.decode_step(params, cache, tok[:, None], pos)
        return jnp.argmax(logits[:, -1], -1).astype(jnp.int32), cache

    return (jax.jit(prefill, donate_argnums=(1,)),
            jax.jit(step, donate_argnums=(1,)))


class Loop:
    """The decode loop's state: position, the current cycle's fed and
    served tokens, and the newest finished cycle."""

    def __init__(self, params, cache, step, first, prompt_len, max_len,
                 rewind=None):
        self.params, self.cache, self.step = params, cache, step
        self.rewind = rewind
        self.P, self.L = prompt_len, max_len
        self.pos = prompt_len
        self.tok = first
        self.fed, self.outs = [first], []
        self.done = None

    def once(self, run=None, times=None):
        t0 = time.perf_counter()
        if run is not None:
            with run.span("step_dispatch"):
                tok, self.cache = self.step(self.params, self.cache,
                                            self.tok, self.pos)
            t1 = time.perf_counter()
            with run.span("sample_sync"):
                tok.block_until_ready()
        else:
            tok, self.cache = self.step(self.params, self.cache, self.tok,
                                        self.pos)
            t1 = time.perf_counter()
            tok.block_until_ready()
        t2 = time.perf_counter()
        if times is not None:
            times.append((t2 - t0, t1 - t0, self.pos))
        self.outs.append(tok)
        self.tok = tok
        self.pos += 1
        if self.pos == self.L:
            self.done = (self.fed, self.outs)
            self.pos, self.fed, self.outs = self.P, [tok], []
            if self.rewind is not None:
                self.cache = self.rewind(self.cache)
        else:
            self.fed.append(tok)


def drive(run):
    cell, seed = run.cell, run.seed
    tr, conf = cell.traffic, cell.config
    B, P, L = tr["batch"], tr["prompt_len"], tr["max_len"]
    Bs = tr["prefill_slice"]
    with run.span("setup"):
        model, rules = modelcell.build(conf, run.devices)
        params = modelcell.init_params(model, rules, seed,
                                       conf.get("weights"))
        cache = modelcell.init_cache(model, rules, B, L)
        prompt = prompts(seed, B, P, model.cfg.vocab)
        prefill, step = _fns(model, Bs)
        firsts = []
        for b0 in range(0, B, Bs):
            t, cache = prefill(params, cache, jnp.asarray(prompt[b0:b0 + Bs]),
                               b0)
            firsts.append(t)
        rewind = modelcell.Rewind.of(cache, modelcell.seq_axes(model))
        if rewind is not None:
            cache = rewind(cache)  # compiled here, not in the window
        loop = Loop(params, cache, step, jnp.concatenate(firsts), P, L,
                    rewind)
        for _ in range(tr["warmup_steps"]):
            loop.once()

    times = []
    w0 = run.window_begin()
    while time.perf_counter() - w0 < run.seconds:
        loop.once(run, times)
    window_s = time.perf_counter() - w0
    if run.trace:
        traced = []
        with run.traced():
            for _ in range(tr["trace_steps"]):
                loop.once(run, traced)
        run.facts.update(traced_steps=len(traced))
    while loop.done is None:  # the window finished no cycle: finish one
        loop.once()

    step_s = np.asarray([t[0] for t in times])
    m = conf["model"]
    model_flops = sum(flops.decode_step_flops(m, B, pos)
                      for _, _, pos in times)
    ctx_mean = float(np.mean([pos + 1 for _, _, pos in times]))
    param_bytes = max(
        sum(s.data.nbytes for leaf in jax.tree.leaves(params)
            for s in leaf.addressable_shards if s.device == d)
        for d in run.devices)
    # the embedding table is gathered by rows, unless it is also the head
    embed_bytes = 0 if m.get("tie_embeddings") else max(
        s.data.nbytes for s in params["top"]["tok_embed"].addressable_shards)
    run.facts.update(
        chips=len(run.devices), window_s=window_s, steps=len(times),
        tokens=B * len(times), model_flops=model_flops, model=m,
        host_dispatch_s=[t[1] for t in times],
        step_read_bytes=(param_bytes - embed_bytes) + flops.state_bytes(
            m, B, ctx_mean) * modelcell.fullest_share(loop.cache,
                                                      run.devices),
        device_kind=run.devices[0].device_kind)
    metrics = {"decode_tok_s": B * len(times) / window_s,
               "decode_step_p95_ms": float(np.percentile(step_s, 95)) * 1e3}
    peak = modelcell.peak_bytes(run.devices)

    fed, outs = loop.done
    fed = np.stack([np.asarray(t) for t in fed], 1)  # (B, L - P)
    served = np.stack([np.asarray(t) for t in outs], 1)
    shapes = modelcell.served_shapes(model)
    tp = model.mesh.shape["model"]
    del params, cache, loop, prefill, step
    gc.collect()

    tokens = np.concatenate([prompt, fed], 1)
    run.facts["compared"] = [(tokens, group_ids(B, L, P, Bs),
                              np.arange(P, L), served)]
    t0 = time.perf_counter()
    checks = modelcell.compare(run.facts["compared"], conf, shapes, seed,
                               tp, run.devices[0])
    print(f"reference {time.perf_counter() - t0:.1f} s over "
          f"{served.size} served tokens", file=sys.stderr, flush=True)
    return {"metrics": metrics, "checks": checks,
            "attempted": B * len(times), "failed": 0,
            "memory_peak_bytes": peak}


def group_ids(batch: int, total: int, prompt_len: int, slice_rows: int):
    """The step each token was served in: prompt tokens by prefill
    slice, then one step per decode position."""
    g = np.empty((batch, total), np.int32)
    g[:, :prompt_len] = (np.arange(batch) // slice_rows)[:, None]
    g[:, prompt_len:] = batch // slice_rows + np.arange(
        total - prompt_len)[None, :]
    return g
