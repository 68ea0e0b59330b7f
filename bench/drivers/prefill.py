"""Closed loop of prefill (traffic kind ``prefill``).

Set-up draws the weights and a pool of ``pool`` distinct batches of
seeded prompts, and prefills the first once.  The window prefills one
batch after another from the pool, ``last_only`` into one reused cache
(recurrent states zeroed first: each batch is new sequences).  It keeps
about ``AHEAD_S`` seconds of batches dispatched ahead of the one it
waits for (the depth from a warm batch timed in set-up), so that the
chip stays fed while the host stands still; the sampled tokens are read
only after the window.  When the window's time is up nothing more is
sent, every batch sent is waited for, and the clock is read after that
wait: all of them count, over all of that time.

Correctness: the cache the window left holds the newest served batch.
After the window its sequence axes are widened by ``continue_steps``
positions and greedy decode runs on from it for that many steps, so
what the timed prefill wrote into the cache is read.  That batch, with
its continued tokens, and ``compare_batches - 1`` other served batches
drawn from the seed go through the configuration's float32 reference
(the prompt is one step, each continued token one more); every compared
token is judged by the gap between the reference's best logit and its
own (``modelcell.compare``).
"""

from __future__ import annotations

import collections
import gc
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

from bench import flops, modelcell

AHEAD_S = 6.0  # seconds of batches in flight beyond the one waited for


def drive(run):
    cell, seed = run.cell, run.seed
    tr, conf = cell.traffic, cell.config
    B, S, n_pool = tr["batch"], tr["prompt_len"], tr["pool"]
    with run.span("setup"):
        model, rules = modelcell.build(conf, run.devices)
        params = modelcell.init_params(model, rules, seed,
                                       conf.get("weights"))
        cache = modelcell.init_cache(model, rules, B, S)
        axes = modelcell.seq_axes(model)
        rng = np.random.default_rng(seed)
        pool_np = rng.integers(1, model.cfg.vocab, (n_pool, B, S)).astype(
            np.int32)
        pool = [jnp.asarray(p) for p in pool_np]

        def prefill(params, cache, toks):
            cache = modelcell.fresh(cache, axes)
            logits, cache = model.serve_step(params, cache, toks, 0,
                                             last_only=True)
            return jnp.argmax(logits[:, -1], -1).astype(jnp.int32), cache

        fn = jax.jit(prefill, donate_argnums=(1,))
        tok, cache = fn(params, cache, pool[0])
        tok.block_until_ready()
        t0 = time.perf_counter()
        tok, cache = fn(params, cache, pool[1 % n_pool])
        tok.block_until_ready()
        depth = max(1, round(AHEAD_S / (time.perf_counter() - t0)))

    served = []

    def dispatch():
        nonlocal cache
        i = len(served) % n_pool
        with run.span("step_dispatch"):
            tok, cache = fn(params, cache, pool[i])
        served.append((i, tok))
        return tok

    w0 = run.window_begin()
    in_flight = collections.deque()
    while time.perf_counter() - w0 < run.seconds:
        in_flight.append(dispatch())
        if len(in_flight) > depth:
            with run.span("sample_sync"):
                in_flight.popleft().block_until_ready()
    with run.span("sample_sync"):
        for tok in in_flight:
            tok.block_until_ready()
    window_s = time.perf_counter() - w0
    n_window = len(served)
    if run.trace:
        with run.traced():
            for _ in range(tr["trace_batches"]):
                tok = dispatch()
                with run.span("sample_sync"):
                    tok.block_until_ready()
        run.facts.update(traced_steps=tr["trace_batches"])

    m = conf["model"]
    tp = model.mesh.shape["model"]
    run.facts.update(
        window_s=window_s, steps=n_window, model=m,
        model_flops=n_window * flops.prefill_flops(m, B, S))
    if model.cfg.n_experts:
        run.facts["routing_shape"] = (B * S // tp, m["top_k"],
                                      -(-model.cfg.n_experts // 16) * 16)
    metrics = {"prefill_tok_s": B * S * n_window / window_s}
    peak = modelcell.peak_bytes(run.devices)

    last = served[-1][0]
    cont = continue_decode(model, params, cache, axes, served[-1][1], S,
                           tr["continue_steps"])
    shapes = modelcell.served_shapes(model)
    newest = {j: np.asarray(t) for j, t in served}
    del params, cache, fn, pool, served
    gc.collect()

    others = sorted(set(newest) - {last})
    picks = np.random.default_rng(seed).choice(
        others, size=min(tr["compare_batches"] - 1, len(others)),
        replace=False)
    run.facts["compared"] = [compared_item(pool_np[last], newest[last],
                                           cont)] + [
        compared_item(pool_np[j], newest[j]) for j in picks]
    t0 = time.perf_counter()
    checks = modelcell.compare(run.facts["compared"], conf, shapes, seed,
                               tp, run.devices[0])
    n_cmp = sum(it[3].size for it in run.facts["compared"])
    print(f"reference {time.perf_counter() - t0:.1f} s over {n_cmp} "
          f"compared tokens", file=sys.stderr, flush=True)
    return {"metrics": metrics, "checks": checks,
            "attempted": n_window * B, "failed": 0,
            "memory_peak_bytes": peak}


def continue_decode(model, params, cache, axes, first, start: int,
                    steps: int):
    """Greedy decode of ``steps`` tokens from the served ``cache`` (its
    sequence axes, ``axes`` as ``modelcell.seq_axes`` gives them,
    widened to make room), feeding ``first`` at position ``start``:
    (B, steps) of the tokens chosen."""
    widen = jax.jit(lambda c: modelcell.widen(c, axes, steps))

    def step(params, cache, tok, pos):
        logits, cache = model.decode_step(params, cache, tok[:, None], pos)
        return jnp.argmax(logits[:, -1], -1).astype(jnp.int32), cache

    step = jax.jit(step, donate_argnums=(1,))
    cache = widen(cache)
    tok, out = first, []
    for i in range(steps):
        tok, cache = step(params, cache, tok, start + i)
        out.append(tok)
    return np.stack([np.asarray(t) for t in out], 1)


def compared_item(prompt, served_last, cont=None):
    """(tokens, group ids, positions, served) for
    ``modelcell.compare``: the prompt's last position with the token
    served there, and with ``cont`` the continued tokens after it, each
    its own step."""
    B, S = prompt.shape
    if cont is None:
        return (prompt, np.zeros((B, S), np.int32), np.asarray([S - 1]),
                served_last[:, None])
    n = cont.shape[1]
    tokens = np.concatenate([prompt, served_last[:, None], cont[:, :-1]], 1)
    groups = np.concatenate([np.zeros((B, S), np.int32), np.broadcast_to(
        np.arange(1, n + 1, dtype=np.int32), (B, n))], 1)
    return (tokens, groups, np.arange(S - 1, S + n),
            np.concatenate([served_last[:, None], cont], 1))
