"""Batched serving driver: continuous-batching decode loop.

Usage:
    PYTHONPATH=src python -m repro.launch.serve --arch llama3-8b --smoke \
        --batch 4 --prompt-len 32 --gen 32

Implements the production serving shape: one prefill (writes the KV /
state cache) followed by batched single-token decode steps, with greedy
sampling and per-request completion tracking.  The same ``serve_step``
is what the decode_* dry-run cells lower at the 512-chip meshes.
"""

from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro import configs
from repro.core.scan_api import ScanSpec
from repro.launch import mesh as mesh_lib
from repro.models.model import Model
from repro.serve.metrics import percentile
from repro.sharding import rules as rules_lib


def serve(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--gen", type=int, default=32)
    ap.add_argument("--data-mesh", type=int, default=1)
    ap.add_argument("--model-mesh", type=int, default=1)
    ap.add_argument("--exscan", default="auto",
                    choices=["auto", "123", "1doubling", "two_op",
                             "native", "ring"])
    args = ap.parse_args(argv)
    for name in ("batch", "prompt_len", "gen", "data_mesh", "model_mesh"):
        if getattr(args, name) < 1:
            ap.error(f"--{name.replace('_', '-')} must be >= 1, "
                     f"got {getattr(args, name)}")

    get = configs.get_smoke if args.smoke else configs.get
    cfg = get(args.arch, scan=ScanSpec(kind="exclusive",
                                       algorithm=args.exscan))
    if cfg.encoder_only:
        raise SystemExit("encoder-only arch has no decode loop")
    mesh = mesh_lib.make_host_mesh(args.data_mesh, args.model_mesh)
    model = Model(cfg, mesh)
    # drawn under jit straight into their shardings: eager init would
    # place every tensor on device 0 first
    params = jax.jit(model.init_params, out_shardings=model.param_shardings(
        rules_lib.rules_for(cfg)))(jax.random.PRNGKey(0))

    B, P, G = args.batch, args.prompt_len, args.gen
    rng = np.random.default_rng(0)
    prompts = jnp.asarray(rng.integers(1, cfg.vocab, (B, P)), jnp.int32)

    prefill = jax.jit(lambda p, c, t: model.serve_step(
        p, c, t, 0, last_only=True))
    decode = jax.jit(model.decode_step)

    with jax.set_mesh(mesh):
        cache = model.init_cache(B, P + G)
        # the first call of each step compiles: run both once untimed
        t0 = time.time()
        warm_logits, warm_cache = prefill(params, cache, prompts)
        warm_tok = jnp.argmax(warm_logits[:, -1], axis=-1).astype(jnp.int32)
        jax.block_until_ready(decode(params, warm_cache, warm_tok[:, None], P))
        t_compile = time.time() - t0
        del warm_logits, warm_cache, warm_tok

        t0 = time.time()
        logits, cache = prefill(params, cache, prompts)
        next_tok = jnp.argmax(logits[:, -1], axis=-1).astype(jnp.int32)
        jax.block_until_ready(next_tok)
        t_prefill = time.time() - t0

        generated = [next_tok]
        step_s = []
        for i in range(G - 1):
            t0 = time.time()
            logits, cache = decode(params, cache, next_tok[:, None], P + i)
            next_tok = jnp.argmax(logits[:, -1], axis=-1).astype(jnp.int32)
            jax.block_until_ready(next_tok)
            step_s.append(time.time() - t0)
            generated.append(next_tok)
        t_decode = sum(step_s)

    out = np.stack([np.asarray(t) for t in generated], axis=1)
    print(f"compile + warm-up (prefill, decode): {t_compile:.1f} s")
    print(f"prefill {P} tokens x {B} reqs: {t_prefill*1e3:.1f} ms")
    if G == 1:
        # the prompt's last-token argmax IS the only generated token —
        # there are no decode steps, so no decode rate exists to report
        print("decode: 0 steps (--gen 1 generates the prefill "
              "token only)")
    else:
        tok_s = B * (G - 1) / t_decode if t_decode > 0 else float("inf")
        print(f"decode {G-1} steps x {B} reqs: {t_decode*1e3:.1f} ms "
              f"({tok_s:.1f} tok/s)")
        print(f"decode step latency: p50 {percentile(step_s, 50)*1e3:.2f} "
              f"ms, p99 {percentile(step_s, 99)*1e3:.2f} ms")
    print(f"first request tokens: {out[0][:16]}")
    return out


if __name__ == "__main__":
    mesh_lib.use_compile_cache()
    serve()
