"""Smoke run of the exscan library and its MoE consumer on a TPU.

    python chip_smoke.py             # one chip: kernels + granite-moe serve
    python chip_smoke.py --chips 4   # four chips: the p=4 collectives and
                                     # qwen2-moe expert-parallel serve

One process drives every chip it uses.  Each phase prints one line with
its result and wall seconds; any failure raises, so the script exits
non-zero before its last line.  The last line of a passing run is

    {"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}

There is no CPU path: where JAX finds no TPU the script exits non-zero,
and every kernel call passes ``interpret=INTERPRET`` (False) explicitly,
so no kernel falls back to the Pallas interpreter.  Parameters and
inputs are drawn from fixed seeds.  The module-level sizes are the
deployment widths; ``tests/test_chip_smoke.py`` shrinks them to
rehearse the same phases on CPU.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

REPO = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(REPO, "src"))

SEED = 0
PLATFORM = "tpu"  # the only platform the script accepts
INTERPRET = False  # kernels run compiled

# kernel widths: scan payloads, granite-moe routing (40 experts, top-8)
ROWS, COLS_INT, COLS_F32 = 4096, 128, 1024
MOE_T, MOE_K, MOE_E = 4096, 8, 40
COUNTS = 48  # granite-moe's 40 experts padded to 48: one counts vector
CARRY = (1 << 20) // 4  # a 1 MiB f32 / int32 payload

SERVE_ONE = ["--arch", "granite-moe-3b-a800m", "--batch", "4",
             "--prompt-len", "128", "--gen", "16"]
SERVE_FOUR = ["--arch", "qwen2-moe-a2.7b", "--model-mesh", "4",
              "--batch", "4", "--prompt-len", "64", "--gen", "8"]
SERVE_PINS = ("123", "two_op", "native")

ALGORITHMS = ("123", "two_op", "1doubling", "native", "ring", "auto")
OFFSETS = 60  # int32 MoE dispatch offsets


def phase(name, fn):
    """Run one phase and print its result line with wall seconds."""
    t0 = time.perf_counter()
    detail = fn()
    print(f"[{name}] ok {time.perf_counter() - t0:.3f}s {detail}",
          flush=True)


def _compare(got, want, *, rtol=None, atol=None) -> str:
    """Exact for integers, allclose for floats; returns the max error."""
    import numpy as np

    got, want = np.asarray(got), np.asarray(want)
    assert got.shape == want.shape, (got.shape, want.shape)
    if np.issubdtype(want.dtype, np.integer):
        np.testing.assert_array_equal(got, want)
        return "exact"
    assert np.isfinite(got).all()
    np.testing.assert_allclose(got, want, rtol=rtol, atol=atol)
    return f"max_abs_err={float(np.max(np.abs(got - want))):.3g}"


# ---------------------------------------------------------------------------
# One chip
# ---------------------------------------------------------------------------


def kernel_phases():
    """Every kernel at deployment widths against ``kernels/ref.py`` (the
    round hooks against the plain monoid ops they replace)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.core import monoid as monoid_lib
    from repro.kernels import ops, ref, scan_engine as se

    rng = np.random.default_rng(SEED)
    kw = dict(interpret=INTERPRET)

    def exscan_int():
        x = jnp.asarray(rng.integers(-1000, 1000, (ROWS, COLS_INT)),
                        jnp.int32)
        return _compare(ops.exscan(x, **kw), ref.exscan_ref(x))

    def exscan_f32():
        x = jnp.asarray(rng.standard_normal((ROWS, COLS_F32)), jnp.float32)
        return _compare(ops.exscan(x, **kw), ref.exscan_ref(x),
                        rtol=1e-5, atol=1e-4)

    a = jnp.asarray(rng.uniform(0.8, 1.0, (ROWS, COLS_F32)), jnp.float32)
    b = jnp.asarray(rng.standard_normal((ROWS, COLS_F32)), jnp.float32)
    h0 = jnp.asarray(rng.standard_normal(COLS_F32), jnp.float32)
    ssm_ref = jax.jit(ref.ssm_scan_ref)

    def ssm_scan():
        h, hf = ops.ssm_scan(a, b, h0, **kw)
        hr, hfr = ssm_ref(a, b, h0)
        _compare(hf, hfr, rtol=2e-4, atol=2e-4)
        return _compare(h, hr, rtol=2e-4, atol=2e-4)

    def ssm_summary():
        # (A, B) is the affine element: h_out = A * h_in + B
        at, bt = ops.ssm_chunk_summary(a, b, **kw)
        _, hf = ssm_ref(a, b, h0)
        return _compare(at * h0 + bt, hf, rtol=3e-4, atol=3e-4)

    def moe_routing():
        # top-K of E distinct experts per token, as a router picks them
        assign = np.argsort(rng.random((MOE_T, MOE_E)), axis=1)[:, :MOE_K]
        assign = jnp.asarray(assign, jnp.int32)
        pos, counts = ops.moe_routing(assign, MOE_E, **kw)
        pr, cr = jax.jit(ref.moe_routing_ref, static_argnums=1)(assign,
                                                                MOE_E)
        _compare(counts, cr)
        return _compare(pos, pr)

    add = monoid_lib.get("add")
    affine = monoid_lib.get("affine")

    def round_hooks(lo, hi, **tol):
        _compare(se.block_combine(lo, hi, jnp.add, **kw), lo + hi, **tol)
        for flag in (True, False):
            side = jnp.asarray(flag)
            _compare(se.tree_combine(add, lo, hi, keep=side, **kw),
                     jnp.where(side, lo + hi, hi), **tol)
            _compare(se.tree_exchange(add, lo, hi, side, **kw), lo + hi,
                     **tol)
            w, p = se.tree_scan_reduce(add, lo, hi, lo - hi, side, **kw)
            _compare(w, lo + hi, **tol)
            _compare(p, jnp.where(side, lo + (lo - hi), lo - hi), **tol)
        return "block_combine tree_combine tree_exchange tree_scan_reduce"

    def rounds_int():
        lo = jnp.asarray(rng.integers(0, 512, COUNTS), jnp.int32)
        hi = jnp.asarray(rng.integers(0, 512, COUNTS), jnp.int32)
        return round_hooks(lo, hi)

    def rounds_f32():
        lo = jnp.asarray(rng.standard_normal(CARRY), jnp.float32)
        hi = jnp.asarray(rng.standard_normal(CARRY), jnp.float32)
        detail = round_hooks(lo, hi, rtol=1e-6, atol=1e-6)
        # the non-commutative affine pair: the side bit picks the order
        recv, w = (jnp.abs(lo), hi), (jnp.abs(hi), lo)
        for flag in (True, False):
            got = se.tree_exchange(affine, recv, w, jnp.asarray(flag), **kw)
            want = affine.op(recv, w) if flag else affine.op(w, recv)
            for g, e in zip(got, want):
                _compare(g, e, rtol=1e-6, atol=1e-6)
        return detail + " affine-exchange"

    phase(f"kernel exscan int32[{ROWS},{COLS_INT}]", exscan_int)
    phase(f"kernel exscan f32[{ROWS},{COLS_F32}]", exscan_f32)
    phase(f"kernel ssm_scan f32[{ROWS},{COLS_F32}]", ssm_scan)
    phase(f"kernel ssm_chunk_summary f32[{ROWS},{COLS_F32}]", ssm_summary)
    phase(f"kernel moe_routing T={MOE_T} K={MOE_K} E={MOE_E}", moe_routing)
    phase(f"kernel round hooks int32[{COUNTS}]", rounds_int)
    phase(f"kernel round hooks f32[{CARRY}]", rounds_f32)


def serve_phase(argv, label):
    """Serve a few requests through the driver's entry point; returns
    the generated tokens."""
    import jax
    import numpy as np

    from repro import configs
    from repro.launch.serve import serve

    out = {}

    def run():
        toks = serve(argv)
        get = configs.get_smoke if "--smoke" in argv else configs.get
        vocab = get(argv[argv.index("--arch") + 1]).vocab
        shape = (int(argv[argv.index("--batch") + 1]),
                 int(argv[argv.index("--gen") + 1]))
        assert toks.shape == shape, (toks.shape, shape)
        assert ((toks >= 0) & (toks < vocab)).all(), toks
        out["tokens"] = toks
        peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
                 for d in jax.devices()]
        return (f"tokens[0]={np.asarray(toks[0]).tolist()} "
                f"peak_bytes_in_use={peaks}")

    phase(label, run)
    return out["tokens"]


def one_chip():
    kernel_phases()
    serve_phase(SERVE_ONE, "serve " + " ".join(SERVE_ONE))


# ---------------------------------------------------------------------------
# Four chips
# ---------------------------------------------------------------------------


def collective_phase(mesh, x, alg, with_total, executor):
    """One scan()/scan_with_total() program at p = mesh size vs the
    oracle, its measured stats equal to the plan's IR prediction."""
    import jax
    import numpy as np
    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    from repro.core import oracle, schedule as schedule_lib
    from repro.core.scan_api import ScanSpec, plan, scan, scan_with_total

    p = x.shape[0]
    pallas = executor == "pallas"
    ex = (schedule_lib.PallasExecutor("x", interpret=INTERPRET) if pallas
          else schedule_lib.SPMDExecutor("x"))
    spec = ScanSpec(kind="exclusive", monoid="add", algorithm=alg,
                    axis_name="x")
    fn = scan_with_total if with_total else scan
    f = jax.jit(shard_map(
        lambda v: fn(v, spec, executor=ex), mesh=mesh, in_specs=P("x"),
        out_specs=(P("x"), P("x")) if with_total else P("x"),
        # shard_map has no replication rule for pallas_call
        check_vma=not pallas))
    with schedule_lib.collect_stats() as st:
        compiled = f.lower(x).compile()
    got = compiled(x)
    prefix, total = got if with_total else (got, None)
    want = oracle.exscan_reference(list(x), np.add, np.zeros_like(x[0]))
    np.testing.assert_array_equal(np.asarray(prefix), np.stack(want))
    if with_total:
        np.testing.assert_array_equal(
            np.asarray(total), np.broadcast_to(x.sum(0), x.shape))

    kind = "scan_total" if with_total else "exclusive"
    pl = plan(spec.over("x", kind=kind), p=p, nbytes=x[0].nbytes)
    launches = (pl.schedule().kernel_launches(True, fused=True)
                if pallas else 0)
    passes = pl.kernel_passes if pallas else 0
    measured = (st.rounds, st.op_applications, st.kernel_launches,
                st.hbm_passes)
    predicted = (pl.rounds, pl.op_applications, launches, passes)
    assert measured == predicted, (measured, predicted)
    if launches and not INTERPRET:
        # the stats follow the IR; the program must hold the kernels
        # too (a hook that fell back to jnp would leave none)
        assert "tpu_custom_call" in compiled.as_text(), "no kernel"
    seg = f" S={pl.segments}" if pl.segments > 1 else ""
    return (f"plan={pl.algorithm}{seg} profile={pl.cost_model_source} "
            f"rounds={st.rounds} ops={st.op_applications} "
            f"launches={st.kernel_launches} passes={st.hbm_passes}")


def four_chips():
    import jax
    import numpy as np
    from jax.sharding import Mesh

    p = 4
    mesh = Mesh(np.array(jax.devices()[:p]), ("x",))
    rng = np.random.default_rng(SEED)
    for m in (OFFSETS, CARRY):
        x = rng.integers(0, 1 << 16, (p, m)).astype(np.int32)
        for alg in ALGORITHMS:
            for with_total in (False, True):
                for executor in ("spmd", "pallas"):
                    name = "scan_with_total" if with_total else "scan"
                    phase(f"p={p} {name} {alg} {executor} int32[{m}]",
                          lambda: collective_phase(mesh, x, alg,
                                                   with_total, executor))
    tokens = {}
    for alg in SERVE_PINS:
        argv = SERVE_FOUR + ["--exscan", alg]
        tokens[alg] = serve_phase(argv, "serve " + " ".join(argv))
    ref = tokens[SERVE_PINS[0]]
    phase(f"serve tokens identical across --exscan {'/'.join(SERVE_PINS)}",
          lambda: _same_tokens(tokens, ref))


def _same_tokens(tokens, ref):
    for alg, toks in tokens.items():
        assert (toks == ref).all(), (alg, toks, ref)
    return f"tokens[0]={ref[0].tolist()}"


# ---------------------------------------------------------------------------


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="1: kernels and one-chip serve; 4: only the "
                         "cross-chip collectives and expert-parallel "
                         "serve")
    args = ap.parse_args(argv)

    import jax

    # without the repository around it the script stops here, before
    # JAX looks for a device
    from repro.launch import mesh as mesh_lib

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != PLATFORM:
        sys.exit(f"chip_smoke: no TPU found (JAX reports platform "
                 f"{dev.platform!r})")
    if len(devices) < args.chips:
        sys.exit(f"chip_smoke: --chips {args.chips} needs {args.chips} "
                 f"devices, JAX reports {len(devices)}")

    cache = mesh_lib.use_compile_cache()
    print(f"[device] platform={dev.platform} kind={dev.device_kind} "
          f"count={len(devices)} compile_cache={cache} "
          f"cost_profile={mesh_lib.current_profile().source}", flush=True)
    if args.chips == 1:
        one_chip()
    else:
        four_chips()
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}), flush=True)


if __name__ == "__main__":
    main()
