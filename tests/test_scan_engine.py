"""The single-pass chunked scan engine (kernels/scan_engine, DESIGN §7).

Four layers of coverage, all in interpret mode (device-free):

  * engine unit tests — ``monoid_exscan`` across every elementwise
    monoid, the affine chunk scan/summary, ``block_combine`` edge
    shapes (widths ∤ 128, single row, bf16/int32) and the identity-
    valued padding;
  * the ONE-affine-definition regression: ``core.monoid.affine_combine``
    is the object every consumer imports, and the engine's affine
    instance is bit-identical to the XLA formulation built from it;
  * IR kernel accounting — ``Schedule.kernel_passes``/``kernel_launches``
    at the ISSUE acceptance point (ring p=64/S=8: fused halves the
    baseline's HBM passes at equal launches; fused-doubling scan_total:
    fused halves the launches);
  * the executor parity sweep (subprocess, 17 fake devices): the fused
    ``PallasExecutor`` is bit-identical to the SPMD executor AND the
    numpy simulator for p ∈ 2..17 across monoids, including the fused
    masked prep rounds of the segmented ring, the fused scan_reduce
    butterfly, and k-leaf mixed-dtype payloads batched per dtype group
    — with measured kernel stats equal to the IR prediction in both
    fused and baseline modes.
"""

import jax.numpy as jnp
import numpy as np
import pytest

from hypothesis import given, settings
from hypothesis import strategies as st

from helpers import run_with_devices

from repro.core import monoid as monoid_lib
from repro.core import schedule as schedule_lib
from repro.kernels import scan_engine


# ------------------- monoid_exscan: every elementwise monoid -------------


def _np_exscan(x, op, ident):
    out = np.empty_like(x)
    out[0] = ident
    for t in range(1, len(x)):
        out[t] = op(out[t - 1], x[t - 1])
    return out


@pytest.mark.parametrize("name,ident", [
    ("add", 0), ("max", np.iinfo(np.int32).min),
    ("min", np.iinfo(np.int32).max), ("xor", 0)])
def test_monoid_exscan_int_exact(name, ident):
    ops = {"add": np.add, "max": np.maximum, "min": np.minimum,
           "xor": np.bitwise_xor}
    rng = np.random.default_rng(hash(name) % 2**31)
    x = rng.integers(-1000, 1000, (512, 7)).astype(np.int32)
    got = scan_engine.monoid_exscan(jnp.asarray(x), name,
                                    block_rows=128, interpret=True)
    want = _np_exscan(x, ops[name], ident)
    np.testing.assert_array_equal(np.asarray(got), want)


def test_monoid_exscan_mul_float():
    rng = np.random.default_rng(3)
    x = rng.uniform(0.9, 1.1, (256, 5)).astype(np.float32)
    got = scan_engine.monoid_exscan(jnp.asarray(x), "mul",
                                    block_rows=64, interpret=True)
    want = np.ones_like(x)
    want[1:] = np.cumprod(x[:-1], axis=0)
    np.testing.assert_allclose(np.asarray(got), want, rtol=1e-5)


def test_monoid_exscan_rejects_structured_monoid():
    with pytest.raises(ValueError, match="not elementwise"):
        scan_engine.monoid_exscan(jnp.zeros((4, 4)), "affine",
                                  block_rows=4, interpret=True)


def test_chunked_scan_chunking_invariance():
    """Multi-chunk carry propagation == one big chunk, bitwise."""
    rng = np.random.default_rng(11)
    x = jnp.asarray(rng.integers(-50, 50, (256, 3)).astype(np.int64))
    one = scan_engine.monoid_exscan(x, "add", block_rows=256,
                                    interpret=True)
    many = scan_engine.monoid_exscan(x, "add", block_rows=32,
                                     interpret=True)
    np.testing.assert_array_equal(np.asarray(one), np.asarray(many))


@settings(max_examples=20, deadline=None)
@given(n=st.integers(min_value=1, max_value=300),
       d=st.integers(min_value=1, max_value=150),
       seed=st.integers(min_value=0, max_value=2**31 - 1))
def test_monoid_exscan_max_property(n, d, seed):
    rng = np.random.default_rng(seed)
    x = rng.integers(-1000, 1000, (n, d)).astype(np.int32)
    got = scan_engine.monoid_exscan(jnp.asarray(x), "max",
                                    block_rows=n, interpret=True)
    want = _np_exscan(x, np.maximum, np.iinfo(np.int32).min)
    np.testing.assert_array_equal(np.asarray(got), want)


# --------------- ONE affine definition + bit-identity (satellite) --------


def test_affine_combine_single_definition():
    """Every consumer binds the ONE core affine combine — the dedup
    this PR enforces (kernels, mamba, rwkv, AFFINE monoid)."""
    from repro.kernels import ssm_chunk_scan  # noqa: F401  (delegate)
    from repro.models import mamba, rwkv

    f = monoid_lib.affine_combine
    assert scan_engine._affine_combine is f
    assert mamba._affine is f
    assert rwkv._affine is f
    assert monoid_lib._affine_op is f  # back-compat alias


def test_affine_engine_bit_identical_to_xla_formulation():
    """The engine's affine instance computes the SAME recurrence as
    the XLA chunked formulation built from the same ``affine_combine``.

    Bit-identity is asserted on integer affine elements (a ∈ {0, 1}),
    where every ⊕ is exact — float32 can differ by a few ulps between
    in-kernel and host XLA fusion, so the float check is a tight
    allclose, not the dedup regression itself."""
    from jax import lax

    rng = np.random.default_rng(5)
    T, D = 64, 128
    a = jnp.asarray(rng.integers(0, 2, (T, D)).astype(np.int32))
    b = jnp.asarray(rng.integers(-99, 99, (T, D)).astype(np.int32))
    h0 = jnp.asarray(rng.integers(-99, 99, (1, D)).astype(np.int32))
    h, hf = scan_engine.affine_chunk_scan(a, b, h0, chunk=16,
                                          interpret=True)
    want = []
    cur = np.asarray(h0)
    for t in range(T):
        cur = np.asarray(a[t]) * cur + np.asarray(b[t])
        want.append(cur[0])
    np.testing.assert_array_equal(np.asarray(h), np.stack(want))
    np.testing.assert_array_equal(np.asarray(hf), want[-1][None])

    af = jnp.asarray(rng.uniform(0.8, 1.0, (T, D)).astype(np.float32))
    bf = jnp.asarray(rng.standard_normal((T, D)).astype(np.float32))
    hf0 = jnp.asarray(rng.standard_normal((1, D)).astype(np.float32))
    got, _ = scan_engine.affine_chunk_scan(af, bf, hf0, chunk=T,
                                           interpret=True)
    incl = lax.associative_scan(monoid_lib.affine_combine, (af, bf),
                                axis=0)
    _, ref = monoid_lib.affine_combine((jnp.ones_like(hf0), hf0), incl)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               rtol=2e-5, atol=2e-6)


def test_affine_chunk_summary_single_pass_matches_two_pass():
    """(A_total, B_total) from the carry's a-leaf == the old prod+scan
    two-traversal result."""
    rng = np.random.default_rng(6)
    T, D = 128, 64
    a = rng.uniform(0.7, 1.0, (T, D)).astype(np.float32)
    b = rng.standard_normal((T, D)).astype(np.float32)
    at, bt = scan_engine.affine_chunk_summary(
        jnp.asarray(a), jnp.asarray(b), chunk=32, interpret=True)
    np.testing.assert_allclose(np.asarray(at), np.prod(a, axis=0,
                                                       keepdims=True),
                               rtol=3e-4, atol=3e-4)
    h = np.zeros((1, D), np.float32)
    for t in range(T):
        h = a[t] * h + b[t]
    np.testing.assert_allclose(np.asarray(bt), h, rtol=3e-4, atol=3e-4)


# ----------- block_combine edge cases + identity padding (satellites) ----


@pytest.mark.parametrize("shape", [(1, 5), (3, 130), (7,), (2, 5, 9),
                                   (1, 1), (129,)])
@pytest.mark.parametrize("dtype", [np.int32, jnp.bfloat16])
def test_block_combine_edge_shapes(shape, dtype):
    """Widths ∤ 128, single-row and bf16/int32 payloads: the engine's
    tiling/padding never leaks into the truncated output."""
    rng = np.random.default_rng(int(np.prod(shape)))
    if dtype is np.int32:
        a = jnp.asarray(rng.integers(-99, 99, shape).astype(dtype))
        b = jnp.asarray(rng.integers(-99, 99, shape).astype(dtype))
    else:
        a = jnp.asarray(rng.standard_normal(shape), dtype=dtype)
        b = jnp.asarray(rng.standard_normal(shape), dtype=dtype)
    for op in (jnp.add, jnp.maximum, jnp.minimum):
        got = scan_engine.block_combine(a, b, op, interpret=True)
        assert got.dtype == a.dtype
        np.testing.assert_array_equal(np.asarray(got),
                                      np.asarray(op(a, b)))


def test_block_combine_masked_edge_shapes():
    rng = np.random.default_rng(9)
    for shape in [(1, 5), (3, 130), (129,)]:
        a = jnp.asarray(rng.integers(-99, 99, shape).astype(np.int32))
        b = jnp.asarray(rng.integers(-99, 99, shape).astype(np.int32))
        for keep in (False, True):
            got = scan_engine.block_combine(
                a, b, jnp.maximum, keep=jnp.asarray(keep),
                interpret=True)
            want = np.maximum(a, b) if keep else np.asarray(b)
            np.testing.assert_array_equal(np.asarray(got),
                                          np.asarray(want))


def test_leaf_identity_values():
    assert scan_engine.leaf_identity("add", np.int32) == 0
    assert scan_engine.leaf_identity("xor", np.int64) == 0
    assert scan_engine.leaf_identity("mul", np.float32) == 1
    assert scan_engine.leaf_identity("max", np.int32) == \
        np.iinfo(np.int32).min
    assert scan_engine.leaf_identity("min", np.int32) == \
        np.iinfo(np.int32).max
    assert scan_engine.leaf_identity("max", np.float32) == -np.inf
    assert scan_engine.leaf_identity("min", np.float32) == np.inf
    with pytest.raises(KeyError):
        scan_engine.leaf_identity("matmul", np.float32)


def test_pad_tile_uses_monoid_identity():
    """The pad lanes hold the monoid identity, not zeros — max/min/mul
    can never read garbage even if a caller stops truncating."""
    flat = jnp.asarray(np.arange(5, dtype=np.int32) - 100)
    for name, op in (("max", jnp.maximum), ("min", jnp.minimum)):
        pv = scan_engine._op_identity(op, np.int32)
        tiled, br = scan_engine._pad_tile(flat, pv, 256)
        assert tiled.shape == (1, scan_engine.LANE) and br == 1
        np.testing.assert_array_equal(np.asarray(tiled)[0, 5:],
                                      np.full(123, pv, np.int32))
    # unknown ops keep the legacy zero pad (hardening default)
    assert scan_engine._op_identity(lambda a, b: a, np.int32) == 0


def test_identity_padding_keeps_pad_lanes_inert():
    """identity ⊕ identity == identity through the whole kernel: the
    padded region of the OUTPUT tile is still the identity."""
    a = jnp.asarray(np.full(5, -7, np.int32))
    pv = scan_engine.leaf_identity("max", np.int32)
    out, = scan_engine._round_call(
        __import__("functools").partial(scan_engine._combine_kernel,
                                        jnp.maximum),
        [a, a], (pv, pv), 1, name="block_combine", interpret=True)
    np.testing.assert_array_equal(np.asarray(out),
                                  np.full(5, -7, np.int32))


# ------------------- tree-level batched round kernels --------------------


def _int_tree(rng, dtypes=(np.int64, np.int64, np.int32)):
    return {k: jnp.asarray(rng.integers(-999, 999, (n,)).astype(dt))
            for (k, n), dt in zip((("a", 16), ("b", 5), ("c", 7)),
                                  dtypes)}


def test_tree_combine_batches_dtype_groups():
    """Three leaves, two dtypes → per-leaf results identical to the
    plain op while the int64 pair shares one pallas_call."""
    rng = np.random.default_rng(21)
    lo, hi = _int_tree(rng), _int_tree(rng)
    m = monoid_lib.MAX
    got = scan_engine.tree_combine(m, lo, hi, interpret=True)
    for k in lo:
        np.testing.assert_array_equal(
            np.asarray(got[k]), np.maximum(np.asarray(lo[k]),
                                           np.asarray(hi[k])))
    for keep in (0, 1):
        got = scan_engine.tree_combine(m, lo, hi,
                                       keep=jnp.asarray(keep),
                                       interpret=True)
        for k in lo:
            want = np.maximum(np.asarray(lo[k]), np.asarray(hi[k])) \
                if keep else np.asarray(hi[k])
            np.testing.assert_array_equal(np.asarray(got[k]), want)


def test_tree_exchange_and_scan_reduce_both_sides():
    rng = np.random.default_rng(22)
    m = monoid_lib.ADD
    recv, w, prefix = (_int_tree(rng) for _ in range(3))
    for low in (0, 1):
        got = scan_engine.tree_exchange(m, recv, w, jnp.asarray(low),
                                        interpret=True)
        for k in recv:
            np.testing.assert_array_equal(
                np.asarray(got[k]),
                np.asarray(recv[k]) + np.asarray(w[k]))
        w2, p2 = scan_engine.tree_scan_reduce(
            m, recv, w, prefix, jnp.asarray(low), interpret=True)
        for k in recv:
            np.testing.assert_array_equal(
                np.asarray(w2[k]),
                np.asarray(recv[k]) + np.asarray(w[k]))
            want_p = np.asarray(prefix[k]) + np.asarray(recv[k]) \
                if low else np.asarray(prefix[k])
            np.testing.assert_array_equal(np.asarray(p2[k]), want_p)


def test_tree_hooks_decline_unserved_payloads():
    """MATMUL and non-pair affine payloads return None — the executor
    falls back to the plain XLA op."""
    m = monoid_lib.MATMUL
    x = jnp.zeros((4, 4))
    assert scan_engine.tree_combine(m, x, x, interpret=True) is None
    aff = monoid_lib.AFFINE
    bad = (jnp.zeros((3,)), jnp.zeros((4,)))  # shape-mismatched pair
    assert scan_engine.tree_combine(aff, bad, bad,
                                    interpret=True) is None
    assert scan_engine.tree_exchange(aff, bad, bad, jnp.asarray(1),
                                     interpret=True) is None
    assert scan_engine.tree_scan_reduce(aff, bad, bad, bad,
                                        jnp.asarray(1),
                                        interpret=True) is None


def test_affine_tree_hooks_match_core_op():
    """Integer affine elements (a ∈ {0, 1}): every ⊕ exact, so the
    fused kernels must reproduce the core op bitwise."""
    rng = np.random.default_rng(23)

    def pair():
        return (jnp.asarray(rng.integers(0, 2, (37,))
                            .astype(np.int32)),
                jnp.asarray(rng.integers(-99, 99, (37,))
                            .astype(np.int32)))

    m = monoid_lib.AFFINE
    recv, w, prefix = pair(), pair(), pair()
    for low in (0, 1):
        got = scan_engine.tree_exchange(m, recv, w, jnp.asarray(low),
                                        interpret=True)
        want = m.op(recv, w) if low else m.op(w, recv)
        for g, wnt in zip(got, want):
            np.testing.assert_array_equal(np.asarray(g),
                                          np.asarray(wnt))
        w2, p2 = scan_engine.tree_scan_reduce(
            m, recv, w, prefix, jnp.asarray(low), interpret=True)
        want_w = m.op(recv, w) if low else m.op(w, recv)
        want_p = m.op(recv, prefix) if low else prefix
        for g, wnt in zip((*w2, *p2), (*want_w, *want_p)):
            np.testing.assert_array_equal(np.asarray(g),
                                          np.asarray(wnt))


# ------------------- IR kernel accounting (acceptance point) -------------


def test_ring_p64_s8_fused_halves_hbm_passes():
    """The ISSUE acceptance gate, off the IR alone: p=64/S=8 ring —
    69 launches either way (the rolled round table launches once per
    round), but fused does each prep in ONE sweep where baseline pays
    a combine launch plus a select sweep: 138 → 69 passes, exactly
    2×."""
    sched = schedule_lib.build_ring(64, 8)
    assert sched.kernel_launches(True, fused=True) == 69
    assert sched.kernel_launches(True, fused=False) == 69
    fused = sched.kernel_passes(True, fused=True)
    base = sched.kernel_passes(True, fused=False)
    assert (fused, base) == (69, 138)
    assert base >= 2 * fused


def test_scan_total_p64_fused_halves_launches():
    """fused-doubling at p=64: 6 scan_reduce rounds; fused batches the
    (P, T) register pair into ONE pallas_call per round (6L/6P) where
    the commutative baseline pays two launches (12L/12P) and the
    non-commutative one 3 launches + 2 select sweeps (18L/30P)."""
    sched = schedule_lib.build_scan_total(64)
    assert (sched.kernel_launches(True, fused=True),
            sched.kernel_passes(True, fused=True)) == (6, 6)
    assert (sched.kernel_launches(True, fused=False),
            sched.kernel_passes(True, fused=False)) == (12, 12)
    assert (sched.kernel_launches(False, fused=True),
            sched.kernel_passes(False, fused=True)) == (6, 6)
    assert (sched.kernel_launches(False, fused=False),
            sched.kernel_passes(False, fused=False)) == (18, 30)


def test_plan_carries_kernel_passes():
    from repro.core.scan_api import ScanSpec, plan

    pl = plan(ScanSpec(kind="exclusive", algorithm="ring", segments=8),
              p=64, nbytes=2048)
    assert pl.kernel_passes == \
        pl.schedule().kernel_passes(monoid_lib.ADD.commutative)
    rows = pl.explain()
    assert all("kernel_passes" in r for r in rows)
    chosen = [r for r in rows if r["chosen"]]
    assert chosen and chosen[0]["kernel_passes"] == pl.kernel_passes


def test_gamma_pass_pricing_opt_in():
    """gamma_pass=0 (the default) prices passes at zero — bit-identical
    costs to the historical model; nonzero gamma_pass separates fused
    from baseline pass budgets that op counts cannot distinguish."""
    from repro.core.scan_api import CostModel

    kw = dict(hops=10, serial_bytes=1e4, ops=20, payload_bytes=256)
    base = CostModel()
    assert base.cost(**kw) == base.cost(**kw, passes=69)
    priced = CostModel(gamma_pass=1e-9)
    assert priced.cost(**kw, passes=138) - priced.cost(**kw, passes=69) \
        == pytest.approx(1e-9 * 69 * 256)


def test_schedule_features_optional_pass_regressor():
    from repro.core import tune

    sched = schedule_lib.build_ring(64, 8)
    three = tune.schedule_features(sched, 2048, commutative=True)
    assert len(three) == 3
    four = tune.schedule_features(sched, 2048, commutative=True,
                                  passes=True)
    assert four[:3] == three
    assert four[3] == sched.kernel_passes(True) * (2048 // 8)


# ------------- executor parity sweep: p ∈ 2..17, all executors -----------


_SWEEP = """
import jax, numpy as np
from jax.sharding import Mesh, PartitionSpec as P
from jax import shard_map
from repro.core import monoid as monoid_lib
from repro.core.scan_api import ScanSpec, plan
from repro.core.schedule import (PallasExecutor, SPMDExecutor,
                                 SimulatorExecutor, collect_stats)

devices = jax.devices()
sim = SimulatorExecutor()
checked = 0


def run(p, spec, payload, m, in_specs, out_specs, exact, atol=0.0):
    global checked
    mesh = Mesh(np.array(devices[:p]).reshape(p), ("x",))
    pl = plan(spec, p=p, nbytes=sum(
        np.asarray(v).nbytes for v in jax.tree.leaves(payload)) // p)
    sched = pl.schedule()
    ref_spmd = jax.jit(shard_map(
        lambda v: SPMDExecutor("x").execute(sched, v, m), mesh=mesh,
        in_specs=in_specs, out_specs=out_specs))(payload)
    ref_sim = sim.execute(sched, payload, m)
    outs = {}
    for fused in (True, False):
        ex = PallasExecutor("x", interpret=True, fused=fused)
        fn = jax.jit(shard_map(
            lambda v: ex.execute(sched, v, m), mesh=mesh,
            in_specs=in_specs, out_specs=out_specs, check_vma=False))
        with collect_stats() as st:
            jax.make_jaxpr(fn)(payload)
        assert st.kernel_launches == sched.kernel_launches(
            m.commutative, fused=fused), (spec, fused, "launches")
        assert st.hbm_passes == sched.kernel_passes(
            m.commutative, fused=fused), (spec, fused, "passes")
        if fused:
            assert st.hbm_passes == pl.kernel_passes, (spec, "plan")
        outs[fused] = fn(payload)
    for ref in (ref_spmd, ref_sim):
        for fused in (True, False):
            for g, w in zip(jax.tree.leaves(outs[fused]),
                            jax.tree.leaves(ref)):
                g, w = np.asarray(g), np.asarray(w)
                if exact:
                    assert np.array_equal(g, w), (spec, fused)
                else:
                    np.testing.assert_allclose(g, w, rtol=1e-12,
                                               atol=atol)
    checked += 1


rng = np.random.default_rng(0)
ADD, MAX, AFF = monoid_lib.ADD, monoid_lib.MAX, monoid_lib.AFFINE
for p in range(2, 18):
    x = rng.integers(-(1 << 40), 1 << 40, (p, 16)).astype(np.int64)
    for alg in ("123", "ring"):
        spec = ScanSpec(kind="exclusive", algorithm=alg, axis_name="x")
        run(p, spec, x, ADD, P("x"), P("x"), exact=True)
    run(p, ScanSpec(kind="exclusive", algorithm="123", monoid="max",
                    axis_name="x"), x, MAX, P("x"), P("x"), exact=True)
    a = rng.uniform(0.5, 1.5, (p, 8))
    b = rng.standard_normal((p, 8))
    run(p, ScanSpec(kind="exclusive", algorithm="native",
                    monoid="affine", axis_name="x"), (a, b), AFF,
        P("x"), P("x"), exact=False, atol=1e-12)

# fused scan_reduce butterfly (exscan+allreduce registers) at 2-powers,
# including the non-commutative affine side-select path
for p in (4, 8, 16):
    x = rng.integers(-(1 << 40), 1 << 40, (p, 16)).astype(np.int64)
    run(p, ScanSpec(kind="scan_total", algorithm="fused_doubling",
                    axis_name="x"), x, ADD, P("x"), P("x"),
        exact=True)
    a = rng.uniform(0.5, 1.5, (p, 8))
    b = rng.standard_normal((p, 8))
    run(p, ScanSpec(kind="scan_total", algorithm="fused_doubling",
                    monoid="affine", axis_name="x"), (a, b), AFF,
        P("x"), P("x"), exact=False, atol=1e-12)

# k-slot batching: mixed-dtype payload tree, masked ring preps included
tree = {"a": rng.integers(-99, 99, (8, 16)).astype(np.int64),
        "b": rng.integers(-99, 99, (8, 5)).astype(np.int64),
        "c": rng.integers(-99, 99, (8, 7)).astype(np.int32)}
for alg, S in (("123", None), ("ring", 4)):
    spec = ScanSpec(kind="exclusive", algorithm=alg, segments=S,
                    axis_name="x")
    run(8, spec, tree, ADD, P("x"), P("x"), exact=True)

print("OK engine sweep", checked)
"""


def test_engine_parity_sweep_p2_to_17():
    """Fused PallasExecutor == SPMD == simulator for p ∈ 2..17 across
    monoids (bitwise for int64; affine ≤1e-12), with measured kernel
    launch/pass counts equal to the IR prediction in BOTH modes."""
    out = run_with_devices(_SWEEP, 17)
    # 16 p-values x 4 specs + 3 scan_total p's x 2 + 2 tree cases
    assert "OK engine sweep 72" in out
