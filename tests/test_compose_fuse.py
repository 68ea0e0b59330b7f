"""Composition/fusion tests (ISSUE-3): every plan — single-axis,
multi-axis (composed into ONE axis-annotated schedule) and fused —
lowers to one executable Schedule.

Covers the acceptance criteria: the composed multi-axis schedule is
bit-identical to the legacy three-sub-plan execution at p in 2..17
(simulator), executable by all three executors with simulator-measured
stats matching the plan's predictions; ``fused_scan`` of k small
same-axis exscans equals k independent scans while using the
single-scan round count; the fused exscan+allreduce ("scan_total")
returns (prefix, total) in the allreduce's round count at power-of-two
p; and the plan cache reports hits for repeated ``plan()`` calls.
"""

import numpy as np
import pytest

from hypothesis import given, settings
from hypothesis import strategies as hst

from helpers import run_with_devices

from repro.core import monoid as monoid_lib
from repro.core import schedule as schedule_lib
from repro.core.scan_api import (
    ScanSpec, algorithms, plan, plan_cache_clear, plan_cache_info,
    plan_fused)
from repro.core.schedule import (
    SimulatorExecutor, collect_stats, compose, fuse, make_layout,
    pack_payloads, unpack_payloads)


def _exclusive_ref(x):
    ref = np.zeros_like(x)
    ref[1:] = np.cumsum(x[:-1], axis=0)
    return ref


# ---------------------------------------------------------------------------
# Composed multi-axis schedules == the legacy three-sub-plan execution
# ---------------------------------------------------------------------------


def _legacy_subplan_execute(pl, x, m):
    """The pre-refactor multi-axis execution: run the three sub-plans'
    schedules separately (inner exscan / minor allreduce per major
    group, outer exscan of totals across groups) plus the combining ⊕
    — the reference the composed single schedule must reproduce
    bit-for-bit."""
    sim = SimulatorExecutor()
    inner_pl, reduce_pl, outer_pl = pl.sub_plans
    p_out, p_in = outer_pl.p, inner_pl.p
    grp = x.reshape(p_out, p_in, *x.shape[1:])
    op = monoid_lib.NUMPY_OPS[m.name]
    inner = np.stack([sim.execute(inner_pl.schedule(), grp[g], m)
                      for g in range(p_out)])
    total = np.stack([sim.execute(reduce_pl.schedule(), grp[g], m)
                      for g in range(p_out)])
    # outer exscan runs on the (replicated) minor-axis totals: one
    # value per major group (take minor rank 0's copy)
    outer = sim.execute(outer_pl.schedule(), total[:, 0], m)
    combined = op(outer[:, None], inner)
    return combined.reshape(x.shape)


def test_composed_bit_identical_to_legacy_subplans():
    sim = SimulatorExecutor()
    for p_in in range(2, 18):
        for p_out in (2, 3):
            p = p_out * p_in
            x = (np.arange(p * 4, dtype=np.int64).reshape(p, 4) ** 2
                 % 100003)
            pl = plan(ScanSpec(kind="exclusive", algorithm="auto",
                               axis_name=("A", "B")),
                      p=(p_out, p_in), nbytes=32)
            want = _legacy_subplan_execute(pl, x, monoid_lib.ADD)
            with collect_stats() as st:
                got = sim.execute(pl.schedule(), x, monoid_lib.ADD)
            assert np.array_equal(got, want), (p_out, p_in)
            assert np.array_equal(got, _exclusive_ref(x))
            assert st.rounds == pl.rounds, (p_out, p_in, st, pl)
            assert st.op_applications == pl.op_applications
            assert st.allgathers == pl.allgathers
            assert pl.algorithm.startswith("composite(")


def test_composed_three_axes_and_noncommutative():
    sim = SimulatorExecutor()
    # three axes, non-commutative affine monoid
    ps = (2, 3, 4)
    p = int(np.prod(ps))
    rng = np.random.default_rng(0)
    a = rng.standard_normal((p, 8))
    b = rng.standard_normal((p, 8))
    pl = plan(ScanSpec(kind="exclusive", algorithm="auto",
                       monoid="affine", axis_name=("A", "B", "C")),
              p=ps, nbytes=128)
    sched = pl.schedule()
    assert sched.axes == (("A", 2), ("B", 3), ("C", 4))
    with collect_stats() as st:
        ga, gb = sim.execute(sched, (a, b), monoid_lib.AFFINE)
    oa = np.ones_like(a)
    ob = np.zeros_like(b)
    ca, cb = np.ones(8), np.zeros(8)
    for r in range(p):
        oa[r], ob[r] = ca, cb
        ca, cb = a[r] * ca, a[r] * cb + b[r]
    np.testing.assert_allclose(ga, oa, rtol=1e-12)
    np.testing.assert_allclose(gb, ob, rtol=1e-12)
    assert st.rounds == pl.rounds
    assert st.op_applications == pl.op_applications


def test_composed_with_segmented_ring_stage():
    # a large payload on the minor axis makes the inner stage a
    # segmented ring inside the composed schedule
    pl = plan(ScanSpec(kind="exclusive", algorithm="auto",
                       axis_name=("A", "B")), p=(2, 12),
              nbytes=2 << 20)
    assert pl.sub_plans[0].algorithm == "ring"
    assert pl.sub_plans[0].segments > 1
    res = schedule_lib.verify_plan(pl)
    assert res["ok"], res
    # one notch down the payload axis the mid-m block builders own
    # the inner stage instead, inside the same composed structure
    pl = plan(ScanSpec(kind="exclusive", algorithm="auto",
                       axis_name=("A", "B")), p=(2, 12),
              nbytes=1 << 20)
    assert pl.sub_plans[0].algorithm == "quartering"
    res = schedule_lib.verify_plan(pl)
    assert res["ok"], res


def test_compose_transform_validation():
    from repro.core.schedule import (
        build_123, build_butterfly, build_hillis_steele)

    with pytest.raises(ValueError, match="allreduce"):
        compose(build_123(4), build_hillis_steele(4), build_123(2),
                minor_axis="B", outer_axis="A")
    with pytest.raises(ValueError, match="share p"):
        compose(build_123(4), build_butterfly(8), build_123(2),
                minor_axis="B", outer_axis="A")
    with pytest.raises(ValueError, match="outer_axis"):
        compose(build_123(4), build_butterfly(4), build_123(2),
                minor_axis="B")


# ---------------------------------------------------------------------------
# Fused k-scans: packed payload, single-scan round count
# ---------------------------------------------------------------------------


def test_fused_equals_independent_scans_with_single_scan_rounds():
    sim = SimulatorExecutor()
    spec = ScanSpec(kind="exclusive", monoid="add", algorithm="auto",
                    axis_name="x")
    for p in range(2, 18):
        rng = np.random.default_rng(p)
        sizes = (2, 5, 3, 8)
        xs = [rng.integers(0, 1 << 30, size=(p, n)).astype(np.int64)
              for n in sizes]
        fp = plan_fused([spec] * len(xs), p, [n * 8 for n in sizes])
        assert fp.fused, p
        single = plan(spec, p=p, nbytes=8 * sum(sizes))
        assert fp.rounds == single.rounds  # NOT k x single
        with collect_stats() as st:
            outs = fp.execute(xs, executor=sim)
        for o, x in zip(outs, xs):
            assert np.array_equal(o, _exclusive_ref(x)), p
        assert st.rounds == fp.rounds == fp.packed.rounds, (p, st)
        assert st.op_applications == fp.packed.op_applications


def test_fused_decision_respects_cost_model():
    from repro.core.scan_api import CostModel

    spec = ScanSpec(kind="exclusive", monoid="add", algorithm="auto")
    # latency-dominated: fusing always wins (α·q once, not k·α·q)
    fp = plan_fused([spec] * 4, 36, [8] * 4,
                    cost_model=CostModel(alpha=1.0, beta=0.0,
                                         gamma=0.0))
    assert fp.fused and fp.rounds == plan(spec, 36, nbytes=32).rounds
    # a single scan never "fuses"
    fp1 = plan_fused([spec], 36, [8])
    assert not fp1.fused and fp1.rounds == fp1.plans[0].rounds
    # conflicting algorithm pins fall back to serial execution
    fp2 = plan_fused([spec.over(None, algorithm="123"),
                      spec.over(None, algorithm="ring")], 36, [8, 8])
    assert not fp2.fused
    # non-segmentable monoids cannot pack
    fp3 = plan_fused([spec.over(None, monoid="matmul")] * 2, 8,
                     [128, 128])
    assert not fp3.fused


def test_fused_verify_and_affine_payloads():
    spec = ScanSpec(kind="exclusive", monoid="affine",
                    algorithm="auto", axis_name="x")
    fp = plan_fused([spec] * 3, 9, [64] * 3)
    res = fp.verify()
    assert res["ok"], res
    assert res["rounds_measured"] == res["rounds_predicted"]


def test_payload_layout_pack_roundtrip():
    rng = np.random.default_rng(0)
    xs = [rng.standard_normal((3, 4)), rng.standard_normal((7,)),
          rng.standard_normal((2, 2, 2))]
    layout = make_layout(xs)
    assert layout.n == 3 and layout.totals == (12 + 7 + 8,)
    packed = pack_payloads(layout, xs, xp=np)
    outs = unpack_payloads(layout, packed)
    for o, x in zip(outs, xs):
        np.testing.assert_array_equal(o, x)
    # mismatched dtypes refuse to pack
    with pytest.raises(ValueError, match="dtype"):
        make_layout([xs[0], xs[1].astype(np.float32)])
    # tuple payloads (affine-style) share one treedef
    ys = [(rng.standard_normal(4), rng.standard_normal(4)),
          (rng.standard_normal(6), rng.standard_normal(6))]
    layout = make_layout(ys)
    packed = pack_payloads(layout, ys, xp=np)
    outs = unpack_payloads(layout, packed)
    for o, y in zip(outs, ys):
        np.testing.assert_array_equal(o[0], y[0])
        np.testing.assert_array_equal(o[1], y[1])
    with pytest.raises(ValueError, match="tree structure"):
        make_layout([ys[0], xs[0]])


def test_fuse_transform_validation():
    from repro.core.schedule import build_123, build_butterfly

    layout = make_layout([np.zeros(3), np.zeros(5)])
    fused = fuse([build_123(8)], layout)
    assert fused.layout is layout and fused.rounds == build_123(8).rounds
    assert fused.algorithm == "fused[2](123)"
    with pytest.raises(ValueError, match="share kind"):
        fuse([build_123(8), build_butterfly(8)], layout)
    with pytest.raises(ValueError, match="already fused"):
        fuse([fused], layout)
    # same kind, mismatched output lists refuse to fuse
    import dataclasses as dc

    from repro.core.schedule import build_scan_total

    st = build_scan_total(8)
    with pytest.raises(ValueError, match="share outputs"):
        fuse([st, dc.replace(st, outputs=("$w",))], layout)


def _check_fused_bucket(p, xs, dtype, rng):
    """One bucket's property: k mixed-size payloads of one dtype fuse
    into the single-scan round count and every unpacked result matches
    the host exscan."""
    sim = SimulatorExecutor()
    spec = ScanSpec(kind="exclusive", monoid="add", algorithm="auto",
                    axis_name="x")
    fp = plan_fused([spec] * len(xs), p,
                    [x[0].nbytes for x in xs])
    assert fp.fused == (len(xs) > 1), (p, dtype)
    with collect_stats() as st:
        outs = fp.execute(xs, executor=sim)
    for o, x in zip(outs, xs):
        assert o.dtype == x.dtype
        if np.issubdtype(x.dtype, np.integer):
            np.testing.assert_array_equal(o, _exclusive_ref(x))
        else:  # ⊕ order differs from cumsum's: bit-exact only for ints
            np.testing.assert_allclose(o, _exclusive_ref(x),
                                       rtol=1e-12, atol=1e-12)
    assert st.rounds == fp.rounds, (p, dtype, st.rounds, fp.rounds)
    res = fp.verify()  # simulator drift check on the same plan
    assert res["ok"], (p, dtype, res)


def test_fused_property_mixed_sizes_and_dtypes_every_p():
    # deterministic property sweep: p in 2..17, random payload-size
    # mixes, int64 and float64 buckets (dtype is part of the bucket —
    # mixed dtypes refuse to pack, asserted at the end)
    for p in range(2, 18):
        rng = np.random.default_rng(1000 + p)
        k = int(rng.integers(1, 6))
        sizes = [int(rng.integers(1, 32)) for _ in range(k)]
        ints = [rng.integers(0, 1 << 30, size=(p, n)).astype(np.int64)
                for n in sizes]
        _check_fused_bucket(p, ints, np.int64, rng)
        floats = [rng.standard_normal((p, n)) for n in sizes]
        _check_fused_bucket(p, floats, np.float64, rng)
    # a mixed-dtype batch is NOT one bucket: the pack refuses
    spec = ScanSpec(kind="exclusive", monoid="add", algorithm="auto",
                    axis_name="x")
    fp = plan_fused([spec] * 2, 8, [32, 32])
    bad = [np.zeros((8, 4), np.int64), np.zeros((8, 4), np.float64)]
    with pytest.raises(ValueError, match="dtype"):
        fp.execute(bad, executor=SimulatorExecutor())


def test_fused_scan_total_multi_output():
    # k fused scan_totals: ONE packed butterfly, every request gets its
    # own (prefix, total) back via unpack_fused_outputs
    from repro.core.schedule import unpack_fused_outputs

    sim = SimulatorExecutor()
    spec = ScanSpec(kind="scan_total", monoid="add", algorithm="auto",
                    axis_name="x")
    for p in (4, 8, 9, 13, 16):
        rng = np.random.default_rng(p)
        xs = [rng.integers(0, 1 << 20, size=(p, n)).astype(np.int64)
              for n in (3, 1, 6)]
        fp = plan_fused([spec] * len(xs), p,
                        [x[0].nbytes for x in xs])
        assert fp.fused, p
        single = plan(spec, p=p, nbytes=sum(x[0].nbytes for x in xs))
        assert fp.rounds == single.rounds
        with collect_stats() as st:
            outs = fp.execute(xs, executor=sim)
        for (prefix, total), x in zip(outs, xs):
            np.testing.assert_array_equal(prefix, _exclusive_ref(x))
            np.testing.assert_array_equal(
                total, np.broadcast_to(x.sum(0), x.shape))
        assert st.rounds == fp.rounds, (p, st.rounds, fp.rounds)
        assert fp.verify()["ok"], p
    # unpack_fused_outputs on a plain (single-output) result is just
    # unpack_payloads
    xs = [np.arange(6).reshape(2, 3), np.arange(2)]
    layout = make_layout(xs)
    packed = pack_payloads(layout, xs, xp=np)
    outs = unpack_fused_outputs(layout, packed)
    for o, x in zip(outs, xs):
        np.testing.assert_array_equal(o, x)
    # two outputs: payload i gets (out0_i, out1_i)
    outs = unpack_fused_outputs(layout, (packed, packed), 2)
    for (a, b), x in zip(outs, xs):
        np.testing.assert_array_equal(a, x)
        np.testing.assert_array_equal(b, x)


@settings(max_examples=25, deadline=None)
@given(p=hst.integers(min_value=2, max_value=17),
       sizes=hst.lists(hst.integers(min_value=1, max_value=16),
                       min_size=2, max_size=5),
       seed=hst.integers(min_value=0, max_value=2**31 - 1))
def test_fused_property_hypothesis(p, sizes, seed):
    rng = np.random.default_rng(seed)
    xs = [rng.integers(0, 1 << 30, size=(p, n)).astype(np.int64)
          for n in sizes]
    _check_fused_bucket(p, xs, np.int64, rng)


# ---------------------------------------------------------------------------
# scan_total: fused exscan+allreduce
# ---------------------------------------------------------------------------


def test_scan_total_simulator_every_p():
    sim = SimulatorExecutor()
    for p in range(1, 18):
        x = np.arange(max(p, 1) * 4, dtype=np.int64).reshape(-1, 4)[:p]
        pl = plan(ScanSpec(kind="scan_total", algorithm="auto"), p=p,
                  nbytes=32)
        with collect_stats() as st:
            prefix, total = sim.execute(pl.schedule(), x,
                                        monoid_lib.ADD)
        assert np.array_equal(prefix, _exclusive_ref(x)), p
        assert np.array_equal(
            total, np.broadcast_to(x.sum(0), x.shape)), p
        assert st.rounds == pl.rounds, (p, st, pl)
        assert st.op_applications == pl.op_applications, (p, st, pl)
        # power-of-two p: BOTH results in the allreduce's round count
        if p >= 2 and not (p & (p - 1)):
            assert pl.algorithm == "fused_doubling"
            assert pl.rounds == int(np.ceil(np.log2(p)))


def test_scan_total_pinned_variants_cover_exclusive_algorithms():
    assert algorithms("scan_total") == (
        "123", "1doubling", "fused_doubling", "halving", "native",
        "quartering", "reduce_scatter", "ring", "two_op")
    for alg in algorithms("scan_total"):
        res = schedule_lib.verify_plan(
            plan(ScanSpec(kind="scan_total", algorithm=alg), p=9,
                 nbytes=1024))
        assert res["ok"], (alg, res)
    # the fused butterfly strictly beats exscan+allreduce serially: at
    # p=16 it needs 4 rounds where 123 + butterfly would pay 5 + 4
    fused = plan(ScanSpec(kind="scan_total", algorithm="auto"), p=16,
                 nbytes=8)
    serial = (plan(ScanSpec(kind="exclusive", algorithm="123"), p=16,
                   nbytes=8).rounds
              + plan(ScanSpec(kind="allreduce", algorithm="butterfly"),
                     p=16, nbytes=8).rounds)
    assert fused.rounds == 4 and serial == 9


def test_scan_total_multi_axis_composes():
    pl = plan(ScanSpec(kind="scan_total", algorithm="auto",
                       axis_name=("pod", "data")), p=(2, 8), nbytes=16)
    assert len(pl.sub_plans) == 2  # no separate allreduce stage
    res = schedule_lib.verify_plan(pl)
    assert res["ok"], res
    # rounds: inner fused butterfly (3) + outer (1) — the allreduce the
    # §5 rewrite needs rides the inner scan_total for free
    assert pl.rounds == 4


# ---------------------------------------------------------------------------
# Plan cache observability
# ---------------------------------------------------------------------------


def test_plan_cache_reports_hits():
    plan_cache_clear()
    spec = ScanSpec(kind="exclusive", algorithm="auto")
    before = plan_cache_info()
    assert before["hits"] == 0 and before["size"] == 0
    a = plan(spec, p=16, nbytes=128)
    mid = plan_cache_info()
    b = plan(spec, p=16, nbytes=128)
    after = plan_cache_info()
    assert a is b
    assert after["hits"] == mid["hits"] + 1
    assert after["size"] == mid["size"]


# ---------------------------------------------------------------------------
# SPMD + Pallas executors on composed/fused schedules (subprocess with
# fake devices; acceptance criterion: one IR, three executors)
# ---------------------------------------------------------------------------

_SPMD_COMPOSED = """
import jax, numpy as np
from jax.sharding import Mesh, PartitionSpec as P
from jax import shard_map
from repro.core import monoid as monoid_lib
from repro.core.scan_api import ScanSpec, scan, plan, scan_with_total, \\
    fused_scan
from repro.core.schedule import (
    SimulatorExecutor, PallasExecutor, collect_stats)

x = np.arange(8 * 4, dtype=np.int64).reshape(8, 4)
ref = np.zeros_like(x)
ref[1:] = np.cumsum(x[:-1], axis=0)
mesh2 = Mesh(np.array(jax.devices()).reshape(2, 4), ("pod", "data"))
mesh1 = Mesh(np.array(jax.devices()).reshape(8), ("x",))

# multi-axis spec -> ONE composed schedule, SPMD == simulator == plan
spec = ScanSpec(kind="exclusive", algorithm="auto",
                axis_name=("pod", "data"))
pl = plan(spec, p=(2, 4), nbytes=32)
assert pl.algorithm.startswith("composite(")
with collect_stats() as st:
    f = jax.jit(shard_map(lambda v: scan(v, spec), mesh=mesh2,
                          in_specs=P(("pod", "data")),
                          out_specs=P(("pod", "data"))))
    got = np.asarray(f(x))
assert np.array_equal(got, ref)
assert (st.rounds, st.op_applications, st.allgathers) == (
    pl.rounds, pl.op_applications, pl.allgathers), (st, pl)
with collect_stats() as st_sim:
    sim = SimulatorExecutor().execute(pl.schedule(), x, monoid_lib.ADD)
assert np.array_equal(np.asarray(sim), got)
assert st_sim.bytes_per_round == st.bytes_per_round
print("OK composed spmd", pl.rounds)

# plan.lower() retargets the same composed schedule at the Pallas
# executor (the third backend)
ex = PallasExecutor(interpret=True)
fp = jax.jit(shard_map(pl.lower(ex), mesh=mesh2,
                       in_specs=P(("pod", "data")),
                       out_specs=P(("pod", "data")), check_vma=False))
assert np.array_equal(np.asarray(fp(x)), ref)
print("OK composed pallas")

# fused exscan+allreduce: (prefix, total) in the allreduce's rounds
tspec = ScanSpec(kind="exclusive", algorithm="auto", axis_name="x")
with collect_stats() as st:
    g = jax.jit(shard_map(lambda v: scan_with_total(v, tspec),
                          mesh=mesh1, in_specs=P("x"),
                          out_specs=(P("x"), P("x"))))
    pref, tot = g(x)
assert np.array_equal(np.asarray(pref), ref)
assert np.array_equal(np.asarray(tot),
                      np.broadcast_to(x.sum(0), x.shape))
assert st.rounds == 3  # ceil(log2(8)): allreduce round count for BOTH
print("OK scan_with_total", st.rounds)

# fused_scan: 3 concurrent exscans ride the single-scan round count
xs = [np.arange(8 * n, dtype=np.int64).reshape(8, n)
      for n in (2, 3, 5)]
espec = ScanSpec(kind="exclusive", algorithm="auto", axis_name="x")
with collect_stats() as st:
    h = jax.jit(shard_map(
        lambda a, b, c: tuple(fused_scan(
            [(a, espec), (b, espec), (c, espec)])),
        mesh=mesh1, in_specs=(P("x"),) * 3, out_specs=(P("x"),) * 3))
    outs = h(*xs)
for o, xi in zip(outs, xs):
    r = np.zeros_like(xi)
    r[1:] = np.cumsum(xi[:-1], axis=0)
    assert np.array_equal(np.asarray(o), r)
single = plan(espec, p=8, nbytes=sum(xi[0].nbytes for xi in xs))
assert st.rounds == single.rounds, (st.rounds, single.rounds)
print("OK fused_scan", st.rounds)
"""


def test_spmd_composed_fused_and_scan_total():
    out = run_with_devices(_SPMD_COMPOSED, 8)
    assert out.count("OK") == 4
