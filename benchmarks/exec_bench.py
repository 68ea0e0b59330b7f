"""Execution-engine benchmark: trace size, compile time, walltime.

The compiled-round-table claim (DESIGN §7) made measurable: for each
registered exclusive algorithm at p ∈ {8, 64, 256} this emits

  * ``trace_eqns``   — jaxpr equation count of the traced SPMD program
    (including nested sub-jaxprs, so a rolled ``lax.scan`` body counts
    once and an unrolled ring pays per round) and the trace seconds;
  * ``compile_seconds`` — XLA compile time of the jitted ``shard_map``
    program (the p=256 *unrolled* ring is tens of seconds — the
    reason the round-table executor exists — so that cell is opt-in
    via ``--full``);
  * ``simulated_seconds`` — the deterministic simulated clock of
    :func:`repro.core.tune.measure_schedule_simulated` under the
    default ICI pricing (device-free walltime proxy, reproducible in
    CI).

The segmented ring is measured in BOTH executor modes (``rolled``:
the single-``lax.scan`` round table; ``unrolled``: one trace site per
round), so the win is a ratio in the same JSON, not a claim.

At p = 64 the fused Pallas round path (DESIGN §7) is measured against
its per-round ``block_combine`` baseline: the pinned S=8 segmented
ring and the fused-doubling scan_total run under
``PallasExecutor(fused=True)`` and ``fused=False``, recording the
kernel-launch and HBM-pass counts from ``collect_stats()`` (asserted
equal to the IR's ``Schedule.kernel_passes``/``kernel_launches``
prediction), the interpret-mode execution walltime, and the bitwise
drift against the SPMD executor on the same int64 payload.

``--check`` is the CI gate: the p=256 ring's rolled trace must stay
under ``TRACE_EQ_BUDGET`` equations and beat the unrolled trace by at
least ``MIN_ROLLED_WIN``×, AND the fused Pallas path must cost at
least ``MIN_FUSED_PASS_WIN``× fewer HBM passes than baseline on the
p=64 S=8 ring, launch fewer kernels than baseline on the p=64
scan_total, match the IR prediction exactly, and show zero drift.

Each p needs its own fake-device count, which jax fixes at first
initialization — so the parent process spawns one worker subprocess
per p (``--worker``) and aggregates their rows into
``BENCH_exec.json``.
"""

from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import time

DEFAULT_JSON = "BENCH_exec.json"
PS = (8, 64, 256)
ALGS = ("123", "1doubling", "two_op", "native", "ring",
        "halving", "quartering", "reduce_scatter")
PAYLOAD_ELEMS = 256  # int64 -> 2 KiB per rank
TRACE_EQ_BUDGET = 256  # p=256 rolled-ring trace ceiling (measured: ~92)
MIN_ROLLED_WIN = 5.0  # acceptance floor for unrolled/rolled eq ratio
PALLAS_P = 64  # fused-vs-baseline Pallas cell (ISSUE acceptance point)
PALLAS_RING_S = 8  # pinned ring segment count for the pass-count gate
MIN_FUSED_PASS_WIN = 2.0  # baseline/fused HBM-pass floor (measured 2.0)
# compile timing runs everywhere EXCEPT the p=256 unrolled ring
# (~30 s of XLA time proving the point; enable with --full)
SLOW_COMPILE_P = 256

MARK = "BENCH_EXEC_ROWS "


def worker(p: int, full: bool) -> list[dict]:
    import numpy as np

    import jax
    from jax import shard_map
    from jax.sharding import Mesh
    from jax.sharding import PartitionSpec as P_

    from repro.core import monoid as monoid_lib
    from repro.core import schedule as schedule_lib
    from repro.core import tune
    from repro.core.scan_api import ScanSpec, plan
    from repro.launch import mesh as mesh_lib

    assert len(jax.devices()) >= p, (len(jax.devices()), p)
    m = monoid_lib.ADD
    x = np.arange(p * PAYLOAD_ELEMS, dtype=np.int64).reshape(
        p, PAYLOAD_ELEMS)
    nbytes = x[0].nbytes
    mesh = Mesh(np.array(jax.devices()[:p]).reshape(p), ("x",))
    truth = mesh_lib.DEFAULT_PROFILE.model("ici")
    rows = []
    for alg in ALGS:
        pl = plan(ScanSpec(kind="exclusive", algorithm=alg), p=p,
                  nbytes=nbytes)
        sched = pl.schedule()
        sim_seconds, _ = tune.measure_schedule_simulated(
            sched, nbytes, truth)
        modes = (("rolled", False), ("unrolled", True)) \
            if alg == "ring" else (("rolled", False),)
        for mode, unrolled in modes:
            ex = schedule_lib.SPMDExecutor("x", unrolled=unrolled)
            fn = shard_map(lambda v: ex.execute(sched, v, m),
                           mesh=mesh, in_specs=P_("x"),
                           out_specs=P_("x"))
            t0 = time.perf_counter()
            eqs = schedule_lib.jaxpr_eqn_count(jax.make_jaxpr(fn)(x))
            trace_s = time.perf_counter() - t0
            row = {
                "p": p, "algorithm": alg, "mode": mode,
                "segments": pl.segments, "rounds": pl.rounds,
                "payload_bytes": nbytes, "trace_eqns": eqs,
                "trace_seconds": trace_s,
                "simulated_seconds": sim_seconds,
            }
            if full or not (unrolled and p >= SLOW_COMPILE_P):
                t0 = time.perf_counter()
                jax.jit(fn).lower(x).compile()
                row["compile_seconds"] = time.perf_counter() - t0
            rows.append(row)
    if p == PALLAS_P:
        rows.extend(_pallas_rows(p, mesh, m, x, nbytes))
    return rows


def _pallas_rows(p: int, mesh, m, x, nbytes: int) -> list[dict]:
    """Fused-vs-baseline Pallas rows at the acceptance point p=64.

    Two schedules: the pinned S=8 segmented ring (the pass-count gate
    — launches are EQUAL between modes there, the fusion win is one
    sweep per prep round instead of two) and the fused-doubling
    scan_total (the launch-count gate — fused batches each round's
    (payload, total) registers into ONE ``pallas_call``).  Kernel
    stats are read from ``collect_stats()`` over the trace and checked
    against the IR prediction; outputs are compared bitwise against
    the SPMD executor on the same int64 payload."""
    import numpy as np

    import jax
    from jax import shard_map
    from jax.sharding import PartitionSpec as P_

    from repro.core import schedule as schedule_lib
    from repro.core.scan_api import ScanSpec, plan

    rows = []
    cases = (
        ("ring", plan(ScanSpec(kind="exclusive", algorithm="ring",
                               segments=PALLAS_RING_S),
                      p=p, nbytes=nbytes)),
        ("fused_doubling", plan(ScanSpec(kind="scan_total",
                                         algorithm="fused_doubling"),
                                p=p, nbytes=nbytes)),
    )
    for alg, pl_ in cases:
        sched = pl_.schedule()
        ref_fn = shard_map(
            lambda v, s=sched: schedule_lib.SPMDExecutor("x").execute(
                s, v, m),
            mesh=mesh, in_specs=P_("x"), out_specs=P_("x"))
        ref = jax.tree.map(np.asarray, jax.jit(ref_fn)(x))
        for mode, fused in (("pallas_fused", True),
                            ("pallas_baseline", False)):
            ex = schedule_lib.PallasExecutor("x", interpret=True,
                                             fused=fused)
            fn = shard_map(lambda v, e=ex, s=sched: e.execute(s, v, m),
                           mesh=mesh, in_specs=P_("x"),
                           out_specs=P_("x"), check_vma=False)
            with schedule_lib.collect_stats() as st:
                jax.make_jaxpr(fn)(x)
            compiled = jax.jit(fn).lower(x).compile()
            t0 = time.perf_counter()
            out = jax.block_until_ready(compiled(x))
            wall = time.perf_counter() - t0
            drift = max(
                (int(np.max(np.abs(np.asarray(a) - np.asarray(b))))
                 if np.asarray(a).size else 0)
                for a, b in zip(jax.tree.leaves(out),
                                jax.tree.leaves(ref)))
            rows.append({
                "p": p, "algorithm": alg, "mode": mode,
                "segments": pl_.segments, "rounds": pl_.rounds,
                "payload_bytes": nbytes,
                "kernel_launches": st.kernel_launches,
                "hbm_passes": st.hbm_passes,
                "predicted_launches": sched.kernel_launches(
                    m.commutative, fused=fused),
                "predicted_passes": sched.kernel_passes(
                    m.commutative, fused=fused),
                "plan_kernel_passes": pl_.kernel_passes,
                "exec_seconds": wall,
                "max_drift": drift,
            })
    return rows


_SRC = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "src")


def _spawn_worker(p: int, full: bool) -> list[dict]:
    if _SRC not in sys.path:
        sys.path.insert(0, _SRC)
    from repro.launch.mesh import fake_device_env

    env = fake_device_env(p)
    # CPU-only: fake devices; the child must never claim a chip
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = _SRC + os.pathsep + env.get("PYTHONPATH", "")
    cmd = [sys.executable, os.path.abspath(__file__), "--worker",
           str(p)]
    if full:
        cmd.append("--full")
    proc = subprocess.run(cmd, env=env, capture_output=True, text=True)
    if proc.returncode != 0:
        raise SystemExit(
            f"exec_bench worker p={p} failed (rc={proc.returncode})\n"
            f"{proc.stdout}\n{proc.stderr}")
    for line in proc.stdout.splitlines():
        if line.startswith(MARK):
            return json.loads(line[len(MARK):])
    raise SystemExit(f"worker p={p} emitted no rows:\n{proc.stdout}")


def check(rows: list[dict]) -> list[str]:
    """The CI gates: (1) trace-size budget — p=256 rolled ring under
    the fixed equation ceiling AND >= MIN_ROLLED_WIN x smaller than
    the unrolled trace of the same schedule; (2) fused-kernel budget —
    at p=64 the fused Pallas path pays >= MIN_FUSED_PASS_WIN x fewer
    HBM passes than baseline on the S=8 ring, strictly fewer kernel
    launches on the scan_total butterfly, matches the IR's
    kernel_launches/kernel_passes prediction exactly, and drifts zero
    bits from the SPMD executor."""
    failures = []
    by = {(r["p"], r["algorithm"], r["mode"]): r for r in rows}
    rolled = by.get((256, "ring", "rolled"))
    unrolled = by.get((256, "ring", "unrolled"))
    if rolled is None or unrolled is None:
        return [f"missing p=256 ring rows (have {sorted(by)})"]
    if rolled["trace_eqns"] > TRACE_EQ_BUDGET:
        failures.append(
            f"p=256 rolled ring trace {rolled['trace_eqns']} eqns "
            f"exceeds budget {TRACE_EQ_BUDGET}")
    ratio = unrolled["trace_eqns"] / max(rolled["trace_eqns"], 1)
    if ratio < MIN_ROLLED_WIN:
        failures.append(
            f"rolled trace win {ratio:.1f}x below the "
            f"{MIN_ROLLED_WIN}x floor "
            f"({unrolled['trace_eqns']} -> {rolled['trace_eqns']})")
    failures.extend(_check_pallas(by))
    return failures


def _check_pallas(by: dict) -> list[str]:
    failures = []
    cells = {(alg, mode): by.get((PALLAS_P, alg, mode))
             for alg in ("ring", "fused_doubling")
             for mode in ("pallas_fused", "pallas_baseline")}
    missing = sorted(k for k, v in cells.items() if v is None)
    if missing:
        return [f"missing p={PALLAS_P} pallas rows: {missing}"]
    for (alg, mode), r in cells.items():
        tag = f"p={PALLAS_P} {alg} {mode}"
        if r["kernel_launches"] != r["predicted_launches"] \
                or r["hbm_passes"] != r["predicted_passes"]:
            failures.append(
                f"{tag}: measured kernel stats "
                f"({r['kernel_launches']}L/{r['hbm_passes']}P) != IR "
                f"prediction ({r['predicted_launches']}L/"
                f"{r['predicted_passes']}P)")
        if r["max_drift"] != 0:
            failures.append(
                f"{tag}: nonzero drift {r['max_drift']} vs SPMD")
    ring_f = cells[("ring", "pallas_fused")]
    ring_b = cells[("ring", "pallas_baseline")]
    win = ring_b["hbm_passes"] / max(ring_f["hbm_passes"], 1)
    if win < MIN_FUSED_PASS_WIN:
        failures.append(
            f"fused ring pass win {win:.2f}x below the "
            f"{MIN_FUSED_PASS_WIN}x floor "
            f"({ring_b['hbm_passes']} -> {ring_f['hbm_passes']})")
    st_f = cells[("fused_doubling", "pallas_fused")]
    st_b = cells[("fused_doubling", "pallas_baseline")]
    if st_f["kernel_launches"] >= st_b["kernel_launches"]:
        failures.append(
            f"fused scan_total launches {st_f['kernel_launches']} not "
            f"below baseline {st_b['kernel_launches']}")
    return failures


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        description="Trace-size / compile-time / simulated-walltime "
                    "benchmark of the schedule executors.")
    ap.add_argument("--worker", type=int, default=None,
                    help="internal: run one device-count cell")
    ap.add_argument("--ps", type=lambda s: tuple(
        int(t) for t in s.split(",") if t), default=PS,
        help="comma-separated rank counts (default 8,64,256)")
    ap.add_argument("--full", action="store_true",
                    help="also compile the p=256 unrolled ring "
                         "(tens of seconds of XLA time)")
    ap.add_argument("--check", action="store_true",
                    help="fail unless the p=256 rolled-ring trace is "
                         "under the equation budget and >=5x smaller "
                         "than unrolled, AND the p=64 fused Pallas "
                         "path beats baseline (>=2x fewer ring HBM "
                         "passes, fewer scan_total launches, zero "
                         "drift) (CI gate)")
    ap.add_argument("--json", nargs="?", const=DEFAULT_JSON,
                    default=None, metavar="PATH",
                    help=f"write rows as JSON (default {DEFAULT_JSON})")
    args = ap.parse_args(argv)

    if args.worker is not None:
        rows = worker(args.worker, args.full)
        print(MARK + json.dumps(rows))
        return 0

    rows = []
    for p in args.ps:
        rows.extend(_spawn_worker(p, args.full))
    for r in rows:
        key = f"exec/{r['algorithm']}/{r['mode']}/p{r['p']}"
        if r["mode"].startswith("pallas_"):
            print(f"{key}/kernel_launches,{r['kernel_launches']},"
                  f"pallas_calls")
            print(f"{key}/hbm_passes,{r['hbm_passes']},payload_sweeps")
            print(f"{key}/exec_s,{r['exec_seconds']:.3f},"
                  f"cpu_interpret_walltime")
            print(f"{key}/max_drift,{r['max_drift']},bits_vs_spmd")
            continue
        print(f"{key}/trace_eqns,{r['trace_eqns']},jaxpr_equations")
        print(f"{key}/trace_s,{r['trace_seconds']:.3f},cpu_seconds")
        if "compile_seconds" in r:
            print(f"{key}/compile_s,{r['compile_seconds']:.3f},"
                  f"cpu_seconds")
        print(f"{key}/simulated_us,{r['simulated_seconds'] * 1e6:.2f},"
              f"default_ici_clock")
    if args.json:
        from repro.core.benchmeta import bench_metadata

        with open(args.json, "w") as f:
            json.dump({"meta": bench_metadata(),
                       "schema_version": 2, "benchmark": "exec_bench",
                       "trace_eq_budget": TRACE_EQ_BUDGET,
                       "min_fused_pass_win": MIN_FUSED_PASS_WIN,
                       "rows": rows}, f, indent=1, sort_keys=True)
        print(f"wrote {args.json}")
    if args.check:
        failures = check(rows)
        if failures:
            raise SystemExit("exec-bench gate failed: "
                             + "; ".join(failures))
        print("exec-bench gate OK (trace budget + fused kernel win)")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
