import os

os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: lower + compile every (arch × shape × mesh) cell.

The two lines above MUST precede every other import (jax locks the
device count at first init): the dry-run — and only the dry-run — sees
512 placeholder CPU devices so ``jax.make_mesh`` can build the
production meshes (16x16 single pod, 2x16x16 multi-pod).

Usage:
    PYTHONPATH=src python -m repro.launch.dryrun --arch llama3-8b \
        --shape train_4k [--multi-pod]
    PYTHONPATH=src python -m repro.launch.dryrun --all [--multi-pod]
    PYTHONPATH=src python -m repro.launch.dryrun --all --both-meshes \
        --json out.json

Per cell it prints ``compiled.memory_analysis()`` (proves the program
fits HBM) and ``compiled.cost_analysis()`` FLOPs/bytes, plus the parsed
collective wire bytes — the inputs to EXPERIMENTS.md §Roofline.
"""

import argparse  # noqa: E402
import dataclasses  # noqa: E402
import json  # noqa: E402
import time  # noqa: E402
import traceback  # noqa: E402

import jax  # noqa: E402

from repro import configs  # noqa: E402
from repro.core import scan_api  # noqa: E402
from repro.core import schedule as schedule_lib  # noqa: E402
from repro.core.scan_api import ScanSpec  # noqa: E402
from repro.launch import mesh as mesh_lib  # noqa: E402
from repro.launch import roofline as rl  # noqa: E402
from repro.launch import steps as steps_lib  # noqa: E402
from repro.launch.mesh import make_production_mesh  # noqa: E402


def _verify_scan_plans(cfg, mesh) -> list:
    """Resolve the cell's scan spec per mesh axis and execute each
    plan's schedule IR in the numpy simulator executor against the host
    reference (no devices), so plan/measurement drift fails the cell
    before the compile does.

    Covers the payload regimes and monoid families the cell's call
    sites re-target the spec to: the MoE-dispatch-sized small "add"
    payload (doubling schedules), a 1 MiB context-carry-sized one
    (segmented ring on bandwidth-bound axes) under both "add" and the
    non-commutative "affine" carry monoid, and the non-segmentable
    "matmul" path — plus the composed forms the consumers actually
    issue: the multi-axis batch×model scan (ONE axis-annotated
    schedule since the composition refactor), the fused
    exscan+allreduce ("scan_total") that MoE dispatch runs, and a
    fused k-scan bundle (compression offsets).
    """
    checks = []
    small = 4 * max(cfg.n_experts, 16)  # int32 expert counts
    cases = (("add", small), ("add", 1 << 20), ("affine", 1 << 20),
             ("matmul", small))
    with scan_api.use_cost_model(mesh_lib.axis_cost_model):
        for axis in mesh.axis_names:
            for mono, nbytes in cases:
                pl = scan_api.plan(
                    cfg.scan_spec.over(axis, monoid=mono),
                    p=mesh.shape[axis], nbytes=nbytes)
                res = schedule_lib.verify_plan(pl)
                checks.append({"axis": axis, "monoid": mono,
                               "nbytes": nbytes, **res})
                if not res["ok"]:
                    raise RuntimeError(
                        f"scan plan/schedule drift on axis {axis!r} "
                        f"({mono}): {res}")
        # composed multi-axis (what MoE dispatch runs over batch axes ×
        # model) and its fused scan_total form — one schedule each
        maxes = tuple(mesh.axis_names)
        msizes = tuple(int(mesh.shape[a]) for a in maxes)
        for kind in ("exclusive", "scan_total"):
            pl = scan_api.plan(
                cfg.scan_spec.over(maxes, kind=kind, monoid="add",
                                   algorithm="auto", segments=None),
                p=msizes, nbytes=small)
            res = schedule_lib.verify_plan(pl)
            checks.append({"axis": maxes, "monoid": "add", "kind": kind,
                           "nbytes": small, **res})
            if not res["ok"]:
                raise RuntimeError(
                    f"composed {kind} plan/schedule drift over "
                    f"{maxes}: {res}")
        # fused k-scan bundle (compression offsets: k tiny same-axis
        # exscans riding one schedule's rounds)
        axis = mesh.axis_names[-1]
        fp = scan_api.plan_fused(
            [cfg.scan_spec.over(axis, kind="exclusive", monoid="add",
                                algorithm="auto", segments=None)] * 4,
            int(mesh.shape[axis]), [16] * 4)
        res = fp.verify()
        checks.append({"axis": axis, "monoid": "add", "kind": "fused",
                       "nbytes": 16, "algorithm": "fused[4]",
                       "segments": 1, **res})
        if not res["ok"]:
            raise RuntimeError(
                f"fused scan plan/schedule drift on axis {axis!r}: "
                f"{res}")
    return checks


def _probe(cfg, shape, mesh, repeats: int):
    """Compile an UNROLLED reduced-depth twin of the cell and return
    (flops, bytes, CollectiveStats).  XLA's cost_analysis counts a
    ``while`` (lax.scan) body once regardless of trip count, so the full
    cell's per-device cost is reconstructed from two unrolled probes:
        cost(R) = probe(1) + (R - 1) * (probe(2) - probe(1)),
    exact for a uniform scanned stack (embed/head live in probe(1))."""
    unit = len(cfg.pattern())
    cfg_p = dataclasses.replace(cfg, n_layers=unit * repeats,
                                unroll_stack=True)
    with scan_api.use_cost_model(mesh_lib.axis_cost_model):
        compiled = steps_lib.lower_cell(cfg_p, shape, mesh).compile()
    cost = compiled.cost_analysis()
    coll = rl.parse_collectives(compiled.as_text())
    return (float(cost.get("flops", 0.0)),
            float(cost.get("bytes accessed", 0.0)), coll)


def _extrapolate(p1, p2, repeats: int):
    f1, b1, c1 = p1
    f2, b2, c2 = p2
    r = repeats - 1
    flops = f1 + r * (f2 - f1)
    bytes_ = b1 + r * (b2 - b1)
    ops = sorted(set(c1.op_counts) | set(c2.op_counts))
    counts = {o: c1.op_counts.get(o, 0)
              + r * (c2.op_counts.get(o, 0) - c1.op_counts.get(o, 0))
              for o in ops}
    byts = {o: c1.op_bytes.get(o, 0.0)
            + r * (c2.op_bytes.get(o, 0.0) - c1.op_bytes.get(o, 0.0))
            for o in ops}
    return flops, bytes_, rl.CollectiveStats(counts, byts)


def run_cell(arch: str, shape_name: str, multi_pod: bool,
             verbose: bool = True, strategy: str = "tp",
             probes: bool = True, profile_dir: str | None = None,
             **cfg_overrides) -> dict:
    cfg = configs.get(arch, sharding_strategy=strategy, **cfg_overrides)
    shape = steps_lib.SHAPES[shape_name]
    ok, reason = steps_lib.applicable(cfg, shape)
    cell = {
        "arch": arch,
        "shape": shape_name,
        "mesh": "2x16x16" if multi_pod else "16x16",
        "strategy": strategy,
    }
    if not ok:
        cell["status"] = "skipped"
        cell["reason"] = reason
        if verbose:
            print(f"[SKIP] {arch} x {shape_name}: {reason}")
        return cell

    mesh = make_production_mesh(multi_pod=multi_pod)
    n_dev = mesh.devices.size
    # install the calibrated cost profile for this mesh (defaults when
    # none is persisted) and record the pricing provenance per cell
    profile = mesh_lib.use_calibrated_profile(mesh,
                                              directory=profile_dir)
    cell["cost_profile"] = profile.provenance(
        mesh_lib.mesh_fingerprint(mesh))
    if verbose:
        print(f"  cost profile: {profile.source} "
              f"fingerprint={profile.fingerprint()}")
    cell["scan_plan_checks"] = _verify_scan_plans(cfg, mesh)
    t0 = time.time()
    # "auto" scan specs price each mesh axis by its interconnect tier
    # (DCI for "pod" on the multi-pod mesh) while this cell traces
    with scan_api.use_cost_model(mesh_lib.axis_cost_model):
        lowered = steps_lib.lower_cell(cfg, shape, mesh)
    t_lower = time.time() - t0
    t0 = time.time()
    compiled = lowered.compile()
    t_compile = time.time() - t0
    mem = compiled.memory_analysis()

    # cost probes (scan-body extrapolation — see _probe docstring);
    # the multi-pod pass skips them (roofline table is single-pod only)
    t0 = time.time()
    if probes:
        p1 = _probe(cfg, shape, mesh, 1)
        p2 = _probe(cfg, shape, mesh, 2)
        flops, bytes_hbm, coll = _extrapolate(p1, p2, cfg.n_repeats)
    else:
        cost = compiled.cost_analysis()
        flops = float(cost.get("flops", 0.0))
        bytes_hbm = float(cost.get("bytes accessed", 0.0))
        coll = rl.parse_collectives(compiled.as_text())
    t_probe = time.time() - t0

    training = shape.kind == "train"
    seq_for_flops = shape.seq
    tokens = shape.batch * (shape.seq if shape.kind != "decode" else 1)
    model_flops = cfg.model_flops_per_token(seq_for_flops, training) * tokens
    roof = rl.Roofline(
        flops=flops, bytes_hbm=bytes_hbm, collective=coll,
        compute_s=flops / rl.PEAK_FLOPS,
        memory_s=bytes_hbm / rl.HBM_BW,
        collective_s=coll.total_bytes / rl.LINK_BW,
        model_flops=model_flops, n_devices=n_dev)

    cell.update(
        status="ok",
        lower_s=round(t_lower, 1),
        compile_s=round(t_compile, 1),
        probe_s=round(t_probe, 1),
        flops_per_device=roof.flops,
        bytes_per_device=roof.bytes_hbm,
        collective_bytes=roof.collective.total_bytes,
        collective_ops=roof.collective.op_counts,
        collective_op_bytes=roof.collective.op_bytes,
        compute_s=roof.compute_s,
        memory_s=roof.memory_s,
        collective_s=roof.collective_s,
        dominant=roof.dominant,
        model_flops=model_flops,
        useful_flops_fraction=roof.useful_flops_fraction,
        mfu_bound=roof.mfu_bound,
        memory_analysis={
            "argument_bytes": getattr(mem, "argument_size_in_bytes", None),
            "output_bytes": getattr(mem, "output_size_in_bytes", None),
            "temp_bytes": getattr(mem, "temp_size_in_bytes", None),
            "peak_bytes": getattr(
                mem, "peak_memory_in_bytes",
                getattr(mem, "temp_size_in_bytes", None)),
        },
    )
    if verbose:
        print(f"[OK] {arch} x {shape_name} @ {cell['mesh']} "
              f"(lower {t_lower:.0f}s, compile {t_compile:.0f}s)")
        plans = {(c["axis"], c["monoid"], c["nbytes"]):
                 f"{c['algorithm']}/S{c['segments']}"
                 for c in cell["scan_plan_checks"]}
        print(f"  scan plans verified (simulator): {plans}")
        print(f"  memory_analysis: {cell['memory_analysis']}")
        print(f"  cost: {roof.flops:.3e} FLOP/dev, "
              f"{roof.bytes_hbm:.3e} B/dev, "
              f"{roof.collective.total_bytes:.3e} wire B "
              f"{dict(roof.collective.op_counts)}")
        print(f"  roofline: compute {roof.compute_s*1e3:.2f} ms | "
              f"memory {roof.memory_s*1e3:.2f} ms | "
              f"collective {roof.collective_s*1e3:.2f} ms "
              f"-> {roof.dominant}-bound; "
              f"useful/HLO flops {roof.useful_flops_fraction:.2f}; "
              f"MFU bound {roof.mfu_bound:.2f}")
    return cell


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None,
                    choices=list(steps_lib.SHAPES))
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--both-meshes", action="store_true")
    ap.add_argument("--json", default=None)
    ap.add_argument("--strategy", default="tp",
                    choices=["tp", "fsdp_sp", "decode_ws"])
    ap.add_argument("--no-remat", action="store_true")
    ap.add_argument("--remat-policy", default="nothing",
                    choices=["nothing", "dots"])
    ap.add_argument("--no-probes", action="store_true",
                    help="skip cost probes (compile-only pass)")
    ap.add_argument("--exscan", default=None,
                    choices=["auto", "123", "1doubling", "two_op",
                             "native", "ring"])
    ap.add_argument("--profile-dir", default=None,
                    help="calibrated cost-profile store (default: "
                         "tune/profiles or $REPRO_PROFILE_DIR)")
    args = ap.parse_args()

    assert jax.device_count() == 512, (
        "dry-run must see 512 placeholder devices")

    cells = []
    if args.all:
        targets = [(a, s) for a in configs.ARCHITECTURES
                   for s in steps_lib.SHAPES]
    else:
        assert args.arch and args.shape, "--arch/--shape or --all"
        targets = [(args.arch, args.shape)]

    meshes = [False, True] if args.both_meshes else [args.multi_pod]
    failures = 0
    for multi_pod in meshes:
        for arch, shape in targets:
            try:
                cells.append(run_cell(
                    arch, shape, multi_pod, strategy=args.strategy,
                    probes=not args.no_probes,
                    profile_dir=args.profile_dir,
                    **(({"remat": False} if args.no_remat else {})
                       | ({"remat_policy": args.remat_policy}
                          if args.remat_policy != "nothing" else {})
                       | ({"scan": ScanSpec(kind="exclusive",
                                            algorithm=args.exscan)}
                          if args.exscan else {}))))
            except Exception as e:  # noqa: BLE001
                failures += 1
                traceback.print_exc()
                cells.append({"arch": arch, "shape": shape,
                              "mesh": "2x16x16" if multi_pod else "16x16",
                              "status": "FAILED", "error": str(e)[:500]})
    if args.json:
        with open(args.json, "w") as f:
            json.dump(cells, f, indent=1)
        print(f"wrote {args.json}")
    print(f"\n{sum(1 for c in cells if c['status'] == 'ok')} ok, "
          f"{sum(1 for c in cells if c['status'] == 'skipped')} skipped, "
          f"{failures} failed")
    raise SystemExit(1 if failures else 0)


if __name__ == "__main__":
    main()
