"""Device time of a traced run by program scope, for a reader.

    python3 bench/scopes.py --workload <name> --seed <n> --seconds <s> \\
        [--trace-steps N] [--out FILE]

Runs the cell as ``bench/run.py --trace 1`` does (its result line comes
first on standard output), then reads the same profile once more with
``trace.from_xplane`` and ``trace.reduce_events`` (each device op's name
path carries the ``jax.named_scope``s the program opens; PERF.md section
3 maps each scope to the code that opens it), keeping every host event.
The last line of standard output (and a line of ``--out``) is one JSON
object:

- ``scope_ms``: device milliseconds per traced step by program scope.
  An op counts toward every scope on its path, an op with none toward
  ``(unscoped)``; so ``embed`` + ``layers`` + ``head`` + ``(unscoped)``
  add up to ``op_ms`` when every scope lies inside those three.
- ``scope_count``: op executions per traced step by scope.
- ``top_ops``: the ops that take most time, with their scope path.  A
  fusion carries the metadata of one of the ops fused into it.
- ``window``: the untraced window's end-to-end metrics and ``setup_s``,
  and what tracing costs: step time and host dispatch time per step,
  untraced against traced (device busy time, ``busy_ms``, is known only
  traced).
- ``compiles_in_window``: backend compilations while the measured
  window ran (``jax.monitoring``), which should be 0.
- ``stalls``: each idle gap of the device over 50 ms inside a
  ``step_dispatch`` host span, with the host events open at its middle.

The harness does not call this tool, and it changes nothing a benchmark
run measures.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time
import types

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

from bench import run as harness, spec, trace as trace_lib  # noqa: E402

COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
TOP = 12  # ops listed
STALL_NS = 50e6  # an idle gap this long inside a dispatch is a stall


def stalls(device_ops: dict, host: list, window: tuple,
           min_gap_ns: float) -> list:
    """Device idle gaps of at least ``min_gap_ns`` whose middle lies in
    a ``step_dispatch`` span, each with the host events open at its
    middle (longest first)."""
    w0, w1 = window
    dispatch = [(s, s + d) for nm, s, d in host if nm == "step_dispatch"]
    out = []
    for dev, ops in device_ops.items():
        busy = trace_lib._union([(max(s, w0), min(s + d, w1))
                                 for _, s, d, _, _ in ops
                                 if min(s + d, w1) > max(s, w0)])
        edges = [w0] + [x for iv in busy for x in iv] + [w1]
        for g0, g1 in zip(edges[::2], edges[1::2]):
            mid = (g0 + g1) / 2
            if g1 - g0 < min_gap_ns or not any(
                    s <= mid < e for s, e in dispatch):
                continue
            open_ = sorted(((nm, d) for nm, s, d in host
                            if s <= mid < s + d), key=lambda e: -e[1])
            out.append({"device": dev, "gap_ms": (g1 - g0) * 1e-6,
                        "host_events": [[nm, d * 1e-6]
                                        for nm, d in open_[:12]]})
    return out


def _compile_counter():
    """Wall-clock times of the backend compilations from now on."""
    import jax

    times = []

    def listen(event, duration_secs, **kwargs):
        if event == COMPILE_EVENT:
            times.append(time.perf_counter())

    jax.monitoring.register_event_duration_secs_listener(listen)
    return times


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace-steps", type=int, default=0,
                    help="traced steps (or batches) in place of the "
                         "traffic mix's own")
    ap.add_argument("--out", default="")
    args = ap.parse_args(argv)

    kept, runs, results = [], [], []
    # keep the profile bench/run.py would delete, and the run and what
    # the traffic kind's module returned, to read after its result line
    harness.shutil = types.SimpleNamespace(
        rmtree=lambda path, **kw: kept.append(path))
    find_module = spec.driver

    def recording(kind):
        mod = find_module(kind)

        def drive(run):
            runs.append(run)
            results.append(mod.drive(run))
            return results[-1]

        return types.SimpleNamespace(drive=drive)

    harness.spec.driver = recording
    if args.trace_steps:
        key = ("trace_steps" if harness.load(args.workload).traffic[
            "kind"] == "decode" else "trace_batches")
        harness.TRAFFIC_OVERRIDES = {**harness.TRAFFIC_OVERRIDES,
                                     key: args.trace_steps}
    compiles = _compile_counter()
    rc = harness.main(["--workload", args.workload, "--seed",
                       str(args.seed), "--seconds", str(args.seconds),
                       "--trace", "1"])
    run, result = runs[0], results[0]
    device_ops, host, window = trace_lib.from_xplane(
        kept[0], len(run.devices), host_names=None)
    host_spans = [e for e in host if e[0] in trace_lib.SPANS]
    reduced = trace_lib.reduce_events(device_ops, host_spans, window)
    steps = run.facts["traced_steps"]
    per = 1e3 / steps
    t0, t1 = run.t_window, run.t_window + run.facts["window_s"]
    traced_dispatch = sorted(d for nm, s, d in host_spans
                             if nm == "step_dispatch"
                             and window[0] <= s < window[1])
    untraced = run.facts.get("host_dispatch_s") or []
    top = sorted(reduced["path_s"].items(), key=lambda kv: -kv[1])
    line = {
        "workload": args.workload, "seed": args.seed,
        "traced_steps": steps,
        "scope_ms": {k: v * per for k, v in sorted(
            reduced["scope_s"].items(), key=lambda kv: -kv[1])},
        "scope_count": {k: v / steps for k, v in sorted(
            reduced["scope_count"].items())},
        "op_ms": sum(reduced["op_s"].values()) * per,
        "top_level_ms": sum(reduced["scope_s"].get(k, 0.0) for k in
                            trace_lib.TOP_LEVEL + (trace_lib.UNSCOPED,))
        * per,
        "busy_ms": reduced["busy_s"] * per,
        "category_ms": {k: v * per for k, v in
                        reduced["category_s"].items()},
        "top_ops": [[nm, s * per, path or trace_lib.UNSCOPED]
                    for (nm, path), s in top[:TOP]],
        "top_unscoped": [[nm, s * per] for (nm, path), s in top
                         if not path][:TOP],
        "window": {
            "metrics": dict(result["metrics"],
                            setup_s=run.t_window - harness.T_START),
            "step_ms_untraced": run.facts["window_s"] / max(
                run.facts.get("steps", 0), 1) * 1e3,
            "step_ms_traced": reduced["window_s"] * per,
            "dispatch_ms_untraced": (sorted(untraced)[len(untraced) // 2]
                                     * 1e3 if untraced else None),
            "dispatch_ms_traced": (traced_dispatch[len(traced_dispatch)
                                                   // 2] * 1e-6
                                   if traced_dispatch else None),
        },
        "compiles_in_window": sum(t0 <= t < t1 for t in compiles),
        "stalls": stalls(device_ops, host, window, STALL_NS),
    }
    text = json.dumps(line)
    print(text, flush=True)
    if args.out:
        with open(args.out, "a") as f:
            f.write(text + "\n")
    return rc


if __name__ == "__main__":
    sys.exit(main())
