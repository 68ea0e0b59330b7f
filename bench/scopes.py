"""Device time of a traced run by program scope.

    python3 bench/scopes.py --workload <name> --seed <n> --seconds <s> \\
        [--trace-steps N] [--out FILE]

Runs the cell as ``bench/run.py --trace 1`` does (its result line comes
first on standard output), then reads the same profile once more, this
time keeping each device op's name path: the HLO ``op_name`` of the op,
which carries the ``jax.named_scope``s the program opens (PERF.md
section 3 maps each scope to the code that opens it).  The last line of
standard output (and a line of ``--out``) is one JSON object:

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
import collections
import glob
import json
import os
import re
import sys
import time
import types

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

from bench import run as harness, spec, trace as trace_lib  # noqa: E402

# The program's scopes (jax.named_scope in src/repro): the model
# (models/model.py), attention (models/attention.py), the MoE layer
# (models/moe.py) and the scan executor's runs and local steps
# (core/schedule.py); its rounds are ``round<i>.<kind>`` and a scan call
# is ``exscan.<schedule>`` (core/scan_api.py).  Every other part of an
# op's name path is JAX's own (``jit(..)``, ``while``, ``body``, einsum
# specs) and is not a scope.
SCOPES = frozenset((
    "embed", "layers", "head", "ffn",
    "attn", "qkv", "kv_cache", "attn_core", "attn_out",
    "moe", "router", "routing", "dispatch_scan", "dispatch",
    "all_to_all", "experts", "combine", "shared_expert",
    "scan_reduce", "seg_shift", "block_exchange", "allgather", "fold",
    "bcast"))
_SCOPE = re.compile(r"round\d+\.\w+|exscan\..+")
TOP_LEVEL = ("embed", "layers", "head")
UNSCOPED = "(unscoped)"
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
TOP = 12  # ops listed
STALL_NS = 50e6  # an idle gap this long inside a dispatch is a stall


def program_scopes(path: str) -> tuple[str, ...]:
    """The program scopes on an op's name path, outermost first, each
    once: ``jit(step)/layers/while/body/attn/kv_cache/
    dynamic_update_slice`` -> ``("layers", "attn", "kv_cache")``.  An
    op that XLA merged from several carries their paths joined by
    ``;``: the first is read, as a fusion carries one op's."""
    out = []
    first = path.split(";")[0]
    for part in first.split("/")[:-1]:  # the last part is the op itself
        if (part in SCOPES or _SCOPE.fullmatch(part)) and part not in out:
            out.append(part)
    return tuple(out)


def _varint(buf, i):
    out = shift = 0
    while True:
        byte = buf[i]
        i += 1
        out |= (byte & 0x7F) << shift
        if byte < 0x80:
            return out, i
        shift += 7


def _fields(buf):
    """(field number, value) of each field of a protobuf message: an
    int for a varint, a memoryview for a length-delimited field, None
    for a fixed-width one."""
    i = 0
    while i < len(buf):
        key, i = _varint(buf, i)
        wire = key & 7
        if wire == 0:
            value, i = _varint(buf, i)
        elif wire == 2:
            size, i = _varint(buf, i)
            value, i = buf[i:i + size], i + size
        elif wire in (1, 5):
            value, i = None, i + (8 if wire == 1 else 4)
        else:
            raise ValueError(f"protobuf wire type {wire} is not read here")
        yield key >> 3, value


def op_paths(data: bytes) -> dict:
    """{device plane name: {op event name: name path}} from the bytes
    of an ``.xplane.pb`` (an XSpace).  A device op's name path is the
    ``tf_op`` stat of its event metadata, the HLO ``op_name`` without
    its ``:type`` suffix; ``jax.profiler.ProfileData`` gives events but
    not their metadata, so the XSpace is read here at the wire level:
    XSpace.planes = 1; XPlane.name = 2, .event_metadata = 4 and
    .stat_metadata = 5 (maps: key 1, value 2); XEventMetadata.name = 2,
    .stats = 5; XStatMetadata.name = 2; XStat.metadata_id = 1,
    .str_value = 5, .ref_value = 7."""
    out = {}
    for num, plane in _fields(memoryview(data)):
        if num != 1:
            continue
        fields = list(_fields(plane))
        name = next((bytes(v).decode() for f, v in fields if f == 2), "")
        if not name.startswith("/device:"):
            continue
        stat_names = {}
        for f, entry in fields:
            if f == 5:
                md = dict(_fields(entry)).get(2, b"")
                stat_names[dict(_fields(md)).get(1)] = bytes(
                    dict(_fields(md)).get(2, b"")).decode()
        tf_op = {k for k, v in stat_names.items() if v == "tf_op"}
        paths = {}
        for f, entry in fields:
            if f != 4:
                continue
            md = dict(_fields(entry)).get(2, b"")
            ev_name, path = None, ""
            for g, v in _fields(md):
                if g == 2:
                    ev_name = bytes(v).decode()
                elif g == 5:
                    stat = dict(_fields(v))
                    if stat.get(1) in tf_op:
                        path = (bytes(stat[5]).decode() if 5 in stat
                                else stat_names.get(stat.get(7), ""))
            if ev_name is not None:
                tail = path.rsplit("/", 1)[-1]
                paths[ev_name] = path.rsplit(":", 1)[0] if ":" in tail \
                    else path
        out[name] = paths
    return out


def reduce_scopes(device_ops: dict, window: tuple) -> dict:
    """device_ops: {device: [(name, start_ns, dur_ns, kind, path)]};
    window: (start_ns, end_ns).  Per device, averaged over the devices:
    ``scope_s`` and ``scope_count`` by program scope (``(unscoped)`` for
    an op with none), ``op_s`` (all ops), and ``path_s`` {(name, scope
    path): s}.  Container ops (``while`` and the like) are left out, as
    ``trace.reduce_events`` leaves them out of op time."""
    w0, w1 = window
    n = max(len(device_ops), 1)
    scope_s = collections.Counter()
    scope_count = collections.Counter()
    path_s = collections.Counter()
    op_s = 0.0
    for ops in device_ops.values():
        for name, s, d, kind, path in ops:
            dt = min(s + d, w1) - max(s, w0)
            if dt <= 0 or kind in trace_lib.CONTAINERS:
                continue
            scopes = program_scopes(path)
            op_s += dt / n
            path_s[(name, "/".join(scopes))] += dt / n
            for scope in scopes or (UNSCOPED,):
                scope_s[scope] += dt / n
                scope_count[scope] += 1 / n
    return {"scope_s": {k: v * 1e-9 for k, v in scope_s.items()},
            "scope_count": dict(scope_count),
            "op_s": op_s * 1e-9,
            "path_s": {k: v * 1e-9 for k, v in path_s.items()}}


def read_profile(trace_dir: str, n_devices: int):
    """(device_ops with name paths, host events) from the newest
    ``.xplane.pb`` under ``trace_dir``; the device planes are those
    ``trace.from_xplane`` reads, host events are every event of the
    host planes as (name, start_ns, dur_ns)."""
    from jax.profiler import ProfileData

    files = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    newest = max(files, key=os.path.getmtime)
    pd = ProfileData.from_file(newest)
    with open(newest, "rb") as f:
        paths = op_paths(f.read())
    device_ops, host = {}, []
    for plane in pd.planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                host += [(ev.name, ev.start_ns, ev.duration_ns)
                         for ev in line.events]
        elif plane.name.startswith("/device:") and "CPU" not in plane.name:
            for line in plane.lines:
                if line.name == "XLA Ops":
                    known = paths.get(plane.name, {})
                    device_ops[plane.name] = [
                        (*trace_lib.parse_op(ev.name), ev.start_ns,
                         ev.duration_ns, known.get(ev.name, ""))
                        for ev in line.events]
    device_ops = {k: [(nm, s, d, kind, p) for nm, kind, s, d, p in v]
                  for k, v in sorted(device_ops.items())[:n_devices]}
    return device_ops, host


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
    _, host_spans, window = trace_lib.from_xplane(kept[0],
                                                  len(run.devices))
    device_ops, host = read_profile(kept[0], len(run.devices))
    reduced = trace_lib.reduce_events(
        {k: [op[:4] for op in v] for k, v in device_ops.items()},
        host_spans, window)
    scoped = reduce_scopes(device_ops, window)
    steps = run.facts["traced_steps"]
    per = 1e3 / steps
    t0, t1 = run.t_window, run.t_window + run.facts["window_s"]
    traced_dispatch = sorted(d for nm, s, d in host_spans
                             if nm == "step_dispatch"
                             and window[0] <= s < window[1])
    untraced = run.facts.get("host_dispatch_s") or []
    top = sorted(scoped["path_s"].items(), key=lambda kv: -kv[1])
    line = {
        "workload": args.workload, "seed": args.seed,
        "traced_steps": steps,
        "scope_ms": {k: v * per for k, v in sorted(
            scoped["scope_s"].items(), key=lambda kv: -kv[1])},
        "scope_count": {k: v / steps for k, v in sorted(
            scoped["scope_count"].items())},
        "op_ms": scoped["op_s"] * per,
        "top_level_ms": sum(scoped["scope_s"].get(k, 0.0)
                            for k in TOP_LEVEL + (UNSCOPED,)) * per,
        "busy_ms": reduced["busy_s"] * per,
        "category_ms": {k: v * per for k, v in
                        reduced["category_s"].items()},
        "top_ops": [[nm, s * per, path or UNSCOPED]
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
