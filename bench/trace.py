"""Reduction of a profiler trace to the numbers the per-layer metrics
read: device busy time and idle share, device time by op name, by
category and by program scope, and the idle gaps attributed to the
harness's host spans.

``reduce_events`` works on plain lists so that it can be checked on a
hand-made trace; ``from_xplane`` extracts those lists from the
``.xplane.pb`` file the JAX profiler writes, each device op with its
name path (``op_paths``), which carries the ``jax.named_scope``s the
program opens (PERF.md section 3 maps each scope to the code that opens
it).
"""

from __future__ import annotations

import collections
import glob
import os
import re

# host spans the harness records (jax.profiler.TraceAnnotation)
SPANS = ("setup", "step_dispatch", "sample_sync", "traced")
# ops whose events span the ops nested in them: busy time, not op time
CONTAINERS = ("while", "conditional", "call")
COLLECTIVES = ("all-to-all", "all-gather", "all-reduce",
               "collective-permute", "reduce-scatter")
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


_HLO = re.compile(r"^%?(\S+) = .*? ([a-z][\w-]*)\(")


def parse_op(text: str) -> tuple[str, str]:
    """(short name, op kind) of a device op event, whose name is its
    HLO instruction text (``%fusion.3 = bf16[..] fusion(..)``) or a
    bare name."""
    m = _HLO.match(text)
    if m:
        return m.group(1), m.group(2)
    return text, text.split(".")[0]


def category(kind: str) -> str:
    """Collective family of an op kind (``all-gather-start`` ->
    ``all-gather``), else the kind itself."""
    for c in COLLECTIVES:
        if kind.startswith(c):
            return c
    return kind


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


def _union(intervals):
    """Merged, sorted (start, end) intervals."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return out


def reduce_events(device_ops: dict, host_spans: list, window: tuple):
    """device_ops: {device: [(name, start_ns, dur_ns, kind[, path]),
    ...]}, ``path`` the op's name path ("" or left out where it has
    none); host_spans: [(name, start_ns, dur_ns)]; window: (start_ns,
    end_ns).

    Every number is per device, averaged over the devices given, and
    covers the whole window.  Container ops (a ``while`` and the ops of
    its body overlap) count toward busy time only.
    Returns a dict with ``window_s``, ``busy_s``, ``idle_share``,
    ``op_s`` {name: s}, ``category_s`` {category: s}, ``op_count``
    {name: n}, ``scope_s`` and ``scope_count`` {program scope: s, n}
    (an op counts toward every scope on its path, one with none toward
    ``(unscoped)``), ``path_s`` {(name, scope path): s}, and
    ``idle_by_span`` {span: s}: idle time whose midpoint falls in the
    innermost host span open at that moment (``other`` where none
    is)."""
    w0, w1 = window
    n = max(len(device_ops), 1)
    op_s = collections.Counter()
    cat_s = collections.Counter()
    op_count = collections.Counter()
    scope_s = collections.Counter()
    scope_count = collections.Counter()
    path_s = collections.Counter()
    scopes_of = {}
    busy = 0
    idle_by_span = collections.Counter()
    spans = sorted((s, s + d, name) for name, s, d in host_spans
                   if name in SPANS and name != "traced")
    for ops in device_ops.values():
        clipped = []
        for name, s, d, kind, *path in ops:
            s0, s1 = max(s, w0), min(s + d, w1)
            if s1 <= s0:
                continue
            clipped.append((s0, s1))
            if kind in CONTAINERS:
                continue
            dt = (s1 - s0) / n
            op_s[name] += dt
            cat_s[category(kind)] += dt
            op_count[name] += 1
            path = path[0] if path else ""
            if path not in scopes_of:
                scopes_of[path] = program_scopes(path)
            scopes = scopes_of[path]
            path_s[(name, "/".join(scopes))] += dt
            for scope in scopes or (UNSCOPED,):
                scope_s[scope] += dt
                scope_count[scope] += 1 / n
        merged = _union(clipped)
        busy += sum(e - s for s, e in merged) / n
        edges = [w0] + [x for iv in merged for x in iv] + [w1]
        for g0, g1 in zip(edges[::2], edges[1::2]):
            if g1 <= g0:
                continue
            mid = (g0 + g1) / 2
            inner = [(s, e, nm) for s, e, nm in spans if s <= mid < e]
            label = max(inner)[2] if inner else "other"
            idle_by_span[label] += (g1 - g0) / n
    window_s = (w1 - w0) * 1e-9
    return {
        "window_s": window_s,
        "busy_s": busy * 1e-9,
        "idle_share": 1.0 - busy * 1e-9 / window_s if window_s else None,
        "op_s": {k: v * 1e-9 for k, v in op_s.items()},
        "category_s": {k: v * 1e-9 for k, v in cat_s.items()},
        "op_count": {k: v / n for k, v in op_count.items()},
        "scope_s": {k: v * 1e-9 for k, v in scope_s.items()},
        "scope_count": dict(scope_count),
        "path_s": {k: v * 1e-9 for k, v in path_s.items()},
        "idle_by_span": {k: v * 1e-9 for k, v in idle_by_span.items()},
    }


def breakdown(reduced: dict, top: int = 10) -> dict:
    ops = sorted(reduced["op_s"].items(), key=lambda kv: -kv[1])[:top]
    gaps = sorted(reduced["idle_by_span"].items(),
                  key=lambda kv: -kv[1])[:top]
    return {"device_ops": [[k, v] for k, v in ops],
            "idle_gaps": [[k, v] for k, v in gaps]}


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


def from_xplane(trace_dir: str, n_devices: int, op_line: str = "XLA Ops",
                host_names=SPANS):
    """(device_ops, host_spans, window) from the newest ``.xplane.pb``
    under ``trace_dir``; the window is the harness's ``traced`` span.
    Device planes are the first ``n_devices`` ``/device:`` planes by
    name (host and device events share one clock in the file); each op
    is (name, start_ns, dur_ns, kind, name path).  Host events are
    those named in ``host_names``, or all where it is None."""
    from jax.profiler import ProfileData

    files = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    newest = max(files, key=os.path.getmtime)
    pd = ProfileData.from_file(newest)
    with open(newest, "rb") as f:
        paths = op_paths(f.read())
    host_spans, device_ops = [], {}
    for plane in pd.planes:  # one pass: the views are read in place
        name = plane.name
        if name.startswith("/host:"):
            for line in plane.lines:
                host_spans += [(ev.name, ev.start_ns, ev.duration_ns)
                               for ev in line.events
                               if host_names is None
                               or ev.name in host_names]
        elif name.startswith("/device:") and "CPU" not in name:
            known = paths.get(name, {})
            for line in plane.lines:
                if line.name == op_line:
                    device_ops[name] = [
                        (*parse_op(ev.name), ev.start_ns, ev.duration_ns,
                         known.get(ev.name, ""))
                        for ev in line.events]
    device_ops = {k: [(n, s, d, kind, p) for n, kind, s, d, p in v]
                  for k, v in sorted(device_ops.items())[:n_devices]}
    traced = [(s, s + d) for name, s, d in host_spans if name == "traced"]
    if not traced:
        raise ValueError("the trace holds no 'traced' host span")
    return device_ops, host_spans, traced[-1]
