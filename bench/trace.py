"""Reduction of a profiler trace to the numbers the per-layer metrics
read: device busy time and idle share, device time by op name and by
category, and the idle gaps attributed to the harness's host spans.

``reduce_events`` works on plain lists so that it can be checked on a
hand-made trace; ``from_xplane`` extracts those lists from the
``.xplane.pb`` file the JAX profiler writes.
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
    """device_ops: {device: [(name, start_ns, dur_ns, kind), ...]};
    host_spans: [(name, start_ns, dur_ns)]; window: (start_ns, end_ns).

    Every number is per device, averaged over the devices given.
    Container ops (a ``while`` and the ops of its body overlap) count
    toward busy time only.
    Returns a dict with ``window_s``, ``busy_s``, ``idle_share``,
    ``op_s`` {name: s}, ``category_s`` {category: s}, ``op_count``
    {name: n}, and ``idle_by_span`` {span: s}: idle time whose midpoint
    falls in the innermost host span open at that moment (``other``
    where none is)."""
    w0, w1 = window
    n = max(len(device_ops), 1)
    op_s = collections.Counter()
    cat_s = collections.Counter()
    op_count = collections.Counter()
    busy = 0
    idle_by_span = collections.Counter()
    spans = sorted((s, s + d, name) for name, s, d in host_spans
                   if name in SPANS and name != "traced")
    for ops in device_ops.values():
        clipped = []
        for name, s, d, kind in ops:
            s0, s1 = max(s, w0), min(s + d, w1)
            if s1 <= s0:
                continue
            clipped.append((s0, s1))
            if kind in CONTAINERS:
                continue
            op_s[name] += (s1 - s0) / n
            cat_s[category(kind)] += (s1 - s0) / n
            op_count[name] += 1
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
        "idle_by_span": {k: v * 1e-9 for k, v in idle_by_span.items()},
    }


def breakdown(reduced: dict, top: int = 10) -> dict:
    ops = sorted(reduced["op_s"].items(), key=lambda kv: -kv[1])[:top]
    gaps = sorted(reduced["idle_by_span"].items(),
                  key=lambda kv: -kv[1])[:top]
    return {"device_ops": [[k, v] for k, v in ops],
            "idle_gaps": [[k, v] for k, v in gaps]}


def from_xplane(trace_dir: str, n_devices: int, op_line: str = "XLA Ops"):
    """(device_ops, host_spans, window) from the newest ``.xplane.pb``
    under ``trace_dir``; the window is the harness's ``traced`` span.
    Device planes are the first ``n_devices`` ``/device:`` planes by
    name (host and device events share one clock in the file)."""
    from jax.profiler import ProfileData

    files = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    pd = ProfileData.from_file(max(files, key=os.path.getmtime))
    host_spans, device_ops = [], {}
    for plane in pd.planes:  # one pass: the views are read in place
        name = plane.name
        if name.startswith("/host:"):
            for line in plane.lines:
                host_spans += [(ev.name, ev.start_ns, ev.duration_ns)
                               for ev in line.events if ev.name in SPANS]
        elif name.startswith("/device:") and "CPU" not in name:
            for line in plane.lines:
                if line.name == op_line:
                    device_ops[name] = [
                        (*parse_op(ev.name), ev.start_ns, ev.duration_ns)
                        for ev in line.events]
    device_ops = {k: [(n, s, d, kind) for n, kind, s, d in v]
                  for k, v in sorted(device_ops.items())[:n_devices]}
    traced = [(s, s + d) for name, s, d in host_spans if name == "traced"]
    if not traced:
        raise ValueError("the trace holds no 'traced' host span")
    return device_ops, host_spans, traced[-1]
