"""Runs one cell of BENCHMARK.json once and prints its result line.

    python3 bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Loads the cell's configuration and traffic mix, builds and warms up the
system under test (set-up), measures for ``--seconds``, checks what the
timed path produced against the plain reference, and prints one JSON
object as the last line of standard output: ``correct``,
``attempted``, ``failed``, ``metrics`` (the cell's end-to-end metrics,
or with ``--trace 1`` its per-layer metrics), ``device``, with
``--trace 1`` ``breakdown``, and last ``checks``, each compared number
beside its limit (also the last lines of standard error).

There is no CPU path: where JAX finds no TPU, or fewer chips than the
cell asks for, it exits non-zero and prints no result.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import contextlib  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

from bench import spec, trace as trace_lib  # noqa: E402

PLATFORM = "tpu"  # the only platform a run accepts
# test hooks: a benchmark in place of BENCHMARK.json, and entries that
# replace those of the loaded configuration / traffic
BENCHMARK: dict | None = None
CONFIG_OVERRIDES: dict = {}
TRAFFIC_OVERRIDES: dict = {}


class Run:
    """What a driver gets: the cell, the seed and devices, and the
    harness's clock, host spans and tracer."""

    def __init__(self, cell, seed, seconds, trace, devices):
        self.cell, self.seed, self.seconds = cell, seed, seconds
        self.trace, self.devices = trace, devices
        self.t_window = None
        self.trace_dir = None
        self.facts: dict = {}

    @staticmethod
    def span(name):
        import jax

        return jax.profiler.TraceAnnotation(name)

    def window_begin(self) -> float:
        """Start the measured window.  What set-up left is collected
        once and frozen, so that no full collection of the garbage
        collector walks it inside the window."""
        gc.collect()
        gc.freeze()
        self.t_window = time.perf_counter()
        return self.t_window

    @contextlib.contextmanager
    def traced(self):
        """Profile the enclosed steps (``--trace 1`` only)."""
        if not self.trace:
            yield
            return
        import jax

        self.trace_dir = tempfile.mkdtemp(prefix="bench_trace_")
        jax.profiler.start_trace(self.trace_dir)
        try:
            with self.span("traced"):
                yield
        finally:
            jax.profiler.stop_trace()


def load(workload: str):
    cell = spec.load_cell(workload, BENCHMARK)
    cell.config = {**cell.config, **CONFIG_OVERRIDES}
    cell.traffic = {**cell.traffic, **TRAFFIC_OVERRIDES}
    return cell


def _device_check(chips: int):
    import jax

    devices = jax.devices()
    if devices[0].platform != PLATFORM:
        sys.exit(f"bench: no TPU found (JAX reports platform "
                 f"{devices[0].platform!r})")
    if len(devices) < chips:
        sys.exit(f"bench: the cell needs {chips} chips, JAX reports "
                 f"{len(devices)}")
    return devices[:chips]


def _compile_cache():
    """JAX's persistent cache in ``$JAX_COMPILATION_CACHE_DIR`` or at
    the fixed path ``<checkout>/.jax_cache``; every compile is kept."""
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or os.path.join(
        ROOT, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


def judge(values: dict, limits: dict) -> tuple[bool, dict]:
    """(correct, {name: {"value", "limit"}}): every compared number at
    or under its limit."""
    checks = {name: {"value": values[name], "limit": limit}
              for name, limit in limits.items()}
    return all(c["value"] <= c["limit"] for c in checks.values()), checks


def _per_layer(run, cell, reduced):
    facts = dict(run.facts, trace=reduced)
    out = {}
    for m in cell.per_layer:
        value = spec.metric_reader(m["name"])(facts)
        if value is not None:
            out[m["name"]] = {"value": value, "unit": m["unit"]}
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    cell = load(args.workload)
    devices = _device_check(cell.chips)
    _compile_cache()
    kind = devices[0].device_kind
    if PLATFORM == "tpu":
        spec.peaks(kind)  # a device outside the peaks table is an error

    run = Run(cell, args.seed, args.seconds, bool(args.trace), devices)
    run.facts.update(kind=cell.traffic["kind"], device_kind=kind,
                     chips=len(devices))
    driver = spec.driver(cell.traffic["kind"])
    result = driver.drive(run)
    setup_s = run.t_window - T_START

    correct, checks = judge(result["checks"], cell.limits)
    device = {"platform": devices[0].platform, "kind": kind,
              "count": len(devices),
              "memory_peak_bytes": result["memory_peak_bytes"]}
    out = {"correct": correct, "attempted": result["attempted"],
           "failed": result["failed"] if correct else max(
               1, result["failed"])}
    if args.trace:
        device_ops, host_spans, window = trace_lib.from_xplane(
            run.trace_dir, len(devices))
        reduced = trace_lib.reduce_events(device_ops, host_spans, window)
        shutil.rmtree(run.trace_dir, ignore_errors=True)
        out["metrics"] = _per_layer(run, cell, reduced)
        device.update(busy_s=reduced["busy_s"],
                      window_s=reduced["window_s"])
        out["device"] = device
        out["breakdown"] = trace_lib.breakdown(reduced)
    else:
        metrics = dict(result["metrics"], setup_s=setup_s)
        units = {m["name"]: m["unit"] for m in cell.end_to_end}
        out["metrics"] = {k: {"value": v, "unit": units[k]}
                          for k, v in metrics.items() if k in units}
        out["device"] = device
    out["checks"] = checks
    for name, c in checks.items():
        print(f"check {name} = {c['value']!r} (limit {c['limit']!r})",
              file=sys.stderr, flush=True)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
