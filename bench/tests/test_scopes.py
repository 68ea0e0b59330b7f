"""Checks of the reduction by program scope (``bench/trace.py``), the
scope metrics, ``bench/scopes.py`` and the expert-parallel cell on CPU.

- the reduction by program scope on a hand-made trace (nested scopes,
  an op with none, a container op, two devices, the window's edges),
  and the scope metrics' helper on the same trace;
- the tool rehearsed end to end on CPU devices;
- the float32 reference against the program's forward with the
  qwen2-moe layer (shared expert, untied head) over one and four
  ranks, and the planted fault of the expert-parallel cell: the
  dispatch ``all_to_all`` left out must come out not correct.

Run: ``python -m pytest bench/tests -q`` from the repository root.
"""

from __future__ import annotations

import json

import pytest

from bench.tests import test_bench_harness as harness_tests

ROOT = harness_tests.ROOT
QWEN_CELL = "qwen2-moe.ep4.decode"


# ---------------------------------------------------------------- paths


def test_program_scopes_of_a_path():
    from bench import trace as scopes

    path = ("jit(step)/jit(main)/layers/while/body/closed_call/moe/"
            "dispatch_scan/exscan.fused_doubling/scan_reduce/"
            "round1.scan_reduce/ppermute")
    assert scopes.program_scopes(path) == (
        "layers", "moe", "dispatch_scan", "exscan.fused_doubling",
        "scan_reduce", "round1.scan_reduce")
    # einsum specs, JAX's own names and the op itself are no scopes;
    # a scope met twice counts once
    assert scopes.program_scopes(
        "jit(step)/layers/attn/qkv/bsd,dh->bsh/dot_general") == (
        "layers", "attn", "qkv")
    assert scopes.program_scopes("jit(step)/head/head/dot_general") == (
        "head",)
    assert scopes.program_scopes("jit(step)/argmax") == ()
    assert scopes.program_scopes("") == ()
    # the op's own name is never a scope, even where it reads like one
    assert scopes.program_scopes("jit(step)/embed") == ()
    # an op XLA merged from several carries their paths joined by ";"
    assert scopes.program_scopes(
        "jit(f)/layers/attn/attn_out/reshape;attn/attn_core/reshape") == (
        "layers", "attn", "attn_out")


def _message(*fields):
    """Protobuf wire bytes of (field number, int | bytes | str)."""
    def varint(v):
        out = b""
        while True:
            out += bytes([(v & 0x7F) | (0x80 if v > 0x7F else 0)])
            v >>= 7
            if not v:
                return out

    out = b""
    for num, value in fields:
        if isinstance(value, int):
            out += varint(num << 3) + varint(value)
        else:
            value = value.encode() if isinstance(value, str) else value
            out += varint(num << 3 | 2) + varint(len(value)) + value
    return out


def test_op_paths_read_from_event_metadata():
    """A hand-built XSpace: a device plane whose event metadata carry
    ``tf_op`` as a string and as a reference to a stat name, an event
    with no ``tf_op`` (an op XLA inserted), and a host plane that is
    not read; fixed-width fields are skipped."""
    from bench import trace

    def stat_md(i, name):  # stat_metadata map entry
        return _message((1, i), (2, _message((1, i), (2, name))))

    def event_md(i, name, *stats):  # event_metadata map entry
        return _message((1, i), (2, _message(
            (1, i), (2, name), (4, name.split()[0]),
            *[(5, st) for st in stats])))

    tf_op = lambda value: _message((1, 7), (5, value))  # noqa: E731
    device = _message(
        (1, 3), (2, "/device:TPU:0"),
        (5, stat_md(7, "tf_op")), (5, stat_md(9, "flops")),
        (5, stat_md(11, "jit(f)/head/dot_general:dot_general")),
        (4, event_md(1, "%fusion.1 = f32[8]", tf_op(
            "jit(f)/layers/while/body/attn/kv_cache/add:"))),
        # a flops stat, with a double (a fixed64 field) beside its int
        (4, event_md(2, "%copy.5 = f32[8]",
                     _message((1, 9), (4, 12)) + b"\x11" + bytes(8))),
        (4, event_md(3, "%fusion.2 = f32[4]",
                     _message((1, 7), (7, 11)))))
    host = _message((1, 1), (2, "/host:CPU"), (5, stat_md(7, "tf_op")))
    space = _message((1, device), (1, host))
    assert trace.op_paths(space) == {"/device:TPU:0": {
        "%fusion.1 = f32[8]": "jit(f)/layers/while/body/attn/kv_cache/add",
        "%copy.5 = f32[8]": "",
        "%fusion.2 = f32[4]": "jit(f)/head/dot_general"}}


L = "jit(step)/layers/while/body/"


def _hand_trace():
    """Window 0..100 ns.  Device A: embed 0-10, a kv_cache update 10-30
    inside attn inside layers, an expert matmul 30-50 inside moe, an op
    with no scope 50-60 (argmax), and the head 95-120 (clipped to
    95-100).  Device B: a while loop 0-80 (container: busy time only)
    around the same kv_cache op 0-40 and an unscoped copy 40-80."""
    return {
        "A": [("fusion.1", 0, 10, "fusion", "jit(step)/embed/gather"),
              ("dus.2", 10, 20, "dynamic-update-slice",
               L + "attn/kv_cache/dynamic_update_slice"),
              ("fusion.3", 30, 20, "fusion",
               L + "moe/experts/end,edf->enf/dot_general"),
              ("fusion.4", 50, 10, "fusion", "jit(step)/argmax"),
              ("fusion.5", 95, 25, "fusion",
               "jit(step)/head/dot_general")],
        "B": [("while.9", 0, 80, "while", "jit(step)/layers/while"),
              ("dus.2", 0, 40, "dynamic-update-slice",
               L + "attn/kv_cache/dynamic_update_slice"),
              ("copy.7", 40, 40, "copy", "")],
    }


def test_scope_reduction_hand_counted():
    from bench import trace

    ops = _hand_trace()
    r = trace.reduce_events(ops, [], (0, 100))
    s = {k: v * 1e9 for k, v in r["scope_s"].items()}
    # per device mean: layers (20 + 20 | 40) / 2, attn and kv_cache
    # the same, moe and experts 20 / 2, embed 10 / 2, head 5 / 2,
    # unscoped (10 | 40) / 2; the while loop is in none
    assert s == pytest.approx({
        "embed": 5, "layers": 40, "attn": 30, "kv_cache": 30,
        "moe": 10, "experts": 10, "head": 2.5, "(unscoped)": 25})
    assert r["scope_count"] == pytest.approx({
        "embed": 0.5, "layers": 1.5, "attn": 1.0, "kv_cache": 1.0,
        "moe": 0.5, "experts": 0.5, "head": 0.5, "(unscoped)": 1.0})
    # every op once: the top-level scopes and (unscoped) add up to op time
    top = sum(s[k] for k in trace.TOP_LEVEL + (trace.UNSCOPED,))
    op_s = sum(r["op_s"].values()) * 1e9
    assert top == pytest.approx(op_s) == pytest.approx(72.5)
    assert r["path_s"][("dus.2", "layers/attn/kv_cache")] * 1e9 == \
        pytest.approx(30)
    assert r["path_s"][("copy.7", "")] * 1e9 == pytest.approx(20)
    # ops without their name paths: the same op time, all unscoped
    bare = trace.reduce_events(
        {k: [op[:4] for op in v] for k, v in ops.items()}, [], (0, 100))
    assert sum(bare["op_s"].values()) * 1e9 == pytest.approx(72.5)
    assert bare["scope_s"] == pytest.approx({"(unscoped)": 72.5e-9})


def test_scope_metrics_on_the_hand_trace():
    """The scope readers' helper over the hand-made trace, read as two
    traced steps of a decode run, with an all-to-all of a scan call
    added on device A."""
    from bench import trace
    from bench.metrics import _scopes

    ops = _hand_trace()
    ops["A"].append(("all-to-all.6", 60, 10, "all-to-all",
                     L + "moe/all_to_all/exscan.ring/round0.fold/a"))
    facts = {"kind": "decode", "traced_steps": 2,
             "trace": trace.reduce_events(ops, [], (0, 100)),
             "model": {"name": "t", "family": "moe", "n_layers": 2,
                       "d_model": 8, "n_heads": 2, "n_kv_heads": 2,
                       "d_ff": 8, "vocab": 16, "n_experts": 4,
                       "top_k": 2}}
    # kv_cache 30 ns a device over 2 steps; moe 10 + 5 (10 / 2 devices)
    assert _scopes.scope_ms(facts, "decode", "kv_cache") == \
        pytest.approx(15e-6)
    assert _scopes.scope_ms(facts, "decode", "moe") == pytest.approx(
        7.5e-6)
    assert _scopes.scope_ms(facts, "decode", "attn_core") is None
    assert _scopes.scope_ms(facts, "prefill", "moe") is None
    # unscoped 25 of 77.5 ns of op time
    assert _scopes.unscoped_share(facts, "decode") == pytest.approx(
        100 * 25 / 77.5)
    assert _scopes.collective_ms(facts, "decode") == pytest.approx(
        2.5e-6)
    # 5 ns under exscan.ring, over 2 steps of 2 MoE layers (2 calls)
    assert _scopes.exscan_us(facts, "decode") == pytest.approx(
        5 / 2 / 2 * 1e-3)
    assert _scopes.unscoped_share({"kind": "decode"}, "decode") is None


def test_stall_gaps_are_named_by_open_host_events():
    from bench import scopes

    # one device idle 20-80 ns inside step_dispatch 10-90, and 90-100
    # inside sample_sync; host event "compile" 30-70 is open at the
    # first gap's middle (50), "other" 0-100 too
    ops = {"A": [("fusion.1", 0, 20, "fusion", ""),
                 ("fusion.2", 80, 10, "fusion", "")]}
    host = [("step_dispatch", 10, 80), ("sample_sync", 90, 10),
            ("compile", 30, 40), ("other", 0, 100)]
    out = scopes.stalls(ops, host, (0, 100), min_gap_ns=30)
    assert len(out) == 1
    assert out[0]["gap_ms"] == pytest.approx(60e-6)
    assert [e[0] for e in out[0]["host_events"]] == [
        "other", "step_dispatch", "compile"]


# ------------------------------------------------------------ rehearsal

_TOOL = """
import json, os, sys
sys.path[:0] = [{root!r}, os.path.join({root!r}, "src")]
os.environ["JAX_COMPILATION_CACHE_DIR"] = {cache!r}
import bench.run as r
from bench import scopes, spec
r.PLATFORM = "cpu"
spec.peaks = lambda kind: {{"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9}}
cell = r.load({workload!r})
r.CONFIG_OVERRIDES = {{"model": {smoke!r}[cell.config["name"]]}}
r.TRAFFIC_OVERRIDES = {traffic!r}[cell.traffic["kind"]]
sys.exit(scopes.main(["--workload", {workload!r}, "--seed", "12345",
                      "--seconds", "0.5", "--trace-steps", "4"]))
"""


def test_scopes_tool_rehearsed_on_cpu(tmp_path):
    """The tool runs a cell as ``--trace 1`` does and adds its line (on
    CPU devices the profile holds no device ops, so no scope is read)."""
    workload = "granite-moe.decode"
    proc = harness_tests._python(_TOOL.format(
        root=ROOT, cache=str(tmp_path), workload=workload,
        smoke=harness_tests.SMOKE, traffic=harness_tests.TRAFFIC), 1)
    assert proc.returncode == 0, proc.stderr[-4000:]
    lines = [json.loads(ln) for ln in proc.stdout.splitlines()
             if ln.startswith("{")]
    result, line = lines[-2], lines[-1]
    assert result["correct"] is True
    assert line["traced_steps"] == 4
    assert line["compiles_in_window"] == 0
    assert set(line["window"]["metrics"]) == {
        "decode_tok_s", "decode_step_p95_ms", "setup_s"}
    assert line["window"]["dispatch_ms_traced"] > 0
    assert line["scope_ms"] == {} and line["stalls"] == []


# ------------------------------------------------- expert-parallel cell


@pytest.mark.parametrize("tp", [1, 4])
def test_qwen_reference_matches_program_forward(tp):
    """float32 program forward vs the reference with a shared expert and
    an untied head, at a size where the capacity rule drops slots; over
    4 ranks the cross-rank offsets (the dispatch scan) decide which
    slots drop, and the reference grouped as one rank differs."""
    model = dict(harness_tests.SMOKE["qwen2-moe-a2.7b"],
                 dtype="float32", capacity_factor=1.25)
    proc = harness_tests._python(
        harness_tests._REF.format(root=ROOT, tp=tp, model=model), tp)
    assert proc.returncode == 0, proc.stderr[-4000:]
    out = json.loads(proc.stdout.strip().splitlines()[-1])
    assert out[str(tp)] < 1e-4 * max(1.0, out["scale"]), out
    if tp > 1:
        assert out["1"] > 1e-2, out


_NO_ALL_TO_ALL = """
import jax
jax.lax.all_to_all = lambda x, *a, **k: x
"""


def test_qwen_all_to_all_left_out_is_not_correct(tmp_path):
    """Tokens that never travel to their experts' ranks are served by
    the wrong experts: the cell must come out not correct."""
    out, err = harness_tests.run_cell(tmp_path, QWEN_CELL,
                                      patch=_NO_ALL_TO_ALL)
    assert out["correct"] is False, (out, err[-2000:])
