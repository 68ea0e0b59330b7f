"""Checks of the benchmark harness on CPU.

- the trace reduction on a hand-made trace, and the FLOP and byte
  functions against hand counts;
- the float32 reference against the program's own forward at small
  sizes (one device, and four with the experts spread over them), and
  the seeded weights bit for bit against digests of earlier draws, a
  configuration's own draw rules, and the cache's recurrent-state
  leaves (zeroed, kept, rewound);
- a rehearsal of every cell's driver at small sizes, with the platform
  and sizes overridden from outside the harness, and the harness's
  refusals (no TPU, a device kind missing from the peaks table);
- the control (the fp8 reference in the program's place) and planted
  faults of the timed path, each of which must come out not correct;
- a four-chip prefill cell of a configuration the benchmark already has
  joining a copy of it by data files alone (``four_chip_prefill/``:
  its limits file and list entries), rehearsed on 4 CPU devices.

The CPU sizes are data: ``sizes/configs/<configuration>.json`` (the
model sizes of the rehearsal, ``smoke``, and of the control,
``control``) and ``sizes/traffic/<traffic kind>.json`` (entries that
replace the traffic mix's).  A cell without them is never rehearsed.

Run: ``python -m pytest bench/tests -q`` from the repository root.
"""

from __future__ import annotations

import hashlib
import json
import os
import shutil
import subprocess
import sys
import textwrap

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
SRC = os.path.join(ROOT, "src")
sys.path[:0] = [ROOT, SRC]

SIZES = os.path.join("bench", "tests", "sizes")


def _sizes(root=ROOT, part="configs") -> dict:
    """{name: sizes} of every file in ``sizes/<part>/``."""
    d = os.path.join(root, SIZES, part)
    return {f[:-5]: json.load(open(os.path.join(d, f)))
            for f in sorted(os.listdir(d)) if f.endswith(".json")}


# The model sizes of each configuration's rehearsal (``control`` is the
# size the control is read at: the configuration's own vocabulary and
# more layers and width, so that its readings come near those at the
# cell's own size, PERF.md section 2, and are judged against the
# committed limits), and the traffic entries of each kind.
SMOKE = {k: v["smoke"] for k, v in _sizes().items()}
TRAFFIC = _sizes(part="traffic")


def _bench(root=ROOT) -> dict:
    return json.load(open(os.path.join(root, "BENCHMARK.json")))


def cpu_sizes(workload, size="smoke", root=ROOT):
    """(model sizes, traffic entries) a cell is rehearsed at on CPU;
    fails where its configuration or traffic kind has no sizes file."""
    bench = _bench(root)
    w = {w["name"]: w for w in bench["workloads"]}[workload]
    kind = json.load(open(os.path.join(root, "bench", "traffic",
                                       w["traffic"] + ".json")))["kind"]
    configs, traffic = _sizes(root), _sizes(root, "traffic")
    if w["config"] not in configs or kind not in traffic:
        pytest.fail(f"{workload}: no CPU sizes in {SIZES} for configuration"
                    f" {w['config']!r} or traffic kind {kind!r}; a cell "
                    f"is never rehearsed at full size")
    return configs[w["config"]][size], traffic[kind]


# Runs one cell in a fresh interpreter on CPU devices; ``PATCH`` plants
# a fault before the run.
_RUN = """
import json, os, sys
sys.path[:0] = [{root!r}, {src!r}]
os.environ["JAX_COMPILATION_CACHE_DIR"] = {cache!r}
import bench.run as r
from bench import spec
r.PLATFORM = "cpu"
r.BENCHMARK = {bench!r}
spec.peaks = lambda kind: {{"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9}}
r.CONFIG_OVERRIDES = {{"model": {model!r}}}
r.TRAFFIC_OVERRIDES = {traffic!r}
{patch}
sys.exit(r.main(["--workload", {workload!r}, "--seed", "8589934597",
                 "--seconds", "0.5", "--trace", {trace!r}]))
"""


def _env(n_devices):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    flags = [f for f in env.get("XLA_FLAGS", "").split()
             if "device_count" not in f]
    env["XLA_FLAGS"] = " ".join(
        flags + [f"--xla_force_host_platform_device_count={n_devices}"])
    return env


def _python(code, n_devices, timeout=600):
    return subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                          env=_env(n_devices), capture_output=True,
                          text=True, timeout=timeout, cwd=ROOT)


def _chips(workload, root=ROOT):
    return {w["name"]: w["chips"]
            for w in _bench(root)["workloads"]}[workload]


def run_cell(tmp_path, workload, trace=0, patch="", root=ROOT):
    model, traffic = cpu_sizes(workload, root=root)
    proc = _python(_RUN.format(root=root, src=SRC, cache=str(tmp_path),
                               workload=workload, model=model,
                               traffic=traffic, patch=patch,
                               trace=str(trace), bench=_bench(root)),
                   _chips(workload, root))
    assert proc.returncode == 0, proc.stdout[-2000:] + proc.stderr[-4000:]
    return json.loads(proc.stdout.strip().splitlines()[-1]), proc.stderr


def _workloads():
    return [w["name"] for w in _bench()["workloads"]]


# ---------------------------------------------------------------- trace


def test_trace_reduction_hand_counted():
    from bench import trace

    # window 0..100 ns; device A busy 10-30 (fusion.1), 20-40
    # (all-to-all.3, overlapping), 60-70 (collective-permute-start.2);
    # device B busy 0-50 (fusion.1 inside while.9, which counts toward
    # busy time only) and 95-120 (clipped to 95-100)
    ops = {"A": [("fusion.1", 10, 20, "fusion"),
                 ("all-to-all.3", 20, 20, "all-to-all"),
                 ("collective-permute-start.2", 60, 10,
                  "collective-permute-start")],
           "B": [("while.9", 0, 50, "while"),
                 ("fusion.1", 0, 50, "fusion"),
                 ("fusion.2", 95, 25, "fusion")]}
    spans = [("step_dispatch", 0, 45), ("sample_sync", 45, 55),
             ("traced", 0, 100), ("unrelated", 0, 100)]
    r = trace.reduce_events(ops, spans, (0, 100))
    # busy: A = 30 (10-40) + 10 = 40; B = 50 + 5 = 55; mean 47.5 ns
    assert r["busy_s"] == pytest.approx(47.5e-9)
    assert r["window_s"] == pytest.approx(100e-9)
    assert r["idle_share"] == pytest.approx(0.525)
    assert r["op_s"]["fusion.1"] == pytest.approx((20 + 50) / 2 * 1e-9)
    assert r["op_s"]["fusion.2"] == pytest.approx(2.5e-9)
    assert "while.9" not in r["op_s"]
    assert r["category_s"]["all-to-all"] == pytest.approx(10e-9)
    assert r["category_s"]["collective-permute"] == pytest.approx(5e-9)
    assert r["category_s"]["fusion"] == pytest.approx(37.5e-9)
    # idle gaps: A 0-10 (dispatch), 40-60 (mid 50: sync), 70-100 (sync);
    # B 50-95 (mid 72.5: sync); per device mean
    assert r["idle_by_span"]["step_dispatch"] == pytest.approx(5e-9)
    assert r["idle_by_span"]["sample_sync"] == pytest.approx(47.5e-9)
    b = trace.breakdown(r, top=2)
    assert [k for k, _ in b["device_ops"]] == ["fusion.1",
                                               "all-to-all.3"]
    assert b["idle_gaps"][0][0] == "sample_sync"


def test_trace_op_names():
    from bench import trace

    text = ("%convert_reduce_fusion.4 = f32[1536]{0:T(1024)S(1)} fusion("
            "bf16[32,1536]{1,0:T(8,128)(2,1)} %get-tuple-element.585), "
            "kind=kLoop")
    assert trace.parse_op(text) == ("convert_reduce_fusion.4", "fusion")
    text = ("%all-gather-start.2 = (bf16[8,2048]{1,0}, bf16[32,2048]{1,0})"
            " all-gather-start(bf16[8,2048]{1,0} %x), dimensions={0}")
    assert trace.parse_op(text) == ("all-gather-start.2",
                                    "all-gather-start")
    assert trace.category("all-gather-start") == "all-gather"
    assert trace.category("collective-permute-done") == \
        "collective-permute"
    assert trace.category("fusion") == "fusion"


# ---------------------------------------------------------------- flops


def _cfg(name):
    return json.load(open(os.path.join(ROOT, "bench", "configs",
                                       name + ".json")))["model"]


def test_flops_granite_hand_count():
    from bench import flops

    m = _cfg("granite-moe-3b-a800m")
    # per layer: q 1536x1536, k and v 1536x512 each, o 1536x1536
    proj = 2 * (1536 * 1536 * 2 + 1536 * 512 * 2)
    experts = 8 * 3 * 2 * 1536 * 512
    router = 2 * 1536 * 40
    attn = 4 * 24 * 64 * 100
    assert flops.token_flops(m, 100) == 32 * (proj + experts + router
                                               + attn)
    assert flops.head_flops(m) == 2 * 1536 * 49155
    assert flops.state_bytes(m, 2, 10) == 32 * 2 * 10 * 2 * 8 * 64 * 2


def test_flops_prefill_hand_count():
    from bench import flops

    m = _cfg("granite-moe-3b-a800m")
    # a prefill of 1 x 3 tokens: attention over 1 + 2 + 3 keys, logits
    # of the last position only
    no_attn = flops.token_flops(m, 0)
    attn = 32 * 4 * 24 * 64 * 6
    assert flops.prefill_flops(m, 1, 3) == (
        3 * no_attn + attn + 2 * 1536 * 49155)
    assert flops.decode_step_flops(m, 2, 9) == 2 * (
        flops.token_flops(m, 10) + 2 * 1536 * 49155)


def test_flops_moe_routing_hand_count():
    from bench import flops

    ops, nbytes = flops.moe_routing(16384, 8, 48)
    assert ops == 16384 * 8
    assert nbytes == 4 * 16384 * 8 * 2 + 4 * 48


# ------------------------------------------------------------ reference

_REF = """
import json, os, sys
sys.path[:0] = [{root!r}, os.path.join({root!r}, "src")]
import jax, jax.numpy as jnp, numpy as np
from bench import modelcell
from bench.reference import moe as ref_lib
conf = {{"mesh": {{"data": 1, "model": {tp}}}, "model": {model!r}}}
model, rules = modelcell.build(conf, jax.devices())
params = modelcell.init_params(model, rules, 12345)
B, S = 4, 64
tokens = np.random.default_rng(0).integers(1, 256, (B, S)).astype(np.int32)
with jax.set_mesh(model.mesh):
    logits = jax.jit(model.forward)(params, jnp.asarray(tokens))[0]
logits = np.asarray(logits)[..., :256]
shapes = modelcell.served_shapes(model)
out = {{}}
for tp in sorted({{1, {tp}}}):
    ref = ref_lib.Reference(conf["model"], shapes, 12345, tp)
    h = ref.hidden(tokens, np.zeros((B, S), np.int32), np.arange(S))
    want = np.asarray(ref.logits(h, ref.head()))
    out[tp] = float(np.max(np.abs(want - logits)))
out["scale"] = float(np.max(np.abs(logits)))
print(json.dumps(out))
"""


@pytest.mark.parametrize("tp,tie", [(1, True), (1, False), (4, True)])
def test_reference_matches_program_forward(tp, tie):
    """float32 program forward vs the reference, at a size where the
    capacity rule drops slots, with the output head tied to the
    embedding and apart; with the experts over 4 devices the cross-rank
    offsets decide which slots drop, and the reference grouped as one
    rank differs."""
    model = dict(SMOKE["granite-moe-3b-a800m"], n_kv_heads=4,
                 dtype="float32", capacity_factor=1.25,
                 tie_embeddings=tie)
    proc = _python(_REF.format(root=ROOT, tp=tp, model=model), tp)
    assert proc.returncode == 0, proc.stderr[-4000:]
    out = json.loads(proc.stdout.strip().splitlines()[-1])
    assert out[str(tp)] < 1e-4 * max(1.0, out["scale"]), out
    if tp > 1:
        assert out["1"] > 1e-2, out


# ------------------------------------------------------------ rehearsal


@pytest.mark.parametrize("trace", [0, 1])
@pytest.mark.parametrize("workload", _workloads())
def test_cell_rehearsed_on_cpu(tmp_path, workload, trace):
    out, err = run_cell(tmp_path, workload, trace)
    assert out["correct"] is True, (out, err[-3000:])
    assert list(out)[-1] == "checks"
    for c in out["checks"].values():
        assert c["value"] <= c["limit"]
    bench = _bench()
    if trace:
        assert {"busy_s", "window_s"} <= set(out["device"])
        assert set(out["breakdown"]) == {"device_ops", "idle_gaps"}
    else:
        want = {m["name"] for m in bench["end_to_end"]
                if workload in m.get("workloads", [workload])}
        assert set(out["metrics"]) == want
        assert all(v["value"] > 0 for v in out["metrics"].values())
    assert out["device"]["platform"] == "cpu"


def test_harness_refuses_cpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, "bench/run.py", "--workload",
         _workloads()[0],
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=300)
    assert proc.returncode != 0
    assert "no TPU found" in proc.stderr
    assert proc.stdout.strip() == ""


def test_unknown_device_kind_refused():
    from bench import spec

    assert spec.peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        spec.peaks("TPU v9 imaginary")


def test_every_name_resolves_to_its_files():
    from bench import flops, spec

    bench = _bench()
    for w in bench["workloads"]:
        cell = spec.load_cell(w["name"], bench)
        assert cell.per_layer, w["name"]
        spec.driver(cell.traffic["kind"])
        ref = spec.reference(cell.config)
        assert {"Reference", "gap_values", "summary"} <= set(vars(ref))
        for layer in flops.layers(cell.config["model"]):
            counts = spec.counts(layer.kind)
            assert callable(counts.flops) and callable(counts.state_bytes)
        for m in cell.per_layer:
            assert spec.metric_reader(m["name"])({}) is None


@pytest.mark.parametrize("workload", _workloads())
def test_every_cell_has_cpu_sizes(workload):
    """A cell's configuration has a sizes file with both model sizes, and
    its traffic kind one: without them the cell would be rehearsed at
    full size."""
    smoke, traffic = cpu_sizes(workload)
    control, _ = cpu_sizes(workload, "control")
    assert smoke["n_layers"] <= 8 and control["d_model"] <= 256
    assert traffic


def test_weight_draws_bit_identical():
    """Every leaf of both MoE configurations, at small shapes, as the
    program and as the reference draw it, matches the digest of the
    same draw made before configurations could state their own rules
    (``weight_digests.json``)."""
    import jax
    import jax.numpy as jnp

    from bench import modelcell, weights as W

    pinned = json.load(open(os.path.join(ROOT, "bench", "tests",
                                         "weight_digests.json")))
    seed = pinned["seed"]

    def digest(x):
        return hashlib.sha256(np.asarray(x).tobytes()).hexdigest()[:16]

    for name, want in pinned["configs"].items():
        config = json.load(open(os.path.join(
            ROOT, "bench", "configs", name + ".json")))
        conf = dict(config, model=want["model"],
                    mesh={"data": 1, "model": 1})
        model, rules = modelcell.build(conf, jax.devices())
        params = modelcell.init_params(model, rules, seed,
                                       conf.get("weights"))
        got = {f"top/{k}": digest(v) for k, v in params["top"].items()}
        got.update({f"blocks/{j}/{k}": digest(v)
                    for j, b in enumerate(params["blocks"])
                    for k, v in b.items()})
        assert got == want["leaves"], name
        shapes = modelcell.served_shapes(model)
        key, dtype = W.seed_key(seed), jnp.dtype(want["model"]["dtype"])
        for k, shape in shapes["top"].items():
            assert digest(W.top_leaf(key, k, shape, dtype, conf.get(
                "weights"))) == want["leaves"][f"top/{k}"], (name, k)
        for layer in range(want["model"]["n_layers"]):
            for k, v in W.layer_leaves(key, 0, layer, shapes["blocks"][0],
                                       dtype, conf.get("weights")).items():
                assert digest(v) == digest(params["blocks"][0][k][layer])


def test_weight_draw_rules():
    """A configuration's ``weights`` rules (``ones``, ``zeros``,
    ``uniform``) as the program draws them (all layers under ``vmap``)
    and as the reference draws them (one layer at a time), bit for bit;
    a leaf the block does not name keeps the default draw."""
    import jax
    import jax.numpy as jnp

    from bench import weights as W

    draws = {"a": "ones", "b": "zeros", "c": {"uniform": [-5, -1]}}
    shapes = {"a": (16,), "b": (16,), "c": (64, 8), "d": (64, 8)}
    abstract = {"top": {}, "blocks": ({k: jax.ShapeDtypeStruct(
        (3,) + s, jnp.bfloat16) for k, s in shapes.items()},)}
    key = W.seed_key(8589934597)
    prog = jax.jit(lambda k: W.params(abstract, k, draws))(key)["blocks"][0]
    for layer in range(3):
        ref = W.layer_leaves(key, 0, layer, shapes, jnp.bfloat16, draws)
        for k in shapes:
            assert np.array_equal(np.asarray(prog[k][layer]),
                                  np.asarray(ref[k])), (k, layer)
    c = np.asarray(prog["c"], np.float32)
    assert np.all(prog["a"] == 1) and np.all(prog["b"] == 0)
    assert c.min() >= -5 and c.max() <= -1 and abs(c.mean() + 3) < 0.1
    d = np.asarray(prog["d"], np.float32)
    assert np.abs(d).max() <= np.sqrt(3) / np.sqrt(64) * 1.01
    with pytest.raises(ValueError):
        W.frozen({"a": "twos"})


def test_recurrent_state_leaves():
    """A cache leaf with no sequence axis is a recurrent state: zeroed
    for new sequences, kept as it is when the sequence axes widen, and
    put back to a saved moment by ``Rewind``; keys and values are left
    to the steps that write them."""
    import jax.numpy as jnp

    from bench import modelcell

    cache = {"kv": jnp.arange(24.0).reshape(2, 4, 3),
             "state": jnp.full((2, 3), 7.0)}
    axes = [1, None]  # jax.tree.leaves order: "kv", "state"
    assert modelcell.Rewind.of(cache, [1, 1]) is None
    rewind = modelcell.Rewind.of(cache, axes)
    new = modelcell.fresh(cache, axes)
    assert np.all(np.asarray(new["state"]) == 0)
    assert np.array_equal(np.asarray(new["kv"]), np.asarray(cache["kv"]))
    wide = modelcell.widen(cache, axes, 2)
    assert wide["kv"].shape == (2, 6, 3) and np.all(
        np.asarray(wide["kv"][:, 4:]) == 0)
    assert np.array_equal(np.asarray(wide["state"]),
                          np.asarray(cache["state"]))
    back = rewind({"kv": new["kv"] + 1, "state": new["state"] - 1})
    assert np.all(np.asarray(back["state"]) == 7)
    assert np.array_equal(np.asarray(back["kv"]),
                          np.asarray(cache["kv"]) + 1)


# ---------------------------------------------------- control and faults

_STALE = """
from repro.models.model import Model
_orig = Model.decode_step
def _stale(self, params, cache, tokens, cache_len):
    return _orig(self, params, cache, tokens, cache_len)[0], cache
Model.decode_step = _stale
"""
_ALTERED = """
from repro.models.model import Model
_orig = Model.serve_step
def _altered(self, *a, **k):
    logits, cache = _orig(self, *a, **k)
    return logits.at[0, :, 1].add(1e4), cache
Model.serve_step = _altered
"""
_UNWRITTEN = """
from repro.models.model import Model
_orig = Model.serve_step
def _unwritten(self, params, cache, tokens, *a, **k):
    logits, new = _orig(self, params, cache, tokens, *a, **k)
    return logits, (cache if tokens.shape[1] > 1 else new)
Model.serve_step = _unwritten
"""


def _faults():
    out = []
    for w in _workloads():
        if w.endswith("decode"):
            out += [(w, "state_unchanged", _STALE),
                    (w, "token_altered", _ALTERED)]
        if w.endswith("prefill"):
            out += [(w, "cache_not_written", _UNWRITTEN),
                    (w, "token_altered", _ALTERED)]
    return out


@pytest.mark.parametrize("workload,fault,patch", _faults(),
                         ids=[f"{w}-{f}" for w, f, _ in _faults()])
def test_planted_fault_is_not_correct(tmp_path, workload, fault, patch):
    out, err = run_cell(tmp_path, workload, patch=patch)
    assert out["correct"] is False, (fault, out)


_CAL = """
import os, sys
sys.path[:0] = [{root!r}, {src!r}]
os.environ["JAX_COMPILATION_CACHE_DIR"] = {cache!r}
import bench.run as r
from bench import calibrate, spec
r.PLATFORM = "cpu"
r.BENCHMARK = {bench!r}
r.CONFIG_OVERRIDES = {{"model": {model!r}}}
r.TRAFFIC_OVERRIDES = {traffic!r}
calibrate.main(["--workload", {workload!r}, "--seeds", {seeds!r},
                "--control-seeds", {seeds!r}] + {extra!r})
"""


def calibrate_cell(tmp_path, workload, seeds="1,2,3", extra=(),
                   root=ROOT):
    """calibrate.py's lines for a cell at its control size on CPU."""
    model, traffic = cpu_sizes(workload, "control", root)
    proc = _python(_CAL.format(root=root, src=SRC, cache=str(tmp_path),
                               workload=workload, model=model,
                               traffic=traffic, bench=_bench(root),
                               seeds=seeds, extra=list(extra)),
                   _chips(workload, root))
    assert proc.returncode == 0, proc.stderr[-4000:]
    return [json.loads(ln) for ln in proc.stdout.splitlines()
            if ln.startswith("{")]


@pytest.mark.parametrize("workload", _workloads())
def test_control_is_not_correct(tmp_path, workload):
    """The control, the fp8 reference in the program's place, at a size
    a test run holds: judged against the cell's committed limits as a
    run judges the program, it comes out not correct on every seed."""
    lines = calibrate_cell(tmp_path, workload)
    assert len(lines) == 3
    for ln in lines:
        assert ln["control_correct"] is False, ln


def test_benchmark_files_alone_fail(tmp_path):
    """In a directory holding only BENCHMARK.json and bench/, a run
    exits non-zero and prints no result (the program is not there)."""
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(os.path.join(ROOT, "bench"), tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    code = (f"import sys; sys.path.insert(0, {str(tmp_path)!r}); "
            "import bench.run as r; r.PLATFORM = 'cpu'; "
            f"sys.exit(r.main(['--workload', {_workloads()[0]!r}, "
            "'--seed', '1', '--seconds', '1', '--trace', '0']))")
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    proc = subprocess.run([sys.executable, "-c", code], cwd=tmp_path,
                          env=env, capture_output=True, text=True,
                          timeout=300)
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""
    assert "repro" in proc.stderr


# ------------------------------------------- a cell by data files


FOUR_CHIP_PREFILL = os.path.join(ROOT, "bench", "tests",
                                 "four_chip_prefill")


def _digests(root) -> dict:
    """{path under bench/: sha256} of every file but compiled ones."""
    out = {}
    for d, dirs, files in os.walk(os.path.join(root, "bench")):
        dirs[:] = [x for x in dirs if x != "__pycache__"]
        for f in files:
            path = os.path.join(d, f)
            out[os.path.relpath(path, root)] = hashlib.sha256(
                open(path, "rb").read()).hexdigest()
    return out


def _join(copy, fixture):
    """Adds the fixture's files under ``bench/`` to the copy, each new,
    and its list entries (``entries.json``) to the copy's
    BENCHMARK.json."""
    tree = os.path.join(fixture, "bench")
    for d, _, files in os.walk(tree):
        for f in files:
            rel = os.path.relpath(os.path.join(d, f), fixture)
            dst = os.path.join(copy, rel)
            assert not os.path.exists(dst), rel
            os.makedirs(os.path.dirname(dst), exist_ok=True)
            shutil.copy(os.path.join(d, f), dst)
    bench = _bench(copy)
    add = json.load(open(os.path.join(fixture, "entries.json")))
    bench["workloads"] += add["workloads"]
    for m in bench["end_to_end"] + bench["per_layer"]:
        if m["name"] in add["metric_workloads"]:
            m["workloads"] += add["metric_workloads"][m["name"]]
    with open(os.path.join(copy, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f, indent=1)


@pytest.mark.parametrize("fault,patch", [
    ("cache_not_written", _UNWRITTEN), ("token_altered", _ALTERED)],
    ids=["cache_not_written", "token_altered"])
def test_four_chip_prefill_cell_joins_by_data_files_alone(tmp_path, fault,
                                                          patch):
    """A four-chip prefill cell of a configuration the benchmark has
    (``four_chip_prefill/``: qwen2-moe-a2.7b under the prefill mix,
    experts over 4 devices) joins a copy of the benchmark by its limits
    file and list entries of BENCHMARK.json alone: on 4 CPU devices it
    rehearses correct, traced and untraced; its fp8 control comes out
    not correct, with the bfloat16 witness below the control; the
    planted fault comes out not correct; no file the copy's bench/ had
    changes."""
    copy = tmp_path / "copy"
    copy.mkdir()
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), copy)
    shutil.copytree(os.path.join(ROOT, "bench"), copy / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    before = _digests(copy)
    _join(copy, FOUR_CHIP_PREFILL)
    workload = "qwen2-moe.ep4.prefill"
    assert _chips(workload, str(copy)) == 4
    cache = tmp_path / "cache"
    for trace in (0, 1):
        out, err = run_cell(cache, workload, trace, root=str(copy))
        assert out["correct"] is True, (trace, out, err[-3000:])
        assert out["device"]["count"] == 4, out
        if trace:
            assert "mfu.prefill" in out["metrics"], out
        else:
            assert "prefill_tok_s" in out["metrics"], out
    lines = calibrate_cell(cache, workload, seeds="5", extra=["--witness"],
                           root=str(copy))
    assert lines[0]["control_correct"] is False, lines
    assert lines[0]["bf16"]["mean_logit_gap"] < lines[0]["control"][
        "mean_logit_gap"], lines
    out, _ = run_cell(cache, workload, patch=patch, root=str(copy))
    assert out["correct"] is False, (fault, out)
    after = _digests(copy)
    assert {k: after[k] for k in before} == before
