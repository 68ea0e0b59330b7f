"""The program's named scopes, read from the lowered step's op metadata.

Every layer boundary of the serving and forward paths opens a
``jax.named_scope`` (models/model.py, attention.py, moe.py), and the
scan executor names each call ``exscan.<schedule>`` and each round
``round<i>.<kind>`` (core/scan_api.py, core/schedule.py).  Scopes are
metadata: a profile attributes each device op to the scopes on its
``op_name`` path.  These tests read the paths from
``lower(...).as_text(debug_info=True)`` at the smoke configs on CPU, and
on 4 virtual devices for the cross-device scan.
"""

import json
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh

from helpers import run_with_devices
from repro.configs import granite_3_2b, qwen2_moe_a2_7b
from repro.models.model import Model

MODEL_SCOPES = {"embed", "layers", "head"}
ATTN_SCOPES = {"attn", "qkv", "attn_core", "attn_out"}
MOE_SCOPES = {"moe", "router", "routing", "dispatch_scan", "dispatch",
              "all_to_all", "experts", "combine", "shared_expert"}


def _paths(text: str) -> list[str]:
    """The name paths of the lowered program's locations."""
    return re.findall(r'loc\("([^"]*)"', text)


def _op_names(text: str) -> list[str]:
    """The ``op_name`` metadata of a compiled program's instructions:
    whole paths, where the lowered text's locations are relative to the
    function (shard_map body, checkpoint) that holds them."""
    return re.findall(r'op_name="([^"]*)"', text)


def _scopes(text: str) -> set[str]:
    return {part for path in _paths(text) for part in path.split("/")[:-1]}


def _mesh1():
    return Mesh(np.array(jax.devices()[:1]).reshape(1, 1),
                ("data", "model"))


def _serve_text(cfg, seq: int) -> str:
    model = Model(cfg, _mesh1())
    params = model.abstract_params()
    cache = model.abstract_cache(2, 16)
    toks = jax.ShapeDtypeStruct((2, seq), jnp.int32)
    step = jax.jit(lambda p, c, t: model.serve_step(p, c, t, 0,
                                                    last_only=seq > 1))
    with jax.set_mesh(model.mesh):
        return step.lower(params, cache, toks).as_text(debug_info=True)


@pytest.mark.parametrize("seq", [1, 8], ids=["decode", "prefill"])
def test_moe_serve_step_carries_every_scope(seq):
    """Decode and prefill of the MoE smoke config (routed and shared
    experts): the model, attention (with the KV-cache write) and every
    part of the MoE layer."""
    scopes = _scopes(_serve_text(qwen2_moe_a2_7b.SMOKE, seq))
    want = MODEL_SCOPES | ATTN_SCOPES | {"kv_cache"} | MOE_SCOPES
    assert want <= scopes, sorted(want - scopes)


def test_dense_serve_step_has_ffn_scope():
    scopes = _scopes(_serve_text(granite_3_2b.SMOKE, 1))
    want = MODEL_SCOPES | ATTN_SCOPES | {"kv_cache", "ffn"}
    assert want <= scopes, sorted(want - scopes)
    assert not scopes & MOE_SCOPES


def test_forward_carries_model_scopes():
    """The training forward: the model, attention and MoE scopes, and
    no cache scope."""
    cfg = qwen2_moe_a2_7b.SMOKE
    model = Model(cfg, _mesh1())
    toks = jax.ShapeDtypeStruct((2, 8), jnp.int32)
    with jax.set_mesh(model.mesh):
        text = jax.jit(model.forward).lower(
            model.abstract_params(), toks).as_text(debug_info=True)
    scopes = _scopes(text)
    assert MODEL_SCOPES | ATTN_SCOPES | MOE_SCOPES <= scopes
    assert "kv_cache" not in scopes


def test_serve_scopes_nest_under_layers():
    """In the compiled decode step every attention and MoE op lies
    under ``layers``, and the embedding and head outside it."""
    cfg = qwen2_moe_a2_7b.SMOKE
    model = Model(cfg, _mesh1())
    step = jax.jit(lambda p, c, t: model.serve_step(p, c, t, 0))
    with jax.set_mesh(model.mesh):
        text = step.lower(model.abstract_params(),
                          model.abstract_cache(2, 16),
                          jax.ShapeDtypeStruct((2, 1), jnp.int32)
                          ).compile().as_text()
    # instructions that run carry their whole path from ``jit(..)``;
    # the scalar computations a reduction applies keep a relative one
    paths = [p for p in _op_names(text) if p.startswith("jit(")]
    assert MODEL_SCOPES | {"attn", "moe"} <= {
        part for path in paths for part in path.split("/")}
    for path in paths:
        parts = path.split("/")[:-1]
        if set(parts) & (ATTN_SCOPES | MOE_SCOPES):
            assert "layers" in parts, path
        if "embed" in parts or "head" in parts:
            assert "layers" not in parts, path


_SCAN = """
import json, re
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import Mesh, PartitionSpec as P
from repro.core import scan_api, schedule
mesh = Mesh(np.asarray(jax.devices()[:4]), ("model",))
spec = scan_api.ScanSpec(kind="exclusive", monoid="add",
                         algorithm={alg!r}).over("model")
def body(x):
    prefix, total = scan_api.scan_with_total(x[0], spec)
    return prefix[None], total[None]
f = jax.shard_map(body, mesh=mesh, in_specs=P("model"),
                  out_specs=(P("model"), P("model")), check_vma=False)
with schedule.collect_stats() as st:
    text = jax.jit(f).lower(jax.ShapeDtypeStruct((4, 64), jnp.int32)
                            ).as_text(debug_info=True)
parts = {{p for path in re.findall(r'loc\\("([^"]*)"', text)
         for p in path.split("/")[:-1]}}
print(json.dumps({{"rounds": st.rounds, "scopes": sorted(parts)}}))
"""


@pytest.mark.parametrize("alg", ["auto", "123", "two_op", "1doubling"])
def test_p4_scan_with_total_names_its_rounds(alg):
    """A p=4 scan_with_total: one ``exscan.<schedule>`` scope and one
    ``round<i>.<kind>`` scope per communication round, as many as
    ``collect_stats()`` counts."""
    out = json.loads(run_with_devices(_SCAN.format(alg=alg), 4,
                                      x64=False).strip().splitlines()[-1])
    exscan = [s for s in out["scopes"] if s.startswith("exscan.")]
    rounds = sorted(int(m.group(1)) for s in out["scopes"]
                    if (m := re.fullmatch(r"round(\d+)\.\w+", s)))
    assert len(exscan) == 1, out
    assert out["rounds"] >= 2
    assert rounds == list(range(out["rounds"])), out


_MOE4 = """
import json, re
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import AxisType, Mesh
from repro.configs import qwen2_moe_a2_7b
from repro.models.model import Model
mesh = Mesh(np.asarray(jax.devices()[:4]).reshape(1, 4),
            ("data", "model"), axis_types=(AxisType.Auto,) * 2)
model = Model(qwen2_moe_a2_7b.SMOKE, mesh)
step = jax.jit(lambda p, c, t: model.serve_step(p, c, t, 0))
with jax.set_mesh(mesh):
    text = step.lower(model.abstract_params(), model.abstract_cache(8, 16),
                      jax.ShapeDtypeStruct((8, 1), jnp.int32)
                      ).compile().as_text()
print(json.dumps(re.findall(r'op_name="([^"]*)"', text)))
"""


def test_expert_parallel_scan_nests_under_dispatch_scan():
    """Over 4 ranks the MoE dispatch scan runs across devices, and its
    rounds' collective-permutes lie under layers/moe/dispatch_scan in
    the compiled step."""
    paths = json.loads(run_with_devices(_MOE4, 4, x64=False)
                       .strip().splitlines()[-1])
    permutes = [p for p in paths if p.endswith("/ppermute")]
    assert permutes
    for path in permutes:
        assert re.search(r"/layers/.*/moe/(.*/)?dispatch_scan/exscan\.[^/]+/"
                         r"(.*/)?round\d+\.\w+/", path), path
