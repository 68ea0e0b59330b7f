"""Ahead-of-time compiles for a described TPU v5e (no chip attached).

The TPU compiler is installed with JAX, and it compiles for a topology
that is described rather than attached.  These cases compile the
kernels of the main path at deployment widths, and the p=4 scan
programs on a 2x2 mesh, so a kernel that Mosaic refuses (an unaligned
slice, a primitive with no TPU lowering) fails here instead of on the
chip.  Nothing runs; results are checked by the CPU suites and by
``chip_smoke.py`` on the chip.

The topology is described inside a fixture, never at import: only one
process may load the TPU library, so a worker that was not given this
file must not touch it.
"""

import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import shard_map
from jax.sharding import (Mesh, NamedSharding, PartitionSpec as P,
                          SingleDeviceSharding)

from repro.core import monoid as monoid_lib
from repro.core import schedule as schedule_lib
from repro.core.scan_api import ScanSpec, scan
from repro.kernels import ops, scan_engine


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep the cache out of it
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", prev)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def mesh4(topo):
    return Mesh(np.array(topo.devices[:4]), ("x",))


def _hlo(fn, *args):
    return jax.jit(fn).lower(*args).compile().as_text()


def _kernel_cases():
    i32, f32 = jnp.int32, jnp.float32
    add = monoid_lib.get("add")
    return {
        "exscan_add_int32": (lambda x: ops.exscan(x, interpret=False),
                             [((4096, 1024), i32)]),
        "ssm_scan_affine_f32": (
            lambda a, b: ops.ssm_scan(a, b, interpret=False),
            [((4096, 1024), f32)] * 2),
        "ssm_chunk_summary_f32": (
            lambda a, b: ops.ssm_chunk_summary(a, b, interpret=False),
            [((4096, 1024), f32)] * 2),
        "moe_routing_e128": (
            lambda a: ops.moe_routing(a, 128, interpret=False),
            [((4096, 8), i32)]),
        "block_combine_int32": (
            lambda a, b: scan_engine.block_combine(a, b, jnp.add),
            [((48,), i32)] * 2),
        "tree_combine_masked_f32": (
            lambda a, b, k: scan_engine.tree_combine(add, a, b, keep=k),
            [((1 << 18,), f32)] * 2 + [((), jnp.bool_)]),
    }


# the name each kernel's pallas_call carries: its entry point's
KERNEL_NAMES = {
    "exscan_add_int32": "monoid_exscan",
    "ssm_scan_affine_f32": "affine_chunk_scan",
    "ssm_chunk_summary_f32": "affine_chunk_summary",
    "moe_routing_e128": "moe_routing",
    "block_combine_int32": "block_combine",
    "tree_combine_masked_f32": "tree_combine",
}


@pytest.mark.parametrize("case", sorted(_kernel_cases()))
def test_kernel_compiles_for_v5e(one_chip, case):
    """The kernel compiles to a custom call that takes its entry point's
    name, as its device op does in a profile (``moe_routing.N``)."""
    fn, shapes = _kernel_cases()[case]
    args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip)
            for s, d in shapes]
    hlo = _hlo(fn, *args)
    assert "tpu_custom_call" in hlo
    name = KERNEL_NAMES[case]
    assert re.search(rf"%{name}(\.\d+)? = .* custom-call\(", hlo), name


@pytest.mark.parametrize("executor,alg,kernel", [
    ("pallas", "123", True),
    ("spmd", "ring", False),
])
def test_p4_scan_compiles_for_v5e_2x2(mesh4, executor, alg, kernel):
    """scan() at p=4, 1 MiB int32 per rank (the ring runs S>1
    segments through its rolled loop)."""
    ex = (schedule_lib.PallasExecutor("x", interpret=False)
          if executor == "pallas" else schedule_lib.SPMDExecutor("x"))
    spec = ScanSpec(kind="exclusive", monoid="add", algorithm=alg,
                    axis_name="x")
    f = shard_map(lambda v: scan(v, spec, executor=ex), mesh=mesh4,
                  in_specs=P("x"), out_specs=P("x"),
                  check_vma=executor == "spmd")
    x = jax.ShapeDtypeStruct((4, 1 << 18), jnp.int32,
                             sharding=NamedSharding(mesh4, P("x")))
    hlo = _hlo(f, x)
    assert ("tpu_custom_call" in hlo) == kernel
    assert "collective-permute" in hlo


def _decode_step_hlo(topo, cfg, n_chips, batch=32, max_len=1536):
    """The compiled decode step as the serving loop runs it: weights and
    KV cache sharded by their logical axes over ``(1, n_chips)``, the
    cache donated.  Returns (hlo, stacked-cache shard dims, the number
    of parameter leaves)."""
    from jax.sharding import AxisType

    from repro.models.model import Model
    from repro.sharding import rules as rules_lib

    mesh = Mesh(np.asarray(topo.devices[:n_chips]).reshape(1, n_chips),
                ("data", "model"), axis_types=(AxisType.Auto,) * 2)
    model = Model(cfg, mesh)
    rules = rules_lib.rules_for(cfg)

    def placed(tree, shardings):
        return jax.tree.map(lambda a, s: jax.ShapeDtypeStruct(
            a.shape, a.dtype, sharding=s), tree, shardings)

    params = placed(model.abstract_params(), model.param_shardings(rules))
    shapes = model.abstract_cache(batch, max_len)
    kv_ok = cfg.n_kv_heads % n_chips == 0
    cache = placed(shapes, rules_lib.tree_shardings(
        rules, model.cache_logical_axes(kv_shardable=kv_ok), mesh, shapes))
    rep = NamedSharding(mesh, P())

    def step(params, cache, tok, pos):
        logits, cache = model.decode_step(params, cache, tok[:, None], pos)
        return jnp.argmax(logits[:, -1], -1).astype(jnp.int32), cache

    with jax.set_mesh(mesh):
        hlo = jax.jit(step, donate_argnums=(1,)).lower(
            params, cache, jax.ShapeDtypeStruct((batch,), jnp.int32,
                                                sharding=rep),
            jax.ShapeDtypeStruct((), jnp.int32, sharding=rep),
        ).compile().as_text()
    k = cache[0]["k"]
    dims = ",".join(map(str, k.sharding.shard_shape(k.shape)))
    return hlo, dims, len(jax.tree.leaves(params))


@pytest.mark.parametrize("arch,n_chips", [
    ("granite_moe_3b_a800m", 1),
    ("qwen2_moe_a2_7b", 4),
])
def test_decode_step_moves_no_whole_kv_cache(topo, arch, n_chips):
    """The decode step reads the stacked KV cache in place and writes
    only the new rows into the donated buffer: no copy and no fresh
    buffer of the whole cache (the layer scan's stacked outputs), and
    both cache leaves alias their outputs.  B=32 over 1536 positions,
    the decode cells' shapes.

    The cache is laid out row-major, its last axis on the lanes: for
    granite's 64-wide heads the merged (..., 1536, 8 * 64) leaf, which
    keeps the sequence axis off the lanes so that a written position is
    whole rows; for qwen's 128-wide heads (..., 1536, 4, 128).  No
    layer's slice of the cache is copied, as a per-head view of the
    merged leaf would make the compiler do."""
    from repro import configs
    from repro.models.attention import merged_heads

    cfg = configs.get(arch)
    hlo, dims, n_params = _decode_step_hlo(topo, cfg, n_chips)
    ndim = len(dims.split(","))
    assert ndim == (4 if merged_heads(cfg.head_dim_) else 5), dims
    layouts = re.findall(rf"= bf16\[{dims}\]\{{([0-9,]*):[^}}]*\}} "
                         r"parameter\(", hlo)
    row_major = ",".join(map(str, reversed(range(ndim))))
    assert layouts and set(layouts) == {row_major}, layouts
    layer = dims.split(",", 1)[1]
    slices = re.findall(rf"= bf16\[(?:1,)?{layer}\]\{{[^}}]*\}} copy\(",
                        hlo)
    assert not slices, slices
    whole = rf"= bf16\[{dims}\]\{{[^}}]*\}} "
    copies = re.findall(whole + r"copy\(", hlo)
    buffers = [ln for ln in re.findall(whole + r"custom-call\(.*", hlo)
               if "AllocateBuffer" in ln]
    assert len(copies) + len(buffers) == 0, (copies, buffers)
    assert re.search(whole + r"dynamic-update-slice\(", hlo)
    alias = re.search(r"input_output_alias=\{(.*?)\}, entry_computation",
                      hlo)
    assert alias, "no input_output_alias"
    for out, arg in ((1, n_params), (2, n_params + 1)):
        assert f"{{{out}}}: ({arg}, {{}}" in alias.group(1), alias.group(1)
