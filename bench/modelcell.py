"""The system under test for the model cells: the program's ``Model``
built from a configuration file, its mesh, weights drawn from the seed
straight into their shardings, and a sharded KV cache; and the
comparison of what it served with the float32 reference."""

from __future__ import annotations

import jax
import numpy as np

from bench import weights as W
from bench.reference import moe as ref_lib


def build(config: dict, devices):
    """(model, rules) for ``config['model']`` on ``config['mesh']``."""
    from jax.sharding import AxisType, Mesh

    from repro.models.config import ModelConfig
    from repro.models.model import Model
    from repro.sharding import rules as rules_lib

    data, model_axis = config["mesh"]["data"], config["mesh"]["model"]
    n = data * model_axis
    mesh = Mesh(np.asarray(devices[:n]).reshape(data, model_axis),
                ("data", "model"), axis_types=(AxisType.Auto,) * 2)
    cfg = ModelConfig(**config["model"])
    return Model(cfg, mesh), rules_lib.rules_for(cfg)


def init_params(model, rules, seed: int):
    """All weights in one jitted call, drawn on the devices in the
    served dtype."""
    abstract = model.abstract_params()
    return jax.jit(lambda key: W.params(abstract, key),
                   out_shardings=model.param_shardings(rules))(
        W.seed_key(seed))


def init_cache(model, rules, batch: int, max_len: int):
    from repro.sharding import rules as rules_lib

    shapes = model.abstract_cache(batch, max_len)
    kv_ok = model.cfg.n_kv_heads % model.mesh.shape["model"] == 0
    shard = rules_lib.tree_shardings(
        rules, model.cache_logical_axes(kv_shardable=kv_ok), model.mesh,
        shapes)
    return jax.jit(lambda: model.init_cache(batch, max_len),
                   out_shardings=shard)()


def served_shapes(model) -> dict:
    """The served layout the reference draws its weights in."""
    ab = model.abstract_params()
    return {"top": {k: tuple(v.shape) for k, v in ab["top"].items()},
            "blocks": [{k: tuple(v.shape[1:]) for k, v in b.items()}
                       for b in ab["blocks"]]}


def peak_bytes(devices) -> int:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in devices]
    return int(max(peaks))


def compare(items, model_cfg: dict, shapes: dict, seed: int, tp: int,
            device, control: bool = False) -> dict:
    """The logit gaps (``reference.moe.summary``) over every compared
    token.  ``items``: (tokens (B, T), group ids (B, T), positions (P,),
    served (B, P)): teacher-forced sequences, the step each token was
    served in, and the tokens chosen at those positions.  With
    ``control`` the fp8 control's first choices at the same positions
    are judged in place of the served tokens."""
    ref = ref_lib.Reference(model_cfg, shapes, seed, tp, "f32", device)
    ctrl = (ref_lib.Reference(model_cfg, shapes, seed, tp, "fp8", device)
            if control else None)
    out = []
    with jax.default_matmul_precision("highest"):
        for tokens, groups, pos, served in items:
            h = ref.hidden(tokens, groups, pos)
            if ctrl is None:
                out.append(ref_lib.gap_values(ref, h, served))
            else:
                hc = ctrl.hidden(tokens, groups, pos)
                out.append(ref_lib.gap_values(ref, h, ctrl=ctrl,
                                              h_ctrl=hc))
    return ref_lib.summary(np.concatenate([g.ravel() for g in out]))
