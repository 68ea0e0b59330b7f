"""The system under test for the model cells: the program's ``Model``
built from a configuration file, its mesh, weights drawn from the seed
straight into their shardings, and a sharded cache; and the comparison
of what it served with the configuration's plain reference.

Each cache leaf is handled by its own axes: the program's
``cache_logical_axes`` names a leaf's sequence axis ``cache_seq...``
(keys and values); a leaf with none is a recurrent state."""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from bench import spec, weights as W


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


def init_params(model, rules, seed: int, draws=None):
    """All weights in one jitted call, drawn on the devices in the
    served dtype; ``draws`` is the configuration's ``weights`` block."""
    abstract = model.abstract_params()
    return jax.jit(lambda key: W.params(abstract, key, draws),
                   out_shardings=model.param_shardings(rules))(
        W.seed_key(seed))


def _kv_shardable(model) -> bool:
    """Whether the KV heads split over the model axis; asked only where
    the pattern has attention."""
    if all(s.kind != "attn" for s in model.cfg.pattern()):
        return True
    return model.cfg.n_kv_heads % model.mesh.shape["model"] == 0


def init_cache(model, rules, batch: int, max_len: int):
    from repro.sharding import rules as rules_lib

    shapes = model.abstract_cache(batch, max_len)
    shard = rules_lib.tree_shardings(
        rules, model.cache_logical_axes(kv_shardable=_kv_shardable(model)),
        model.mesh, shapes)
    return jax.jit(lambda: model.init_cache(batch, max_len),
                   out_shardings=shard)()


def seq_axes(model) -> list:
    """For each cache leaf, in ``jax.tree.leaves`` order, the index of
    its sequence axis (logical name ``cache_seq...``), or None for a
    recurrent state."""
    axes = model.cache_logical_axes(kv_shardable=_kv_shardable(model))
    names = jax.tree.leaves(axes, is_leaf=lambda x: isinstance(x, tuple)
                            and all(isinstance(a, (str, type(None)))
                                    for a in x))
    return [next((i for i, a in enumerate(ax)
                  if a and a.startswith("cache_seq")), None)
            for ax in names]


def widen(cache, axes: list, steps: int):
    """The cache with ``steps`` more positions on each leaf's sequence
    axis; recurrent states as they are."""
    leaves, tree = jax.tree.flatten(cache)
    return jax.tree.unflatten(tree, [
        t if a is None else jnp.pad(
            t, [(0, steps if i == a else 0) for i in range(t.ndim)])
        for t, a in zip(leaves, axes)])


def fresh(cache, axes: list):
    """The cache for new sequences from position 0: recurrent states
    zeroed (keys and values past the new length are never read)."""
    leaves, tree = jax.tree.flatten(cache)
    return jax.tree.unflatten(tree, [
        jnp.zeros_like(t) if a is None else t
        for t, a in zip(leaves, axes)])


class Rewind:
    """Puts the recurrent states of a cache back to what they held at
    one moment (after the prompt), for a decode loop that starts its
    positions over; keys and values are rewritten by the steps
    themselves.  ``None`` from ``of`` where the cache has no such
    state."""

    def __init__(self, cache, axes: list):
        leaves = jax.tree.leaves(cache)
        self.idx = [i for i, a in enumerate(axes) if a is None]
        self.saved = [jnp.copy(leaves[i]) for i in self.idx]

        def put(cache, saved):
            leaves, tree = jax.tree.flatten(cache)
            for i, s in zip(self.idx, saved):
                leaves[i] = jnp.copy(s)  # saved stays the caller's
            return jax.tree.unflatten(tree, leaves)

        self._put = jax.jit(put, donate_argnums=(0,))

    @classmethod
    def of(cls, cache, axes: list):
        return cls(cache, axes) if None in axes else None

    def __call__(self, cache):
        return self._put(cache, self.saved)


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


def fullest_share(tree, devices) -> float:
    """The share of ``tree``'s bytes held on its fullest device."""
    arrays = jax.tree.leaves(tree)
    held = max(sum(s.data.nbytes for a in arrays
                   for s in a.addressable_shards if s.device == d)
               for d in devices)
    return held / sum(a.nbytes for a in arrays)


def compare(items, config: dict, shapes: dict, seed: int, tp: int,
            device, control: str | None = None) -> dict:
    """The logit gaps (the reference module's ``summary``) over every
    compared token.  ``items``: (tokens (B, T), group ids (B, T),
    positions (P,), served (B, P)): teacher-forced sequences, the step
    each token was served in, and the tokens chosen at those positions.
    With ``control`` (a precision of the reference: ``"fp8"``, the
    control, or ``"bf16"``, the witness) that reference's first choices
    at the same positions are judged in place of the served tokens."""
    lib = spec.reference(config)
    m, draws = config["model"], config.get("weights")
    ref = lib.Reference(m, shapes, seed, tp, "f32", device, draws=draws)
    ctrl = (lib.Reference(m, shapes, seed, tp, control, device,
                          draws=draws) if control else None)
    out = []
    with jax.default_matmul_precision("highest"):
        for tokens, groups, pos, served in items:
            h = ref.hidden(tokens, groups, pos)
            if ctrl is None:
                out.append(lib.gap_values(ref, h, served))
            else:
                hc = ctrl.hidden(tokens, groups, pos)
                out.append(lib.gap_values(ref, h, ctrl=ctrl, h_ctrl=hc))
    return lib.summary(np.concatenate([g.ravel() for g in out]))
