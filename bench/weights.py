"""Seeded weights, made on the device in the type they are served in.

Every leaf of a layer is drawn from its own 32-bit stream, a hash of
(seed, crc32(path), layer): element i gets the murmur3 finaliser of
``i * golden + stream``, turned into a uniform value of unit variance
with exact arithmetic, times one scale factor.  Integer hashing is a
few operations an element, several times cheaper on the chip than
threefry, and gives the same bits however the leaf is batched or
sharded.  The same function serves the program (all layers of a leaf at
once, under ``vmap``) and the reference (one layer at a time), so both
see bit-identical bfloat16 values without the reference taking anything
the program made.

A configuration may state how some of its leaves are drawn, by leaf
name, in its ``"weights"`` block (``draws`` here): ``"ones"``,
``"zeros"`` or ``{"uniform": [lo, hi]}`` (lo + (hi - lo) x a uniform
value on [0, 1) from the same bits).  A leaf it does not name keeps the
rule of ``scale``.
"""

from __future__ import annotations

import math
import zlib

import jax
import jax.numpy as jnp
import numpy as np

NORMS = ("norm1", "norm2", "final_norm")


GOLDEN = np.uint32(0x9E3779B9)


def seed_key(seed: int) -> jax.Array:
    """A 32-bit key from any whole-number seed (more than 32 bits
    too); pass it to jitted code as an argument."""
    word = np.random.SeedSequence(int(seed)).generate_state(1)[0]
    return jnp.asarray(np.uint32(word))


def _fmix(x):
    """murmur3's 32-bit finaliser."""
    x = x ^ (x >> 16)
    x = x * np.uint32(0x85EBCA6B)
    x = x ^ (x >> 13)
    x = x * np.uint32(0xC2B2AE35)
    return x ^ (x >> 16)


def _path_key(base, path: str):
    return _fmix(base ^ np.uint32(zlib.crc32(path.encode())))


def _fold(key, layer):
    return _fmix(key + jnp.asarray(layer, jnp.uint32) * GOLDEN)


def _bits(key, shape):
    flat = jnp.zeros(shape, jnp.uint32)
    stride = 1
    for dim in reversed(range(len(shape))):
        flat = flat + jax.lax.broadcasted_iota(jnp.uint32, shape,
                                               dim) * np.uint32(stride)
        stride *= shape[dim]
    return _fmix(flat * GOLDEN + key)


def scale(name: str, shape) -> float:
    if name in NORMS:
        return 0.1
    if name == "tok_embed":  # (vocab, d): a row is one token's vector
        return 1.0 / math.sqrt(shape[-1])
    fan_in = shape[-2] if len(shape) >= 2 else shape[-1]
    return (2.0 if name == "router" else 1.0) / math.sqrt(fan_in)


def frozen(draws) -> tuple:
    """A configuration's ``weights`` block as sorted (name, rule) pairs,
    hashable: each rule ``"ones"``, ``"zeros"`` or ("uniform", lo, hi)."""
    out = []
    for name, rule in sorted((draws or {}).items()):
        if isinstance(rule, dict) and set(rule) == {"uniform"}:
            lo, hi = rule["uniform"]
            rule = ("uniform", float(lo), float(hi))
        elif isinstance(rule, (tuple, list)) and rule[0] == "uniform":
            rule = ("uniform", float(rule[1]), float(rule[2]))
        elif rule not in ("ones", "zeros"):
            raise ValueError(f"weights: no draw rule {rule!r} for {name!r}")
        out.append((name, rule))
    return tuple(out)


def leaf_value(key, shape, name: str, dtype, draws=None):
    """Uniform on [-sqrt(3), sqrt(3)) times ``scale``; norms are 1 plus
    that.  Exact up to the one multiply and the final cast.  ``draws``
    ({name: rule}, rules as ``frozen`` gives them) overrides that rule
    for the leaves it names."""
    rule = (draws or {}).get(name)
    if rule == "ones":
        return jnp.ones(shape, dtype)
    if rule == "zeros":
        return jnp.zeros(shape, dtype)
    bits = _bits(key, tuple(shape))
    if rule is not None:
        _, lo, hi = rule
        u = (bits >> 8).astype(jnp.float32) * np.float32(2.0 ** -24)
        return (np.float32(lo) + np.float32(hi - lo) * u).astype(dtype)
    u = (bits >> 9).astype(jnp.int32) - (1 << 22)  # [-2^22, 2^22)
    v = u.astype(jnp.float32) * np.float32(
        math.sqrt(3.0) * 2.0 ** -22 * scale(name, shape))
    if name in NORMS:
        v = v + np.float32(1.0)
    return v.astype(dtype)


def params(abstract, base, draws=None):
    """The program's parameter tree (shapes from ``abstract``: the
    model's ``abstract_params()``), drawn from the key ``base``
    (``seed_key(seed)``) and the configuration's ``weights`` block
    ``draws``.  Call under ``jax.jit`` with the key as an argument, so
    one compiled program serves every seed, and the model's shardings
    as ``out_shardings``."""
    draws = dict(frozen(draws))
    top = {name: leaf_value(_path_key(base, f"top/{name}"), s.shape, name,
                            s.dtype, draws)
           for name, s in abstract["top"].items()}
    blocks = []
    for j, defs in enumerate(abstract["blocks"]):
        out = {}
        for name, s in defs.items():
            k = _path_key(base, f"blocks/{j}/{name}")
            layers = jnp.arange(s.shape[0])
            out[name] = jax.vmap(
                lambda l, k=k, s=s, name=name: leaf_value(
                    _fold(k, l), s.shape[1:], name, s.dtype, draws)
            )(layers)
        blocks.append(out)
    return {"top": top, "blocks": tuple(blocks)}


def top_leaf(base, name: str, shape, dtype, draws=None):
    """One top-level leaf, as ``params`` draws it."""
    return leaf_value(_path_key(base, f"top/{name}"), shape, name, dtype,
                      dict(frozen(draws)))


def layer_leaves(base, j: int, layer, shapes: dict, dtype, draws=None):
    """Layer ``layer`` of pattern position ``j``: {name: value}, with
    ``shapes`` the per-layer shapes (without the stacking axis)."""
    draws = dict(frozen(draws))
    return {name: leaf_value(
        _fold(_path_key(base, f"blocks/{j}/{name}"), layer),
        shape, name, dtype, draws) for name, shape in shapes.items()}
