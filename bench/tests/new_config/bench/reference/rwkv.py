"""Plain float32 forward of the RWKV-6 ("Finch") decoder the program
serves (its ``LayerSpec`` kind ``rwkv``).

Written from the layer equations, one token after another, in
``jax.numpy`` with every matrix product at ``Precision.HIGHEST``: token
embedding; per layer a time mix (pre-norm, token shift with learned
interpolation, projections r, k, v, g, a data-dependent decay
w = exp(-exp(clip(x_w W_decay + b, -8, 4))), per head the state
S_t = diag(w_t) S_(t-1) + k_t v_t^T read as r_t (S_(t-1) + u k_t v_t^T),
an RMS norm per head, the silu gate and the output projection) and a
channel mix (pre-norm, token shift, relu^2 key, sigmoid receptance);
final RMS norm and output head.  A sequence starts from a zero state
and a zero shift.  ``group_id`` is not read: no token of this layer
depends on another sequence.

``precision`` is ``"f32"``, ``"fp8"`` (the control) or ``"bf16"`` (the
witness), as ``bench/reference/__init__.py`` says; the state recurrence
is float32 in every case, as the program keeps it.  Weights are drawn
from the seed one layer at a time with ``bench/weights.py``, in the
served dtype, then widened to float32.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax

from bench import weights as W
from bench.reference import gap_values, summary  # noqa: F401

HIGHEST = lax.Precision.HIGHEST
HEAD_DIM = 64
FP8 = jnp.float8_e4m3fn
FP8_MAX = 448.0


def _round(x, precision):
    if precision == "fp8":
        s = FP8_MAX / jnp.maximum(jnp.max(jnp.abs(x)), 1e-30)
        return (x * s).astype(FP8).astype(jnp.float32) / s
    if precision == "bf16":
        return x.astype(jnp.bfloat16).astype(jnp.float32)
    return x


def mm(spec, a, b, precision):
    return jnp.einsum(spec, _round(a, precision), _round(b, precision),
                      precision=HIGHEST)


def rmsnorm(x, scale, eps):
    return x * lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale


def _shift(x):
    return jnp.concatenate([jnp.zeros_like(x[:, :1]), x[:, :-1]], 1)


@functools.partial(jax.jit, static_argnames=("eps", "precision"))
def _layer(x, w, eps, precision):
    B, T, d = x.shape
    H, hd = d // HEAD_DIM, HEAD_DIM

    def proj(z, name):
        return mm("btd,de->bte", z, w[name], precision)

    xn = rmsnorm(x, w["norm1"], eps)
    xp = _shift(xn)
    lerp = lambda mu: xn + (xp - xn) * w[mu]  # noqa: E731
    r = proj(lerp("mu_r"), "wr").reshape(B, T, H, hd)
    k = proj(lerp("mu_k"), "wk").reshape(B, T, H, hd)
    v = proj(lerp("mu_v"), "wv").reshape(B, T, H, hd)
    g = jax.nn.silu(proj(lerp("mu_g"), "wg"))
    decay = jnp.exp(-jnp.exp(jnp.clip(
        proj(lerp("mu_w"), "w_decay") + w["decay_bias"], -8.0, 4.0)))
    decay = decay.reshape(B, T, H, hd)
    u = w["bonus_u"].reshape(H, hd)

    def step(s, inp):  # s: (B, H, hd, hd)
        r_t, k_t, v_t, w_t = inp
        kv = k_t[..., :, None] * v_t[..., None, :]
        out = jnp.einsum("bhi,bhij->bhj", r_t, s + u[..., None] * kv,
                         precision=HIGHEST)
        return w_t[..., None] * s + kv, out

    _, out = lax.scan(step, jnp.zeros((B, H, hd, hd), jnp.float32),
                      tuple(a.swapaxes(0, 1) for a in (r, k, v, decay)))
    out = out.swapaxes(0, 1)  # (B, T, H, hd)
    out = out * lax.rsqrt(jnp.mean(out * out, -1, keepdims=True) + eps)
    x = x + mm("bte,ed->btd", out.reshape(B, T, d) * g, w["wo"],
               precision)

    xn2 = rmsnorm(x, w["norm2"], eps)
    xp2 = _shift(xn2)
    lerp2 = lambda mu: xn2 + (xp2 - xn2) * w[mu]  # noqa: E731
    kk = jnp.square(jax.nn.relu(mm("btd,df->btf", lerp2("mu_ck"),
                                   w["cm_wk"], precision)))
    cm = mm("btf,fd->btd", kk, w["cm_wv"], precision)
    rr = jax.nn.sigmoid(mm("btd,de->bte", lerp2("mu_cr"), w["cm_wr"],
                           precision))
    return x + rr * cm


@functools.partial(jax.jit, static_argnums=(1, 2, 3, 4))
def _top_leaf(key, name, shape, dtype, draws):
    return W.top_leaf(key, name, shape, dtype, dict(draws)).astype(
        jnp.float32)


@functools.partial(jax.jit, static_argnums=(2, 3, 4))
def _layer_leaves(key, layer, shapes, dtype, draws):
    return {k: v.astype(jnp.float32) for k, v in W.layer_leaves(
        key, 0, layer, dict(shapes), dtype, dict(draws)).items()}


class Reference:
    """The reference model for one configuration and seed (interface in
    ``bench/reference/__init__.py``; ``tp`` is not read)."""

    def __init__(self, model: dict, shapes: dict, seed: int, tp: int = 1,
                 precision: str = "f32", device=None, draws=None):
        if len(shapes["blocks"]) != 1:
            raise ValueError("the reference covers one rwkv layer kind "
                             "per repeat")
        self.m, self.shapes = model, shapes
        self.key = W.seed_key(seed)
        self.precision = precision
        self.dtype = jnp.dtype(model.get("dtype", "bfloat16"))
        self.device = device or jax.devices()[0]
        self.draws = W.frozen(draws)
        self.eps = float(model.get("norm_eps", 1e-6))

    def _put(self, x):
        return jax.device_put(x, self.device)

    def _top(self, name):
        return _top_leaf(self._put(self.key), name,
                         self.shapes["top"][name], self.dtype, self.draws)

    def hidden(self, tokens, group_id, keep_pos):
        x = self._top("tok_embed")[self._put(jnp.asarray(tokens,
                                                          jnp.int32))]
        shapes = tuple(sorted(self.shapes["blocks"][0].items()))
        for layer in range(self.m["n_layers"]):
            w = _layer_leaves(self._put(self.key),
                              self._put(jnp.int32(layer)), shapes,
                              self.dtype, self.draws)
            x = _layer(x, w, self.eps, self.precision)
        x = x[:, self._put(jnp.asarray(keep_pos, jnp.int32))]
        return rmsnorm(x, self._top("final_norm"), self.eps)

    def head(self):
        name = "tok_embed" if self.m.get("tie_embeddings") else "lm_head"
        w = self._top(name)
        w = w.T if name == "tok_embed" else w
        return w[:, :self.m["vocab"]]

    def logits(self, h, head):
        return mm("bpd,dv->bpv", h, head, self.precision)
