"""Public jit'd wrappers around the Pallas kernels.

Handle arbitrary shapes/dtypes by lane-padding to TPU-friendly tiles
and choose block sizes from a VMEM budget.  ``interpret=None`` runs the
kernels compiled on TPU and in the Pallas interpreter elsewhere, so they
execute — and are tested — everywhere; pass ``interpret=False`` where a
fall-back to the interpreter must be impossible (``chip_smoke.py``).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels import blelloch_exscan as _bl
from repro.kernels import moe_routing as _moe
from repro.kernels import ssm_chunk_scan as _ssm

LANE = 128
_VMEM_BUDGET = 4 * 1024 * 1024  # conservative half-ish of 16 MiB VMEM


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def _pad_to(x: jax.Array, multiple: int, axis: int) -> jax.Array:
    n = x.shape[axis]
    pad = (-n) % multiple
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths)


def _pick_block_rows(d: int, itemsize: int, max_rows: int) -> int:
    """Largest power-of-two row count whose (rows, d) tile fits VMEM."""
    rows = max_rows
    while rows > 8 and rows * d * itemsize * 3 > _VMEM_BUDGET:
        rows //= 2
    return max(rows, 8)


def exscan(x: jax.Array, *, interpret: bool | None = None) -> jax.Array:
    """Exclusive prefix sum along axis 0 of an (n, d) or (n,) array."""
    if interpret is None:
        interpret = not _on_tpu()
    squeeze = x.ndim == 1
    if squeeze:
        x = x[:, None]
    n, d = x.shape
    xp = _pad_to(_pad_to(x, LANE, 1), 8, 0)
    np_, dp = xp.shape
    rows = _pick_block_rows(dp, xp.dtype.itemsize, min(np_, 256))
    xp = _pad_to(xp, rows, 0)
    out = _bl.blelloch_exscan(xp, block_rows=rows, interpret=interpret)
    out = out[:n, :d]
    return out[:, 0] if squeeze else out


def ssm_scan(
    a: jax.Array,
    b: jax.Array,
    h0: jax.Array | None = None,
    *,
    interpret: bool | None = None,
):
    """Diagonal linear recurrence h_t = a_t h_{t-1} + b_t, axis 0.

    a, b: (T, D); h0: (D,) or None.  Returns (h: (T, D), h_final: (D,)).
    Padding note: decay `a` must pad with ONES (identity), b with zeros.
    """
    if interpret is None:
        interpret = not _on_tpu()
    T, D = a.shape
    if h0 is None:
        h0 = jnp.zeros((D,), b.dtype)
    padD = (-D) % LANE
    padT = (-T) % 8
    ap = jnp.pad(a, ((0, padT), (0, padD)), constant_values=1.0)
    bp = jnp.pad(b, ((0, padT), (0, padD)))
    h0p = jnp.pad(h0[None, :], ((0, 0), (0, padD)))
    Tp, Dp = ap.shape
    chunk = _pick_block_rows(Dp, bp.dtype.itemsize, min(Tp, 256))
    padT2 = (-Tp) % chunk
    if padT2:
        ap = jnp.pad(ap, ((0, padT2), (0, 0)), constant_values=1.0)
        bp = jnp.pad(bp, ((0, padT2), (0, 0)))
    h, _ = _ssm.ssm_chunk_scan(ap, bp, h0p, chunk=chunk, interpret=interpret)
    h = h[:T, :D]
    return h, h[-1]


def ssm_chunk_summary(
    a: jax.Array, b: jax.Array, *, interpret: bool | None = None
):
    """Chunk summary (A_total, B_total) of a sequence slice: the AFFINE
    monoid element composed across devices by core.collectives.exscan."""
    if interpret is None:
        interpret = not _on_tpu()
    T, D = a.shape
    padD = (-D) % LANE
    ap = jnp.pad(a, ((0, 0), (0, padD)), constant_values=1.0)
    bp = jnp.pad(b, ((0, 0), (0, padD)))
    Tp = ap.shape[0]
    chunk = _pick_block_rows(ap.shape[1], bp.dtype.itemsize, min(Tp, 256))
    padT = (-Tp) % chunk
    if padT:
        ap = jnp.pad(ap, ((0, padT), (0, 0)), constant_values=1.0)
        bp = jnp.pad(bp, ((0, padT), (0, 0)))
    a_tot, b_tot = _ssm.ssm_chunk_summary(ap, bp, chunk=chunk, interpret=interpret)
    return a_tot[0, :D], b_tot[0, :D]


def moe_routing(
    assignment: jax.Array,
    num_experts: int,
    *,
    interpret: bool | None = None,
):
    """Write positions within expert buffers + per-expert counts.

    assignment: (T, K) int32.  Returns (positions (T,K) i32, counts (E,) i32).
    """
    if interpret is None:
        interpret = not _on_tpu()
    T, K = assignment.shape
    padE = (-num_experts) % LANE
    E = num_experts + padE
    if E == num_experts:
        E += LANE  # guarantee a sentinel column for token padding
    block = min(T, max(8, _VMEM_BUDGET // (8 * E * 4)))
    # round block down to a divisor-friendly power of two
    b = 8
    while b * 2 <= block:
        b *= 2
    block = b
    padT = (-T) % block
    ap = jnp.pad(assignment, ((0, padT), (0, 0)), constant_values=E - 1)
    pos, counts = _moe.moe_routing(
        ap, num_experts=E, block_tokens=block, interpret=interpret
    )
    return pos[:T], counts[0, :num_experts]
