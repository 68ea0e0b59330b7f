"""Fused MoE routing-offset Pallas TPU kernel.

Given per-(token, slot) expert assignments, computes each entry's write
position inside its expert's buffer (the exclusive count of earlier
same-expert entries) plus per-expert totals — the quantities whose
*cross-device* prefix is then taken with the paper's 123-doubling exscan
to build all-to-all dispatch offsets (models/moe.py).

TPU adaptation: a histogram-scan.  Sequential grid over token blocks,
running per-expert counters in VMEM scratch; within a block the
per-token expert counts (block_tokens, E) are scanned over tokens with
the engine's sublane scan on the VPU, and slots within a token are
ordered by a K-step running sum.  One pass, no atomics (the GPU idiom)
needed — grid order gives determinism for free.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.scan_engine import sublane_scan, tuple_combine


def _routing_kernel(assign_ref, pos_ref, counts_ref, carry_ref, *, num_experts):
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _init():
        carry_ref[...] = jnp.zeros_like(carry_ref)

    assign = assign_ref[...]  # (bt, K) int32
    bt, k = assign.shape
    slot = jax.lax.broadcasted_iota(jnp.int32, (bt, k), 1)
    expert = jax.lax.broadcasted_iota(jnp.int32, (bt, num_experts), 1)
    # one (bt, E) one-hot per slot; slot j's column is picked with a
    # lane select + reduction (no 1-D flatten, no lane slice)
    onehots = []
    for j in range(k):
        col = jnp.sum(jnp.where(slot == j, assign, 0), axis=1,
                      keepdims=True)  # (bt, 1)
        onehots.append((col == expert).astype(jnp.int32))
    per_token = functools.reduce(jnp.add, onehots)  # (bt, E)
    # exclusive count of earlier tokens' entries, per expert
    incl, = sublane_scan(tuple_combine(jnp.add), (per_token,))
    base = carry_ref[...] + incl - per_token  # (bt, E)
    pos = jnp.zeros((bt, k), jnp.int32)
    for j, oh in enumerate(onehots):
        # earlier slots of the same token come first (row-major order)
        pos_j = jnp.sum(base * oh, axis=1, keepdims=True)  # (bt, 1)
        pos = jnp.where(slot == j, pos_j, pos)
        base = base + oh
    pos_ref[...] = pos
    new_counts = carry_ref[...] + jnp.sum(per_token, axis=0,
                                          keepdims=True)
    carry_ref[...] = new_counts

    @pl.when(i == pl.num_programs(0) - 1)
    def _final():
        counts_ref[...] = new_counts


@functools.partial(
    jax.jit, static_argnames=("num_experts", "block_tokens", "interpret")
)
def moe_routing(
    assignment: jax.Array,
    *,
    num_experts: int,
    block_tokens: int = 256,
    interpret: bool = False,
):
    """Positions within expert buffers + per-expert counts.

    Args:
      assignment: (T, K) int32 expert ids, T % block_tokens == 0.

    Returns:
      positions: (T, K) int32; counts: (1, num_experts) int32.
    """
    T, K = assignment.shape
    assert T % block_tokens == 0, (T, block_tokens)
    grid = (T // block_tokens,)
    kernel = functools.partial(_routing_kernel, num_experts=num_experts)
    positions, counts = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[pl.BlockSpec((block_tokens, K), lambda i: (i, 0))],
        out_specs=[
            pl.BlockSpec((block_tokens, K), lambda i: (i, 0)),
            pl.BlockSpec((1, num_experts), lambda i: (0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((T, K), jnp.int32),
            jax.ShapeDtypeStruct((1, num_experts), jnp.int32),
        ],
        scratch_shapes=[pltpu.VMEM((1, num_experts), jnp.int32)],
        interpret=interpret,
        name="moe_routing",
    )(assignment)
    return positions, counts
