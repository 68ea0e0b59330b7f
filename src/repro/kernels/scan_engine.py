"""Single-pass chunked scan engine: ONE Pallas kernel family for every
on-chip scan in the repo (DESIGN §7 "Kernel engine").

LightScan-style single-pass chunked scans dominate multi-pass/tree
formulations on accelerators: a sequential grid walks chunk-sized row
blocks while a VMEM carry register holds the running prefix, so the
payload crosses HBM exactly once.  This module generalizes that idiom
over the core :mod:`repro.core.monoid` algebra and backs three callers:

  * the rank-local pre/post phase of every device plan
    (``kernels.blelloch_exscan.blelloch_exscan`` → :func:`monoid_exscan`
    — no longer cumsum-only: any elementwise monoid);
  * the Mamba/RWKV SSM chunk scan (``kernels.ssm_chunk_scan`` →
    :func:`affine_chunk_scan` / :func:`affine_chunk_summary`, the
    affine-monoid instance — its private ``_affine`` duplicate of the
    core monoid is gone);
  * the per-round ⊕ hooks of ``core.schedule.PallasExecutor``
    (:func:`tree_combine`, :func:`tree_exchange`,
    :func:`tree_scan_reduce`): a round's recv ⊕ W combine, its
    receive-mask/side select, and the store of the result run in ONE
    grid pass, and the k payload leaves of a round (fused-layout slots,
    scan_reduce's (P, T) pair) are batched into a single ``pallas_call``
    so k payloads cost one HBM traversal, not k.

Padding uses the *monoid identity* (not literal zeros), so non-zero-
identity monoids (max/min/mul, the affine pair) can never read garbage
from padded lanes — identity ⊕ identity = identity keeps pad lanes
inert even if a caller stops truncating.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core import monoid as monoid_lib

LANE = 128  # TPU lane width: last dim of every tile

# ---------------------------------------------------------------------------
# Monoid adapter: which monoids the engine serves, identities for padding
# ---------------------------------------------------------------------------

_OP_NAMES = {
    jnp.add: "add",
    jnp.multiply: "mul",
    jnp.maximum: "max",
    jnp.minimum: "min",
    jnp.bitwise_xor: "xor",
}


def leaf_identity(name: str, dtype):
    """Identity *scalar* of an elementwise monoid at ``dtype`` — the
    pad value for lane/row padding (max/min are dtype-dependent)."""
    dtype = jnp.dtype(dtype)
    if name in ("add", "xor"):
        return 0
    if name == "mul":
        return 1
    is_int = jnp.issubdtype(dtype, jnp.integer)
    if name == "max":
        return int(jnp.iinfo(dtype).min) if is_int else float("-inf")
    if name == "min":
        return int(jnp.iinfo(dtype).max) if is_int else float("inf")
    raise KeyError(f"no identity scalar for monoid {name!r}")


def _op_identity(op, dtype):
    """Pad identity for a raw ``op`` callable (the ``block_combine``
    compatibility surface receives ops, not monoids).  Unknown ops keep
    the legacy zero pad — padded lanes are always truncated from the
    output, so this is a hardening default, not a correctness one."""
    name = _OP_NAMES.get(op)
    return leaf_identity(name, dtype) if name is not None else 0


def supports(m: monoid_lib.Monoid) -> bool:
    """Can the engine serve this monoid on-chip?  Elementwise monoids
    (``leaf_op``) and the affine pair; MATMUL falls back to plain XLA."""
    return m.leaf_op is not None or m.name == "affine"


@functools.lru_cache(maxsize=None)
def tuple_combine(op):
    """Lift an elementwise ``op`` to the engine's tuple-of-leaves
    combine signature (cached so jit sees one stable callable per op)."""

    def combine(lo, hi):
        return tuple(op(a, b) for a, b in zip(lo, hi))

    return combine


# The affine instance uses the ONE core definition — no private copy.
_affine_combine = monoid_lib.affine_combine


# ---------------------------------------------------------------------------
# The chunked scan kernel: sequential grid + VMEM carry, any monoid
# ---------------------------------------------------------------------------


def sublane_scan(combine, xs):
    """Inclusive scan over axis 0 of same-shape (rows, D) leaf tuples.

    Hillis–Steele in log2(rows) rounds: each round rolls the leaves
    down by a power of two (``pltpu.roll`` along sublanes) and combines
    every row with the one that far above it, a ``broadcasted_iota``
    mask keeping rows with no such partner.  Mosaic lowers roll, iota
    and select; it refuses ``lax.associative_scan``'s strided slices.
    """
    rows = xs[0].shape[0]
    row = lax.broadcasted_iota(jnp.int32, xs[0].shape, 0)
    k = 1
    while k < rows:
        above = tuple(pltpu.roll(x, k, 0) for x in xs)
        comb = combine(above, xs)
        xs = tuple(jnp.where(row >= k, c, x) for c, x in zip(comb, xs))
        k *= 2
    return xs


def _scan_body(combine, n_in, exclusive, traj, fin, *refs):
    """One grid step of the single-pass chunked scan.

    ``refs``: n_in chunk inputs, n_in (1, D) init rows, len(traj)
    trajectory outputs, len(fin) final rows, n_in VMEM carry scratch.
    The carry holds the inclusive prefix of every prior chunk; one
    :func:`sublane_scan` + one carry combine serve the whole chunk.
    """
    x_refs = refs[:n_in]
    init_refs = refs[n_in:2 * n_in]
    k = 2 * n_in
    out_refs = refs[k:k + len(traj)]
    fin_refs = refs[k + len(traj):k + len(traj) + len(fin)]
    carry_refs = refs[k + len(traj) + len(fin):]

    i = pl.program_id(0)

    @pl.when(i == 0)
    def _seed():
        for c, ini in zip(carry_refs, init_refs):
            c[...] = ini[...]

    xs = tuple(r[...] for r in x_refs)
    incl = sublane_scan(combine, xs)
    cvals = tuple(c[...] for c in carry_refs)
    full = combine(cvals, incl)  # (1, D) carry broadcasts over chunk
    # rolled down by one row: rows 1.. are the exclusive prefixes and
    # row 0 wraps around to the chunk's last (inclusive) row — both
    # without an unaligned sublane slice
    rolled = tuple(pltpu.roll(f, 1, 0) for f in full)
    last = tuple(r[0:1, :] for r in rolled)
    if exclusive:
        row = lax.broadcasted_iota(jnp.int32, rolled[0].shape, 0)
        outs = tuple(jnp.where(row == 0, c, r)
                     for c, r in zip(cvals, rolled))
    else:
        outs = full
    for o_ref, j in zip(out_refs, traj):
        o_ref[...] = outs[j]
    for c, l in zip(carry_refs, last):
        c[...] = l

    @pl.when(i == pl.num_programs(0) - 1)
    def _finish():
        for f_ref, j in zip(fin_refs, fin):
            f_ref[...] = last[j]


def chunked_scan(xs, init, combine, *, name, exclusive=False,
                 traj=(0,), final=(), chunk=256, interpret=False):
    """Single-pass chunked scan over axis 0 of (T, D) leaf tuples.

    ``combine`` takes/returns tuples of leaves; ``init`` seeds the VMEM
    carry ((1, D) rows — the exclusive prefix of row 0).  ``traj``
    selects which leaves' trajectories are written, ``final`` which
    leaves' inclusive totals come back as (1, D) rows.  ``name`` is the
    kernel's name in HLO and profiles (the calling entry point's).
    Returns ``(trajectory_leaves, final_leaves)``.
    """
    xs = tuple(xs)
    init = tuple(init)
    n_in = len(xs)
    T, D = xs[0].shape
    if T % chunk:
        raise ValueError(f"rows {T} not a multiple of chunk {chunk}")
    traj = tuple(traj)
    final = tuple(final)
    x_spec = pl.BlockSpec((chunk, D), lambda i: (i, 0))
    row_spec = pl.BlockSpec((1, D), lambda i: (0, 0))
    out_shape = ([jax.ShapeDtypeStruct((T, D), xs[j].dtype)
                  for j in traj]
                 + [jax.ShapeDtypeStruct((1, D), xs[j].dtype)
                    for j in final])
    kernel = functools.partial(_scan_body, combine, n_in, exclusive,
                               traj, final)
    outs = pl.pallas_call(
        kernel,
        grid=(T // chunk,),
        in_specs=[x_spec] * n_in + [row_spec] * n_in,
        out_specs=[x_spec] * len(traj) + [row_spec] * len(final),
        out_shape=out_shape,
        scratch_shapes=[pltpu.VMEM((1, D), x.dtype) for x in xs],
        interpret=interpret,
        name=name,
    )(*xs, *init)
    return tuple(outs[:len(traj)]), tuple(outs[len(traj):])


@functools.partial(jax.jit,
                   static_argnames=("monoid", "block_rows", "interpret"))
def monoid_exscan(x, monoid: str = "add", *, block_rows: int = 256,
                  interpret: bool = False):
    """Exclusive scan of (n, d) rows under any elementwise monoid —
    the rank-local phase of every device plan.  Row 0 gets the monoid
    identity; row t the ⊕ of rows [0, t)."""
    m = monoid_lib.get(monoid)
    if m.leaf_op is None:
        raise ValueError(f"monoid {monoid!r} is not elementwise")
    n, d = x.shape
    if n % block_rows:
        raise ValueError(f"rows {n} not a multiple of {block_rows}")
    init = jnp.full((1, d), leaf_identity(m.name, x.dtype), x.dtype)
    (out,), _ = chunked_scan(
        (x,), (init,), tuple_combine(m.leaf_op), name="monoid_exscan",
        exclusive=True,
        traj=(0,), final=(), chunk=block_rows, interpret=interpret)
    return out


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def affine_chunk_scan(a, b, h0, *, chunk: int = 256,
                      interpret: bool = False):
    """h_t = a_t * h_{t-1} + b_t — the affine-monoid engine instance.

    The carry pair is the affine element ((∏a so far), h_last); each
    chunk's trajectory is the b-leaf of carry ∘ chunk-scan, i.e.
    ``cum_a * h_in + cum_b`` exactly as the dedicated SSM kernel
    computed it.  Returns (h (T, D), h_final (1, D))."""
    init = (jnp.ones_like(h0), h0)
    (h,), (h_final,) = chunked_scan(
        (a, b), init, _affine_combine, name="affine_chunk_scan",
        exclusive=False, traj=(1,), final=(1,), chunk=chunk,
        interpret=interpret)
    return h, h_final


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def affine_chunk_summary(a, b, *, chunk: int = 256,
                         interpret: bool = False):
    """Whole-sequence affine summary (a_total, b_total) in ONE pass —
    the carry's a-leaf chains the chunk products, so no second
    ``prod`` traversal of ``a`` is needed."""
    D = a.shape[1]
    init = (jnp.ones((1, D), a.dtype), jnp.zeros((1, D), a.dtype))
    _, (a_tot, b_tot) = chunked_scan(
        (a, b), init, _affine_combine, name="affine_chunk_summary",
        exclusive=False, traj=(), final=(0, 1), chunk=chunk,
        interpret=interpret)
    return a_tot, b_tot


# ---------------------------------------------------------------------------
# Fused round-combine kernels (the PallasExecutor ⊕ hooks)
# ---------------------------------------------------------------------------


def _combine_kernel(op, a_ref, b_ref, o_ref):
    o_ref[...] = op(a_ref[...], b_ref[...])


def _masked_combine_kernel(op, a_ref, b_ref, k_ref, o_ref):
    keep = k_ref[0, 0] != 0
    o_ref[...] = jnp.where(keep, op(a_ref[...], b_ref[...]), b_ref[...])


def _exchange_kernel(op, r_ref, w_ref, s_ref, o_ref):
    # butterfly round: the side bit picks the combine order; the two
    # orders, the select and the store are ONE grid pass (the XLA
    # baseline is two combine launches plus a select sweep)
    low = s_ref[0, 0] != 0
    r, w = r_ref[...], w_ref[...]
    o_ref[...] = jnp.where(low, op(r, w), op(w, r))


def _scan_reduce_kernel(op, commutative, r_ref, w_ref, p_ref, s_ref,
                        w_out, p_out):
    # fused exscan+allreduce round: both registers (window total T and
    # exclusive prefix P) update in one traversal of the three inputs
    low = s_ref[0, 0] != 0
    r, w, p = r_ref[...], w_ref[...], p_ref[...]
    if commutative:
        w_out[...] = op(r, w)
    else:
        w_out[...] = jnp.where(low, op(r, w), op(w, r))
    p_out[...] = jnp.where(low, op(r, p), p)


def _affine_combine_kernel(al, bl, ah, bh, oa, ob):
    ca, cb = _affine_combine((al[...], bl[...]), (ah[...], bh[...]))
    oa[...] = ca
    ob[...] = cb


def _affine_masked_kernel(al, bl, ah, bh, k_ref, oa, ob):
    keep = k_ref[0, 0] != 0
    a_hi, b_hi = ah[...], bh[...]
    ca, cb = _affine_combine((al[...], bl[...]), (a_hi, b_hi))
    oa[...] = jnp.where(keep, ca, a_hi)
    ob[...] = jnp.where(keep, cb, b_hi)


def _affine_exchange_kernel(ar, br, aw, bw, s_ref, oa, ob):
    low = s_ref[0, 0] != 0
    recv = (ar[...], br[...])
    w = (aw[...], bw[...])
    la, lb = _affine_combine(recv, w)
    ha, hb = _affine_combine(w, recv)
    oa[...] = jnp.where(low, la, ha)
    ob[...] = jnp.where(low, lb, hb)


def _affine_scan_reduce_kernel(ar, br, aw, bw, ap, bp, s_ref,
                               oaw, obw, oap, obp):
    low = s_ref[0, 0] != 0
    recv = (ar[...], br[...])
    w = (aw[...], bw[...])
    p = (ap[...], bp[...])
    la, lb = _affine_combine(recv, w)
    ha, hb = _affine_combine(w, recv)
    oaw[...] = jnp.where(low, la, ha)
    obw[...] = jnp.where(low, lb, hb)
    pa, pb = _affine_combine(recv, p)
    oap[...] = jnp.where(low, pa, p[0])
    obp[...] = jnp.where(low, pb, p[1])


def _pad_tile(flat, pad_value, block_rows):
    """(n,) flat → identity-padded (rows, LANE) tile + block height."""
    n = flat.size
    lane_pad = (-n) % LANE
    if lane_pad:
        flat = jnp.pad(flat, (0, lane_pad), constant_values=pad_value)
    tiled = flat.reshape(-1, LANE)
    rows = tiled.shape[0]
    br = min(block_rows, rows)
    row_pad = (-rows) % br
    if row_pad:
        tiled = jnp.pad(tiled, ((0, row_pad), (0, 0)),
                        constant_values=pad_value)
    return tiled, br


def _round_call(kernel, ins, pad_values, n_out, *, name, scalar=None,
                block_rows=256, interpret=False):
    """Launch ONE round kernel over same-size flat operands, named
    ``name`` (the calling entry point) in HLO and profiles.

    ``ins`` are 1-D same-dtype buffers (a whole dtype group of payload
    leaves, pre-concatenated); each is identity-padded to the (rows,
    LANE) tiling.  ``scalar`` (receive mask / butterfly side bit) rides
    in SMEM.  Returns ``n_out`` flat buffers truncated to input size.
    """
    n = ins[0].size
    tiles = []
    br = 1
    for v, pv in zip(ins, pad_values):
        t, br = _pad_tile(v, pv, block_rows)
        tiles.append(t)
    rows = tiles[0].shape[0]
    tile_spec = pl.BlockSpec((br, LANE), lambda i: (i, 0))
    in_specs = [tile_spec] * len(tiles)
    operands = list(tiles)
    if scalar is not None:
        operands.append(jnp.reshape(jnp.asarray(scalar, jnp.int32),
                                    (1, 1)))
        in_specs.append(pl.BlockSpec(memory_space=pltpu.SMEM))
    outs = pl.pallas_call(
        kernel,
        grid=(rows // br,),
        in_specs=in_specs,
        out_specs=[tile_spec] * n_out,
        out_shape=[jax.ShapeDtypeStruct(tiles[0].shape, tiles[0].dtype)
                   for _ in range(n_out)],
        interpret=interpret,
        name=name,
    )(*operands)
    return [o.reshape(-1)[:n] for o in outs]


@functools.partial(
    jax.jit,
    static_argnames=("op", "block_rows", "interpret", "pad_value"))
def block_combine(a, b, op, *, keep=None, block_rows: int = 256,
                  interpret: bool = False, pad_value=None):
    """a ⊕ b over arbitrary-shape arrays through (block_rows, LANE)
    VMEM tiles — one launch, one HBM pass.  With ``keep`` (a traced
    bool) the receive-mask select fuses into the same pass:
    where(keep, a ⊕ b, b).  Padding uses the monoid identity of ``op``
    (override with ``pad_value``), so max/min never see pad garbage."""
    shape = a.shape
    pv = pad_value if pad_value is not None else _op_identity(op, a.dtype)
    ins = [a.reshape(-1), b.reshape(-1)]
    if keep is None:
        out, = _round_call(functools.partial(_combine_kernel, op), ins,
                           (pv, pv), 1, name="block_combine",
                           block_rows=block_rows, interpret=interpret)
    else:
        out, = _round_call(functools.partial(_masked_combine_kernel, op),
                           ins, (pv, pv), 1, name="block_combine",
                           scalar=keep, block_rows=block_rows,
                           interpret=interpret)
    return out.reshape(shape)


# --- tree-level entry points: k payload leaves, one pallas_call ----------


def _flat_pair(tree):
    """The affine payload shape the kernels serve: a flat (a, b) pair
    of same-shape/dtype arrays.  Returns (a, b) or None."""
    if isinstance(tree, (tuple, list)) and len(tree) == 2:
        a, b = tree
        if (hasattr(a, "shape") and hasattr(b, "shape")
                and a.shape == b.shape
                and getattr(a, "dtype", None) == getattr(b, "dtype",
                                                         None)):
            return a, b
    return None


def _dtype_groups(leaves):
    groups: dict = {}
    for i, leaf in enumerate(leaves):
        groups.setdefault(jnp.dtype(leaf.dtype), []).append(i)
    return groups


def _batched_elementwise(kernel_fn, m, trees, n_out, *, name, scalar,
                         block_rows, interpret):
    """Run one elementwise round kernel over every leaf of ``trees``
    (same structure each), batched so all leaves of one dtype share a
    single ``pallas_call`` — k fused-layout slots cost one HBM
    traversal, not k."""
    leaves0, treedef = jax.tree.flatten(trees[0])
    flat_trees = [leaves0] + [treedef.flatten_up_to(t)
                              for t in trees[1:]]
    n_leaves = len(leaves0)
    out_leaves = [[None] * n_leaves for _ in range(n_out)]
    for dtype, idxs in _dtype_groups(leaves0).items():
        pv = leaf_identity(m.name, dtype)
        sizes = [leaves0[i].size for i in idxs]
        ins = [jnp.concatenate([ft[i].reshape(-1) for i in idxs])
               if len(idxs) > 1 else ft[idxs[0]].reshape(-1)
               for ft in flat_trees]
        outs = _round_call(kernel_fn, ins, (pv,) * len(ins), n_out,
                           name=name, scalar=scalar,
                           block_rows=block_rows, interpret=interpret)
        for k, flat in enumerate(outs):
            off = 0
            for i, sz in zip(idxs, sizes):
                out_leaves[k][i] = flat[off:off + sz].reshape(
                    leaves0[i].shape)
                off += sz
    return tuple(jax.tree.unflatten(treedef, ol) for ol in out_leaves)


def _pair_ins(*pairs):
    return [x.reshape(-1) for pair in pairs for x in pair]


def _pair_pads(n_pairs):
    return (1, 0) * n_pairs  # affine identity: a-leaves 1, b-leaves 0


def _pair_out(tree_like, flats):
    a, b = _flat_pair(tree_like)
    out = (flats[0].reshape(a.shape), flats[1].reshape(b.shape))
    return type(tree_like)(out) if isinstance(tree_like, list) else out


def tree_combine(m, lo, hi, *, keep=None, block_rows=256,
                 interpret=False):
    """Engine ⊕ over payload trees: where(keep, lo ⊕ hi, hi) (plain ⊕
    when ``keep`` is None) in one batched pass.  Returns None when the
    monoid/payload shape is not engine-served (caller falls back)."""
    if m.leaf_op is not None:
        op = m.leaf_op
        if keep is None:
            kern = functools.partial(_combine_kernel, op)
        else:
            kern = functools.partial(_masked_combine_kernel, op)
        out, = _batched_elementwise(kern, m, (lo, hi), 1,
                                    name="tree_combine", scalar=keep,
                                    block_rows=block_rows,
                                    interpret=interpret)
        return out
    if m.name == "affine":
        plo, phi = _flat_pair(lo), _flat_pair(hi)
        if plo is None or phi is None:
            return None
        kern = (_affine_combine_kernel if keep is None
                else _affine_masked_kernel)
        flats = _round_call(kern, _pair_ins(plo, phi), _pair_pads(2), 2,
                            name="tree_combine", scalar=keep,
                            block_rows=block_rows, interpret=interpret)
        return _pair_out(hi, flats)
    return None


def tree_exchange(m, recv, w, low_side, *, block_rows=256,
                  interpret=False):
    """Non-commutative butterfly round: both combine orders, the side
    select and the store in ONE pass (XLA baseline: 2 launches + a
    select sweep).  Returns the new W, or None if not engine-served."""
    if m.leaf_op is not None:
        kern = functools.partial(_exchange_kernel, m.leaf_op)
        out, = _batched_elementwise(kern, m, (recv, w), 1,
                                    name="tree_exchange", scalar=low_side,
                                    block_rows=block_rows,
                                    interpret=interpret)
        return out
    if m.name == "affine":
        pr, pw = _flat_pair(recv), _flat_pair(w)
        if pr is None or pw is None:
            return None
        flats = _round_call(_affine_exchange_kernel, _pair_ins(pr, pw),
                            _pair_pads(2), 2, name="tree_exchange",
                            scalar=low_side,
                            block_rows=block_rows, interpret=interpret)
        return _pair_out(w, flats)
    return None


def tree_scan_reduce(m, recv, w, prefix, low_side, *, block_rows=256,
                     interpret=False):
    """Fused exscan+allreduce round: the (P, T) register pair updates
    in ONE batched pass (XLA baseline: 2 launches commutative, 3
    launches + 2 select sweeps otherwise).  Returns (w, prefix) or
    None if not engine-served."""
    if m.leaf_op is not None:
        kern = functools.partial(_scan_reduce_kernel, m.leaf_op,
                                 m.commutative)
        w2, p2 = _batched_elementwise(kern, m, (recv, w, prefix), 2,
                                      name="tree_scan_reduce",
                                      scalar=low_side,
                                      block_rows=block_rows,
                                      interpret=interpret)
        return w2, p2
    if m.name == "affine":
        pr, pw, pp = (_flat_pair(recv), _flat_pair(w),
                      _flat_pair(prefix))
        if pr is None or pw is None or pp is None:
            return None
        flats = _round_call(_affine_scan_reduce_kernel,
                            _pair_ins(pr, pw, pp), _pair_pads(3), 4,
                            name="tree_scan_reduce", scalar=low_side,
                            block_rows=block_rows,
                            interpret=interpret)
        return _pair_out(w, flats[:2]), _pair_out(prefix, flats[2:])
    return None
