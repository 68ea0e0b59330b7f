"""Expert-parallel MoE layer with exscan-based global dispatch accounting.

Design (DESIGN.md §3.1): experts are sharded over the "model" mesh axis
(EP == TP degree); tokens travel to their experts with a single
``all_to_all`` per direction inside ``shard_map``.  Buffers are
capacity-padded — (src, expert)-capacity ``cap`` keeps every shape
static — and the *drop policy* is GLOBAL and deterministic: a token is
kept iff its global position within its expert (across all token-holding
devices) is under the expert's global capacity.  That global position is

    global_pos = exscan(per-device expert counts)[expert] + local_pos

computed with the paper's exclusive scan over the data axes — a
(num_experts,)-int vector per MoE layer per step: exactly the small-m,
latency-dominated regime the paper targets.  The capacity accounting
also needs the *global* per-expert dispatch counts (the capacity
allreduce), so both ride ONE fused "scan_total" schedule
(``scan_api.scan_with_total``): at power-of-two group counts the fused
(prefix, total) butterfly delivers offsets AND totals in the
allreduce's ⌈log₂p⌉ rounds instead of exscan + allreduce back to
back.  The planner (``cfg.scan_spec``, default ``algorithm="auto"``)
picks the round-optimal schedule for the axis size; benchmarks pin
explicit algorithms via ``scan=ScanSpec(algorithm=...)`` to compare
them in-situ (each pin maps onto its with-total variant).  The fused
totals are exact dispatch counts, so the load-balance metric's
expert-fraction term comes straight from them — no second top-k pass
over the full logits outside the manual region.

The per-slot position *within* a device is the Pallas moe_routing kernel
on TPU and its pure-jnp oracle elsewhere (:func:`local_routing`).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

from repro.core import scan_api
from repro.kernels import ops as kops
from repro.kernels import ref as kref
from repro.models import params as PD
from repro.models.common import rmsnorm, swiglu


def batch_axes(mesh) -> tuple[str, ...]:
    return tuple(a for a in ("pod", "data") if a in mesh.axis_names)


def local_routing(top_e, num_experts):
    """Each (token, slot)'s position within its expert's buffer plus
    per-expert counts: the Pallas kernel on TPU, its pure-jnp oracle
    elsewhere (the kernel's interpreter would dominate a CPU step)."""
    if jax.default_backend() == "tpu":
        return kops.moe_routing(top_e, num_experts, interpret=False)
    return kref.moe_routing_ref(top_e, num_experts)


def _swiglu_experts(t, gate, up, down):
    """t: (E_l, n, d); weights: (E_l, d, f) / (E_l, f, d)."""
    g = jax.nn.silu(jnp.einsum("end,edf->enf", t, gate))
    u = jnp.einsum("end,edf->enf", t, up)
    return jnp.einsum("enf,efd->end", g * u, down)


def _swiglu_experts_ws(t, gate, up, down, fsdp_axes):
    """Weight-STATIONARY expert FFN (§Perf, decode cells): expert
    weights stay sharded on their d_model dim over the FSDP axes; the
    (tiny) token activations move instead — one dynamic d-slice, two
    psums of (E_l, n, f)/(E_l, n, d) activations — eliminating the
    per-step FSDP weight all-gather that dominates decode memory/wire.

    t: (E_l, n, d) full-d tokens; gate/up: (E_l, d_l, f);
    down: (E_l, f, d_l) where d_l = d / prod(fsdp_axes sizes)."""
    d_l = gate.shape[1]
    idx = jnp.int32(0)
    n_shards = 1
    for ax in fsdp_axes:
        size = lax.axis_size(ax)
        idx = idx * size + lax.axis_index(ax)
        n_shards *= size
    t_l = lax.dynamic_slice_in_dim(t, idx * d_l, d_l, axis=2)
    g = jnp.einsum("end,edf->enf", t_l, gate)
    u = jnp.einsum("end,edf->enf", t_l, up)
    g = lax.psum(g, fsdp_axes)
    u = lax.psum(u, fsdp_axes)
    h = jax.nn.silu(g) * u
    out_l = jnp.einsum("enf,efd->end", h, down)  # (E_l, n, d_l)
    # reassemble full d: every shard contributes its slice
    out = jnp.zeros(t.shape, out_l.dtype)
    out = lax.dynamic_update_slice_in_dim(out, out_l, idx * d_l, axis=2)
    return lax.psum(out, fsdp_axes)


def moe_ffn(cfg, p, x, mesh):
    """MoE feed-forward on normed input x: (B, S, d) -> (y, aux_metrics).

    Must be called under jit with shardings of ``mesh``; internally drops
    to shard_map for dispatch.
    """
    e_pad = PD.experts_padded(cfg)
    e_real = cfg.n_experts
    k = cfg.top_k
    tp = mesh.shape["model"]
    e_local = e_pad // tp
    bt = batch_axes(mesh)
    n_data = 1
    for a in bt:
        n_data *= mesh.shape[a]

    B, S, d = x.shape
    bt_w = bt  # weight FSDP axes — independent of token sharding
    if n_data > 1 and B % n_data != 0:
        # batch too small to shard (e.g. long-context decode, B=1):
        # replicate tokens over the data axes instead.
        bt = ()
        n_data = 1
    n0_full = (B // max(n_data, 1)) * S  # tokens per data-shard
    # fsdp_sp strategy: the sequence dim is ALREADY sharded over "model"
    # — each rank dispatches its own seq shard, no slicing or gather.
    seq_sp = (cfg.sharding_strategy == "fsdp_sp"
              and S % tp == 0 and S >= tp)
    # weight-stationary expert FFN for small token counts (decode):
    # moves activations instead of FSDP-gathering expert weights.
    n_fsdp = 1
    for a in bt_w:
        n_fsdp *= mesh.shape[a]
    ws = (bool(bt_w) and d % n_fsdp == 0 and B * S * k <= 4096
          and cfg.moe_weight_stationary)
    if ws:
        # ws needs IDENTICAL tokens on every FSDP rank (the d-sliced
        # partial products psum across them): replicate the (tiny)
        # token set instead of batch-sharding it.  Duplicated routing
        # for <=4096 slots is noise; the weight all-gather it replaces
        # is the whole expert stack per step.
        bt = ()
        n_data = 1
        n0_full = B * S
    # Token-split over the model axis ("sequence-parallel MoE"): each
    # model rank dispatches 1/tp of the tokens, so expert FLOPs are not
    # duplicated across TP.  Tiny decode batches fall back to the
    # replicated-dispatch path (identical y on every model rank).
    token_split = (not seq_sp) and n0_full % tp == 0 and n0_full >= tp

    def local_moe(xl, router, gate, up, down):
        # xl: (B_l, S, d) — one data-shard's tokens, full d (replicated
        # across the model axis at entry unless seq_sp).
        B_l, S_l, _ = xl.shape
        toks_all = xl.reshape(B_l * S_l, d)
        if seq_sp:
            n0 = B_l * S_l
            toks = toks_all
            scan_axes = bt + ("model",)
            n_groups = n_data * tp
        elif token_split:
            n0 = (B_l * S_l) // tp
            m_rank = lax.axis_index("model")
            toks = lax.dynamic_slice_in_dim(toks_all, m_rank * n0, n0, 0)
            scan_axes = bt + ("model",)
            n_groups = n_data * tp
        else:
            n0 = B_l * S_l
            toks = toks_all
            scan_axes = bt
            n_groups = n_data
        with jax.named_scope("router"):
            logits = jnp.einsum("nd,de->ne", toks,
                                router).astype(jnp.float32)
            emask = jnp.arange(e_pad) < e_real
            logits = jnp.where(emask, logits, -jnp.inf)
            probs = jax.nn.softmax(logits, axis=-1)
            top_p, top_e = lax.top_k(probs, k)  # (n0, k)
            top_p = top_p / jnp.sum(top_p, axis=-1, keepdims=True)

        # local positions within each expert (Pallas kernel on TPU)
        with jax.named_scope("routing"):
            positions, counts = local_routing(top_e, e_pad)
            counts = counts.astype(jnp.int32)  # (e_pad,)

        # ---- the paper's collective: global dispatch offsets fused
        # with the capacity allreduce (one scan_total schedule) ----
        with jax.named_scope("dispatch_scan"):
            if len(scan_axes) >= 1 and n_groups > 1:
                offsets, totals = scan_api.scan_with_total(
                    counts, cfg.scan_spec.over(
                        scan_axes if len(scan_axes) > 1
                        else scan_axes[0],
                        kind="exclusive", monoid="add"))
            else:
                offsets = jnp.zeros_like(counts)
                totals = counts

        cap = max(8, int(cfg.capacity_factor * n0 * k / e_pad))
        cap_global = cap * n_groups
        with jax.named_scope("dispatch"):
            flat_e = top_e.reshape(-1)  # (n0*k,)
            flat_pos = positions.reshape(-1)
            global_pos = offsets[flat_e] + flat_pos
            keep = (flat_pos < cap) & (global_pos < cap_global)

            # scatter into (e_pad * cap, d) send buffer (drop
            # out-of-bounds)
            slot = jnp.where(keep, flat_e * cap + flat_pos, e_pad * cap)
            toks_rep = jnp.repeat(toks, k, axis=0)  # (n0*k, d)
            buf = jnp.zeros((e_pad * cap, d), xl.dtype)
            buf = buf.at[slot].set(toks_rep, mode="drop")

        # dispatch: (tp, e_local*cap, d) -> all_to_all over "model"
        with jax.named_scope("all_to_all"):
            buf = buf.reshape(tp, e_local * cap, d)
            recv = lax.all_to_all(buf, "model", split_axis=0,
                                  concat_axis=0, tiled=False)
            # recv: (tp_src, e_local, cap, d) -> (e_local, tp_src*cap, d)
            recv = recv.reshape(tp, e_local, cap, d).transpose(1, 0, 2, 3)
            recv = recv.reshape(e_local, tp * cap, d)

        with jax.named_scope("experts"):
            if ws:
                out = _swiglu_experts_ws(recv, gate, up, down, bt_w)
            else:
                out = _swiglu_experts(recv, gate, up, down)

        # reverse trip
        with jax.named_scope("all_to_all"):
            out = out.reshape(e_local, tp, cap, d).transpose(1, 0, 2, 3)
            out = out.reshape(tp, e_local * cap, d)
            back = lax.all_to_all(out, "model", split_axis=0,
                                  concat_axis=0, tiled=False)
            back = back.reshape(e_pad * cap, d)

        # combine: gather own slots, weight by (renormalized) gate probs
        with jax.named_scope("combine"):
            got = jnp.take(back, jnp.minimum(slot, e_pad * cap - 1),
                           axis=0)
            valid = (keep & (slot < e_pad * cap))[:, None]
            got = jnp.where(valid, got, 0)
            weighted = got.reshape(n0, k, d) * top_p[..., None].astype(
                xl.dtype)
            y = weighted.sum(axis=1)  # (n0, d)
            kept = keep.reshape(n0, k).astype(jnp.float32)
            if token_split:
                y = lax.all_gather(y.reshape(1, n0, d), "model", axis=0,
                                   tiled=True)
                kept = lax.all_gather(kept.reshape(1, n0, k), "model",
                                      axis=0, tiled=True)
        # totals: global per-expert dispatch counts (identical on every
        # rank — replicated dispatch computes the same counts, sharded
        # dispatch all-reduced them in the fused scan)
        return (y.reshape(B_l, S_l, d), kept.reshape(B_l, S_l, k),
                totals)

    bt_spec = bt if bt else None
    seq_spec = "model" if seq_sp else None
    wspec = bt_w if ws else None  # weight-stationary: keep FSDP dim
    y, kept, totals = jax.shard_map(
        local_moe,
        mesh=mesh,
        in_specs=(
            P(bt_spec, seq_spec, None),
            P(None, None),
            P("model", wspec, None),
            P("model", wspec, None),
            P("model", None, wspec),
        ),
        out_specs=(P(bt_spec, seq_spec, None),
                   P(bt_spec, seq_spec, None),
                   P(None)),
        check_vma=False,
    )(x, p["router"], p["moe_gate"], p["moe_up"], p["moe_down"])

    # ---- metrics computed under GSPMD (outside the manual region) ----
    # the fused scan's totals are the exact global (token, slot) counts
    # per expert, so the load-balance fraction term needs no second
    # routing pass: frac_e = totals_e / n_tokens
    logits = jnp.einsum("bsd,de->bse", x, p["router"]).astype(jnp.float32)
    emask = jnp.arange(e_pad) < e_real
    logits = jnp.where(emask, logits, -jnp.inf)
    probs = jax.nn.softmax(logits, axis=-1)
    frac = totals.astype(jnp.float32) / (B * S)
    pmean = probs.reshape(-1, e_pad).mean(axis=0)
    lb = e_real * jnp.sum(frac[:e_real] * pmean[:e_real]) / k
    dropped = 1.0 - jnp.mean(kept)
    aux = jnp.stack([lb, dropped])
    return y, aux


@jax.named_scope("moe")
def moe_block(cfg, p, x, mesh):
    """Pre-norm MoE FFN sub-block with optional shared experts."""
    xn = rmsnorm(x, p["norm2"], cfg.norm_eps)
    y, aux = moe_ffn(cfg, p, xn, mesh)
    if cfg.n_shared_experts:
        with jax.named_scope("shared_expert"):
            y = y + swiglu(xn, p["shared_gate"], p["shared_up"],
                           p["shared_down"])
    return x + y, aux
