"""Token-choice top-k MoE with grouped, capacity-bounded dispatch.

Formulation (see docs/architecture.md): tokens are split into G dispatch
groups (vmapped); within a group, slot positions come from a cumsum over an
(slots, E) one-hot -- never a (tokens, E, capacity) tensor.  The dispatch
buffer is (G, E, capacity, d): with G sharded on the data axis and expert
weights' E dim sharded on the data axis too, XLA SPMD lowers the dense /
fakequant expert einsum to the canonical expert-parallel all-to-all (GSPMD
MoE pattern).  The packed path runs a Pallas grouped kernel, which XLA SPMD
does not partition -- so on a multi-device mesh ``moe_forward`` draws the
partition boundary itself: ``_expert_parallel_ffn`` wraps the grouped kernel
in ``shard_map`` over the ep (data) axis, each device holding only its E/ep
rows of the packed banks (placed by ``parallel/sharding.param_sharding_tree``
via the registry's ``shard_stacked_fn`` plan) and launching the kernel on a
local-E grid, with the same all-to-all dispatch/combine the dense path gets
from GSPMD (``parallel/collectives.py``; see docs/parallelism.md).  Capacity
overflow drops slots (GShard semantics); an aux load-balance loss is
returned.

Expert weights run through one of three paths (docs/kernels.md):
  * dense bf16 einsum (training / bf16 serving),
  * fakequant: the stacked (E, d, f) banks are quantize-dequantized along
    d at forward time (accuracy experiments),
  * packed: ``pack_model_weights`` replaced the banks with stacked wire-format
    containers (``PackedStackedTensor``) and the expert einsum dispatches --
    by container type, through the format registry -- to the grouped packed
    matmul kernel (``kernels/razer_grouped_matmul.py``), never materializing
    a bf16 copy of the bank.

DeepSeek-V2 style shared experts (always-on dense SwiGLU) are supported.
"""
from __future__ import annotations

import math
from typing import Tuple

import jax
import jax.numpy as jnp

from repro.core import registry
from repro.core.policy import as_policy
from repro.core.qlinear import QuantLike, qlinear
from repro.parallel.sharding import P, get_ctx, shard_activation, stacked_plan

from .config import ArchConfig
from .layers import DEFAULT_QUANT, dense_init, swiglu, swiglu_init


def moe_init(key, cfg: ArchConfig, dtype=jnp.float32):
    ks = jax.random.split(key, 5)
    e, d, f = cfg.n_experts, cfg.d_model, cfg.moe_d_ff
    scale = 1.0 / math.sqrt(d)
    p = {
        "router": dense_init(ks[0], d, e, dtype=dtype),
        "experts": {
            "gate": jax.random.normal(ks[1], (e, d, f), dtype) * scale,
            "up": jax.random.normal(ks[2], (e, d, f), dtype) * scale,
            "down": jax.random.normal(ks[3], (e, f, d), dtype) * (1.0 / math.sqrt(f)),
        },
    }
    if cfg.n_shared_experts:
        p["shared"] = swiglu_init(ks[4], d, cfg.n_shared_experts * f, dtype=dtype)
    return p


def _pick_groups(t: int) -> int:
    """Dispatch group count: matches the data-axis size when a mesh context is
    active (so the group dim shards exactly); else a small divisor of t."""
    ctx = get_ctx()
    want = 16
    if ctx is not None and ctx.data_axis:
        want = ctx.axis_size(ctx.data_axis)
        if ctx.batch_axes:
            want = max(want, ctx.axis_size(ctx.batch_axes))
    g = math.gcd(t, want)
    return max(g, 1)


def _group_dispatch(xg, topi, e: int, cap: int):
    """Per-group dispatch (vmapped over G).

    xg: (tg, d); topi: (tg, k). Returns (buf (e, cap, d), slot_expert,
    slot_pos, slot_keep, slot_token) for the combine step.
    """
    tg, d = xg.shape
    k = topi.shape[-1]
    slot_expert = topi.reshape(-1)  # (tg*k,)
    slot_token = jnp.repeat(jnp.arange(tg), k)
    onehot = jax.nn.one_hot(slot_expert, e, dtype=jnp.int32)  # (tg*k, e)
    pos = jnp.cumsum(onehot, axis=0) * onehot  # position+1 at own expert
    slot_pos = jnp.sum(pos, axis=-1) - 1  # (tg*k,)
    keep = slot_pos < cap
    # dropped slots scatter into a sacrificial row at index `cap`
    safe_pos = jnp.where(keep, slot_pos, cap)
    buf = jnp.zeros((e, cap + 1, d), xg.dtype)
    buf = buf.at[slot_expert, safe_pos].add(xg[slot_token])
    return buf[:, :cap, :], slot_expert, safe_pos, keep, slot_token


def _group_combine(h, slot_expert, slot_pos, keep, slot_token, topw, tg: int):
    """h: (e, cap, d) expert outputs -> (tg, d) weighted combine."""
    d = h.shape[-1]
    k = topw.shape[-1]
    h_pad = jnp.pad(h, ((0, 0), (0, 1), (0, 0)))  # restore sacrificial row
    slots = h_pad[slot_expert, slot_pos]  # (tg*k, d)
    w = topw.reshape(-1) * keep.astype(topw.dtype)
    out = jnp.zeros((tg, d), h.dtype)
    return out.at[slot_token].add(slots * w[:, None].astype(h.dtype))


def _expert_parallel_ffn(buf, we, gentry, ctx, ep: int, tp: int = 1):
    """Packed grouped FFN under shard_map over the ep (data) x tp (model) axes.

    buf: (g, e, cap, d) dispatch buffer.  Each device holds only its
    E/ep x K/tp tile of the packed gate/up/down banks (the registry plan
    ``shard_stacked_fn`` both places the leaves and localizes the container
    metadata inside the body) and launches the grouped kernel on a local
    (E/ep, M/bm, N/bn, (K/tp)/bk) grid.  The wire format is untouched: a
    bank shard is byte-identical to packing that E/ep x K/tp sub-bank
    directly (docs/parallelism.md).

    Under tp > 1 the buffer's d dim enters ALREADY sharded on the model axis
    (the "moe_buf" activation layout) and is never gathered: each grouped
    matmul computes a full-N partial product over its local K slice and the
    partial-sum exchange is fused into the epilogue as one last-dim
    ``psum_scatter`` -- gate/up scatter over f (feeding silu*mul its f/tp
    tile, which is exactly down's K-shard), down scatters back over d, so
    the output leaves d-sharded just like the input.

    Token-movement strategies over ep, both keeping the banks sharded:
      * ``ep > 1 and g % ep == 0`` (prefill / large batches): the group dim
        shards over ep and tokens reach their experts with the same
        all-to-all dispatch/combine the dense einsum gets from GSPMD.
      * ``ep > 1`` otherwise (decode: t, and so g, smaller than ep): the
        buffer is tiny and replicated over ep; each device slices out its
        own experts' slots, computes them, and one activation all-gather
        rebuilds the buffer -- never a gather of the (much larger) bank.
      * ``ep == 1`` (pure tp): every device computes all E experts over its
        K/tp slice; the only collectives are the two fused psum_scatters.

    Single-device meshes never reach this function -- ``moe_forward`` gates
    on ep > 1 or tp > 1 and otherwise runs the unsharded launch.
    """
    from repro.kernels.ops import reduce_scatter_epilogue
    from repro.parallel.collectives import (
        combine_from_expert_shards,
        dispatch_to_expert_shards,
    )

    eax = ctx.data_axis if ep > 1 else None
    tax = ctx.model_axis if tp > 1 else None
    g, e, cap, d = buf.shape
    grouped_mm = gentry.grouped_matmul_kernel
    (gateup_specs, localize), k_ok = stacked_plan(gentry, we["gate"], eax, tax)
    if not k_ok:  # plan predates the K-shard hook: degrade to ep-only
        tax, tp = None, 1
    (down_specs, _), _ = stacked_plan(gentry, we["down"], eax, tax)
    local_e = e // ep
    dl = d // tp  # buf's local d width under the model axis
    all_to_all = ep > 1 and g % ep == 0

    def local_ffn(xe, gate_l, up_l, down_l):
        # under tp each matmul yields a full-N PARTIAL over the local K
        # slice; the reduce-scatter epilogue hands silu*mul its f/tp tile
        # (== down's K-shard) and the d output back in buf layout
        hg = reduce_scatter_epilogue(grouped_mm(xe, gate_l), tax)
        hu = reduce_scatter_epilogue(grouped_mm(xe, up_l), tax)
        h = jax.nn.silu(hg) * hu
        return reduce_scatter_epilogue(grouped_mm(h, down_l), tax)  # (e/ep, g*cap, d/tp)

    def ffn_a2a(buf_l, gate_l, up_l, down_l):
        gate_l, up_l, down_l = (localize(b, ep, tp) for b in (gate_l, up_l, down_l))
        x = dispatch_to_expert_shards(buf_l, eax)  # (g, e/ep, cap, d/tp)
        xe = x.transpose(1, 0, 2, 3).reshape(local_e, g * cap, dl)
        ho = local_ffn(xe, gate_l, up_l, down_l)
        ho = ho.reshape(local_e, g, cap, dl).transpose(1, 0, 2, 3)
        return combine_from_expert_shards(ho, eax)  # (g/ep, e, cap, d/tp)

    def ffn_replicated_tokens(buf_r, gate_l, up_l, down_l):
        gate_l, up_l, down_l = (localize(b, ep, tp) for b in (gate_l, up_l, down_l))
        idx = jax.lax.axis_index(eax)
        # this device's experts' slots out of the (ep-replicated) buffer;
        # slice order matches shard_map's contiguous bank-leaf sharding
        bl = jax.lax.dynamic_slice_in_dim(buf_r, idx * local_e, local_e, axis=1)
        xe = bl.transpose(1, 0, 2, 3).reshape(local_e, g * cap, dl)
        ho = local_ffn(xe, gate_l, up_l, down_l).reshape(local_e, g, cap, dl)
        full = jax.lax.all_gather(ho, eax, axis=0, tiled=True)  # (e, g, cap, d/tp)
        return full.transpose(1, 0, 2, 3)

    def ffn_tp_only(buf_r, gate_l, up_l, down_l):
        gate_l, up_l, down_l = (localize(b, 1, tp) for b in (gate_l, up_l, down_l))
        xe = buf_r.transpose(1, 0, 2, 3).reshape(e, g * cap, dl)
        ho = local_ffn(xe, gate_l, up_l, down_l).reshape(e, g, cap, dl)
        return ho.transpose(1, 0, 2, 3)

    if eax is None:
        body, g_ax = ffn_tp_only, None
    elif all_to_all:
        body, g_ax = ffn_a2a, eax
    else:
        body, g_ax = ffn_replicated_tokens, None
    buf_spec = P(g_ax, None, None, tax)
    return jax.shard_map(
        body,
        mesh=ctx.mesh,
        in_specs=(buf_spec, gateup_specs, gateup_specs, down_specs),
        out_specs=buf_spec,
        check_vma=False,
    )(buf, we["gate"], we["up"], we["down"])


def moe_forward(
    x, p, cfg: ArchConfig, *, quant: QuantLike = DEFAULT_QUANT
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """x: (B, S, d) -> (y, aux_loss). Router kept f32 (paper convention:
    routing logits are precision-critical; see the ``*router*`` dense rule)."""
    b, s, d = x.shape
    t = b * s
    e, k = cfg.n_experts, cfg.topk
    xf = x.reshape(t, d)

    logits = (xf.astype(jnp.float32) @ p["router"].astype(jnp.float32))  # (t, e)
    probs = jax.nn.softmax(logits, axis=-1)
    topw, topi = jax.lax.top_k(probs, k)
    topw = topw / jnp.maximum(jnp.sum(topw, axis=-1, keepdims=True), 1e-9)

    # Switch-style aux load-balance loss
    frac_tokens = jnp.mean(jax.nn.one_hot(topi[:, 0], e, dtype=jnp.float32), axis=0)
    frac_probs = jnp.mean(probs, axis=0)
    aux = e * jnp.sum(frac_tokens * frac_probs)

    g = _pick_groups(t)
    tg = t // g
    cap = max(int(math.ceil(tg * k / e * cfg.capacity_factor)), 1)

    xg = xf.reshape(g, tg, d)
    tig = topi.reshape(g, tg, k)
    twg = topw.reshape(g, tg, k).astype(x.dtype)

    buf, se, sp, keep, st = jax.vmap(_group_dispatch, in_axes=(0, 0, None, None))(
        xg, tig, e, cap
    )
    buf = shard_activation(buf, "moe_buf")  # (g, e, cap, d)

    we = p["experts"]
    gentries = {r: registry.grouped_entry(we[r]) for r in ("gate", "up", "down")}
    n_grouped = sum(v is not None for v in gentries.values())
    if 0 < n_grouped < 3:
        # pack_model_weights packs a bank all-or-none (both reduction dims
        # must be block multiples); a mixed trio means hand-built params
        raise ValueError(
            "MoE expert bank mixes packed and dense weights: "
            + ", ".join(f"{r}={'packed' if v is not None else 'dense'}"
                        for r, v in gentries.items())
        )
    gentry = gentries["gate"]
    if gentry is not None:
        # packed deployment path: the banks are stacked wire-format containers
        # (pack_model_weights under the default ``*experts*`` stacked rule).
        grouped_mm = gentry.grouped_matmul_kernel
        if grouped_mm is None:
            raise TypeError(
                f"format {gentry.name!r} packs stacked banks but registered no "
                f"grouped_matmul_kernel; cannot run the packed expert einsum"
            )
        ctx = get_ctx()
        on_mesh = ctx is not None and ctx.mesh is not None
        ep = ctx.axis_size(ctx.data_axis) if on_mesh and ctx.data_axis else 1
        tp = ctx.axis_size(ctx.model_axis) if on_mesh and ctx.model_axis else 1
        f = we["gate"].shape[2]
        ep_eff = ep if ep > 1 and e % ep == 0 else 1
        # K-shard eligibility for the whole trio: gate/up reduce over d,
        # down over f, and each psum_scatter tiles the other dim -- so both
        # must split into whole 16-element quant blocks per device
        tp_eff = tp if tp > 1 and d % (tp * 16) == 0 and f % (tp * 16) == 0 else 1
        if gentry.shard_stacked_fn is not None and (ep_eff > 1 or tp_eff > 1):
            # expert-parallel and/or tensor-parallel: shard_map the grouped
            # kernel over the ep x tp axes, E/ep x K/tp bank tiles + a
            # local-E grid over local K per device, partial-sum
            # reduce-scatter fused into the epilogue (docs/parallelism.md)
            hout = _expert_parallel_ffn(buf, we, gentry, ctx, ep_eff, tp_eff)
        else:
            # unsharded launch (single device, ep=1 mesh, or E not divisible
            # by ep -- then param placement replicated the bank): flatten
            # (g, e, cap, d) -> per-expert (e, g*cap, d) rows and run the
            # registered grouped packed matmul; no bf16 bank materialized.
            xe = buf.transpose(1, 0, 2, 3).reshape(e, g * cap, d)
            hg = grouped_mm(xe, we["gate"])
            hu = grouped_mm(xe, we["up"])
            h = jax.nn.silu(hg) * hu
            hout = grouped_mm(h, we["down"])  # (e, g*cap, d)
            hout = hout.reshape(e, g, cap, d).transpose(1, 0, 2, 3)
    else:
        wspec = as_policy(quant).weight
        if wspec.quantizes and wspec.mode == "fakequant":
            # fakequant quantizes the stacked (E, d, f) banks along d at
            # forward time, per expert (vmapped): each expert gets its own
            # tensor scale, exactly matching what pack_stacked_weights encodes
            # on the wire -- so fakequant and packed MoE forwards agree.
            we = {k_: jax.vmap(lambda w_: wspec.qdq(w_, axis=0))(v) for k_, v in we.items()}
        hg = jnp.einsum("gecd,edf->gecf", buf, we["gate"].astype(buf.dtype))
        hu = jnp.einsum("gecd,edf->gecf", buf, we["up"].astype(buf.dtype))
        h = jax.nn.silu(hg) * hu
        hout = jnp.einsum("gecf,efd->gecd", h, we["down"].astype(buf.dtype))
    hout = shard_activation(hout, "moe_buf")

    yg = jax.vmap(_group_combine, in_axes=(0, 0, 0, 0, 0, 0, None))(hout, se, sp, keep, st, twg, tg)
    y = yg.reshape(b, s, d)

    if "shared" in p:
        y = y + swiglu(x, p["shared"], quant)
    return y, aux
