"""Attention: GQA/MHA (+qk-norm, local window), MLA, cross-attention.

Full-sequence paths use a chunked online-softmax (flash-style) formulation in
pure JAX -- lax.scan over query chunks with an inner scan over KV chunks --
so 32k prefill never materializes (S, S) score tensors.  Decode paths take a
KV cache and compute single-query attention.

MLA (DeepSeek-V2) implements both the materialized form (train/prefill, MXU
friendly) and the absorbed form (decode: the cache holds only the compressed
c_kv + shared rope key, 576 floats/token).
"""
from __future__ import annotations

import functools
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp

from repro.core.qlinear import QuantLike, QuantizedLinear, qlinear

from .config import ArchConfig
from .layers import DEFAULT_QUANT, apply_mrope, apply_rope, dense_init, rms_norm


# ---------------------------------------------------------------------------
# chunked online-softmax attention core
# ---------------------------------------------------------------------------
import contextvars

# Perf-iteration knob (§Perf): when True, the causal chunked-attention inner
# loop wraps each KV chunk in lax.cond so fully-masked (future) and
# fully-out-of-window chunks are skipped at runtime -- halves causal attention
# FLOPs vs the dense rectangle (flash-style triangular schedule).  Runtime
# win only: XLA's static cost_analysis still counts the taken branch as if
# always executed, so the roofline compute term won't move; see the
# statically-triangular variant in EXPERIMENTS.md §Perf.
SKIP_MASKED_CHUNKS = contextvars.ContextVar("SKIP_MASKED_CHUNKS", default=False)

# "dense": scan over all (q-chunk, kv-chunk) pairs with masking (baseline).
# "triangular": statically enumerate only the causal/banded pairs by diagonal
# offset -- tq(tq+1)/2 pair-GEMMs instead of tq*tk, visible to cost_analysis
# (and O(window*S) for sliding-window archs).  §Perf iteration.
ATTN_SCHEDULE = contextvars.ContextVar("ATTN_SCHEDULE", default="dense")


def _pick_chunk(s: int, target: int = 1024) -> int:
    c = min(s, target)
    while s % c:
        c //= 2
    return max(c, 1)


def chunked_attention(
    q,
    k,
    v,
    *,
    causal: bool = True,
    window: int = 0,
    q_offset: int = 0,
    q_chunk: int = 1024,
    kv_chunk: int = 1024,
):
    """q: (B, Sq, H, hd); k, v: (B, Skv, KVH, hd) with H % KVH == 0.

    Returns (B, Sq, H, hd).  ``q_offset`` is the absolute position of q[0] --
    a python int or a traced scalar; the prefix-cache continuation prefill
    passes the (dynamic) cached length, with KV laid out so every entry's
    logical position IS its buffer index and plain causal masking handles the
    gathered-page padding.  ``window`` > 0 enables sliding-window masking.
    Grouped-head einsums avoid materializing repeated KV heads.
    """
    b, sq, h, hd = q.shape
    _, skv, kvh, _ = k.shape
    g = h // kvh
    qc = _pick_chunk(sq, q_chunk)
    kc = _pick_chunk(skv, kv_chunk)
    tq, tk = sq // qc, skv // kc
    scale = 1.0 / jnp.sqrt(hd).astype(jnp.float32)

    qg = q.reshape(b, tq, qc, kvh, g, hd).transpose(1, 0, 3, 4, 2, 5)  # (tq,b,kvh,g,qc,hd)
    kg = k.reshape(b, tk, kc, kvh, hd).transpose(1, 0, 3, 2, 4)  # (tk,b,kvh,kc,hd)
    vg = v.reshape(b, tk, kc, kvh, hd).transpose(1, 0, 3, 2, 4)

    if (
        ATTN_SCHEDULE.get() == "triangular"
        and causal and isinstance(q_offset, int) and q_offset == 0
        and qc == kc and sq == skv
    ):
        out = _triangular_attention(qg, kg, vg, qc, window, scale)
        return out.transpose(1, 0, 4, 2, 3, 5).reshape(b, sq, h, hd).astype(q.dtype)

    def q_body(_, qi_qblk):
        qi, qblk = qi_qblk  # qblk (b,kvh,g,qc,hd)
        qpos = q_offset + qi * qc + jnp.arange(qc)

        skip = SKIP_MASKED_CHUNKS.get() and (causal or window)

        def kv_compute(carry, ki, kblk, vblk):
            m, l, acc = carry
            kpos = ki * kc + jnp.arange(kc)
            # QK in the storage dtype with f32 accumulation: avoids
            # materializing an f32 copy of K (the §Perf profile showed those
            # converts dominating decode/prefill HBM bytes)
            s = jnp.einsum(
                "bkgqd,bkcd->bkgqc", qblk, kblk, preferred_element_type=jnp.float32
            ) * scale  # (b,kvh,g,qc,kc) f32
            mask = jnp.ones((qc, kc), bool)
            if causal:
                mask &= kpos[None, :] <= qpos[:, None]
            if window:
                mask &= kpos[None, :] > qpos[:, None] - window
            s = jnp.where(mask[None, None, None], s, -1e30)
            m_new = jnp.maximum(m, jnp.max(s, axis=-1))
            p = jnp.exp(s - m_new[..., None])
            alpha = jnp.exp(m - m_new)
            l_new = l * alpha + jnp.sum(p, axis=-1)
            # PV: probabilities cast to V's dtype (flash-kernel convention),
            # f32 accumulation -- V is never converted
            acc_new = acc * alpha[..., None] + jnp.einsum(
                "bkgqc,bkcd->bkgqd", p.astype(vblk.dtype), vblk,
                preferred_element_type=jnp.float32,
            )
            return (m_new, l_new, acc_new)

        def kv_body(carry, ki_kv):
            ki, kblk, vblk = ki_kv
            if not skip:
                return kv_compute(carry, ki, kblk, vblk), None
            # triangular/banded schedule: skip chunks that are fully masked
            needed = jnp.asarray(True)
            if causal:
                needed &= ki * kc <= qpos[-1]  # not entirely in the future
            if window:
                needed &= (ki + 1) * kc - 1 > qpos[0] - window  # not all expired
            return jax.lax.cond(
                needed, lambda c: kv_compute(c, ki, kblk, vblk), lambda c: c, carry
            ), None

        m0 = jnp.full((b, kvh, g, qc), -1e30, jnp.float32)
        l0 = jnp.zeros((b, kvh, g, qc), jnp.float32)
        a0 = jnp.zeros((b, kvh, g, qc, hd), jnp.float32)
        (m, l, acc), _ = jax.lax.scan(kv_body, (m0, l0, a0), (jnp.arange(tk), kg, vg))
        out = acc / jnp.maximum(l, 1e-30)[..., None]
        return None, out

    _, out = jax.lax.scan(q_body, None, (jnp.arange(tq), qg))
    # (tq,b,kvh,g,qc,hd) -> (b, sq, h, hd)
    out = out.transpose(1, 0, 4, 2, 3, 5).reshape(b, sq, h, hd)
    return out.astype(q.dtype)


def _triangular_attention(qg, kg, vg, c: int, window: int, scale):
    """Banded causal attention by diagonal offset (no fully-masked pair ever
    computed).  qg: (t,b,kvh,g,c,hd); kg/vg: (t,b,kvh,c,hd); qc == kc == c.

    Offset d pairs q chunk qi with kv chunk qi-d; only d = 0 needs a mask
    (intra-chunk causal), window additionally bounds d and masks the last
    partial diagonal.  Online-softmax combine is associative, so diagonals
    can be accumulated in any order."""
    t, b, kvh, g, _, hd = qg.shape
    m = jnp.full((t, b, kvh, g, c), -1e30, jnp.float32)
    l = jnp.zeros((t, b, kvh, g, c), jnp.float32)
    acc = jnp.zeros((t, b, kvh, g, c, hd), jnp.float32)
    iq = jnp.arange(c)[:, None]
    ik = jnp.arange(c)[None, :]
    max_d = t if not window else min(t, (window - 1) // c + 2)
    for d in range(max_d):
        n = t - d
        qs, ks, vs = qg[d:], kg[:n], vg[:n]
        s = jnp.einsum("tbkgqd,tbkcd->tbkgqc", qs, ks,
                       preferred_element_type=jnp.float32) * scale
        mask = None
        if d == 0:
            mask = ik <= iq  # intra-chunk causal
        if window:
            wmask = (d * c + iq - ik) < window
            mask = wmask if mask is None else (mask & wmask)
        if mask is not None:
            s = jnp.where(mask[None, None, None, None], s, -1e30)
        md, ld, accd = m[d:], l[d:], acc[d:]
        m_new = jnp.maximum(md, jnp.max(s, axis=-1))
        p = jnp.exp(s - m_new[..., None])
        alpha = jnp.exp(md - m_new)
        ld = ld * alpha + jnp.sum(p, axis=-1)
        accd = accd * alpha[..., None] + jnp.einsum(
            "tbkgqc,tbkcd->tbkgqd", p.astype(vs.dtype), vs,
            preferred_element_type=jnp.float32)
        m = jnp.concatenate([m[:d], m_new]) if d else m_new
        l = jnp.concatenate([l[:d], ld]) if d else ld
        acc = jnp.concatenate([acc[:d], accd]) if d else accd
    return acc / jnp.maximum(l, 1e-30)[..., None]


def decode_attention(q, k_cache, v_cache, cur_len, *, window: int = 0):
    """Single-token decode: q (B, 1, H, hd) against a (B, Smax, KVH, hd) cache.

    ``cur_len`` (scalar int) = number of valid cache positions (incl. the token
    just written).  Positions >= cur_len and outside the window are masked.
    """
    b, _, h, hd = q.shape
    _, smax, kvh, _ = k_cache.shape
    g = h // kvh
    scale = 1.0 / jnp.sqrt(hd).astype(jnp.float32)
    qg = q.reshape(b, kvh, g, hd)
    # cache stays in its storage dtype; f32 lives only in the (small) scores
    s = jnp.einsum("bkgd,bskd->bkgs", qg, k_cache.astype(qg.dtype),
                   preferred_element_type=jnp.float32) * scale
    pos = jnp.arange(smax)
    cur = jnp.asarray(cur_len).reshape(-1, 1)  # scalar -> (1,1); vector -> (B,1)
    mask = pos[None, :] < cur
    if window:
        mask &= pos[None, :] > cur - 1 - window
    s = jnp.where(mask[:, None, None, :], s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bkgs,bskd->bkgd", p.astype(v_cache.dtype), v_cache,
                     preferred_element_type=jnp.float32)
    return out.reshape(b, 1, h, hd).astype(q.dtype)


# ---------------------------------------------------------------------------
# GQA attention block
# ---------------------------------------------------------------------------
def gqa_init(key, cfg: ArchConfig, dtype=jnp.float32):
    hd = cfg.hd
    ks = jax.random.split(key, 4)
    p = {
        "wq": dense_init(ks[0], cfg.d_model, cfg.num_heads * hd, dtype=dtype),
        "wk": dense_init(ks[1], cfg.d_model, cfg.num_kv_heads * hd, dtype=dtype),
        "wv": dense_init(ks[2], cfg.d_model, cfg.num_kv_heads * hd, dtype=dtype),
        "wo": dense_init(ks[3], cfg.num_heads * hd, cfg.d_model, dtype=dtype),
    }
    if cfg.qkv_bias:
        p["bq"] = jnp.zeros((cfg.num_heads * hd,), dtype)
        p["bk"] = jnp.zeros((cfg.num_kv_heads * hd,), dtype)
        p["bv"] = jnp.zeros((cfg.num_kv_heads * hd,), dtype)
    if cfg.qk_norm:
        p["q_norm"] = jnp.zeros((hd,), dtype)
        p["k_norm"] = jnp.zeros((hd,), dtype)
    return p


def _qkv(x, p, cfg: ArchConfig, quant: QuantLike, positions, positions3=None):
    b, s, _ = x.shape
    hd = cfg.hd
    q = qlinear(x, QuantizedLinear(p["wq"], p.get("bq")), quant).reshape(b, s, cfg.num_heads, hd)
    k = qlinear(x, QuantizedLinear(p["wk"], p.get("bk")), quant).reshape(b, s, cfg.num_kv_heads, hd)
    v = qlinear(x, QuantizedLinear(p["wv"], p.get("bv")), quant).reshape(b, s, cfg.num_kv_heads, hd)
    if cfg.qk_norm:
        q = rms_norm(q, p["q_norm"], cfg.norm_eps)
        k = rms_norm(k, p["k_norm"], cfg.norm_eps)
    if not cfg.use_rope:
        return q, k, v
    if cfg.mrope:
        pos3 = positions3 if positions3 is not None else jnp.broadcast_to(positions, (3, b, s))
        q = apply_mrope(q, pos3, cfg.mrope_sections, cfg.rope_theta)
        k = apply_mrope(k, pos3, cfg.mrope_sections, cfg.rope_theta)
    else:
        q = apply_rope(q, positions, cfg.rope_theta)
        k = apply_rope(k, positions, cfg.rope_theta)
    return q, k, v


def gqa_forward(x, p, cfg: ArchConfig, *, quant: QuantLike = DEFAULT_QUANT,
                positions=None, positions3=None, window: int = 0, causal: bool = True):
    """Full-sequence attention (causal by default; whisper encoder sets False)."""
    b, s, _ = x.shape
    if positions is None:
        positions = jnp.broadcast_to(jnp.arange(s), (b, s))
    q, k, v = _qkv(x, p, cfg, quant, positions, positions3)
    out = chunked_attention(q, k, v, causal=causal, window=window)
    return qlinear(out.reshape(b, s, -1), p["wo"], quant)


def paged_attention(kernel, q, cache, pages, cur_len):
    """``kernel(q, cache, pages, cur_len)`` over the paged pool; under an
    active mesh, inside ``shard_map``: XLA cannot partition a Pallas kernel.
    Each device takes the kv heads of its model-axis slice (their q heads
    are contiguous) and the batch rows of its data-axis slice, where those
    divide; the rest of each operand is replicated."""
    from repro.parallel.sharding import get_ctx

    ctx = get_ctx()
    if ctx is None or ctx.mesh is None:
        return kernel(q, cache, pages, cur_len)
    from jax.sharding import PartitionSpec as P

    kvh = cache["k_codes"].shape[2]
    heads = ctx.model_axis if ctx.model_axis and kvh % ctx.axis_size(ctx.model_axis) == 0 else None
    rows = ctx.data_axis if ctx.data_axis and q.shape[0] % ctx.axis_size(ctx.data_axis) == 0 else None
    q_spec = P(rows, None, heads, None)
    pool_spec = {name: P(None, None, heads, None) for name in cache}
    return jax.shard_map(
        kernel, mesh=ctx.mesh, in_specs=(q_spec, pool_spec, P(rows, None), P(rows)),
        out_specs=q_spec, check_vma=False,
    )(q, cache, pages, cur_len)


def gqa_decode(x, p, cfg: ArchConfig, cache, cur_len, *, quant: QuantLike = DEFAULT_QUANT,
               window: int = 0, positions3=None, pages=None):
    """One-token decode. cache = dict(k, v) [bf16], the RaZeR-packed layout
    from serving.kvcache (paper App. C.1), or -- when ``pages`` is given -- a
    paged pool slice from serving.pagepool.  cur_len: scalar or (B,) vector
    (continuous batching); ``pages`` is the (B, NP) page table mapping logical
    pages to physical pool pages.  Returns (y, cache)."""
    b = x.shape[0]
    positions = jnp.broadcast_to(jnp.asarray(cur_len, jnp.int32).reshape(-1, 1), (b, 1))
    q, k, v = _qkv(x, p, cfg, quant, positions,
                   None if positions3 is None else positions3)
    if pages is not None:
        from repro.kernels import ops as kops
        from repro.serving.kvcache import kv_quantize

        if window != 0:
            raise ValueError("paged KV decode does not support sliding windows")
        # quantize the new token and scatter it into its page slot; idle
        # slots (cur_len 0, all-null page row) land on the null page
        kc, km = kv_quantize(k[:, 0])
        vc, vm = kv_quantize(v[:, 0])
        ps = cache["k_codes"].shape[1]
        cl = jnp.broadcast_to(jnp.asarray(cur_len, jnp.int32).reshape(-1), (b,))
        pid = pages[jnp.arange(b), cl // ps]
        slot = cl % ps
        cache = {
            "k_codes": cache["k_codes"].at[pid, slot].set(kc),
            "k_meta": cache["k_meta"].at[pid, slot].set(km),
            "v_codes": cache["v_codes"].at[pid, slot].set(vc),
            "v_meta": cache["v_meta"].at[pid, slot].set(vm),
        }
        out = paged_attention(kops.razer_paged_kv_attention, q, cache, pages, cl + 1)
        y = qlinear(out.reshape(b, 1, -1), p["wo"], quant)
        return y, cache
    if "k_codes" in cache:
        from repro.kernels import ops as kops
        from repro.serving.kvcache import quantized_kv_append, quantized_kv_write

        if window == 0:
            # fused path: dequant happens inside the attention kernel (TPU)
            # or its oracle (CPU); the full cache is never materialized bf16
            cache = quantized_kv_write(cache, k, v, cur_len)
            out = kops.razer_kv_attention(q, cache, jnp.asarray(cur_len) + 1)
            y = qlinear(out.reshape(b, 1, -1), p["wo"], quant)
            return y, cache
        k_cache, v_cache, cache = quantized_kv_append(cache, k, v, cur_len)
    elif jnp.ndim(cur_len) == 0:
        k_cache = jax.lax.dynamic_update_slice_in_dim(cache["k"], k.astype(cache["k"].dtype), cur_len, axis=1)
        v_cache = jax.lax.dynamic_update_slice_in_dim(cache["v"], v.astype(cache["v"].dtype), cur_len, axis=1)
        cache = {"k": k_cache, "v": v_cache}
    else:
        k_cache = cache["k"].at[jnp.arange(b), cur_len].set(k[:, 0].astype(cache["k"].dtype))
        v_cache = cache["v"].at[jnp.arange(b), cur_len].set(v[:, 0].astype(cache["v"].dtype))
        cache = {"k": k_cache, "v": v_cache}
    out = decode_attention(q, k_cache, v_cache, cur_len + 1, window=window)
    y = qlinear(out.reshape(b, 1, -1), p["wo"], quant)
    return y, cache


def gqa_decode_verify(x, p, cfg: ArchConfig, cache, cur_len, *,
                      quant: QuantLike = DEFAULT_QUANT, pages=None):
    """Multi-token VERIFY decode over the paged pool (speculative decoding):
    ``x`` (B, T, d) carries T = speculate_k + 1 tokens per slot -- the last
    committed token plus the k drafts -- at logical positions
    ``cur_len[b] + t``.  All T tokens' K/V quantize and scatter into their
    page slots FIRST (overwriting whatever the draft pass wrote there), then
    ONE multi-query paged-attention call masks each query t to positions
    ``< cur_len + t + 1`` -- per query, exactly the write-then-attend order
    and reduction a vanilla one-token decode step performs, which is what
    keeps greedy verify outputs bit-identical to vanilla decode.  Idle slots
    (cur_len 0, all-null page row) scatter to the null page as usual.
    Returns (y (B, T, d), cache)."""
    from repro.kernels import ops as kops
    from repro.serving.kvcache import kv_quantize

    if pages is None:
        raise ValueError("gqa_decode_verify is a paged-pool path: pages is required")
    b, t, _ = x.shape
    cl = jnp.broadcast_to(jnp.asarray(cur_len, jnp.int32).reshape(-1), (b,))
    positions = cl[:, None] + jnp.arange(t, dtype=jnp.int32)[None, :]  # (B, T)
    q, k, v = _qkv(x, p, cfg, quant, positions)
    kc, km = kv_quantize(k)  # (B, T, kvh, hd//2|hd//16)
    vc, vm = kv_quantize(v)
    ps = cache["k_codes"].shape[1]
    # position cur_len + t lives in page (cur_len + t) // ps, slot % ps; the
    # logical index clips to the table width like write_prefill -- real slots
    # stay in range by the scheduler's len+max_new+k reservation, idle slots'
    # all-null rows land on the null page regardless
    pid = pages[jnp.arange(b)[:, None],
                jnp.minimum(positions // ps, pages.shape[1] - 1)]  # (B, T)
    slot = positions % ps
    cache = {
        "k_codes": cache["k_codes"].at[pid, slot].set(kc),
        "k_meta": cache["k_meta"].at[pid, slot].set(km),
        "v_codes": cache["v_codes"].at[pid, slot].set(vc),
        "v_meta": cache["v_meta"].at[pid, slot].set(vm),
    }
    out = paged_attention(kops.razer_paged_kv_attention_verify, q, cache, pages, cl)
    y = qlinear(out.reshape(b, t, -1), p["wo"], quant)
    return y, cache


def gqa_cache_init(cfg: ArchConfig, batch: int, max_len: int, dtype=jnp.bfloat16):
    shape = (batch, max_len, cfg.num_kv_heads, cfg.hd)
    return {"k": jnp.zeros(shape, dtype), "v": jnp.zeros(shape, dtype)}


# ---------------------------------------------------------------------------
# MLA (DeepSeek-V2)
# ---------------------------------------------------------------------------
def mla_init(key, cfg: ArchConfig, dtype=jnp.float32):
    ks = jax.random.split(key, 6)
    h, dn, dr, dv = cfg.num_heads, cfg.qk_nope_dim, cfg.qk_rope_dim, cfg.v_head_dim
    p = {
        "kv_a": dense_init(ks[0], cfg.d_model, cfg.kv_lora_rank + dr, dtype=dtype),
        "kv_norm": jnp.zeros((cfg.kv_lora_rank,), dtype),
        "kv_b": dense_init(ks[1], cfg.kv_lora_rank, h * (dn + dv), dtype=dtype),
        "wo": dense_init(ks[2], h * dv, cfg.d_model, dtype=dtype),
    }
    if cfg.q_lora_rank:
        p["q_a"] = dense_init(ks[3], cfg.d_model, cfg.q_lora_rank, dtype=dtype)
        p["q_norm"] = jnp.zeros((cfg.q_lora_rank,), dtype)
        p["q_b"] = dense_init(ks[4], cfg.q_lora_rank, h * (dn + dr), dtype=dtype)
    else:
        p["wq"] = dense_init(ks[5], cfg.d_model, h * (dn + dr), dtype=dtype)
    return p


def _mla_q(x, p, cfg: ArchConfig, quant, positions):
    b, s, _ = x.shape
    h, dn, dr = cfg.num_heads, cfg.qk_nope_dim, cfg.qk_rope_dim
    if cfg.q_lora_rank:
        qa = rms_norm(qlinear(x, p["q_a"], quant), p["q_norm"], cfg.norm_eps)
        q = qlinear(qa, p["q_b"], quant)
    else:
        q = qlinear(x, p["wq"], quant)
    q = q.reshape(b, s, h, dn + dr)
    q_nope, q_rope = q[..., :dn], q[..., dn:]
    q_rope = apply_rope(q_rope, positions, cfg.rope_theta)
    return q_nope, q_rope


def _mla_ckv(x, p, cfg: ArchConfig, quant, positions):
    b, s, _ = x.shape
    dr = cfg.qk_rope_dim
    ckv = qlinear(x, p["kv_a"], quant)
    c, k_rope = ckv[..., : cfg.kv_lora_rank], ckv[..., cfg.kv_lora_rank :]
    c = rms_norm(c, p["kv_norm"], cfg.norm_eps)
    k_rope = apply_rope(k_rope.reshape(b, s, 1, dr), positions, cfg.rope_theta).reshape(b, s, dr)
    return c, k_rope


def mla_forward(x, p, cfg: ArchConfig, *, quant: QuantLike = DEFAULT_QUANT, positions=None):
    """Materialized MLA for train/prefill."""
    b, s, _ = x.shape
    h, dn, dr, dv = cfg.num_heads, cfg.qk_nope_dim, cfg.qk_rope_dim, cfg.v_head_dim
    if positions is None:
        positions = jnp.broadcast_to(jnp.arange(s), (b, s))
    q_nope, q_rope = _mla_q(x, p, cfg, quant, positions)
    c, k_rope = _mla_ckv(x, p, cfg, quant, positions)
    kv = qlinear(c, p["kv_b"], quant).reshape(b, s, h, dn + dv)
    k_nope, v = kv[..., :dn], kv[..., dn:]
    k = jnp.concatenate([k_nope, jnp.broadcast_to(k_rope[:, :, None, :], (b, s, h, dr))], axis=-1)
    q = jnp.concatenate([q_nope, q_rope], axis=-1)
    # pad v's head dim to match attention's contraction over hd=dn+dr? no --
    # chunked_attention is agnostic: v has its own head dim (dv).
    out = chunked_attention(q, k, _pad_v(v, dn + dr), causal=True)[..., :dv]
    return qlinear(out.reshape(b, s, h * dv), p["wo"], quant)


def _pad_v(v, hd):
    dv = v.shape[-1]
    if dv == hd:
        return v
    return jnp.pad(v, ((0, 0), (0, 0), (0, 0), (0, hd - dv)))


def mla_decode(x, p, cfg: ArchConfig, cache, cur_len, *, quant: QuantLike = DEFAULT_QUANT):
    """Absorbed MLA decode: cache holds (c_kv, k_rope) only."""
    b = x.shape[0]
    h, dn, dr, dv = cfg.num_heads, cfg.qk_nope_dim, cfg.qk_rope_dim, cfg.v_head_dim
    positions = jnp.broadcast_to(jnp.asarray(cur_len, jnp.int32).reshape(-1, 1), (b, 1))
    q_nope, q_rope = _mla_q(x, p, cfg, quant, positions)  # (b,1,h,dn),(b,1,h,dr)
    c_new, kr_new = _mla_ckv(x, p, cfg, quant, positions)  # (b,1,rank),(b,1,dr)
    if jnp.ndim(cur_len) == 0:
        c_cache = jax.lax.dynamic_update_slice_in_dim(cache["c"], c_new.astype(cache["c"].dtype), cur_len, axis=1)
        r_cache = jax.lax.dynamic_update_slice_in_dim(cache["kr"], kr_new.astype(cache["kr"].dtype), cur_len, axis=1)
    else:
        c_cache = cache["c"].at[jnp.arange(b), cur_len].set(c_new[:, 0].astype(cache["c"].dtype))
        r_cache = cache["kr"].at[jnp.arange(b), cur_len].set(kr_new[:, 0].astype(cache["kr"].dtype))

    w_kv_b = p["kv_b"].reshape(cfg.kv_lora_rank, h, dn + dv)
    w_uk, w_uv = w_kv_b[..., :dn], w_kv_b[..., dn:]
    # absorb: q_eff (b,h,rank); caches never leave their storage dtype
    cd = c_cache.dtype
    q_eff = jnp.einsum("bqhn,rhn->bhr", q_nope.astype(cd), w_uk.astype(cd),
                       preferred_element_type=jnp.float32)
    scale = 1.0 / jnp.sqrt(dn + dr).astype(jnp.float32)
    s = (
        jnp.einsum("bhr,bsr->bhs", q_eff.astype(cd), c_cache, preferred_element_type=jnp.float32)
        + jnp.einsum("bqhr,bsr->bhs", q_rope.astype(cd), r_cache, preferred_element_type=jnp.float32)
    ) * scale
    smax = c_cache.shape[1]
    cur = jnp.asarray(cur_len).reshape(-1, 1)
    mask = jnp.arange(smax)[None, :] < (cur + 1)
    s = jnp.where(mask[:, None, :], s, -1e30)
    pattn = jax.nn.softmax(s, axis=-1)
    ctx = jnp.einsum("bhs,bsr->bhr", pattn.astype(cd), c_cache, preferred_element_type=jnp.float32)
    out = jnp.einsum("bhr,rhv->bhv", ctx.astype(cd), w_uv.astype(cd),
                     preferred_element_type=jnp.float32).astype(x.dtype)
    y = qlinear(out.reshape(b, 1, h * dv), p["wo"], quant)
    return y, {"c": c_cache, "kr": r_cache}


def mla_cache_init(cfg: ArchConfig, batch: int, max_len: int, dtype=jnp.bfloat16):
    return {
        "c": jnp.zeros((batch, max_len, cfg.kv_lora_rank), dtype),
        "kr": jnp.zeros((batch, max_len, cfg.qk_rope_dim), dtype),
    }


# ---------------------------------------------------------------------------
# cross-attention (whisper decoder)
# ---------------------------------------------------------------------------
def cross_init(key, cfg: ArchConfig, dtype=jnp.float32):
    hd = cfg.hd
    ks = jax.random.split(key, 4)
    return {
        "wq": dense_init(ks[0], cfg.d_model, cfg.num_heads * hd, dtype=dtype),
        "wk": dense_init(ks[1], cfg.d_model, cfg.num_kv_heads * hd, dtype=dtype),
        "wv": dense_init(ks[2], cfg.d_model, cfg.num_kv_heads * hd, dtype=dtype),
        "wo": dense_init(ks[3], cfg.num_heads * hd, cfg.d_model, dtype=dtype),
    }


def cross_forward(x, enc, p, cfg: ArchConfig, *, quant: QuantLike = DEFAULT_QUANT):
    """x: (B, Sd, d) queries; enc: (B, Se, d) encoder output (non-causal)."""
    b, sd, _ = x.shape
    se = enc.shape[1]
    hd = cfg.hd
    q = qlinear(x, p["wq"], quant).reshape(b, sd, cfg.num_heads, hd)
    k = qlinear(enc, p["wk"], quant).reshape(b, se, cfg.num_kv_heads, hd)
    v = qlinear(enc, p["wv"], quant).reshape(b, se, cfg.num_kv_heads, hd)
    out = chunked_attention(q, k, v, causal=False)
    return qlinear(out.reshape(b, sd, -1), p["wo"], quant)
