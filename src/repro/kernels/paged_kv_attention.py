"""Pallas TPU kernel: single-query (decode) attention over a PAGED
RaZeR-packed KV pool -- the continuous-batching analogue of
``razer_kv_attention.py``.

The pool stores KV in fixed-size pages of ``page_size`` tokens
(``serving/pagepool.py``); a per-sequence page table maps logical page index
``pi`` to the physical page holding positions ``[pi*ps, (pi+1)*ps)``:

    out[b, h, :] = softmax(q[b, h, :] . K_hat[pages(b), :, kvh(h), :]) @ V_hat

The page table rides the scalar-prefetch channel, so the INDEX MAPS gather:
grid step (b, kvh, pi) DMAs physical page ``page_table[b, pi]`` from HBM into
VMEM, where the tile dequant (same arithmetic decode as the contiguous
kernel -- the page layout is byte-identical wire format) overlaps the MXU
scores matmul.  Masking with ``cur_len`` runs on LOGICAL positions, so null
(padding) pages contribute exp(-inf) = 0 and physical page order is free.

Grid: (B, KVH, pages_per_seq) -- the page dim is innermost/sequential so the
online-softmax (m, l, acc) scratch stays resident, exactly like the S-chunk
loop of the contiguous kernel.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .razer_kv_attention import _dequant_tile, _finish, _scores, merge_even_odd, split_even_odd

__all__ = ["paged_kv_attention_pallas", "paged_kv_attention_verify_pallas"]


def _kernel(pt_ref, cur_len_ref, q_ref, kc_ref, km_ref, vc_ref, vm_ref, o_ref,
            m_ref, l_ref, acc_ref, *, ps, hd, npages):
    pi = pl.program_id(2)

    @pl.when(pi == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, -1e30)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    cur_len = cur_len_ref[pl.program_id(0)]  # per-slot valid length
    k_even, k_odd = _dequant_tile(kc_ref[...], km_ref[...], hd)  # (ps, hd//2) f32
    v_even, v_odd = _dequant_tile(vc_ref[...], vm_ref[...], hd)

    scale = 1.0 / math.sqrt(hd)
    s = _scores(q_ref, k_even, k_odd) * scale  # (G, ps)
    # mask on LOGICAL positions: page pi holds [pi*ps, (pi+1)*ps)
    pos = pi * ps + jax.lax.broadcasted_iota(jnp.int32, (1, ps), 1)
    s = jnp.where(pos < cur_len, s, -1e30)

    m_prev = m_ref[...]  # (G, 1)
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
    p = jnp.exp(s - m_new)
    alpha = jnp.exp(m_prev - m_new)
    l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=-1, keepdims=True)
    acc_ref[0] = acc_ref[0] * alpha + jnp.dot(p, v_even, preferred_element_type=jnp.float32)
    acc_ref[1] = acc_ref[1] * alpha + jnp.dot(p, v_odd, preferred_element_type=jnp.float32)
    m_ref[...] = m_new

    @pl.when(pi == npages - 1)
    def _done():
        _finish(o_ref, acc_ref, l_ref)


@functools.partial(jax.jit, static_argnames=("interpret",))
def paged_kv_attention_pallas(q, k_codes, k_meta, v_codes, v_meta, page_table,
                              cur_len, *, interpret: bool = False):
    """q: (B, H, hd); pool: (P, ps, KVH, hd//2|hd//16) u8;
    page_table: (B, NP) i32 physical page per logical page (0 = null page);
    cur_len: (B,) i32 valid positions per sequence.

    Returns (B, H, hd) f32.  H % KVH == 0."""
    b, h, hd = q.shape
    p_pages, ps, kvh, half = k_codes.shape
    npages = page_table.shape[1]
    assert half * 2 == hd and h % kvh == 0 and page_table.shape[0] == b
    g = h // kvh
    grid = (b, kvh, npages)

    qg = split_even_odd(q.reshape(b, kvh, g, hd)).swapaxes(2, 3)  # (B, KVH, 2, G, hd//2)
    # (P, ps, KVH, x) -> (P, KVH, ps, x): one physical page per grid step is a
    # contiguous (ps, x) block for its kv head
    kc = k_codes.transpose(0, 2, 1, 3)
    km = k_meta.transpose(0, 2, 1, 3)
    vc = v_codes.transpose(0, 2, 1, 3)
    vm = v_meta.transpose(0, 2, 1, 3)

    kernel = functools.partial(_kernel, ps=ps, hd=hd, npages=npages)
    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,  # page_table, cur_len
            grid=grid,
            in_specs=[
                pl.BlockSpec((None, None, 2, g, hd // 2),
                             lambda bi, ki, pi, pt, cl: (bi, ki, 0, 0, 0)),
                # the gather: logical page pi of sequence bi lives at physical
                # page pt[bi, pi] -- the index map IS the page-table lookup
                pl.BlockSpec((None, None, ps, hd // 2),
                             lambda bi, ki, pi, pt, cl: (pt[bi, pi], ki, 0, 0)),
                pl.BlockSpec((None, None, ps, hd // 16),
                             lambda bi, ki, pi, pt, cl: (pt[bi, pi], ki, 0, 0)),
                pl.BlockSpec((None, None, ps, hd // 2),
                             lambda bi, ki, pi, pt, cl: (pt[bi, pi], ki, 0, 0)),
                pl.BlockSpec((None, None, ps, hd // 16),
                             lambda bi, ki, pi, pt, cl: (pt[bi, pi], ki, 0, 0)),
            ],
            out_specs=pl.BlockSpec((None, None, 2, g, hd // 2),
                                   lambda bi, ki, pi, pt, cl: (bi, ki, 0, 0, 0)),
            scratch_shapes=[
                pltpu.VMEM((g, 1), jnp.float32),
                pltpu.VMEM((g, 1), jnp.float32),
                pltpu.VMEM((2, g, hd // 2), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((b, kvh, 2, g, hd // 2), jnp.float32),
        interpret=interpret,
        name="razer_paged_attention",
    )(
        jnp.asarray(page_table, jnp.int32),
        jnp.broadcast_to(jnp.asarray(cur_len, jnp.int32).reshape(-1), (b,)),
        qg, kc, km, vc, vm,
    )
    return merge_even_odd(out.swapaxes(2, 3)).reshape(b, h, hd)


def _verify_kernel(pt_ref, cur_len_ref, q_ref, kc_ref, km_ref, vc_ref, vm_ref,
                   o_ref, m_ref, l_ref, acc_ref, *, ps, hd, npages, t, g):
    """q-length>1 variant for speculative verify: the T queries of slot b sit
    at logical positions ``cur_len[b] + t``, so the mask is per QUERY ROW --
    query t sees positions < cur_len + t + 1 (its own just-written KV
    included).  Identical page loop / online softmax otherwise."""
    pi = pl.program_id(2)

    @pl.when(pi == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, -1e30)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    cur_len = cur_len_ref[pl.program_id(0)]
    k_even, k_odd = _dequant_tile(kc_ref[...], km_ref[...], hd)  # (ps, hd//2) f32
    v_even, v_odd = _dequant_tile(vc_ref[...], vm_ref[...], hd)

    scale = 1.0 / math.sqrt(hd)
    s = _scores(q_ref, k_even, k_odd) * scale  # (T*G, ps)
    pos = pi * ps + jax.lax.broadcasted_iota(jnp.int32, (1, ps), 1)
    # row r of the flattened (T*G) query block belongs to query index r // g
    qt = jax.lax.broadcasted_iota(jnp.int32, (t * g, 1), 0) // g
    s = jnp.where(pos < cur_len + qt + 1, s, -1e30)

    m_prev = m_ref[...]  # (T*G, 1)
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
    p = jnp.exp(s - m_new)
    alpha = jnp.exp(m_prev - m_new)
    l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=-1, keepdims=True)
    acc_ref[0] = acc_ref[0] * alpha + jnp.dot(p, v_even, preferred_element_type=jnp.float32)
    acc_ref[1] = acc_ref[1] * alpha + jnp.dot(p, v_odd, preferred_element_type=jnp.float32)
    m_ref[...] = m_new

    @pl.when(pi == npages - 1)
    def _done():
        _finish(o_ref, acc_ref, l_ref)


@functools.partial(jax.jit, static_argnames=("interpret",))
def paged_kv_attention_verify_pallas(q, k_codes, k_meta, v_codes, v_meta,
                                     page_table, cur_len, *,
                                     interpret: bool = False):
    """q: (B, T, H, hd) -- T = k+1 verify queries per slot at positions
    ``cur_len[b] + t``; pool / page_table as the single-query kernel;
    ``cur_len`` (B,) i32 is the COMMITTED length before the T positions.

    Returns (B, T, H, hd) f32."""
    b, t, h, hd = q.shape
    p_pages, ps, kvh, half = k_codes.shape
    npages = page_table.shape[1]
    assert half * 2 == hd and h % kvh == 0 and page_table.shape[0] == b
    g = h // kvh
    grid = (b, kvh, npages)

    # (B, T, H, hd) -> (B, KVH, 2, T*G, hd//2): one block of T*G query rows
    # (row r is query r // g) per (slot, kv head) grid step, head dims split
    # even/odd like the dequantized K/V tiles
    qg = q.reshape(b, t, kvh, g, hd).transpose(0, 2, 1, 3, 4).reshape(b, kvh, t * g, hd)
    qg = split_even_odd(qg).swapaxes(2, 3)
    kc = k_codes.transpose(0, 2, 1, 3)
    km = k_meta.transpose(0, 2, 1, 3)
    vc = v_codes.transpose(0, 2, 1, 3)
    vm = v_meta.transpose(0, 2, 1, 3)

    kernel = functools.partial(_verify_kernel, ps=ps, hd=hd, npages=npages, t=t, g=g)
    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,  # page_table, cur_len
            grid=grid,
            in_specs=[
                pl.BlockSpec((None, None, 2, t * g, hd // 2),
                             lambda bi, ki, pi, pt, cl: (bi, ki, 0, 0, 0)),
                pl.BlockSpec((None, None, ps, hd // 2),
                             lambda bi, ki, pi, pt, cl: (pt[bi, pi], ki, 0, 0)),
                pl.BlockSpec((None, None, ps, hd // 16),
                             lambda bi, ki, pi, pt, cl: (pt[bi, pi], ki, 0, 0)),
                pl.BlockSpec((None, None, ps, hd // 2),
                             lambda bi, ki, pi, pt, cl: (pt[bi, pi], ki, 0, 0)),
                pl.BlockSpec((None, None, ps, hd // 16),
                             lambda bi, ki, pi, pt, cl: (pt[bi, pi], ki, 0, 0)),
            ],
            out_specs=pl.BlockSpec((None, None, 2, t * g, hd // 2),
                                   lambda bi, ki, pi, pt, cl: (bi, ki, 0, 0, 0)),
            scratch_shapes=[
                pltpu.VMEM((t * g, 1), jnp.float32),
                pltpu.VMEM((t * g, 1), jnp.float32),
                pltpu.VMEM((2, t * g, hd // 2), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((b, kvh, 2, t * g, hd // 2), jnp.float32),
        interpret=interpret,
        name="razer_paged_attention_verify",
    )(
        jnp.asarray(page_table, jnp.int32),
        jnp.broadcast_to(jnp.asarray(cur_len, jnp.int32).reshape(-1), (b,)),
        qg, kc, km, vc, vm,
    )
    # (B, KVH, 2, T*G, hd//2) -> (B, T, H, hd)
    out = merge_even_odd(out.swapaxes(2, 3)).reshape(b, kvh, t, g, hd)
    return out.transpose(0, 2, 1, 3, 4).reshape(b, t, h, hd)
