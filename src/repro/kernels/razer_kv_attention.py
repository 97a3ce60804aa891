"""Pallas TPU kernel: single-query (decode) attention over a RaZeR-packed KV
cache -- the fused hot loop of the App. C.1 + §4.3 serving path that §Perf
cells A/C showed to be the dominant-term win (2.1-2.7x).

    out[b, h, :] = softmax(q[b, h, :] . K_hat[b, :len, kvh(h), :]) @ V_hat[...]

where K_hat/V_hat are dequantized on the fly from the 4.5-bit wire format
(two FP4 codes per byte + one E4M3-scale/SV-sign byte per 16-block).  The
cache is streamed HBM -> VMEM in sequence chunks; the dequant (VPU arithmetic,
no gathers) overlaps the (G, hd) x (hd, sc) MXU scores matmul; softmax is the
online flash-decode accumulation carried in VMEM scratch.

Grid: (B, KVH, S/sc) -- the S dimension is innermost/sequential so the
running (m, l, acc) stay resident.  cur_len arrives as a scalar-prefetch
operand for masking.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["razer_kv_attention_pallas"]


def split_even_odd(x):
    """(..., hd) -> (..., 2, hd//2): [..., 0, i] = x[..., 2i] and
    [..., 1, i] = x[..., 2i+1] -- the column order of the low/high nibble
    halves ``_dequant_tile`` returns."""
    *lead, d = x.shape
    return jnp.swapaxes(x.reshape(*lead, d // 2, 2), -1, -2)


def merge_even_odd(y):
    """Inverse of ``split_even_odd``: (..., 2, hd//2) -> (..., hd)."""
    *lead, _, half = y.shape
    return jnp.swapaxes(y, -1, -2).reshape(*lead, 2 * half)


def _fp4_vals(c, sv):
    """FP4 codes (int32) + per-element special value -> f32 (Eq. 5 + remap)."""
    s = c >> 3
    e = (c >> 1) & 0b11
    m = (c & 1).astype(jnp.float32)
    mag = jnp.where(e == 0, 0.5 * m, jnp.exp2((e - 1).astype(jnp.float32)) * (1.0 + 0.5 * m))
    val = jnp.where(s == 1, -mag, mag)
    return jnp.where(c == 8, sv, val)


def _expand_blocks(x, half):
    """(sc, hd//16) per-block values -> (sc, hd//2) per-byte values: byte
    column i sits in quant block i // 8.  A lane repeat Mosaic cannot
    reshape, so it runs as a 0/1 matmul; the values (E4M3 scales and
    5x scales) are exact in bf16, so the product is exact."""
    nblk = x.shape[-1]
    blk = jax.lax.broadcasted_iota(jnp.int32, (nblk, half), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (nblk, half), 1)
    sel = (col // 8 == blk).astype(jnp.bfloat16)
    return jnp.dot(x.astype(jnp.bfloat16), sel, preferred_element_type=jnp.float32)


def _dequant_tile(codes_packed, meta, hd):
    """codes (sc, hd//2) u8 + meta (sc, hd//16) u8 -> the (even, odd) head-dim
    halves, each (sc, hd//2) f32: byte column i holds head dims 2i (low
    nibble) and 2i+1 (high nibble).  The caller contracts them with the
    matching even/odd query columns (``split_even_odd``) instead of
    interleaving them back, which Mosaic cannot lower along lanes."""
    half = hd // 2
    packed = codes_packed.astype(jnp.int32)
    meta = meta.astype(jnp.int32)
    scode = meta & 0x7F
    e = scode >> 3
    mm = (scode & 7).astype(jnp.float32)
    scale = jnp.where(
        e == 0,
        2.0 ** -6 * (mm / 8.0),
        jnp.exp2((e - 7).astype(jnp.float32)) * (1.0 + mm / 8.0),
    )  # (sc, hd//16)
    sv_scale = jnp.where((meta >> 7) == 1, -5.0, 5.0) * scale
    scale_e = _expand_blocks(scale, half)
    sv_e = _expand_blocks(sv_scale, half)

    def vals(c):
        return jnp.where(c == 8, sv_e, _fp4_vals(c, 0.0) * scale_e)

    return vals(packed & 0xF), vals(packed >> 4)


def _scores(q_ref, k_even, k_odd):
    """(rows, sc) f32 scores of the (2, rows, hd//2) split queries against a
    split K tile."""
    nt = (((1,), (1,)), ((), ()))  # contract head dims: q @ k.T
    qe = q_ref[0].astype(jnp.float32)
    qo = q_ref[1].astype(jnp.float32)
    return (jax.lax.dot_general(qe, k_even, nt, preferred_element_type=jnp.float32)
            + jax.lax.dot_general(qo, k_odd, nt, preferred_element_type=jnp.float32))


def _finish(o_ref, acc_ref, l_ref):
    """Normalize the online-softmax accumulator into the split output."""
    inv = 1.0 / jnp.maximum(l_ref[...], 1e-30)
    o_ref[0] = (acc_ref[0] * inv).astype(o_ref.dtype)
    o_ref[1] = (acc_ref[1] * inv).astype(o_ref.dtype)


def _kernel(cur_len_ref, q_ref, kc_ref, km_ref, vc_ref, vm_ref, o_ref,
            m_ref, l_ref, acc_ref, *, sc, hd, nsteps_s):
    si = pl.program_id(2)

    @pl.when(si == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, -1e30)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    cur_len = cur_len_ref[pl.program_id(0)]  # per-sequence (continuous batching)
    k_even, k_odd = _dequant_tile(kc_ref[...], km_ref[...], hd)  # (sc, hd//2) f32
    v_even, v_odd = _dequant_tile(vc_ref[...], vm_ref[...], hd)

    scale = 1.0 / math.sqrt(hd)
    s = _scores(q_ref, k_even, k_odd) * scale  # (G, sc)
    pos = si * sc + jax.lax.broadcasted_iota(jnp.int32, (1, sc), 1)
    s = jnp.where(pos < cur_len, s, -1e30)

    m_prev = m_ref[...]  # (G, 1)
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
    p = jnp.exp(s - m_new)
    alpha = jnp.exp(m_prev - m_new)
    l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=-1, keepdims=True)
    acc_ref[0] = acc_ref[0] * alpha + jnp.dot(p, v_even, preferred_element_type=jnp.float32)
    acc_ref[1] = acc_ref[1] * alpha + jnp.dot(p, v_odd, preferred_element_type=jnp.float32)
    m_ref[...] = m_new

    @pl.when(si == nsteps_s - 1)
    def _done():
        _finish(o_ref, acc_ref, l_ref)


@functools.partial(jax.jit, static_argnames=("seq_chunk", "interpret"))
def razer_kv_attention_pallas(q, k_codes, k_meta, v_codes, v_meta, cur_len,
                              *, seq_chunk: int = 512, interpret: bool = False):
    """q: (B, H, hd); caches: (B, S, KVH, hd//2|hd//16) u8; cur_len: () or (B,) i32.

    Returns (B, H, hd) f32.  H % KVH == 0; S % seq_chunk == 0."""
    b, h, hd = q.shape
    _, s, kvh, half = k_codes.shape
    assert half * 2 == hd and h % kvh == 0 and s % min(seq_chunk, s) == 0
    g = h // kvh
    sc = min(seq_chunk, s)
    grid = (b, kvh, s // sc)

    qg = split_even_odd(q.reshape(b, kvh, g, hd)).swapaxes(2, 3)  # (B, KVH, 2, G, hd//2)
    # (B, S, KVH, x) -> (B, KVH, S, x) so the S chunk is a contiguous block
    kc = k_codes.transpose(0, 2, 1, 3)
    km = k_meta.transpose(0, 2, 1, 3)
    vc = v_codes.transpose(0, 2, 1, 3)
    vm = v_meta.transpose(0, 2, 1, 3)

    kernel = functools.partial(_kernel, sc=sc, hd=hd, nsteps_s=grid[2])
    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=grid,
            in_specs=[
                pl.BlockSpec((None, None, 2, g, hd // 2),
                             lambda bi, ki, si, cur: (bi, ki, 0, 0, 0)),
                pl.BlockSpec((None, None, sc, hd // 2), lambda bi, ki, si, cur: (bi, ki, si, 0)),
                pl.BlockSpec((None, None, sc, hd // 16), lambda bi, ki, si, cur: (bi, ki, si, 0)),
                pl.BlockSpec((None, None, sc, hd // 2), lambda bi, ki, si, cur: (bi, ki, si, 0)),
                pl.BlockSpec((None, None, sc, hd // 16), lambda bi, ki, si, cur: (bi, ki, si, 0)),
            ],
            out_specs=pl.BlockSpec((None, None, 2, g, hd // 2),
                                   lambda bi, ki, si, cur: (bi, ki, 0, 0, 0)),
            scratch_shapes=[
                pltpu.VMEM((g, 1), jnp.float32),
                pltpu.VMEM((g, 1), jnp.float32),
                pltpu.VMEM((2, g, hd // 2), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((b, kvh, 2, g, hd // 2), jnp.float32),
        interpret=interpret,
        name="razer_kv_attention",
    )(jnp.broadcast_to(jnp.asarray(cur_len, jnp.int32).reshape(-1), (b,)), qg, kc, km, vc, vm)
    return merge_even_odd(out.swapaxes(2, 3)).reshape(b, h, hd)
