"""Pallas TPU kernel: RaZeR packed-weight GEMM (the Marlin-kernel analogue, §4.3).

    y[M, N] = x[M, K] @ dequant(codes[K//2, N], scale_meta[K//16, N])

The weight lives in HBM in the 4.5-bit wire format (two FP4 codes per byte
along K + one scale/meta byte per 16-block).  Each grid step streams a
(bk//2, bn) code tile and a (bk//16, bn) scale tile into VMEM, decodes them to
``compute_dtype`` on the VPU (pure arithmetic -- no gathers), and feeds the MXU
with a (bm, bk) x (bk, bn) matmul accumulated in a float32 VMEM scratch.

TPU adaptation notes (vs the paper's Blackwell kernel):
  * Marlin's stripe partitioning + global reduction stage is unnecessary: the
    TPU grid is sequential over the K dimension per core, so accumulation stays
    in VMEM and there is no inter-block reduction at all.
  * The warp-shuffle weight shuffling becomes a simple packed byte layout; the
    (bk//2, bn) uint8 tile already matches the (32, 128) int8 VMEM tiling.
  * The §4.4 decoder (offset-register semantics) is the `where` chain in
    `_decode_fp4_tile`.

Block sizes default to MXU-aligned (128, 128, 512) and are overridable for the
autotuning sweep in benchmarks/kernel_bench.py (the paper's SM auto-tuning
analogue).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["razer_matmul_pallas", "razer_matmul_kshard_pallas"]


def _decode_e3m3_scale(code):
    """6-bit E3M3 scale code (int32) -> f32 value, pure arithmetic (no table gather).

    value = 2^(1-bias) * (m/8)        if e == 0   (bias = 3)
          = 2^(e-bias) * (1 + m/8)    otherwise
    """
    e = code >> 3
    m = (code & 7).astype(jnp.float32)
    sub = 2.0 ** (1 - 3) * (m / 8.0)
    nrm = jnp.exp2((e - 3).astype(jnp.float32)) * (1.0 + m / 8.0)
    return jnp.where(e == 0, sub, nrm)


def _decode_fp4_tile(c, sv):
    """FP4 codes (int32) + per-element special value -> f32 values.

    Implements Eq. 5 plus the RaZeR remap: code 8 (-0) decodes to ``sv``.
    """
    s = c >> 3
    e = (c >> 1) & 0b11
    m = (c & 1).astype(jnp.float32)
    mag = jnp.where(e == 0, 0.5 * m, jnp.exp2((e - 1).astype(jnp.float32)) * (1.0 + 0.5 * m))
    val = jnp.where(s == 1, -mag, mag)
    return jnp.where(c == 8, sv, val)


def _decode_weight_tile(packed, sm, *, block_k, m0, m1, compute_dtype):
    """One wire-format weight tile -> dense (bk, bn) values in compute_dtype.

    packed: (bk//2, bn) uint8 code bytes; sm: (bk//16, bn) uint8 scale/meta
    bytes.  The bytes widen to int32 first: Mosaic lowers no 8-bit shifts.
    Shared by the 2-D and the grouped kernels -- the wire format has
    exactly one decoder."""
    packed = packed.astype(jnp.int32)
    sm = sm.astype(jnp.int32)
    bk2, bn = packed.shape
    codes = jnp.stack([packed & 0xF, packed >> 4], axis=1).reshape(bk2 * 2, bn)  # interleave along K

    scale = _decode_e3m3_scale(sm & 0x3F)
    meta = sm >> 6
    sv_mag = jnp.where(((meta >> 1) & 1) == 1, jnp.float32(m1), jnp.float32(m0))
    sv = jnp.where((meta & 1) == 1, -sv_mag, sv_mag)

    # broadcast per-block (bk//16, bn) -> per-element (bk, bn)
    nblk = block_k // 16
    sv_e = jnp.broadcast_to(sv[:, None, :], (nblk, 16, bn)).reshape(block_k, bn)
    scale_e = jnp.broadcast_to(scale[:, None, :], (nblk, 16, bn)).reshape(block_k, bn)

    return (_decode_fp4_tile(codes, sv_e) * scale_e).astype(compute_dtype)


def _kernel(x_ref, codes_ref, sm_ref, o_ref, acc_ref, *, nsteps_k, block_k, m0, m1, compute_dtype):
    @pl.when(pl.program_id(2) == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    w = _decode_weight_tile(
        codes_ref[...], sm_ref[...], block_k=block_k, m0=m0, m1=m1, compute_dtype=compute_dtype
    )

    # ---- MXU ---------------------------------------------------------------
    x = x_ref[...].astype(compute_dtype)
    acc_ref[...] += jnp.dot(x, w, preferred_element_type=jnp.float32)

    @pl.when(pl.program_id(2) == nsteps_k - 1)
    def _done():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("m0", "m1", "block_m", "block_n", "block_k", "compute_dtype", "interpret"),
)
def razer_matmul_pallas(
    x,
    codes,
    scale_meta,
    *,
    m0: float,
    m1: float,
    block_m: int = 128,
    block_n: int = 128,
    block_k: int = 512,
    compute_dtype=jnp.bfloat16,
    interpret: bool = False,
):
    """x (M, K) @ packed weight -> (M, N) f32 (tensor_scale NOT applied)."""
    m, k = x.shape
    k2, n = codes.shape
    assert k == 2 * k2, (x.shape, codes.shape)
    assert k % block_k == 0 and m % block_m == 0 and n % block_n == 0, (
        f"shapes ({m},{k},{n}) must divide blocks ({block_m},{block_k},{block_n})"
    )
    assert block_k % 16 == 0
    grid = (m // block_m, n // block_n, k // block_k)

    kernel = functools.partial(
        _kernel,
        nsteps_k=grid[2],
        block_k=block_k,
        m0=float(m0),
        m1=float(m1),
        compute_dtype=compute_dtype,
    )
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_m, block_k), lambda i, j, kk: (i, kk)),
            pl.BlockSpec((block_k // 2, block_n), lambda i, j, kk: (kk, j)),
            pl.BlockSpec((block_k // 16, block_n), lambda i, j, kk: (kk, j)),
        ],
        out_specs=pl.BlockSpec((block_m, block_n), lambda i, j, kk: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), jnp.float32),
        scratch_shapes=[pltpu.VMEM((block_m, block_n), jnp.float32)],
        interpret=interpret,
        name="razer_matmul",
    )(x, codes, scale_meta)


def razer_matmul_kshard_pallas(
    x,
    codes,
    scale_meta,
    *,
    m0: float,
    m1: float,
    axis_name,
    block_m: int = 128,
    block_n: int = 128,
    block_k: int = 512,
    compute_dtype=jnp.bfloat16,
    interpret: bool = False,
):
    """Tensor-parallel K-shard launch: per-shard grid + fused reduce-scatter.

    Call INSIDE ``shard_map`` with this device's K/tp slice: x (M, local_K)
    and the local wire-format tensors (local_K//2, N) / (local_K//16, N).
    The grid is the ordinary (M/bm, N/bn, local_K/bk) launch over LOCAL K --
    each device computes a full-N partial product, then the partial-sum
    exchange is fused into the epilogue as one ``psum_scatter`` over
    ``axis_name`` tiled on the last dim, returning (M, N/tp).  On a size-1
    axis the scatter is the identity, so the result is bit-exact with the
    unsharded launch (docs/parallelism.md).
    """
    y = razer_matmul_pallas(
        x,
        codes,
        scale_meta,
        m0=m0,
        m1=m1,
        block_m=block_m,
        block_n=block_n,
        block_k=block_k,
        compute_dtype=compute_dtype,
        interpret=interpret,
    )
    if axis_name is None:
        return y
    return jax.lax.psum_scatter(y, axis_name, scatter_dimension=y.ndim - 1, tiled=True)
