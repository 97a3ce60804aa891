"""Pallas TPU kernel: grouped RaZeR packed GEMM over stacked expert banks.

    y[E, M, N] = x[E, M, K] @ dequant(codes[E, K//2, N], scale_meta[E, K//16, N])

One kernel invocation runs E independent packed GEMMs -- the MoE expert
einsum (``gecd,edf->gecf`` with the G and capacity dims flattened into M)
without ever materializing a bf16 copy of the expert bank.  This is the
stacked-bank analogue of ``razer_matmul.razer_matmul_pallas``: the per-tile
decode (FP4 codes + E3M3 scale + 2-bit SV metadata -> compute_dtype weights on
the VPU, then MXU matmul) is identical; what changes is the grid.

Grid layout: ``(E, M//bm, N//bn, K//bk)`` with the expert index outermost.
The TPU grid is sequential per core, so the float32 VMEM accumulator is
reused across the K steps of each ``(e, i, j)`` tile exactly as in the 2-D
kernel -- no cross-expert state, no inter-block reduction.  Every BlockSpec
carries a leading size-1 expert dim whose index map pins it to ``e``, so each
grid step streams only one expert's (bm, bk) activation tile, (bk//2, bn)
code tile and (bk//16, bn) scale tile into VMEM.

The per-expert ``tensor_scale`` (a scalar per bank entry) is deliberately NOT
applied in the kernel: the caller multiplies the (E, M, N) output by
``tensor_scale[:, None, None]`` (one broadcast VPU pass), keeping the kernel
signature free of float inputs -- same contract as the 2-D kernel.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .razer_matmul import _decode_weight_tile

__all__ = ["razer_grouped_matmul_pallas", "razer_grouped_matmul_kshard_pallas"]


def _kernel(x_ref, codes_ref, sm_ref, o_ref, acc_ref, *, nsteps_k, block_k, m0, m1, compute_dtype):
    @pl.when(pl.program_id(3) == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # this expert's weight tile, decoded by the shared wire-format decoder
    w = _decode_weight_tile(
        codes_ref[0], sm_ref[0], block_k=block_k, m0=m0, m1=m1, compute_dtype=compute_dtype
    )

    # ---- MXU ---------------------------------------------------------------
    x = x_ref[0].astype(compute_dtype)  # (bm, bk)
    acc_ref[...] += jnp.dot(x, w, preferred_element_type=jnp.float32)

    @pl.when(pl.program_id(3) == nsteps_k - 1)
    def _done():
        o_ref[0] = acc_ref[...].astype(o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("m0", "m1", "block_m", "block_n", "block_k", "compute_dtype", "interpret"),
)
def razer_grouped_matmul_pallas(
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
    """x (E, M, K) @ packed bank -> (E, M, N) f32 (tensor_scale NOT applied)."""
    e, m, k = x.shape
    e2, k2, n = codes.shape
    assert e == e2 and k == 2 * k2, (x.shape, codes.shape)
    assert scale_meta.shape == (e, k // 16, n), (scale_meta.shape, (e, k // 16, n))
    assert k % block_k == 0 and m % block_m == 0 and n % block_n == 0, (
        f"shapes ({e},{m},{k},{n}) must divide blocks ({block_m},{block_k},{block_n})"
    )
    assert block_k % 16 == 0
    grid = (e, m // block_m, n // block_n, k // block_k)

    kernel = functools.partial(
        _kernel,
        nsteps_k=grid[3],
        block_k=block_k,
        m0=float(m0),
        m1=float(m1),
        compute_dtype=compute_dtype,
    )
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, block_m, block_k), lambda ee, i, j, kk: (ee, i, kk)),
            pl.BlockSpec((1, block_k // 2, block_n), lambda ee, i, j, kk: (ee, kk, j)),
            pl.BlockSpec((1, block_k // 16, block_n), lambda ee, i, j, kk: (ee, kk, j)),
        ],
        out_specs=pl.BlockSpec((1, block_m, block_n), lambda ee, i, j, kk: (ee, i, j)),
        out_shape=jax.ShapeDtypeStruct((e, m, n), jnp.float32),
        scratch_shapes=[pltpu.VMEM((block_m, block_n), jnp.float32)],
        interpret=interpret,
        name="razer_grouped_matmul",
    )(x, codes, scale_meta)


def razer_grouped_matmul_kshard_pallas(
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
    """Tensor-parallel K-shard launch over a LOCAL expert bank shard.

    The grouped sibling of ``razer_matmul.razer_matmul_kshard_pallas``: call
    INSIDE ``shard_map`` with x (local_E, M, local_K) and the bank's local
    wire tensors; the grid is the ordinary (local_E, M/bm, N/bn, local_K/bk)
    launch over LOCAL K, and the partial-sum exchange over ``axis_name`` is
    fused into the epilogue as one last-dim-tiled ``psum_scatter``, returning
    (local_E, M, N/tp).  Identity (bit-exact) on a size-1 axis.
    """
    y = razer_grouped_matmul_pallas(
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
