"""Public jit'd wrappers around the Pallas kernels.

Handles: CPU-vs-TPU dispatch (interpret mode / jnp reference on CPU), batch
flattening, M-padding, block-size selection, and the deferred tensor-scale
multiply.  Models and the serving engine call these -- never the raw kernels.

Format-generic callers should use ``quantized_matmul`` / ``quantized_act_qdq``,
which dispatch through the core format registry by packed-container type /
TensorSpec: a new format registered via ``core.registry.register_format`` flows
through without edits here.  The razer-specific entry points below are that
format's registered kernels.

These wrappers are deliberately mesh-blind: under expert parallelism the
shard_map boundary lives ABOVE them (``models/moe.py``), so the grouped
wrapper simply receives the local E/ep bank shard and launches a local-E
grid -- identical code to the single-device launch (docs/parallelism.md).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.core import registry
from repro.core.packing import PackedRazerWeight, PackedStackedTensor

from . import ref
from .razer_grouped_matmul import razer_grouped_matmul_pallas
from .razer_matmul import razer_matmul_pallas
from .razer_quantize import razer_act_qdq_pallas

__all__ = [
    "razer_matmul",
    "razer_grouped_matmul",
    "razer_matmul_kshard",
    "razer_grouped_matmul_kshard",
    "reduce_scatter_epilogue",
    "razer_act_qdq",
    "razer_kv_attention",
    "razer_paged_kv_attention",
    "razer_paged_kv_attention_verify",
    "quantized_matmul",
    "quantized_grouped_matmul",
    "quantized_act_qdq",
    "on_tpu",
    "pick_blocks",
]


def quantized_matmul(x, pw):
    """y = x @ dequant(pw) for ANY registered format's packed container.

    Dispatches by container type through the format registry -- the packed
    analogue of ``jnp.dot``, and what ``qlinear`` uses under the hood."""
    entry = registry.packed_entry(pw)
    if entry is None or entry.matmul_kernel is None:
        raise TypeError(
            f"no registered matmul kernel for packed container {type(pw).__name__}"
        )
    return entry.matmul_kernel(x, pw)


def quantized_grouped_matmul(x, pst):
    """y[..., e, :, :] = x[..., e, :, K] @ dequant(pst[e]) for ANY registered
    format's stacked packed container (the grouped analogue of
    ``quantized_matmul`` -- what ``moe_forward`` uses for packed expert banks)."""
    entry = registry.grouped_entry(pst)
    if entry is None or entry.grouped_matmul_kernel is None:
        raise TypeError(
            f"no registered grouped matmul kernel for stacked container {type(pst).__name__}"
        )
    return entry.grouped_matmul_kernel(x, pst)


def quantized_act_qdq(x, spec):
    """Fused dynamic activation fake-quant for a TensorSpec, if the spec's
    format registered an act kernel; falls back to the spec's qdq numerics."""
    entry = registry.get_format(spec.format)
    if entry.act_kernel is not None:
        return entry.act_kernel(x, spec)
    return spec.qdq(x, axis=-1)


def on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def _largest_divisor(n: int, candidates) -> int:
    for c in candidates:
        if n % c == 0:
            return c
    return n


def pick_blocks(m: int, n: int, k: int):
    """MXU-aligned block shapes (the §4.3 auto-tuner's TPU analogue picks
    from this lattice; see benchmarks/kernel_bench.py).

    Mosaic tiles the last two block dims by (8, 128) unless a block spans
    the whole dim: bm divides M rounded up to 8 (the wrappers pad M), and
    bn / bk are 128-multiples dividing N / K, else the whole dim."""
    bm = _largest_divisor(-(-m // 8) * 8, (256, 128, 64, 32, 16, 8))
    bn = _largest_divisor(n, (256, 128))
    bk = _largest_divisor(k, (512, 256, 128))
    return bm, bn, bk


def razer_matmul(x, pw: PackedRazerWeight, *, force_pallas: bool = False, interpret: bool | None = None):
    """y = x @ dequant(pw) for arbitrary-batch x (..., K).

    On TPU: Pallas kernel.  On CPU: jnp reference (a Pallas CPU 'compile' would
    be interpret-mode anyway and 1000x slower; the reference has identical
    flops/bytes structure for the dry-run roofline).
    """
    k, n = pw.shape
    lead = x.shape[:-1]
    assert x.shape[-1] == k, (x.shape, pw.shape)
    if not (force_pallas or on_tpu()):
        # the reference dequantizes with tensor_scale already applied
        y = ref.razer_matmul_ref(x.reshape(-1, k), pw)
        return y.reshape(*lead, n).astype(x.dtype)

    xf = x.reshape(-1, k)
    m = xf.shape[0]
    bm, bn, bk = pick_blocks(m, n, k)
    pad = (-m) % bm
    if pad:
        xf = jnp.pad(xf, ((0, pad), (0, 0)))
    y = razer_matmul_pallas(
        xf,
        pw.codes,
        pw.scale_meta,
        m0=pw.sv_magnitudes[0],
        m1=pw.sv_magnitudes[1],
        block_m=bm,
        block_n=bn,
        block_k=bk,
        interpret=bool(interpret) if interpret is not None else not on_tpu(),
    )
    y = y[:m] if pad else y
    return (y * pw.tensor_scale).reshape(*lead, n).astype(x.dtype)


def razer_grouped_matmul(
    x, pst: PackedStackedTensor, *, force_pallas: bool = False, interpret: bool | None = None
):
    """y[e] = x[e] @ dequant(pst[e]) for x (E, M, K) -> (E, M, N).

    On TPU: the grouped Pallas kernel (one launch for the whole bank; block
    sizes come from the ``pick_blocks`` divisor lattice, with M-padding as a
    safety net should the lattice ever stop dividing M).  On CPU: the jnp
    reference (dequant + einsum), which has the identical flops/bytes
    structure for the dry-run roofline.

    E is whatever bank the caller holds: the full bank on one device, or a
    local E/ep shard inside the expert-parallel shard_map boundary
    (``models/moe.py``) -- the grid is (local_E, M/bm, N/bn, K/bk) and the
    wire format of each expert row is identical either way, so this wrapper
    needs no sharding awareness (docs/parallelism.md).
    """
    e, k, n = pst.shape
    assert x.ndim == 3 and x.shape[0] == e and x.shape[-1] == k, (x.shape, pst.shape)
    m = x.shape[1]
    if not (force_pallas or on_tpu()):
        # the reference dequantizes with per-expert tensor_scale already applied
        return ref.razer_grouped_matmul_ref(x, pst).astype(x.dtype)
    bm, bn, bk = pick_blocks(m, n, k)
    pad = (-m) % bm
    xp = jnp.pad(x, ((0, 0), (0, pad), (0, 0))) if pad else x
    y = razer_grouped_matmul_pallas(
        xp,
        pst.codes,
        pst.scale_meta,
        m0=pst.sv_magnitudes[0],
        m1=pst.sv_magnitudes[1],
        block_m=bm,
        block_n=bn,
        block_k=bk,
        interpret=bool(interpret) if interpret is not None else not on_tpu(),
    )
    y = y[:, :m] if pad else y
    return (y * pst.tensor_scale[:, None, None]).astype(x.dtype)


def reduce_scatter_epilogue(y, axis_name):
    """Fuse the K-shard partial-sum exchange into a matmul epilogue.

    Inside ``shard_map``, a K-sharded packed matmul leaves each device holding
    a full-N PARTIAL product; this turns those partials into each device's
    N/tp output tile with ONE collective -- ``psum_scatter`` tiled on the last
    dim -- instead of the psum + slice (or all-gather + matmul) a naive
    lowering pays.  ``axis_name=None`` is the unsharded no-op; on a size-1
    axis the scatter is the identity, so single-device results stay bit-exact
    with the meshless path (docs/parallelism.md).
    """
    if axis_name is None:
        return y
    return jax.lax.psum_scatter(y, axis_name, scatter_dimension=y.ndim - 1, tiled=True)


def razer_matmul_kshard(x, pw: PackedRazerWeight, *, axis_name,
                        force_pallas: bool = False, interpret: bool | None = None):
    """K-shard partial matmul + fused reduce-scatter: (..., local_K) -> (..., N/tp).

    Call INSIDE ``shard_map``: ``pw`` is this device's localized K/tp shard
    (``PackedRazerWeight.local_shard``) and x the matching activation slice.
    The local launch is the ordinary ``razer_matmul`` -- the per-shard grid
    falls out of the shard's smaller K -- and the tensor_scale multiply
    commutes with the sum, so applying it to the partial product before the
    exchange is exact."""
    y = razer_matmul(x, pw, force_pallas=force_pallas, interpret=interpret)
    return reduce_scatter_epilogue(y, axis_name)


def razer_grouped_matmul_kshard(x, pst: PackedStackedTensor, *, axis_name,
                                force_pallas: bool = False, interpret: bool | None = None):
    """Grouped K-shard partial matmul + fused reduce-scatter epilogue.

    x (local_E, M, local_K) @ local bank shard -> (local_E, M, N/tp); the
    grouped sibling of ``razer_matmul_kshard`` (see there for the contract).
    Composes with expert parallelism: E is already the local E/ep shard inside
    the moe shard_map boundary, K is additionally this device's K/tp slice."""
    y = razer_grouped_matmul(x, pst, force_pallas=force_pallas, interpret=interpret)
    return reduce_scatter_epilogue(y, axis_name)


def razer_act_qdq(x, *, svs=(5.0, -5.0), block: int = 16, force_pallas: bool = False, interpret: bool | None = None):
    """Fused dynamic activation fake-quant over the last dim (any batch shape)."""
    lead = x.shape[:-1]
    k = x.shape[-1]
    if not (force_pallas or on_tpu()):
        return ref.razer_act_qdq_ref(x, svs=svs, block=block)
    xf = x.reshape(-1, k)
    m = xf.shape[0]
    bm = _largest_divisor(m, (256, 128, 64, 32, 16, 8, 4, 2, 1))
    bk = _largest_divisor(k, (512, 256, 128, 64, 32, 16))
    y = razer_act_qdq_pallas(
        xf,
        svs=tuple(svs),
        block=block,
        block_m=bm,
        block_k=bk,
        interpret=bool(interpret) if interpret is not None else not on_tpu(),
    )
    return y.reshape(*lead, k)


def razer_kv_attention(q, cache, cur_len, *, force_pallas: bool = False, interpret: bool | None = None):
    """Decode attention over a packed KV cache dict (serving.kvcache layout).

    q: (B, 1, H, hd) or (B, H, hd) -> (B, 1, H, hd)."""
    from .razer_kv_attention import razer_kv_attention_pallas

    squeeze = q.ndim == 4
    qf = q[:, 0] if squeeze else q
    if not (force_pallas or on_tpu()):
        out = ref.razer_kv_attention_ref(
            qf, cache["k_codes"], cache["k_meta"], cache["v_codes"], cache["v_meta"], cur_len)
    else:
        out = razer_kv_attention_pallas(
            qf, cache["k_codes"], cache["k_meta"], cache["v_codes"], cache["v_meta"],
            jnp.asarray(cur_len, jnp.int32),
            interpret=bool(interpret) if interpret is not None else not on_tpu())
    out = out.astype(q.dtype)
    return out[:, None] if squeeze else out


def razer_paged_kv_attention(q, cache, page_table, cur_len, *,
                             force_pallas: bool = False, interpret: bool | None = None):
    """Decode attention over a PAGED packed KV pool (serving.pagepool layout:
    pool arrays (P, ps, KVH, x), page_table (B, NP), cur_len (B,)).

    q: (B, 1, H, hd) or (B, H, hd) -> same rank out.  The continuous-batching
    analogue of ``razer_kv_attention``: the page-table lookup happens in the
    kernel's index maps (TPU) or as a plain gather (CPU oracle)."""
    from .paged_kv_attention import paged_kv_attention_pallas

    squeeze = q.ndim == 4
    qf = q[:, 0] if squeeze else q
    if not (force_pallas or on_tpu()):
        out = ref.paged_kv_attention_ref(
            qf, cache["k_codes"], cache["k_meta"], cache["v_codes"], cache["v_meta"],
            page_table, cur_len)
    else:
        out = paged_kv_attention_pallas(
            qf, cache["k_codes"], cache["k_meta"], cache["v_codes"], cache["v_meta"],
            jnp.asarray(page_table, jnp.int32), jnp.asarray(cur_len, jnp.int32),
            interpret=bool(interpret) if interpret is not None else not on_tpu())
    out = out.astype(q.dtype)
    return out[:, None] if squeeze else out


def razer_paged_kv_attention_verify(q, cache, page_table, cur_len, *,
                                    force_pallas: bool = False,
                                    interpret: bool | None = None):
    """Multi-query VERIFY attention over the paged pool (speculative decode).

    q: (B, T, H, hd) -- T = speculate_k + 1 queries per slot, query t at
    logical position ``cur_len[b] + t`` attending positions
    ``< cur_len[b] + t + 1``.  Unlike ``razer_paged_kv_attention``,
    ``cur_len`` here is the COMMITTED length BEFORE the T speculative
    positions (the per-query "+t+1" happens inside); the T positions' own
    wire bytes must already be scattered into the pages.  Returns
    (B, T, H, hd)."""
    from .paged_kv_attention import paged_kv_attention_verify_pallas

    assert q.ndim == 4, f"verify attention wants (B, T, H, hd) queries, got {q.shape}"
    if not (force_pallas or on_tpu()):
        out = ref.paged_kv_attention_verify_ref(
            q, cache["k_codes"], cache["k_meta"], cache["v_codes"], cache["v_meta"],
            page_table, cur_len)
    else:
        out = paged_kv_attention_verify_pallas(
            q, cache["k_codes"], cache["k_meta"], cache["v_codes"], cache["v_meta"],
            jnp.asarray(page_table, jnp.int32), jnp.asarray(cur_len, jnp.int32),
            interpret=bool(interpret) if interpret is not None else not on_tpu())
    return out.astype(q.dtype)
