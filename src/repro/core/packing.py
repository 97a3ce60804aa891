"""Bit-level packing for RaZeR tensors (paper §4.1, §4.3, §4.4).

Wire format (one 16-element block of weights):
  * 16 x 4-bit FP4 codes, packed two-per-byte (low nibble = even element)
  * 1 byte  = [ meta(2b) | E3M3 scale code(6b) ]          (weights)
           or [ meta(1b) | E4M3 scale code(7b) ]          (activations)
  * metadata = (select << 1 | sign) for weights, (sign) for activations;
    select chooses the SV pair (offset register OF0/OF1 in the paper's tensor
    core, Fig. 4), sign gives the SV its sign.

Total: 16*4 + 8 = 72 bits per block = 4.5 bits/value -- exactly NVFP4's
footprint, as the paper requires.

Also implements the §4.4 offset-register semantics bit-exactly:
  OF register: 4-bit signed fixed point s2.1 in [-3.5, 3.5], SV magnitude
  = 6.0 + offset, final SV = (-1)^sign * magnitude.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np

from .formats import FP4_NEG_ZERO_CODE, fp4_decode, fp4_encode, positive_format_values
from .nvfp4 import BlockQuantized
from .razer import razer_quantize

__all__ = [
    "pack_fp4_codes",
    "unpack_fp4_codes",
    "encode_offset_register",
    "decode_offset_register",
    "pack_scale_meta",
    "unpack_scale_meta",
    "unpack_scale_meta_fields",
    "PackedRazerWeight",
    "PackedStackedTensor",
    "pack_weight",
    "pack_stacked_weights",
]


# ---------------------------------------------------------------------------
# 4-bit code packing
# ---------------------------------------------------------------------------
def pack_fp4_codes(codes):
    """(..., K) uint8 nibbles -> (..., K//2) bytes. Low nibble = even index."""
    if codes.shape[-1] % 2:
        raise ValueError("K must be even to pack nibbles")
    lo = codes[..., 0::2].astype(jnp.uint8)
    hi = codes[..., 1::2].astype(jnp.uint8)
    return lo | (hi << 4)


def unpack_fp4_codes(packed):
    """(..., K//2) bytes -> (..., K) uint8 nibbles."""
    lo = packed & 0xF
    hi = packed >> 4
    return jnp.stack([lo, hi], axis=-1).reshape(*packed.shape[:-1], packed.shape[-1] * 2)


# ---------------------------------------------------------------------------
# §4.4 offset registers (tensor-core decoder semantics, validated in tests)
# ---------------------------------------------------------------------------
def encode_offset_register(sv_magnitude: float) -> int:
    """SV magnitude -> 4-bit s2.1 fixed-point offset code (offset from 6.0)."""
    off = float(sv_magnitude) - 6.0
    if not -3.5 <= off <= 3.5 or (off * 2) != int(off * 2):
        raise ValueError(f"SV magnitude {sv_magnitude} not encodable (offset {off})")
    s = 1 if off < 0 else 0
    a = abs(off)
    return (s << 3) | (int(a) << 1) | (int(a * 2) & 1)


def decode_offset_register(code: int) -> float:
    """4-bit s2.1 offset code -> SV magnitude = 6.0 + offset."""
    s = (code >> 3) & 1
    mag = ((code >> 1) & 0b11) + 0.5 * (code & 1)
    return 6.0 + (-mag if s else mag)


# ---------------------------------------------------------------------------
# scale + metadata byte
# ---------------------------------------------------------------------------
def _scale_code(scale, fmt: str):
    grid = positive_format_values(fmt)
    # scales are already exact grid values; nearest-match index is exact.
    mids = (grid[1:] + grid[:-1]) / 2.0
    return jnp.searchsorted(jnp.asarray(mids), scale, side="left").astype(jnp.uint8)


def pack_scale_meta(scale, sv_index, *, weight: bool = True, scale_fmt: str | None = None):
    """(scale values on grid, sv_index in [-1, nsv)) -> one byte per block.

    sv_index ordering follows razer.WEIGHT/ACT_SPECIAL_VALUES: (+m0, -m0, +m1,
    -m1, ...) so  pair = idx >> 1, sign = idx & 1.  Blocks with sv_index == -1
    emit meta 0 (don't-care: they contain no -0 code).
    """
    fmt = scale_fmt or ("e3m3" if weight else "e4m3")
    code = _scale_code(scale, fmt)
    idx = jnp.maximum(sv_index, 0).astype(jnp.uint8)
    if weight:
        if code.dtype != jnp.uint8:
            code = code.astype(jnp.uint8)
        assert fmt == "e3m3", "weight scale+2b meta needs a 6-bit scale format"
        meta = idx & 0b11  # select<<1 | sign
        return (meta << 6) | code
    else:
        assert fmt == "e4m3", "activation scale+1b meta needs a 7-bit scale format"
        meta = idx & 0b1  # sign only (single pair)
        return (meta << 7) | code


def unpack_scale_meta(byte, *, weight: bool = True, sv_magnitudes: Tuple[float, ...] = (5.0, 8.0)):
    """byte -> (scale value f32, special value f32)."""
    if weight:
        code = byte & 0x3F
        meta = byte >> 6
        grid = jnp.asarray(positive_format_values("e3m3"))
        scale = grid[code.astype(jnp.int32)]
        select = (meta >> 1) & 1
        sign = meta & 1
        mags = jnp.asarray(sv_magnitudes, jnp.float32)
        sv = mags[select.astype(jnp.int32)] * jnp.where(sign == 1, -1.0, 1.0)
    else:
        code = byte & 0x7F
        meta = byte >> 7
        grid = jnp.asarray(positive_format_values("e4m3"))
        scale = grid[code.astype(jnp.int32)]
        sv = sv_magnitudes[0] * jnp.where(meta == 1, -1.0, 1.0)
    return scale, sv


def unpack_scale_meta_fields(byte, *, weight: bool = True):
    """byte -> (scale_code, sv_select, sv_sign) raw bit fields.

    The telemetry read path (obs/numerics): ``unpack_scale_meta`` collapses
    the metadata into decoded values, but the audit needs the raw fields --
    the scale CODE for clipping/underflow histograms (code 0 is the grid
    minimum, the top code the grid maximum) and the SV select/sign bits for
    the per-block remap-usage histogram.  Activation bytes have no select
    bit (single pair): select is returned as 0.
    """
    if weight:
        meta = byte >> 6
        return byte & 0x3F, (meta >> 1) & 1, meta & 1
    return byte & 0x7F, jnp.zeros_like(byte), byte >> 7


# ---------------------------------------------------------------------------
# §4.3 GPU-kernel variant: FP16 group scale (block 128) with the 2-bit SV
# metadata hidden in the scale's sign bit + most-significant exponent bit.
# Implemented bit-exactly to validate the paper's Marlin-kernel encoding; the
# TPU path uses the NVFP4-native byte layout above.
# ---------------------------------------------------------------------------
def pack_scale_meta_fp16(scale, sv_index):
    """positive f32 scales (already < 2.0) + sv_index -> uint16 words.

    fp16 layout: [sign | e4 e3 e2 e1 e0 | m9..m0].  A positive scale < 2.0
    has sign=0 and exponent MSB (e4)=0, freeing 2 bits:
        bit15 (sign)  <- SV pair select
        bit14 (e4)    <- SV sign
    """
    h = jax.lax.bitcast_convert_type(scale.astype(jnp.float16), jnp.uint16)
    assert_free = (h & 0xC000) == 0
    h = jnp.where(assert_free, h, h & 0x3FFF)  # defensive: mask if out of range
    idx = jnp.maximum(sv_index, 0).astype(jnp.uint16)
    select = (idx >> 1) & 1
    sign = idx & 1
    return h | (select << 15) | (sign << 14)


def unpack_scale_meta_fp16(word, sv_magnitudes: Tuple[float, float] = (5.0, 8.0)):
    """uint16 word -> (scale f32, special value f32)."""
    select = (word >> 15) & 1
    sign = (word >> 14) & 1
    scale = jax.lax.bitcast_convert_type((word & 0x3FFF).astype(jnp.uint16), jnp.float16)
    mags = jnp.asarray(sv_magnitudes, jnp.float32)
    sv = mags[select.astype(jnp.int32)] * jnp.where(sign == 1, -1.0, 1.0)
    return scale.astype(jnp.float32), sv


def fold_scales_below_two(scales, tensor_scale):
    """Fold powers of two into the tensor scale so every group scale < 2.0
    (keeps the fp16 exponent MSB free; the paper's kernels assume normalized
    weights -- we make the assumption explicit and lossless)."""
    mx = jnp.max(scales)
    k = jnp.ceil(jnp.log2(jnp.maximum(mx, 1e-30) / 2.0))
    k = jnp.maximum(k, 0.0)
    factor = jnp.exp2(k)
    return scales / factor, tensor_scale * factor


# ---------------------------------------------------------------------------
# packed weight container (the kernel's HBM layout)
# ---------------------------------------------------------------------------
@jax.tree_util.register_pytree_node_class
@dataclass
class PackedRazerWeight:
    """RaZeR-quantized weight W (K, N), blocked along K (the reduction dim).

    codes       : (K//2, N) uint8 -- two FP4 codes per byte along K
    scale_meta  : (K//16, N) uint8 -- E3M3 scale + 2-bit SV metadata
    tensor_scale: () f32
    sv_magnitudes: static (m0, m1)
    shape       : logical (K, N)
    """

    codes: jnp.ndarray
    scale_meta: jnp.ndarray
    tensor_scale: jnp.ndarray
    sv_magnitudes: Tuple[float, float]
    shape: Tuple[int, int]

    def tree_flatten(self):
        return (self.codes, self.scale_meta, self.tensor_scale), (self.sv_magnitudes, self.shape)

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(*children, sv_magnitudes=aux[0], shape=aux[1])

    def local_shard(self, k_shards: int) -> "PackedRazerWeight":
        """Static metadata for a K/k_shards tensor-parallel shard of this weight.

        Block scales live along K, so a slice of whole 16-element quant blocks
        is itself a valid wire-format tensor: codes split between (K/tp/2, N)
        byte rows, scale_meta between (K/tp/16, N) rows, and the per-tensor
        scale (a scalar over the WHOLE tensor, not per block) replicates.  At
        the shard_map boundary (core/qlinear.py) the body receives this
        container with its array leaves already sliced to the local K rows;
        ``shape`` is static aux data still naming the global K -- this
        rewrites it to the local value.  The leaves are untouched.
        """
        k, n = self.shape
        if k_shards <= 0 or k % (k_shards * 16):
            raise ValueError(
                f"cannot tensor-parallel-shard packed K={k} over tp={k_shards} "
                f"devices: K must be divisible by tp*quant_block = "
                f"{k_shards}*16 so every shard holds whole 16-element quant "
                f"blocks (see docs/parallelism.md)"
            )
        return PackedRazerWeight(
            codes=self.codes,
            scale_meta=self.scale_meta,
            tensor_scale=self.tensor_scale,
            sv_magnitudes=self.sv_magnitudes,
            shape=(k // k_shards, n),
        )

    def dequantize(self):
        k, n = self.shape
        codes = unpack_fp4_codes(self.codes.T).reshape(n, k)  # (N, K)
        scale, sv = unpack_scale_meta(self.scale_meta.T, weight=True, sv_magnitudes=self.sv_magnitudes)
        # scale/sv: (N, K//16) -> broadcast over the 16 elements of each block
        vals = fp4_decode(codes.reshape(n, k // 16, 16), sv[..., None])
        w = vals * (scale * self.tensor_scale)[..., None]
        return w.reshape(n, k).T  # (K, N)


# ---------------------------------------------------------------------------
# stacked expert banks (E, K, N): one wire container for the whole bank
# ---------------------------------------------------------------------------
@jax.tree_util.register_pytree_node_class
@dataclass
class PackedStackedTensor:
    """A stacked bank of E independent RaZeR-packed (K, N) weights.

    This is the MoE expert-bank container: the grouped matmul kernel consumes
    the whole bank at once (``kernels.razer_grouped_matmul``), so the E dim
    stays leading on every leaf instead of being E separate containers.

    codes       : (E, K//2, N) uint8 -- two FP4 codes per byte along K
    scale_meta  : (E, K//16, N) uint8 -- E3M3 scale + 2-bit SV metadata
    tensor_scale: (E,) f32 -- one per-bank-entry tensor scale (each expert is
                  quantized independently, so its absmax normalization is its
                  own -- matching E separate ``pack_weight`` calls bit-exactly)
    sv_magnitudes: static (m0, m1), shared across the bank
    shape       : logical (E, K, N)

    Every leaf keeps the expert dim leading, which is what makes the bank
    expert-parallel-shardable: splitting on E slices between packed (K, N)
    entries, never through one, so the wire format of each entry is byte-for-
    byte identical whether the bank is whole or an E/ep shard on one device
    (docs/parallelism.md).  ``local_shard`` rewrites the static metadata for
    such a shard.
    """

    codes: jnp.ndarray
    scale_meta: jnp.ndarray
    tensor_scale: jnp.ndarray
    sv_magnitudes: Tuple[float, float]
    shape: Tuple[int, int, int]

    def tree_flatten(self):
        return (self.codes, self.scale_meta, self.tensor_scale), (self.sv_magnitudes, self.shape)

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(*children, sv_magnitudes=aux[0], shape=aux[1])

    def __getitem__(self, e: int) -> PackedRazerWeight:
        """One bank entry as a plain 2-D packed weight (ref-path convenience)."""
        _, k, n = self.shape
        return PackedRazerWeight(
            codes=self.codes[e],
            scale_meta=self.scale_meta[e],
            tensor_scale=self.tensor_scale[e],
            sv_magnitudes=self.sv_magnitudes,
            shape=(k, n),
        )

    def local_shard(self, n_shards: int, k_shards: int = 1) -> "PackedStackedTensor":
        """Static metadata for an (E/n_shards, K/k_shards) shard of this bank.

        At the shard_map boundary (models/moe.py) the body receives this
        container with its array leaves already sliced to the local E/n_shards
        expert rows (and, under tensor parallelism, the local K/k_shards wire
        rows), but ``shape`` is static aux data and still names the global
        sizes -- this rewrites it to the local values.  The leaves themselves
        are untouched: expert-parallel sharding splits the bank only on the
        leading expert dim, never inside a packed (K, N) entry, and a K-shard
        splits between whole 16-element quant blocks (block scales live along
        K), so each local row stays a valid wire-format tensor bit-identical
        to packing that slice directly.
        """
        e, k, n = self.shape
        if n_shards <= 0 or e % n_shards:
            raise ValueError(
                f"cannot split a packed bank of E={e} expert rows into "
                f"{n_shards} equal expert-parallel shards: E must be divisible "
                f"by the ep axis size"
            )
        if k_shards <= 0 or k % (k_shards * 16):
            raise ValueError(
                f"cannot tensor-parallel-shard packed K={k} over tp={k_shards} "
                f"devices: K must be divisible by tp*quant_block = "
                f"{k_shards}*16 so every shard holds whole 16-element quant "
                f"blocks (see docs/parallelism.md)"
            )
        return PackedStackedTensor(
            codes=self.codes,
            scale_meta=self.scale_meta,
            tensor_scale=self.tensor_scale,
            sv_magnitudes=self.sv_magnitudes,
            shape=(e // n_shards, k // k_shards, n),
        )

    def dequantize(self):
        """(E, K, N) f32 -- vmapped single-weight dequant over the bank."""
        _, k, n = self.shape

        def one(codes, sm, ts):
            return PackedRazerWeight(codes, sm, ts, self.sv_magnitudes, (k, n)).dequantize()

        return jax.vmap(one)(self.codes, self.scale_meta, self.tensor_scale)


def pack_stacked_weights(
    w,
    *,
    sv_magnitudes: Tuple[float, float] = (5.0, 8.0),
    block_size: int = 16,
) -> PackedStackedTensor:
    """RaZeR-quantize a stacked (E, K, N) bank per-entry and bit-pack it.

    Each entry is packed by ``pack_weight`` in isolation (independent tensor
    scales), so ``pack_stacked_weights(w)[e]`` round-trips bit-for-bit with
    ``pack_weight(w[e])``.  Entries widen to f32 one at a time: the quantizer's
    temporaries for a whole full-width bank would not fit on one chip.
    """
    if w.ndim != 3:
        raise ValueError("pack_stacked_weights expects a 3-D (E, K, N) bank")
    e, k, n = w.shape
    packed = [
        pack_weight(jnp.asarray(w[i], jnp.float32), sv_magnitudes=sv_magnitudes,
                    block_size=block_size)
        for i in range(e)
    ]
    return PackedStackedTensor(
        codes=jnp.stack([pw.codes for pw in packed]),
        scale_meta=jnp.stack([pw.scale_meta for pw in packed]),
        tensor_scale=jnp.stack([pw.tensor_scale for pw in packed]),
        sv_magnitudes=tuple(float(m) for m in sv_magnitudes),
        shape=(e, k, n),
    )


def pack_weight(
    w,
    *,
    sv_magnitudes: Tuple[float, float] = (5.0, 8.0),
    block_size: int = 16,
) -> PackedRazerWeight:
    """RaZeR-quantize a (K, N) weight along K and bit-pack it."""
    if w.ndim != 2:
        raise ValueError("pack_weight expects a 2-D (K, N) weight")
    k, n = w.shape
    from .razer import sv_pairs_to_set

    svs = sv_pairs_to_set(*sv_magnitudes)
    bq = razer_quantize(w, special_values=svs, block_size=block_size, scale_fmt="e3m3", axis=0)
    # bq.q: (N, K//B, B); bq.block_scale/sv_index: (N, K//B)
    q = bq.q
    uses_sv = (bq.sv_index >= 0)[..., None] & (q == bq.sv[..., None])
    codes = jnp.where(uses_sv, jnp.uint8(FP4_NEG_ZERO_CODE), fp4_encode(q))
    codes = codes.reshape(n, k)  # (N, K)
    packed = pack_fp4_codes(codes).T  # pack along K -> (N, K//2) -> (K//2, N)
    scale_meta = pack_scale_meta(bq.block_scale, bq.sv_index, weight=True).T  # (K//16, N)
    return PackedRazerWeight(
        codes=packed,
        scale_meta=scale_meta,
        tensor_scale=bq.tensor_scale.astype(jnp.float32),
        sv_magnitudes=tuple(float(m) for m in sv_magnitudes),
        shape=(k, n),
    )
