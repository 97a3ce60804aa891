"""KV caches: bf16 or RaZeR-packed (paper App. C.1 joint W/A/KV quantization).

Quantized layout -- per (token, kv-head), head_dim split into 16-element
blocks, each block stored as:
    codes: hd//2 bytes  (two FP4 codes per byte)
    meta : hd//16 bytes (E4M3 scale, 7 bits + 1-bit SV sign, +-5 pair)
=> 4.5 bits/value vs 16: a 3.56x HBM-traffic and capacity win on the decode
path, which is exactly where 32k-context serving is memory-bound.

Quantization (``kv_quantize``) is written for exactly this format, and what
it traces has no gather, ``searchsorted`` or loop: each grid rounding is a
count or a select chain against the float32 midpoints that
``core.formats.round_to_values`` buckets against (ties go to the lower grid
value), so its bytes equal those of the general path, ``KV_SPEC.quantize``
-> ``fp4_encode`` -> ``pack_scale_meta``, which stays the weight packer's
path and the tests' oracle.
Dequantization is vectorized arithmetic (same decode as the Pallas kernel);
the pure-jnp form here is the engine's portable path.
"""
from __future__ import annotations

import functools
from typing import Dict, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.formats import FP4_MAX, FP4_NEG_ZERO_CODE, FP4_POS_VALUES
from repro.core.packing import unpack_fp4_codes
from repro.core.policy import TensorSpec
from repro.kernels.razer_quantize import _E4M3_MIDS, _GRID, _round_fp4
from repro.models.config import ArchConfig

KV_SV = (5.0, -5.0)  # activation-style single pair
KV_SPEC = TensorSpec.kv()  # razer, E4M3 scales, +-5 pair (QuantPolicy.kv default)


# The rounding grids of the KV wire format, with the float32 midpoints that
# ``core.formats.round_to_values`` buckets against.  ``_E4M3_MIDS`` is the
# positive E4M3 grid's, which is in code order; each SV v turns FP4 into
# FP4 ∪ {v} (Eq. 6), which rounds like FP4 except on the band (lo, hi]
# between v's midpoints with its neighbours, where it gives v.
_FP4_MAG_MIDS = (FP4_POS_VALUES[1:] + FP4_POS_VALUES[:-1]) / 2.0


def _sv_band(v: float) -> Tuple[float, float]:
    grid = np.unique(np.append(_GRID, np.float32(v)))
    i = int(np.searchsorted(grid, np.float32(v)))
    mids = (grid[1:] + grid[:-1]) / 2.0
    return float(mids[i - 1]), float(mids[i])


_SV_BANDS = tuple(_sv_band(v) for v in KV_SV)


def quantized_gqa_cache_init(cfg: ArchConfig, batch: int, max_len: int) -> Dict:
    hd = cfg.hd
    assert hd % 16 == 0, "quantized KV needs head_dim % 16 == 0"
    kvh = cfg.num_kv_heads
    return {
        "k_codes": jnp.zeros((batch, max_len, kvh, hd // 2), jnp.uint8),
        "k_meta": jnp.zeros((batch, max_len, kvh, hd // 16), jnp.uint8),
        "v_codes": jnp.zeros((batch, max_len, kvh, hd // 2), jnp.uint8),
        "v_meta": jnp.zeros((batch, max_len, kvh, hd // 16), jnp.uint8),
    }


def _check_kv_spec(spec: TensorSpec) -> TensorSpec:
    """The KV wire format (and ``kv_dequantize``) is fixed: E4M3 scales,
    16-element blocks, the single +-5 SV pair.  A policy kv spec that deviates
    would encode bytes the decode path misreads -- fail loudly instead."""
    if (
        spec.format != "razer"
        or spec.scale_fmt != "e4m3"
        or spec.block_size != 16
        or tuple(spec.special_values or ()) != KV_SV
    ):
        raise ValueError(
            f"unsupported KV-cache spec {spec}; the packed KV wire format currently "
            f"requires format='razer', scale_fmt='e4m3', block_size=16, "
            f"special_values={KV_SV}"
        )
    return spec


def _e4m3_value(code):
    """7-bit E4M3 code (int32) -> its exact float32 value, by bit assembly."""
    e, m = code >> 3, code & 7
    normal = jax.lax.bitcast_convert_type(((e + 120) << 23) | (m << 20), jnp.float32)
    return jnp.where(e == 0, m.astype(jnp.float32) * jnp.float32(2.0**-9), normal)


def kv_quantize(x, spec: TensorSpec = None) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """x (..., hd) -> (codes (..., hd//2), meta (..., hd//16)).

    Activation-style RaZeR: per-block E4M3 scale (no tensor scale), one SV
    pair selected per block, 1-bit metadata.  ``spec`` (a ``QuantPolicy.kv``
    TensorSpec) is validated against the fixed wire layout.

    The bytes are those of ``KV_SPEC.quantize`` -> ``fp4_encode`` ->
    ``pack_scale_meta`` (Eq. 2, 6-7), computed with no gather: the block
    scale's code is a count of E4M3 midpoints below absmax/6, the FP4
    rounding is a select chain and FP4 ∪ {v} replaces it by v on v's band,
    the SV is kept by strict SSE ``<`` in the order none, +5, -5, and a block
    that keeps none writes SV bit 0.  A zero block takes scale code 0 and
    codes 0."""
    _check_kv_spec(spec or KV_SPEC)
    lead, hd = x.shape[:-1], x.shape[-1]
    xb = x.astype(jnp.float32).reshape(*lead, hd // 16, 16)
    absmax = jnp.max(jnp.abs(xb), axis=-1)
    raw = absmax / jnp.float32(FP4_MAX)
    # #{midpoints < raw} == searchsorted(side="left"): the scale's code
    scode = jnp.sum(raw[..., None] > jnp.asarray(_E4M3_MIDS), axis=-1, dtype=jnp.int32)
    # a nonzero block whose scale rounds to 0 takes the smallest scale (code 1)
    scode = jnp.where((scode == 0) & (absmax > 0), 1, scode)
    scale = _e4m3_value(scode)
    scaled = xb / jnp.where(scale == 0, 1.0, scale)[..., None]
    q = _round_fp4(scaled)
    bands = [(scaled > lo) & ~(scaled > hi) for lo, hi in _SV_BANDS]

    def sse(qv):
        # Squared, then added in element order: the roundings of XLA's reduce
        # on the CPU, where the tests' oracle runs.  Written out, no fusion
        # reorders the sum; the no-op max keeps the CPU backend from fusing a
        # square into its add (one rounding where the oracle makes two).
        d = jnp.maximum((qv - scaled) ** 2, 0.0)
        return functools.reduce(jnp.add, [d[..., i] for i in range(16)])

    # Eq. 6-7: keep the candidate grid of least block SSE, by strict <
    best = sse(q)
    sv_index = jnp.full(best.shape, -1, jnp.int32)
    for i, (v, band) in enumerate(zip(KV_SV, bands)):
        err = sse(jnp.where(band, v, q))
        take = err < best
        best = jnp.where(take, err, best)
        sv_index = jnp.where(take, i, sv_index)

    # FP4 code = sign << 3 | magnitude index, counted from ``scaled`` (ties go
    # to the lower value: down in magnitude above 0, up below it); the kept
    # SV's band codes -0
    mag = jnp.zeros(scaled.shape, jnp.int32)
    for m in _FP4_MAG_MIDS:
        mag = mag + ((scaled > float(m)) | (scaled <= -float(m))).astype(jnp.int32)
    codes = jnp.where((scaled < 0) & (mag > 0), mag + 8, mag)
    for i, band in enumerate(bands):
        codes = jnp.where((sv_index == i)[..., None] & band, FP4_NEG_ZERO_CODE, codes)
    # two codes per byte, low nibble = even element (as ``pack_fp4_codes``)
    pairs = codes.astype(jnp.uint8).reshape(*lead, hd // 2, 2)
    lo, hi = (jax.lax.index_in_dim(pairs, i, axis=-1, keepdims=False) for i in (0, 1))
    meta = (((jnp.maximum(sv_index, 0) & 1) << 7) | scode).astype(jnp.uint8)
    return lo | (hi << 4), meta


def kv_dequantize(codes, meta, hd: int):
    """Inverse of kv_quantize -> (..., hd) f32 (arithmetic decode, no gathers)."""
    nib = unpack_fp4_codes(codes)  # (..., hd)
    c = nib.astype(jnp.int32)
    s = c >> 3
    e = (c >> 1) & 0b11
    m = (c & 1).astype(jnp.float32)
    mag = jnp.where(e == 0, 0.5 * m, jnp.exp2((e - 1).astype(jnp.float32)) * (1.0 + 0.5 * m))
    val = jnp.where(s == 1, -mag, mag)
    # scale byte: 7-bit E4M3 code + sign bit of the SV
    code = (meta & 0x7F).astype(jnp.int32)
    sv_sign = (meta >> 7).astype(jnp.int32)
    ee = code >> 3
    mm = (code & 7).astype(jnp.float32)
    scale = jnp.where(
        ee == 0,
        jnp.exp2(jnp.float32(-6)) * (mm / 8.0),
        jnp.exp2((ee - 7).astype(jnp.float32)) * (1.0 + mm / 8.0),
    )
    sv = 5.0 * jnp.where(sv_sign == 1, -1.0, 1.0)
    lead = codes.shape[:-1]
    nblk = hd // 16
    valb = val.reshape(*lead, nblk, 16)
    cb = c.reshape(*lead, nblk, 16)
    valb = jnp.where(cb == FP4_NEG_ZERO_CODE, sv[..., None], valb)
    out = valb * scale[..., None]
    return out.reshape(*lead, hd)


def quantized_kv_write(cache: Dict, k_new, v_new, cur_len) -> Dict:
    """Quantize + write one token's K/V (B, 1, KVH, hd) at cur_len.

    cur_len: scalar or (B,) per-sequence write positions."""
    b = k_new.shape[0]
    kc, km = kv_quantize(k_new[:, 0])
    vc, vm = kv_quantize(v_new[:, 0])
    if jnp.ndim(cur_len) == 0:
        upd = lambda buf, x: jax.lax.dynamic_update_slice_in_dim(buf, x[:, None], cur_len, axis=1)
    else:
        upd = lambda buf, x: buf.at[jnp.arange(b), cur_len].set(x)
    return {
        "k_codes": upd(cache["k_codes"], kc),
        "k_meta": upd(cache["k_meta"], km),
        "v_codes": upd(cache["v_codes"], vc),
        "v_meta": upd(cache["v_meta"], vm),
    }


def quantized_kv_append(cache: Dict, k_new, v_new, cur_len) -> Tuple[jnp.ndarray, jnp.ndarray, Dict]:
    """Append one token's K/V, return dequantized full caches (fallback path
    for windowed attention; the main decode path uses the fused kernel via
    kernels.ops.razer_kv_attention instead)."""
    hd = k_new.shape[-1]
    cache = quantized_kv_write(cache, k_new, v_new, cur_len)
    k_full = kv_dequantize(cache["k_codes"], cache["k_meta"], hd)
    v_full = kv_dequantize(cache["v_codes"], cache["v_meta"], hd)
    return k_full.astype(k_new.dtype), v_full.astype(v_new.dtype), cache


def quantized_kv_prefill(cache: Dict, k, v) -> Dict:
    """Write a whole prefill's K/V (B, S, KVH, hd) into positions [0, S)."""
    kc, km = kv_quantize(k)
    vc, vm = kv_quantize(v)

    def put(buf, x):
        return jax.lax.dynamic_update_slice(buf, x.astype(buf.dtype), (0, 0, 0, 0))

    return {
        "k_codes": put(cache["k_codes"], kc),
        "k_meta": put(cache["k_meta"], km),
        "v_codes": put(cache["v_codes"], vc),
        "v_meta": put(cache["v_meta"], vm),
    }
