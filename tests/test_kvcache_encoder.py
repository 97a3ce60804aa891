"""The KV wire encoder (``serving/kvcache.kv_quantize``) against the general
RaZeR path it replaces on the serve path: ``KV_SPEC.quantize`` ->
``fp4_encode`` -> ``pack_scale_meta``, which stays the weight packer's path.

Eager, the bytes must be equal: codes exactly, meta except the SV bit of a
block that holds no -0 code (a don't-care the encoder writes as 0).  Under
``jax.jit`` the oracle itself drifts from its eager self (XLA fuses its
block sums and rounds them otherwise), so there the encoder may differ from
the eager oracle in no more decoded values than the jitted oracle does.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from _kv_oracle import kv_oracle as _oracle

from repro.core.formats import FP4_NEG_ZERO_CODE, FP4_VALUES, positive_format_values
from repro.core.packing import unpack_fp4_codes
from repro.serving.kvcache import KV_SV, kv_dequantize, kv_quantize
from repro.serving.pagepool import _quantize_scatter

HD = 128


def _mids(grid):
    grid = np.asarray(grid, np.float32)
    return (grid[1:] + grid[:-1]) / np.float32(2.0)


def _with_neighbours(v):
    v = np.asarray(v, np.float32)
    return np.concatenate([v, np.nextafter(v, np.float32(-np.inf)), np.nextafter(v, np.float32(np.inf))])


def _blocks(values, anchor, rng, n):
    """n blocks of 16: an absmax anchor (which fixes the scale) and 15 draws."""
    body = rng.choice(values, size=(n, 15))
    head = np.where(rng.random((n, 1)) < 0.5, anchor, -anchor).astype(np.float32)
    return np.concatenate([head, body], axis=1).astype(np.float32)


def _as_kv(blocks, rng):
    blocks = rng.permutation(blocks, axis=1)  # the anchor anywhere in its block
    pad = -len(blocks) % 32  # zero blocks up to whole (4, hd) rows
    blocks = np.concatenate([blocks, np.zeros((pad, 16), np.float32)])
    return blocks.reshape(-1, 4, HD)


def _random(scale, rng):
    return rng.standard_normal((2, 32, 8, HD)).astype(np.float32) * np.float32(scale)


def _zero_blocks(rng):
    x = rng.standard_normal((2, 32, 8, HD // 16, 16)).astype(np.float32)
    x *= rng.random(x.shape[:-1] + (1,)) < 0.5
    x[0, 0] = 0.0  # whole heads of zeros too
    return x.reshape(2, 32, 8, HD)


def _e4m3_subnormal_absmax(rng):
    # absmax/6 from far below the smallest scale 2^-9 (promoted) to the
    # normal range's start 2^-6, log-uniform per block
    x = rng.standard_normal((2, 32, 8, HD // 16, 16)).astype(np.float32)
    x /= np.abs(x).max(axis=-1, keepdims=True)
    x *= np.exp(rng.uniform(np.log(1e-6), np.log(6 * 2.0**-6), x.shape[:-1] + (1,)))
    return x.astype(np.float32).reshape(2, 32, 8, HD)


def _above_e4m3_max(rng):
    # absmax beyond 6 x 448: the scale clips at 448 and codes clip at +-6
    x = _random(3000.0, rng)
    x[..., ::7] *= np.float32(40.0)
    return x


def _fp4_midpoints(rng):
    # scaled values on every midpoint of FP4 and of FP4 u {+-5}, and both
    # float32 neighbours, at scales 2^k that the +-6 anchor hits exactly
    fp4 = np.unique(FP4_VALUES)
    grids = [fp4] + [np.unique(np.append(fp4, np.float32(v))) for v in KV_SV]
    mids = np.unique(np.concatenate([_mids(g) for g in grids]))
    values = np.concatenate([_with_neighbours(mids), fp4, np.float32(KV_SV)])
    out = []
    for k in (-9, -7, -3, 0, 2, 8):
        b = _blocks(values, np.float32(6.0), rng, 256) * np.float32(2.0**k)
        # blocks of one repeated value: exact SSE ties between candidates
        reps = np.repeat(values[:, None], 15, axis=1) * np.float32(2.0**k)
        reps = np.concatenate([np.full((len(values), 1), 6.0 * 2.0**k, np.float32), reps], axis=1)
        out += [b, reps]
    return _as_kv(np.concatenate(out).astype(np.float32), rng)


def _e4m3_midpoints(rng):
    # absmax/6 on every positive E4M3 value, every midpoint and its float32
    # neighbours: each block's anchor is the float32 a with a / 6 == target
    grid = positive_format_values("e4m3")
    targets = np.concatenate([grid[1:], _with_neighbours(_mids(grid))])
    six = np.float32(6.0)
    anchors = []
    for t in targets:
        a = np.float32(t) * six
        for _ in range(8):
            if a / six == t:
                break
            a = np.nextafter(a, np.float32(np.inf) if a / six < t else np.float32(-np.inf))
        anchors.append(a)
    anchors = np.asarray(anchors, np.float32)
    body = rng.uniform(-1, 1, (len(anchors), 15)).astype(np.float32) * anchors[:, None]
    return _as_kv(np.concatenate([anchors[:, None], body], axis=1), rng)


CASES = {
    **{f"random-{s:g}": (lambda rng, s=s: _random(s, rng)) for s in (1e-4, 1e-2, 1.0, 30.0, 1e3)},
    "zero-blocks": _zero_blocks,
    "e4m3-subnormal-absmax": _e4m3_subnormal_absmax,
    "above-6x448": _above_e4m3_max,
    "fp4-midpoints": _fp4_midpoints,
    "e4m3-midpoints": _e4m3_midpoints,
}


def _case(name, dtype):
    x = CASES[name](np.random.default_rng(sorted(CASES).index(name)))
    return jnp.asarray(x, dtype)


def _sv_bits_by_block(codes, meta):
    nib = np.asarray(unpack_fp4_codes(codes)).reshape(*meta.shape, 16)
    has_neg0 = (nib == FP4_NEG_ZERO_CODE).any(axis=-1)
    return has_neg0, np.asarray(meta) >> 7


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32], ids=["bf16", "f32"])
@pytest.mark.parametrize("name", sorted(CASES))
def test_encoder_bytes_equal_oracle_eager(name, dtype):
    x = _case(name, dtype)
    codes, meta = kv_quantize(x)
    want_codes, want_meta = _oracle(x)
    np.testing.assert_array_equal(np.asarray(codes), np.asarray(want_codes))
    np.testing.assert_array_equal(np.asarray(meta) & 0x7F, np.asarray(want_meta) & 0x7F)
    has_neg0, want_bit = _sv_bits_by_block(want_codes, want_meta)
    _, bit = _sv_bits_by_block(codes, meta)
    np.testing.assert_array_equal(bit[has_neg0], want_bit[has_neg0])
    assert not bit[~has_neg0].any(), "a block with no -0 code writes SV bit 0"


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32], ids=["bf16", "f32"])
@pytest.mark.parametrize("name", sorted(CASES))
def test_encoder_under_jit_drifts_no_more_than_oracle(name, dtype):
    x = _case(name, dtype)
    want = np.asarray(kv_dequantize(*_oracle(x), HD))
    got = np.asarray(kv_dequantize(*jax.jit(kv_quantize)(x), HD))
    oracle_jit = np.asarray(kv_dequantize(*jax.jit(_oracle)(x), HD))
    assert int((got != want).sum()) <= int((oracle_jit != want).sum())


def test_page_scatter_has_no_loop_or_gather_at_qwen3_8b_width():
    """The prefill write of a 512-token Qwen3-8B bucket (36 layers, 8 kv
    heads, hd 128, a 2048-page pool) lowers to straight-line elementwise
    work: no ``while`` and no gather."""
    layers, tokens, kvh, pages = 36, 512, 8, 2049
    u8 = lambda w: jax.ShapeDtypeStruct((layers, pages, 16, kvh, w), jnp.uint8)  # noqa: E731
    pool = {"k_codes": u8(HD // 2), "k_meta": u8(HD // 16), "v_codes": u8(HD // 2), "v_meta": u8(HD // 16)}
    kv = jax.ShapeDtypeStruct((layers, tokens, kvh, HD), jnp.bfloat16)
    idx = jax.ShapeDtypeStruct((tokens,), jnp.int32)
    text = _quantize_scatter.lower(pool, kv, kv, idx, idx).as_text()
    assert "stablehlo.while" not in text
    assert "gather" not in text
