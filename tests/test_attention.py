"""Chunked (flash-style) attention vs naive oracle + perf-toggle equivalence."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _hyp import given, settings, st  # optional-hypothesis shim

from repro.models.attention import (
    SKIP_MASKED_CHUNKS,
    chunked_attention,
    decode_attention,
)


def naive_attention(q, k, v, causal=True, window=0, q_offset=0):
    b, sq, h, hd = q.shape
    _, skv, kvh, _ = k.shape
    g = h // kvh
    kr = jnp.repeat(k, g, axis=2)
    vr = jnp.repeat(v, g, axis=2)
    s = jnp.einsum("bqhd,bkhd->bhqk", q.astype(jnp.float32), kr.astype(jnp.float32))
    s = s / jnp.sqrt(hd)
    qpos = q_offset + jnp.arange(sq)
    kpos = jnp.arange(skv)
    mask = jnp.ones((sq, skv), bool)
    if causal:
        mask &= kpos[None, :] <= qpos[:, None]
    if window:
        mask &= kpos[None, :] > qpos[:, None] - window
    s = jnp.where(mask[None, None], s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", p, vr.astype(jnp.float32)).astype(q.dtype)


def _qkv(b, s, h, kvh, hd, seed=0):
    rng = np.random.default_rng(seed)
    q = jnp.asarray(rng.standard_normal((b, s, h, hd)).astype(np.float32))
    k = jnp.asarray(rng.standard_normal((b, s, kvh, hd)).astype(np.float32))
    v = jnp.asarray(rng.standard_normal((b, s, kvh, hd)).astype(np.float32))
    return q, k, v


@pytest.mark.parametrize("case", [
    dict(b=2, s=32, h=4, kvh=4, hd=8, qc=8, kc=8),
    dict(b=1, s=64, h=4, kvh=2, hd=16, qc=16, kc=32),   # GQA
    dict(b=2, s=48, h=6, kvh=1, hd=8, qc=16, kc=16),    # MQA, ragged chunks
])
def test_chunked_matches_naive_causal(case):
    q, k, v = _qkv(case["b"], case["s"], case["h"], case["kvh"], case["hd"])
    out = chunked_attention(q, k, v, causal=True, q_chunk=case["qc"], kv_chunk=case["kc"])
    ref = naive_attention(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=2e-5, atol=2e-5)


def test_chunked_noncausal_and_window():
    q, k, v = _qkv(1, 64, 2, 2, 8, seed=3)
    out = chunked_attention(q, k, v, causal=False, q_chunk=16, kv_chunk=16)
    ref = naive_attention(q, k, v, causal=False)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=2e-5, atol=2e-5)
    outw = chunked_attention(q, k, v, causal=True, window=8, q_chunk=16, kv_chunk=16)
    refw = naive_attention(q, k, v, causal=True, window=8)
    np.testing.assert_allclose(np.asarray(outw), np.asarray(refw), rtol=2e-5, atol=2e-5)


def test_skip_masked_chunks_equivalent():
    """The lax.cond triangular skip must be bit-compatible with the dense path."""
    q, k, v = _qkv(2, 64, 4, 2, 8, seed=7)
    for kwargs in (dict(causal=True), dict(causal=True, window=8)):
        tok = SKIP_MASKED_CHUNKS.set(False)
        dense = chunked_attention(q, k, v, q_chunk=16, kv_chunk=16, **kwargs)
        SKIP_MASKED_CHUNKS.reset(tok)
        tok = SKIP_MASKED_CHUNKS.set(True)
        skipped = chunked_attention(q, k, v, q_chunk=16, kv_chunk=16, **kwargs)
        SKIP_MASKED_CHUNKS.reset(tok)
        np.testing.assert_allclose(np.asarray(dense), np.asarray(skipped), rtol=1e-6, atol=1e-6)


@settings(max_examples=10, deadline=None)
@given(st.integers(0, 1000), st.sampled_from([1, 2, 4]), st.sampled_from([16, 32]))
def test_chunked_property(seed, g, s):
    kvh = 2
    q, k, v = _qkv(1, s, g * kvh, kvh, 8, seed=seed)
    out = chunked_attention(q, k, v, causal=True, q_chunk=8, kv_chunk=8)
    ref = naive_attention(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=5e-5, atol=5e-5)


def test_decode_matches_naive_last_position():
    q, k, v = _qkv(2, 32, 4, 2, 8, seed=9)
    cur = 20
    full = naive_attention(q[:, cur : cur + 1] * 0 + q[:, cur : cur + 1], k[:, : cur + 1], v[:, : cur + 1], causal=False)
    # decode against a padded cache with cur_len = cur+1
    out = decode_attention(q[:, cur : cur + 1], k, v, cur + 1)
    np.testing.assert_allclose(np.asarray(out), np.asarray(full), rtol=2e-5, atol=2e-5)


def test_decode_vector_cur_len():
    q, k, v = _qkv(2, 32, 4, 2, 8, seed=11)
    cur = jnp.asarray([10, 20])
    out = decode_attention(q[:, :1], k, v, cur)
    for i, c in enumerate([10, 20]):
        ref = decode_attention(q[i : i + 1, :1], k[i : i + 1], v[i : i + 1], c)
        np.testing.assert_allclose(np.asarray(out[i]), np.asarray(ref[0]), rtol=2e-5, atol=2e-5)


def test_triangular_schedule_matches_dense():
    from repro.models.attention import ATTN_SCHEDULE

    q, k, v = _qkv(2, 64, 4, 2, 8, seed=13)
    for kwargs in (dict(causal=True), dict(causal=True, window=12)):
        dense = chunked_attention(q, k, v, q_chunk=16, kv_chunk=16, **kwargs)
        tok = ATTN_SCHEDULE.set("triangular")
        tri = chunked_attention(q, k, v, q_chunk=16, kv_chunk=16, **kwargs)
        ATTN_SCHEDULE.reset(tok)
        np.testing.assert_allclose(np.asarray(tri), np.asarray(dense), rtol=2e-5, atol=2e-5)


def test_triangular_halves_flops():
    from repro.models.attention import ATTN_SCHEDULE
    import jax

    q, k, v = _qkv(1, 128, 2, 2, 16, seed=17)
    f = lambda q, k, v: chunked_attention(q, k, v, causal=True, q_chunk=16, kv_chunk=16)
    dense = jax.jit(f).lower(q, k, v).compile().cost_analysis().get("flops", 0)
    # dense path hides flops in a scan body; unroll comparison via triangular's
    # static form vs the analytic rectangle instead
    tok = ATTN_SCHEDULE.set("triangular")
    tri = jax.jit(f).lower(q, k, v).compile().cost_analysis().get("flops", 0)
    ATTN_SCHEDULE.reset(tok)
    t = 128 // 16
    rect = 2 * 2 * (128 * 128) * 16 * 2  # qk+pv, h=2, full rectangle
    assert tri < 0.75 * rect  # triangular ~ (t+1)/(2t) = 0.56 of the rectangle
