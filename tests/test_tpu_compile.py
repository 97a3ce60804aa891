"""Compile the serve path's Pallas kernels for a TPU v5e that is described,
not attached, at the published widths of the models served on it.

Interpret mode runs the kernels' arithmetic but not the TPU compiler's
rules (block tiling, vector layouts, VMEM); these compiles do.  Nothing
runs, so they say nothing about results or speed.  The topology is described
inside a fixture, never at import: only one process may load the TPU
library, and every test worker imports every test file.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core.packing import PackedRazerWeight, PackedStackedTensor
from repro.kernels import ops
from repro.serving.pagepool import _quantize_scatter

# llama3.2-3b: d=3072, 24/8 heads, hd=128, d_ff=8192; dbrx-132b expert bank
D, D_FF, H, KVH, HD = 3072, 8192, 24, 8, 128
DBRX_E, DBRX_D, DBRX_F = 16, 6144, 10752
SLOTS, PAGE, PAGES_PER_SEQ = 8, 16, 32
QWEN3_8B_LAYERS, QWEN3_8B_POOL_PAGES = 36, 2048 + 1  # + the null page


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    os.environ.setdefault("TPU_LOG_DIR", "disabled")  # else the compiler logs to /tmp
    try:
        return topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    """One described chip; the persistent compilation cache is off meanwhile
    (entries compiled for a described chip cannot be read back without one)."""
    from jax.experimental.compilation_cache import compilation_cache

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _spec(sharding, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _compile_text(fn, *args):
    text = jax.jit(fn).lower(*args).compile().as_text()
    return [line for line in text.splitlines() if "tpu_custom_call" in line]


def _pool(sharding, pages, lead=()):
    return {
        name: _spec(sharding, (*lead, pages, PAGE, KVH, width), jnp.uint8)
        for name, width in (("k_codes", HD // 2), ("k_meta", HD // 16),
                            ("v_codes", HD // 2), ("v_meta", HD // 16))
    }


@pytest.mark.parametrize("m", [SLOTS, 512], ids=["decode", "prefill"])
@pytest.mark.parametrize("k,n", [(D, D), (D, D_FF), (D_FF, D)], ids=["wq", "up", "down"])
def test_razer_matmul_compiles(one_chip, m, k, n):
    pw = PackedRazerWeight(
        codes=_spec(one_chip, (k // 2, n), jnp.uint8),
        scale_meta=_spec(one_chip, (k // 16, n), jnp.uint8),
        tensor_scale=_spec(one_chip, (), jnp.float32),
        sv_magnitudes=(5.0, 8.0), shape=(k, n))
    calls = _compile_text(
        lambda x, w: ops.razer_matmul(x, w, force_pallas=True, interpret=False),
        _spec(one_chip, (m, k), jnp.bfloat16), pw)
    assert len(calls) == 1 and "%razer_matmul" in calls[0]


def test_grouped_matmul_compiles_at_dbrx_bank(one_chip):
    cap = 8
    for k, n in ((DBRX_D, DBRX_F), (DBRX_F, DBRX_D)):
        pst = PackedStackedTensor(
            codes=_spec(one_chip, (DBRX_E, k // 2, n), jnp.uint8),
            scale_meta=_spec(one_chip, (DBRX_E, k // 16, n), jnp.uint8),
            tensor_scale=_spec(one_chip, (DBRX_E,), jnp.float32),
            sv_magnitudes=(5.0, 8.0), shape=(DBRX_E, k, n))
        calls = _compile_text(
            lambda x, w: ops.razer_grouped_matmul(x, w, force_pallas=True, interpret=False),
            _spec(one_chip, (DBRX_E, cap, k), jnp.bfloat16), pst)
        assert len(calls) == 1 and "%razer_grouped_matmul" in calls[0]


def test_paged_attention_compiles(one_chip):
    calls = _compile_text(
        lambda q, c, pt, cl: ops.razer_paged_kv_attention(
            q, c, pt, cl, force_pallas=True, interpret=False),
        _spec(one_chip, (SLOTS, 1, H, HD), jnp.bfloat16),
        _pool(one_chip, SLOTS * PAGES_PER_SEQ + 1),
        _spec(one_chip, (SLOTS, PAGES_PER_SEQ), jnp.int32),
        _spec(one_chip, (SLOTS,), jnp.int32))
    assert len(calls) == 1 and "%razer_paged_attention" in calls[0]


def test_paged_verify_attention_compiles(one_chip):
    calls = _compile_text(
        lambda q, c, pt, cl: ops.razer_paged_kv_attention_verify(
            q, c, pt, cl, force_pallas=True, interpret=False),
        _spec(one_chip, (SLOTS, 4, H, HD), jnp.bfloat16),
        _pool(one_chip, SLOTS * PAGES_PER_SEQ + 1),
        _spec(one_chip, (SLOTS, PAGES_PER_SEQ), jnp.int32),
        _spec(one_chip, (SLOTS,), jnp.int32))
    assert len(calls) == 1 and "%razer_paged_attention_verify" in calls[0]


def test_contiguous_kv_attention_compiles(one_chip):
    s = PAGE * PAGES_PER_SEQ
    cache = {
        name: _spec(one_chip, (SLOTS, s, KVH, width), jnp.uint8)
        for name, width in (("k_codes", HD // 2), ("k_meta", HD // 16),
                            ("v_codes", HD // 2), ("v_meta", HD // 16))
    }
    calls = _compile_text(
        lambda q, c, cl: ops.razer_kv_attention(q, c, cl, force_pallas=True, interpret=False),
        _spec(one_chip, (SLOTS, 1, H, HD), jnp.bfloat16), cache,
        _spec(one_chip, (SLOTS,), jnp.int32))
    assert len(calls) == 1 and "%razer_kv_attention" in calls[0]


def test_page_scatter_compiles_in_place_at_qwen3_8b_width(one_chip):
    """A 512-token prefill's page write into a Qwen3-8B pool (36 layers of
    2049 pages) needs under 0.5 GB of temporaries: the donated pool is
    updated in place, not relaid out, and the encoder keeps no full-size
    intermediate."""
    tokens = 512
    kv = _spec(one_chip, (QWEN3_8B_LAYERS, tokens, KVH, HD), jnp.bfloat16)
    idx = _spec(one_chip, (tokens,), jnp.int32)
    pool = _pool(one_chip, QWEN3_8B_POOL_PAGES, lead=(QWEN3_8B_LAYERS,))
    compiled = _quantize_scatter.lower(pool, kv, kv, idx, idx).compile()
    assert compiled.memory_analysis().temp_size_in_bytes < 0.5e9
