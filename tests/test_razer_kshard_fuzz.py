"""Fuzz sweep for the grouped RaZeR matmul and its K-sharded variants.

Hypothesis draws (E, M, N, K, block-tile) shapes -- including K values the
tp axis CANNOT split into whole quant blocks -- and checks three contracts:

  * the interpret-mode Pallas grouped kernel matches the jnp dequantize
    oracle (``kernels/ref.py``) for every legal tile decomposition, not just
    the tuned ones the benchmarks use;
  * the K-sharded launch is the SAME kernel: with ``axis_name=None`` the
    psum_scatter epilogue is the identity and outputs are bit-identical,
    and under a real 2-device shard_map the sharded result matches the
    unsharded one to f32 reduction-reorder tolerance (bit-exact on a
    (1, 1) mesh);
  * indivisible K is rejected at the ELIGIBILITY layer (replicate, or raise
    under strict) rather than inside a kernel with a shape error.

Each property lives in a ``_check_*`` helper; a deterministic pinned sweep
runs the same helpers on fixed tuples so minimal images without hypothesis
still exercise every code path.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from _hyp import HAVE_HYPOTHESIS, given, settings, st

from repro.core import registry
from repro.core.packing import pack_stacked_weights, pack_weight
from repro.kernels import ops, ref
from repro.kernels.razer_grouped_matmul import (
    razer_grouped_matmul_kshard_pallas,
    razer_grouped_matmul_pallas,
)
from repro.launch.mesh import auto_mesh
from repro.parallel.sharding import packed_weight_specs, stacked_plan

_NDEV = len(jax.devices())


def _bank(e, k, n, seed):
    return jnp.asarray(
        np.random.default_rng(seed).standard_normal((e, k, n)), jnp.float32)


def _x(e, m, k, seed):
    return jnp.asarray(
        np.random.default_rng(seed).standard_normal((e, m, k)), jnp.float32)


def _divisors(n):
    return [d for d in range(1, n + 1) if n % d == 0]


if HAVE_HYPOTHESIS:
    @st.composite
    def _gemm_cases(draw):
        """(e, m, k, n, bm, bn, bk): every block evenly tiles its dim, K a
        multiple of the 16-element quant block, bk a multiple of 16."""
        e = draw(st.integers(1, 3))
        kb = draw(st.integers(1, 6))
        k, bk = 16 * kb, 16 * draw(st.sampled_from(_divisors(kb)))
        m = draw(st.integers(1, 16))
        bm = draw(st.sampled_from(_divisors(m)))
        nb = draw(st.integers(1, 8))
        n, bn = 8 * nb, 8 * draw(st.sampled_from(_divisors(nb)))
        return e, m, k, n, bm, bn, bk
else:  # shim: strategies are unused, tests skip via @given
    def _gemm_cases():
        return st.none()


def _check_grouped_matches_ref(e, m, k, n, bm, bn, bk, seed=0):
    x = _x(e, m, k, seed=seed)
    pst = pack_stacked_weights(_bank(e, k, n, seed=seed + 1))
    m0, m1 = pst.sv_magnitudes
    y_k = razer_grouped_matmul_pallas(
        x, pst.codes, pst.scale_meta, m0=m0, m1=m1,
        block_m=bm, block_n=bn, block_k=bk,
        compute_dtype=jnp.float32, interpret=True,
    ) * pst.tensor_scale[:, None, None]
    y_r = ref.razer_grouped_matmul_ref(x, pst)
    np.testing.assert_allclose(np.asarray(y_k), np.asarray(y_r), rtol=2e-5, atol=2e-5)
    # the K-shard launch with no axis is the identical computation, bit for bit
    y_ks = razer_grouped_matmul_kshard_pallas(
        x, pst.codes, pst.scale_meta, m0=m0, m1=m1, axis_name=None,
        block_m=bm, block_n=bn, block_k=bk,
        compute_dtype=jnp.float32, interpret=True,
    ) * pst.tensor_scale[:, None, None]
    np.testing.assert_array_equal(np.asarray(y_ks), np.asarray(y_k))


def _check_sharded_matches_unsharded(e, m, k, n, seed=0):
    """2-device shard_map over the model axis vs the unsharded launch; K and
    N must both split (k % 32 == 0, n % 2 == 0 -- callers guarantee it)."""
    mesh = auto_mesh((1, 2), ("data", "model"))
    x = _x(e, m, k, seed=seed)
    pst = pack_stacked_weights(_bank(e, k, n, seed=seed + 1))
    y_ref = ops.razer_grouped_matmul(x, pst)
    entry = registry.grouped_entry(pst)
    (specs, localize), k_ok = stacked_plan(entry, pst, None, "model")
    assert k_ok

    def body(x_l, pst_l):
        return ops.razer_grouped_matmul_kshard(
            x_l, localize(pst_l, 1, 2), axis_name="model")

    y_sh = jax.jit(jax.shard_map(
        body, mesh=mesh, in_specs=(P(None, None, "model"), specs),
        out_specs=P(None, None, "model"), check_vma=False))(x, pst)
    np.testing.assert_allclose(np.asarray(y_sh), np.asarray(y_ref),
                               rtol=1e-5, atol=1e-5)


def _check_indivisible_k_is_ineligible(k, n=16, seed=0):
    """k % 32 != 0 (but packable): the tp=2 eligibility layer replicates or
    raises under strict -- the kernel never sees a ragged K shard."""
    mesh = auto_mesh((1, 2), ("data", "model")) if _NDEV >= 2 else None
    pw = pack_weight(_bank(1, k, n, seed=seed)[0])
    if mesh is not None:
        assert packed_weight_specs(pw, mesh) is None
        with pytest.raises(ValueError, match="divisible"):
            packed_weight_specs(pw, mesh, strict=True)
    with pytest.raises(ValueError, match="divisible"):
        pw.local_shard(2)


@pytest.mark.skipif(not HAVE_HYPOTHESIS, reason="hypothesis not installed")
class TestKernelFuzz:
    @settings(max_examples=40, deadline=None)
    @given(_gemm_cases())
    def test_grouped_kernel_matches_ref(self, case):
        _check_grouped_matches_ref(*case)

    @settings(max_examples=20, deadline=None)
    @given(st.integers(1, 3), st.integers(1, 9), st.integers(1, 3),
           st.integers(1, 4))
    def test_sharded_matches_unsharded(self, e, m, kb, nb):
        if _NDEV < 2:
            pytest.skip("needs XLA_FLAGS=--xla_force_host_platform_device_count=2")
        _check_sharded_matches_unsharded(e, m, 32 * kb, 16 * nb, seed=m + kb)

    @settings(max_examples=20, deadline=None)
    @given(st.integers(0, 2))
    def test_indivisible_k_is_ineligible(self, j):
        _check_indivisible_k_is_ineligible(16 * (2 * j + 1))  # 16, 48, 80


# deterministic pinned sweep: the same helpers on fixed tuples, so the
# contracts stay exercised where hypothesis is unavailable
_PINNED = [
    (1, 1, 16, 8, 1, 8, 16),
    (2, 5, 48, 24, 5, 8, 16),
    (3, 8, 64, 32, 4, 16, 32),
    (2, 16, 96, 64, 8, 32, 48),
]


@pytest.mark.parametrize("case", _PINNED)
def test_pinned_grouped_kernel_matches_ref(case):
    _check_grouped_matches_ref(*case)


@pytest.mark.skipif(_NDEV < 2, reason="needs >= 2 host devices")
@pytest.mark.parametrize("e,m,k,n", [(1, 3, 32, 16), (2, 7, 64, 32), (3, 4, 96, 48)])
def test_pinned_sharded_matches_unsharded(e, m, k, n):
    _check_sharded_matches_unsharded(e, m, k, n, seed=k + n)


def test_pinned_indivisible_k_is_ineligible():
    for k in (16, 48, 80):
        _check_indivisible_k_is_ineligible(k)


def test_kshard_bit_exact_on_single_device_mesh():
    """(1, 1) mesh: the fused epilogue must be the identity, not a reorder.

    Both sides are jitted: an eager call and a compiled one may reassociate
    the f32 sums differently, which says nothing about the epilogue."""
    mesh = auto_mesh((1, 1), ("data", "model"))
    x = _x(2, 5, 64, seed=3)
    pst = pack_stacked_weights(_bank(2, 64, 32, seed=4))
    y0 = jax.jit(ops.razer_grouped_matmul)(x, pst)
    entry = registry.grouped_entry(pst)
    (specs, localize), _ = stacked_plan(entry, pst, None, "model")

    def body(x_l, pst_l):
        return ops.razer_grouped_matmul_kshard(
            x_l, localize(pst_l, 1, 1), axis_name="model")

    y1 = jax.jit(jax.shard_map(
        body, mesh=mesh, in_specs=(P(None, None, "model"), specs),
        out_specs=P(None, None, "model"), check_vma=False))(x, pst)
    np.testing.assert_array_equal(np.asarray(y1), np.asarray(y0))
