"""Tensor-parallel packed RaZeR weights (docs/parallelism.md#k-sharding).

RaZeR's wire format keeps its 16-element block scales along K, so any
whole-quant-block K-slice is itself a valid wire tensor: the registry's
``shard_packed_fn`` / k_axis-aware ``shard_stacked_fn`` plans split codes
(K/2 packed rows) and scale_meta (K/16 rows) over the "model" axis, each
device runs the SAME kernel on its local K range, and a
``jax.lax.psum_scatter`` epilogue fuses the cross-device reduction with the
output split the next K-sharded matmul wants.

These tests pin the contracts: the plans and ``local_shard`` metadata
rewrites, eligibility/strict validation (``kshard_size``), placement
(each device really holds K/tp wire rows), sharded-vs-unsharded parity for
the dense qlinear path and the ep x tp MoE path, the serve.py fail-fast,
and the packed dbrx end-to-end through ``Engine.generate`` / ``.serve``.

Multi-device cases use the adaptive ``tp_mesh`` conftest fixture ((2, 2)
ep x tp with >= 4 host devices, (1, 2) with 2; skipped on single-device
runs) and ``eptp_mesh`` ((4, 2), 8 devices).
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from repro.core import registry
from repro.core.packing import (
    PackedRazerWeight,
    PackedStackedTensor,
    pack_stacked_weights,
    pack_weight,
)
from repro.core.policy import QuantPolicy
from repro.core.qlinear import QuantizedLinear, qlinear
from repro.launch.mesh import auto_mesh
from repro.models import moe as moe_mod
from repro.parallel.sharding import (
    kshard_size,
    packed_weight_specs,
    param_sharding_tree,
    sharding_ctx,
    stacked_bank_specs,
    stacked_plan,
)
from repro.serving.engine import pack_model_weights


def _dense(k=64, n=32, seed=0):
    w = np.random.default_rng(seed).standard_normal((k, n)).astype(np.float32)
    return pack_weight(jnp.asarray(w))


def _moe_cfg(**kw):
    from repro.models.config import ArchConfig

    # d_model = moe_d_ff = 32: both reduction dims split into whole quant
    # blocks at tp=2 (32 % (2*16) == 0), the smallest K-shardable trio
    base = dict(
        name="t", family="moe", num_layers=1, d_model=32, num_heads=2, num_kv_heads=2,
        d_ff=64, vocab_size=64, moe=True, n_experts=4, topk=2, moe_d_ff=32,
        capacity_factor=8.0,
    )
    base.update(kw)
    return ArchConfig(**base)


def _packed_moe_params(cfg, seed=0):
    p = moe_mod.moe_init(jax.random.PRNGKey(seed), cfg)
    packed = pack_model_weights({"layers_0": {"moe": p}}, cfg, QuantPolicy.packed())
    return p, packed["layers_0"]["moe"]


def _tokens(cfg, b=5, s=5, seed=1):
    # b*s = 25 tokens: gcd(25, want) == 1 for every dispatch-group target, so
    # the group count (and capacity) is identical with and without a mesh
    # context -- the unsharded run is a like-for-like oracle
    return jnp.asarray(
        np.random.default_rng(seed).standard_normal((b, s, cfg.d_model)), jnp.float32)


# ---------------------------------------------------------------------------
# registry plans + local_shard metadata rewrites (run on any device count)
# ---------------------------------------------------------------------------
def test_registry_shard_packed_plan():
    entry = registry.get_format("razer")
    assert entry.shard_packed_fn is not None
    pw = _dense()
    specs, localize = entry.shard_packed_fn(pw, "model")
    # codes (K/2, N) and scale_meta (K/16, N) split their wire-row dim;
    # the scalar tensor_scale replicates
    assert specs.codes == P("model", None)
    assert specs.scale_meta == P("model", None)
    assert specs.tensor_scale == P()
    local = localize(pw, 2)
    assert isinstance(local, PackedRazerWeight) and local.shape == (32, 32)
    # only the static metadata is rewritten; leaves are untouched
    np.testing.assert_array_equal(np.asarray(local.codes), np.asarray(pw.codes))


def test_registry_stacked_plan_takes_k_axis():
    entry = registry.get_format("razer")
    pst = pack_stacked_weights(jnp.ones((4, 32, 16)))
    specs, localize = entry.shard_stacked_fn(pst, "data", "model")
    assert specs.codes == P("data", "model", None)
    assert specs.scale_meta == P("data", "model", None)
    assert specs.tensor_scale == P("data")
    local = localize(pst, 2, 2)
    assert local.shape == (2, 16, 16)
    # scan-stacked (L, E, rows, N) leaves: E on ep, wire rows on tp
    stacked = jax.tree_util.tree_map(lambda x: jnp.stack([x, x]), pst)
    sspecs, _ = entry.shard_stacked_fn(stacked, "data", "model")
    assert sspecs.codes == P(None, "data", "model", None)
    assert sspecs.tensor_scale == P(None, "data")


def test_stacked_plan_detects_k_axis_support():
    """``stacked_plan`` reports whether the format's plan accepted the k
    axis, so callers can degrade to ep-only for legacy two-arg plans."""
    entry = registry.get_format("razer")
    pst = pack_stacked_weights(jnp.ones((4, 32, 16)))
    (specs, _), k_applied = stacked_plan(entry, pst, "data", "model")
    assert k_applied and specs.codes == P("data", "model", None)
    # no K-shard requested: nothing can be dropped, so the flag stays True
    (specs, _), k_applied = stacked_plan(entry, pst, "data", None)
    assert k_applied and specs.codes == P("data", None, None)

    legacy = registry.FormatEntry(
        name="legacy", quantize=entry.quantize,
        shard_stacked_fn=lambda bank, axis: entry.shard_stacked_fn(bank, axis))
    (specs, _), k_applied = stacked_plan(legacy, pst, "data", "model")
    assert not k_applied and specs.codes == P("data", None, None)


def test_kshard_size_error_messages():
    assert kshard_size(64, 2) == 32
    assert kshard_size(64, 1) == 64
    with pytest.raises(ValueError, match="K=40 .* tp=2 .* divisible .* 2\\*16"):
        kshard_size(40, 2)
    with pytest.raises(ValueError, match="positive"):
        kshard_size(64, 0)


def test_local_shard_rejects_indivisible_k():
    with pytest.raises(ValueError, match="divisible"):
        _dense(k=48).local_shard(2)  # 48 % (2*16) != 0
    pst = pack_stacked_weights(jnp.ones((4, 48, 16)))
    with pytest.raises(ValueError, match="divisible"):
        pst.local_shard(2, k_shards=2)


# ---------------------------------------------------------------------------
# eligibility + strict validation on meshes
# ---------------------------------------------------------------------------
def test_packed_weight_specs_eligibility(tp_mesh):
    with sharding_ctx(tp_mesh) as ctx:
        # eligible: K=64 % (2*16) == 0 and N=32 % 2 == 0
        specs = packed_weight_specs(_dense(), ctx)
        assert specs.codes == P("model", None)
        # K not a whole number of quant blocks per shard: replicate...
        assert packed_weight_specs(_dense(k=48), ctx) is None
        # ...unless strict, which surfaces the divisibility rule
        with pytest.raises(ValueError, match="K=48 .* tp=2"):
            packed_weight_specs(_dense(k=48), ctx, strict=True)
        # N indivisible by tp: the scattered output tile would be ragged
        assert packed_weight_specs(_dense(n=31), ctx) is None
        # plain arrays are not packed containers
        assert packed_weight_specs(jnp.ones((64, 32)), ctx) is None
    # tp=1 mesh: nothing to split
    mesh1 = auto_mesh((1, 1), ("data", "model"))
    assert packed_weight_specs(_dense(), mesh1) is None


def test_stacked_bank_specs_k_shards_on_tp_mesh(tp_mesh):
    tp = tp_mesh.shape["model"]
    pst = pack_stacked_weights(jnp.ones((4, 64, 16)))
    specs = stacked_bank_specs(pst, tp_mesh)
    assert specs.codes[1] == "model"  # wire-row dim on tp
    # K=48 packs (3 whole blocks) but cannot split into whole blocks at
    # tp=2: the ep-only plan survives
    pst48 = pack_stacked_weights(jnp.ones((4, 48, 16)))
    specs48 = stacked_bank_specs(pst48, tp_mesh)
    assert specs48 is not None and specs48.codes[1] is None
    with pytest.raises(ValueError, match=f"K=48 .* tp={tp}"):
        stacked_bank_specs(pst48, tp_mesh, strict=True)


def test_serve_fails_fast_on_indivisible_tp():
    """--tp that cannot split d_model into whole quant blocks dies with the
    divisibility rule before any engine work, not a silent replicate."""
    from repro.launch import serve

    with pytest.raises(ValueError, match=(
            "cannot tensor-parallel-shard the packed K dimension K=64 over tp=3")):
        serve.main(["--arch", "dbrx_132b", "--reduced", "--packed", "--tp", "3",
                    "--requests", "1", "--max-new", "1"])


# ---------------------------------------------------------------------------
# placement: K/tp wire rows per device
# ---------------------------------------------------------------------------
def test_param_sharding_tree_k_shards_dense_packed(tp_mesh):
    tp = tp_mesh.shape["model"]
    k, n = 64, 32
    tree = {"mlp": {"w": _dense(k, n)}}
    shardings = param_sharding_tree(tree, tp_mesh, scan_stacked_prefixes=())
    assert shardings["mlp"]["w"].codes.spec == P("model", None)
    placed = jax.device_put(tree, shardings)["mlp"]["w"]
    assert placed.codes.addressable_shards[0].data.shape == (k // 2 // tp, n)
    assert placed.scale_meta.addressable_shards[0].data.shape == (k // 16 // tp, n)


def test_param_sharding_tree_k_shards_moe_bank(tp_mesh):
    ep, tp = tp_mesh.shape["data"], tp_mesh.shape["model"]
    cfg = _moe_cfg(n_experts=4 * ep)
    _, packed = _packed_moe_params(cfg)
    shardings = param_sharding_tree({"moe": packed}, tp_mesh, scan_stacked_prefixes=())
    placed = jax.device_put({"moe": packed}, shardings)["moe"]
    for role, kdim in (("gate", cfg.d_model), ("up", cfg.d_model), ("down", cfg.moe_d_ff)):
        bank = placed["experts"][role]
        shard = bank.codes.addressable_shards[0].data
        assert shard.shape[0] == cfg.n_experts // ep, role
        assert shard.shape[1] == kdim // 2 // tp, role


# ---------------------------------------------------------------------------
# sharded-vs-unsharded parity (the tentpole contract)
# ---------------------------------------------------------------------------
def test_dense_qlinear_tp_matches_unsharded(tp_mesh):
    k, n = 64, 32
    pw = _dense(k, n)
    lin = QuantizedLinear(w=pw)
    pol = QuantPolicy.packed()
    x = jnp.asarray(
        np.random.default_rng(1).standard_normal((4, k)), jnp.bfloat16)
    y0 = qlinear(x, lin, pol)
    with sharding_ctx(tp_mesh):
        y1 = qlinear(x, lin, pol)
        y_jit = jax.jit(lambda x_: qlinear(x_, lin, pol))(x)
    # the ONLY divergence allowed is one cross-device reduction reorder on
    # each output element (tp partial sums summed by psum_scatter)
    np.testing.assert_allclose(np.asarray(y1, np.float32), np.asarray(y0, np.float32),
                               rtol=0.05, atol=0.25)
    np.testing.assert_allclose(np.asarray(y_jit, np.float32), np.asarray(y0, np.float32),
                               rtol=0.05, atol=0.25)


def test_dense_qlinear_single_device_mesh_bit_exact():
    """A (1, 1) mesh's psum_scatter is the identity: IDENTICAL bits."""
    mesh = auto_mesh((1, 1), ("data", "model"))
    lin = QuantizedLinear(w=_dense())
    pol = QuantPolicy.packed()
    x = jnp.asarray(np.random.default_rng(2).standard_normal((4, 64)), jnp.bfloat16)
    y0 = qlinear(x, lin, pol)
    with sharding_ctx(mesh):
        y1 = qlinear(x, lin, pol)
    np.testing.assert_array_equal(np.asarray(y0), np.asarray(y1))


def test_moe_forward_eptp_matches_unsharded(tp_mesh):
    ep = tp_mesh.shape["data"]
    cfg = _moe_cfg(n_experts=4 * ep)
    _, packed = _packed_moe_params(cfg, seed=3)
    x = _tokens(cfg, seed=4)
    y_ref, aux_ref = moe_mod.moe_forward(x, packed, cfg, quant=QuantPolicy.packed())
    shardings = param_sharding_tree({"m": packed}, tp_mesh, scan_stacked_prefixes=())
    placed = jax.device_put({"m": packed}, shardings)["m"]
    with sharding_ctx(tp_mesh):
        y, aux = moe_mod.moe_forward(x, placed, cfg, quant=QuantPolicy.packed())
        y_jit = jax.jit(
            lambda x_, p_: moe_mod.moe_forward(x_, p_, cfg, quant=QuantPolicy.packed())[0]
        )(x, placed)
    np.testing.assert_allclose(np.asarray(y), np.asarray(y_ref), rtol=2e-3, atol=2e-3)
    np.testing.assert_allclose(np.asarray(y_jit), np.asarray(y_ref), rtol=2e-3, atol=2e-3)
    np.testing.assert_allclose(float(aux), float(aux_ref), rtol=1e-6)


def test_moe_forward_indivisible_k_degrades_to_ep_only(tp_mesh):
    """moe_d_ff=48 cannot K-shard at tp=2; the forward must still run (and
    match) with the expert trio split over ep only."""
    ep = tp_mesh.shape["data"]
    cfg = _moe_cfg(n_experts=4 * ep, moe_d_ff=48)
    _, packed = _packed_moe_params(cfg, seed=5)
    x = _tokens(cfg, seed=6)
    y_ref, _ = moe_mod.moe_forward(x, packed, cfg, quant=QuantPolicy.packed())
    shardings = param_sharding_tree({"m": packed}, tp_mesh, scan_stacked_prefixes=())
    placed = jax.device_put({"m": packed}, shardings)["m"]
    with sharding_ctx(tp_mesh):
        y, _ = moe_mod.moe_forward(x, placed, cfg, quant=QuantPolicy.packed())
    np.testing.assert_allclose(np.asarray(y), np.asarray(y_ref), rtol=2e-3, atol=2e-3)


def test_moe_forward_eptp_full_mesh(eptp_mesh):
    """The full (4, 2) ep x tp mesh: both axes active at once."""
    cfg = _moe_cfg(n_experts=8)
    _, packed = _packed_moe_params(cfg, seed=7)
    x = _tokens(cfg, seed=8)
    y_ref, aux_ref = moe_mod.moe_forward(x, packed, cfg, quant=QuantPolicy.packed())
    shardings = param_sharding_tree({"m": packed}, eptp_mesh, scan_stacked_prefixes=())
    placed = jax.device_put({"m": packed}, shardings)["m"]
    bank = placed["experts"]["gate"]
    assert bank.codes.addressable_shards[0].data.shape[:2] == (2, cfg.d_model // 2 // 2)
    with sharding_ctx(eptp_mesh):
        y, aux = moe_mod.moe_forward(x, placed, cfg, quant=QuantPolicy.packed())
    np.testing.assert_allclose(np.asarray(y), np.asarray(y_ref), rtol=2e-3, atol=2e-3)
    np.testing.assert_allclose(float(aux), float(aux_ref), rtol=1e-6)


# ---------------------------------------------------------------------------
# engine-level: packed dbrx served on a tp mesh
# ---------------------------------------------------------------------------
def test_engine_serves_packed_dbrx_on_tp_mesh(tp_mesh):
    """End-to-end: Engine(mesh=...) K-shards the packed banks (codes really
    hold K/2/tp rows per device) and generate/serve both produce the same
    greedy tokens as the meshless engine."""
    from repro.configs import get_config
    from repro.models import transformer as tf
    from repro.serving.engine import Engine, ServeConfig

    tp = tp_mesh.shape["model"]
    cfg = get_config("dbrx_132b").reduced()
    params = tf.init_params(jax.random.PRNGKey(0), cfg)
    scfg = ServeConfig(max_len=32, max_new_tokens=4, quant=QuantPolicy.packed())
    eng0 = Engine(params, cfg, scfg)
    eng = Engine(params, cfg, scfg, mesh=tp_mesh)

    def find_bank(tree):
        if isinstance(tree, PackedStackedTensor):
            return tree
        if isinstance(tree, dict):
            for v in tree.values():
                b = find_bank(v)
                if b is not None:
                    return b
        return None

    bank = find_bank(eng.params)
    assert bank is not None
    # scan-stacked (L, E, K/2, N) codes: the wire-row dim rides "model" and
    # each device holds 1/tp of the global wire rows
    assert "model" in jax.tree_util.tree_leaves(tuple(bank.codes.sharding.spec))
    assert (bank.codes.addressable_shards[0].data.shape[2]
            == bank.codes.shape[2] // tp)

    prompts = [[1, 2, 3, 4], [5, 6, 7, 8]]
    out0 = eng0.generate(prompts)
    out = eng.generate(prompts)
    assert out == out0
    rep = eng.serve(prompts)
    assert rep.outputs == out0
