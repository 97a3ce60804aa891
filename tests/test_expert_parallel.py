"""Expert-parallel packed MoE serving (docs/parallelism.md).

The grouped RaZeR kernel is a Pallas custom call XLA SPMD cannot partition,
so the repo draws the partition boundary itself: ``param_sharding_tree``
places packed ``PackedStackedTensor`` banks E/ep rows per device (via the
registry's ``shard_stacked_fn`` plan) and ``moe_forward`` shard_maps the
grouped kernel over the ep (data) axis with the dense path's all-to-all
dispatch.  These tests pin the three contracts: each device really holds
only its E/ep expert rows (sharding specs), the sharded forward matches the
single-device packed path and the fakequant oracle, and indivisible E fails
loudly where sharding is demanded / falls back where it is optional.

Multi-device cases use the ``ep_mesh`` conftest fixture (8 host CPU devices,
``XLA_FLAGS=--xla_force_host_platform_device_count=8``; skipped otherwise).
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from repro.core import registry
from repro.core.packing import PackedStackedTensor, pack_stacked_weights
from repro.core.policy import QuantPolicy
from repro.launch.mesh import auto_mesh
from repro.models import moe as moe_mod
from repro.models.config import ArchConfig
from repro.parallel.sharding import (
    expert_shard_size,
    param_sharding_tree,
    sharding_ctx,
    stacked_bank_specs,
)
from repro.serving.engine import pack_model_weights


def _moe_cfg(**kw):
    base = dict(
        name="t", family="moe", num_layers=1, d_model=32, num_heads=2, num_kv_heads=2,
        d_ff=64, vocab_size=64, moe=True, n_experts=16, topk=2, moe_d_ff=32,
        capacity_factor=8.0,
    )
    base.update(kw)
    return ArchConfig(**base)


def _packed_moe_params(cfg, seed=0):
    p = moe_mod.moe_init(jax.random.PRNGKey(seed), cfg)
    packed = pack_model_weights({"layers_0": {"moe": p}}, cfg, QuantPolicy.packed())
    return p, packed["layers_0"]["moe"]


def _tokens(cfg, b=3, s=8, seed=1):
    # b*s = 24 tokens: gcd(24, 16) == gcd(24, 8) == 8, so the dispatch group
    # count (and therefore capacity) is identical with and without the 8-way
    # mesh context -- the unsharded run is a like-for-like oracle.
    return jnp.asarray(
        np.random.default_rng(seed).standard_normal((b, s, cfg.d_model)), jnp.float32)


# ---------------------------------------------------------------------------
# registry plan + divisibility validator (run on any device count)
# ---------------------------------------------------------------------------
def test_registry_shard_stacked_plan():
    entry = registry.get_format("razer")
    assert entry.shard_stacked_fn is not None
    pst = pack_stacked_weights(jnp.ones((8, 32, 16)))
    specs, localize = entry.shard_stacked_fn(pst, "data")
    assert specs.codes == P("data", None, None)
    assert specs.scale_meta == P("data", None, None)
    assert specs.tensor_scale == P("data")
    local = localize(pst, 4)
    assert isinstance(local, PackedStackedTensor) and local.shape == (2, 32, 16)
    # leaves untouched: only the static metadata is rewritten
    np.testing.assert_array_equal(np.asarray(local.codes), np.asarray(pst.codes))


def test_registry_plan_scan_stacked_bank():
    """Per-scan-layer restacked containers (L, E, ...) shard E, not L."""
    pst = pack_stacked_weights(jnp.ones((4, 32, 16)))
    stacked = jax.tree_util.tree_map(lambda x: jnp.stack([x, x]), pst)
    specs, _ = registry.get_format("razer").shard_stacked_fn(stacked, "data")
    assert specs.codes == P(None, "data", None, None)
    assert specs.tensor_scale == P(None, "data")


def test_expert_shard_size_error_message():
    assert expert_shard_size(16, 8) == 2
    with pytest.raises(ValueError, match="E=6 .* ep=8 .* divisible"):
        expert_shard_size(6, 8)
    with pytest.raises(ValueError, match="positive"):
        expert_shard_size(16, 0)


def test_local_shard_rejects_indivisible():
    pst = pack_stacked_weights(jnp.ones((6, 32, 16)))
    with pytest.raises(ValueError, match="divisible"):
        pst.local_shard(4)


def test_stacked_bank_specs_fallbacks():
    mesh = auto_mesh((1, 1), ("data", "model"))
    pst = pack_stacked_weights(jnp.ones((6, 32, 16)))
    # divisible by ep=1: plan exists (trivially replication-equivalent)
    assert stacked_bank_specs(pst, mesh) is not None
    # a plain array is not a registered stacked container
    assert stacked_bank_specs(jnp.ones((6, 32, 16)), mesh) is None


def test_stacked_bank_specs_strict_raises(ep_mesh):
    """E=6 over the 8-way ep mesh: non-strict returns None (replicate),
    strict surfaces the expert_shard_size error message."""
    pst = pack_stacked_weights(jnp.ones((6, 32, 16)))
    assert stacked_bank_specs(pst, ep_mesh) is None
    with pytest.raises(ValueError, match="E=6 .* ep=8"):
        stacked_bank_specs(pst, ep_mesh, strict=True)


# ---------------------------------------------------------------------------
# parameter placement: E/ep rows per device
# ---------------------------------------------------------------------------
def test_param_sharding_tree_splits_packed_bank(ep_mesh):
    cfg = _moe_cfg(n_experts=16)
    _, packed = _packed_moe_params(cfg)
    tree = {"moe": packed}
    shardings = param_sharding_tree(tree, ep_mesh, scan_stacked_prefixes=())
    for role in ("gate", "up", "down"):
        bank = shardings["moe"]["experts"][role]
        assert bank.codes.spec == P("data", None, None), role
        assert bank.scale_meta.spec == P("data", None, None), role
        assert bank.tensor_scale.spec == P("data"), role
    placed = jax.device_put(tree, shardings)["moe"]
    bank = placed["experts"]["gate"]
    # each device holds exactly E/ep = 2 expert rows of every leaf
    assert len(bank.codes.addressable_shards) == 8
    for leaf in (bank.codes, bank.scale_meta, bank.tensor_scale):
        assert leaf.addressable_shards[0].data.shape[0] == cfg.n_experts // 8
    # the router (dense, policy-dense rule) is untouched by the bank plan
    assert shardings["moe"]["router"].spec in (P("data", "model"), P(None, "model"),
                                               P("data", None), P(None, None), P())


def test_param_sharding_tree_replicates_indivisible_bank(ep_mesh):
    cfg = _moe_cfg(n_experts=6)
    _, packed = _packed_moe_params(cfg)
    shardings = param_sharding_tree({"moe": packed}, ep_mesh, scan_stacked_prefixes=())
    bank = shardings["moe"]["experts"]["gate"]
    assert bank.codes.spec == P()
    assert bank.tensor_scale.spec == P()


# ---------------------------------------------------------------------------
# sharded-vs-unsharded parity (the tentpole contract)
# ---------------------------------------------------------------------------
def test_sharded_forward_matches_single_device_and_fakequant(ep_mesh):
    cfg = _moe_cfg(n_experts=16)
    p, packed = _packed_moe_params(cfg)
    x = _tokens(cfg)

    y_ref, aux_ref = moe_mod.moe_forward(x, packed, cfg, quant=QuantPolicy.packed())
    y_fake, aux_fake = moe_mod.moe_forward(x, p, cfg, quant=QuantPolicy.fakequant())

    shardings = param_sharding_tree({"moe": packed}, ep_mesh, scan_stacked_prefixes=())
    placed = jax.device_put({"moe": packed}, shardings)["moe"]

    with sharding_ctx(ep_mesh):
        y_sh, aux_sh = moe_mod.moe_forward(x, placed, cfg, quant=QuantPolicy.packed())
        f = jax.jit(lambda x, p_: moe_mod.moe_forward(x, p_, cfg, quant=QuantPolicy.packed())[0])
        y_jit = f(x, placed)

    # numerically identical to the single-device packed path (f32 rounding)
    np.testing.assert_allclose(np.asarray(y_sh), np.asarray(y_ref), rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(np.asarray(y_jit), np.asarray(y_ref), rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(float(aux_sh), float(aux_ref), rtol=1e-6)
    # and within the wire-format envelope of the fakequant oracle
    np.testing.assert_allclose(np.asarray(y_sh), np.asarray(y_fake), rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(float(aux_sh), float(aux_fake), rtol=1e-6)


def test_sharded_forward_output_stays_group_sharded(ep_mesh):
    """The forward's output exists; intermediate shard_map output is g-sharded
    (the combine runs on the same token shard it dispatched from)."""
    cfg = _moe_cfg(n_experts=8)
    _, packed = _packed_moe_params(cfg, seed=2)
    x = _tokens(cfg, seed=3)
    shardings = param_sharding_tree({"m": packed}, ep_mesh, scan_stacked_prefixes=())
    placed = jax.device_put({"m": packed}, shardings)["m"]
    with sharding_ctx(ep_mesh):
        y, aux = moe_mod.moe_forward(x, placed, cfg, quant=QuantPolicy.packed())
    assert y.shape == x.shape and np.isfinite(float(aux))


def test_sharded_decode_shape_keeps_banks_sharded(ep_mesh):
    """Decode regime: t=2 tokens < ep=8, so the group dim cannot all-to-all.
    The replicated-token strategy must run (banks stay E/ep-sharded, one
    activation all-gather) and match the unsharded packed launch."""
    cfg = _moe_cfg(n_experts=16)
    _, packed = _packed_moe_params(cfg, seed=11)
    x = _tokens(cfg, b=2, s=1, seed=12)  # g = gcd(2, ·) = 2 either way
    y_ref, aux_ref = moe_mod.moe_forward(x, packed, cfg, quant=QuantPolicy.packed())
    shardings = param_sharding_tree({"m": packed}, ep_mesh, scan_stacked_prefixes=())
    placed = jax.device_put({"m": packed}, shardings)["m"]
    with sharding_ctx(ep_mesh):
        y, aux = moe_mod.moe_forward(x, placed, cfg, quant=QuantPolicy.packed())
        y_jit = jax.jit(
            lambda x, p_: moe_mod.moe_forward(x, p_, cfg, quant=QuantPolicy.packed())[0]
        )(x, placed)
    np.testing.assert_allclose(np.asarray(y), np.asarray(y_ref), rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(np.asarray(y_jit), np.asarray(y_ref), rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(float(aux), float(aux_ref), rtol=1e-6)


def test_single_device_mesh_reduces_bit_exactly():
    """A (1, 1) mesh must take the existing unsharded launch: bit-exact."""
    mesh = auto_mesh((1, 1), ("data", "model"))
    cfg = _moe_cfg(n_experts=4)
    _, packed = _packed_moe_params(cfg, seed=4)
    # 25 tokens: gcd(25, want) == 1 for every want, so group count matches
    # between the mesh and no-mesh runs and outputs must be IDENTICAL bits
    x = _tokens(cfg, b=5, s=5, seed=5)
    y_ref, aux_ref = moe_mod.moe_forward(x, packed, cfg, quant=QuantPolicy.packed())
    with sharding_ctx(mesh):
        y_mesh, aux_mesh = moe_mod.moe_forward(x, packed, cfg, quant=QuantPolicy.packed())
    np.testing.assert_array_equal(np.asarray(y_mesh), np.asarray(y_ref))
    np.testing.assert_array_equal(float(aux_mesh), float(aux_ref))


def test_indivisible_e_falls_back_replicated(ep_mesh):
    """E=6 over ep=8 cannot shard: the bank replicates and the forward still
    matches the unsharded packed path (graceful degradation, not a crash)."""
    cfg = _moe_cfg(n_experts=6)
    _, packed = _packed_moe_params(cfg, seed=6)
    x = _tokens(cfg, seed=7)
    y_ref, _ = moe_mod.moe_forward(x, packed, cfg, quant=QuantPolicy.packed())
    shardings = param_sharding_tree({"m": packed}, ep_mesh, scan_stacked_prefixes=())
    placed = jax.device_put({"m": packed}, shardings)["m"]
    with sharding_ctx(ep_mesh):
        y, _ = moe_mod.moe_forward(x, placed, cfg, quant=QuantPolicy.packed())
    np.testing.assert_allclose(np.asarray(y), np.asarray(y_ref), rtol=1e-5, atol=1e-5)


# ---------------------------------------------------------------------------
# collectives: dispatch/combine round-trip + divisibility error
# ---------------------------------------------------------------------------
def test_dispatch_combine_roundtrip(ep_mesh):
    from repro.parallel.collectives import (
        combine_from_expert_shards,
        dispatch_to_expert_shards,
    )

    g, e, cap, d = 8, 16, 4, 8
    buf = jnp.asarray(np.random.default_rng(8).standard_normal((g, e, cap, d)), jnp.float32)

    def roundtrip(b):
        return combine_from_expert_shards(dispatch_to_expert_shards(b, "data"), "data")

    out = jax.jit(jax.shard_map(
        roundtrip, mesh=ep_mesh, in_specs=P("data"), out_specs=P("data"),
        check_vma=False,
    ))(buf)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(buf))


def test_dispatch_rejects_indivisible_e(ep_mesh):
    from repro.parallel.collectives import dispatch_to_expert_shards

    buf = jnp.zeros((8, 6, 4, 8), jnp.float32)  # E=6 over ep=8
    with pytest.raises(ValueError, match="divisible"):
        jax.jit(jax.shard_map(
            lambda b: dispatch_to_expert_shards(b, "data"),
            mesh=ep_mesh, in_specs=P("data"), out_specs=P("data"),
            check_vma=False,
        ))(buf)


# ---------------------------------------------------------------------------
# engine-level smoke: a whole MoE model served on a mesh
# ---------------------------------------------------------------------------
def test_engine_serves_packed_moe_on_mesh(ep_mesh):
    """End-to-end: Engine(mesh=...) packs, places E/ep bank rows per device,
    and generates -- the full serving path through scan-stacked layers."""
    if len(jax.devices()) < 8:
        pytest.skip("needs 8 host devices")
    from repro.configs import get_config
    from repro.models import transformer as tf
    from repro.serving.engine import Engine, ServeConfig

    mesh = auto_mesh((4, 1), ("data", "model"))  # ep=4 divides reduced E=4
    cfg = get_config("dbrx_132b").reduced()
    params = tf.init_params(jax.random.PRNGKey(0), cfg)
    eng = Engine(params, cfg, ServeConfig(max_len=32, max_new_tokens=4,
                                          quant=QuantPolicy.packed()), mesh=mesh)
    # the packed banks really are expert-sharded on the placed param tree
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
    # scan-stacked (L, E, ...) leaves: the expert dim (dim 1) is on "data"
    assert "data" in tuple(bank.codes.sharding.spec)
    assert bank.codes.addressable_shards[0].data.shape[1] == cfg.n_experts // 4
    out = eng.generate([[1, 2, 3, 4], [5, 6, 7, 8]])
    assert all(len(o) == 8 for o in out)
