"""Sharding resolver rules + quantized collectives."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from repro.launch.mesh import auto_mesh
from repro.parallel import sharding as sh
from repro.parallel.collectives import quantized_all_gather, wire_decode, wire_encode


class FakeCtx:
    """Stands in for sharding._Ctx: 16-way data and model axes."""

    model_axis = "model"
    data_axis = "data"
    batch_axes = ("data",)

    def axis_size(self, name):
        if isinstance(name, tuple):
            out = 1
            for a in name:
                out *= self.axis_size(a)
            return out
        return {"data": 16, "model": 16, None: 1}[name]


CTX = FakeCtx()


@pytest.mark.parametrize(
    "path,shape,expect",
    [
        ("embed", (152064, 3584), P("model", "data")),
        ("lm_head", (102400, 5120), P("model", "data")),
        ("layers_0/mixer/wq", (4096, 4096), P("data", "model")),
        ("layers_0/mlp/down", (19200, 7168), P("data", "model")),
        ("layers_0/moe/experts/gate", (160, 5120, 1536), P("data", None, "model")),
        ("layers_0/moe/experts/down", (160, 1536, 5120), P("data", "model", None)),
        ("layers_0/ln1", (4096,), P()),
        ("layers_0/mixer/q_norm", (128,), P()),
    ],
)
def test_param_rules(path, shape, expect):
    assert sh.param_spec(path, shape, CTX) == expect


def test_param_rules_divisibility_fallback():
    # 28 heads * 128 = 3584 divides 16, but a dim of 10 does not: replicated
    assert sh.param_spec("layers_0/mixer/wq", (3584, 3584), CTX) == P("data", "model")
    assert sh.param_spec("layers_0/mixer/wq", (10, 3584), CTX) == P(None, "model")
    assert sh.param_spec("layers_0/mixer/wq", (10, 10), CTX) == P(None, None)


def test_scan_stacked_skips_layer_dim():
    spec = sh.param_spec("layers_0/mixer/wq", (62, 7168, 7168), CTX, scan_stacked=True)
    assert spec == P(None, "data", "model")


def test_input_sharding_batch_fallbacks():
    mesh = auto_mesh((1, 1), ("data", "model"))
    s = sh.input_sharding(mesh, (16, 128))
    assert s.spec == P("data", None)
    s1 = sh.input_sharding(mesh, (1,))  # batch=1: not divisible by... size-1 axes divide
    assert s1.spec == P("data")


def test_shard_activation_noop_without_ctx():
    x = jnp.ones((4, 8))
    assert sh.shard_activation(x, "resid") is x


# ---------------------------------------------------------------------------
# quantized collectives
# ---------------------------------------------------------------------------
def test_wire_roundtrip_is_razer_accurate():
    rng = np.random.default_rng(0)
    w = jnp.asarray(rng.standard_normal((64, 256)).astype(np.float32) * 0.02)
    codes, meta, shape = wire_encode(w)
    # 4.5 bits/value on the wire
    bits = (codes.size + meta.size) * 8
    assert bits / w.size == pytest.approx(4.5)
    back = wire_decode(codes, meta, shape, dtype=jnp.float32)
    rel = float(jnp.linalg.norm(back - w) / jnp.linalg.norm(w))
    assert rel < 0.1
    from repro.kernels.ref import razer_act_qdq_ref

    ref = razer_act_qdq_ref(w.reshape(-1, 256)).reshape(w.shape)
    np.testing.assert_allclose(np.asarray(back), np.asarray(ref), atol=1e-6)


def test_quantized_all_gather_under_shard_map():
    mesh = auto_mesh((1,), ("fsdp",))
    rng = np.random.default_rng(1)
    w = jnp.asarray(rng.standard_normal((8, 32)).astype(np.float32))

    def f(shard):
        return quantized_all_gather(shard, "fsdp")

    out = jax.jit(
        jax.shard_map(f, mesh=mesh, in_specs=P("fsdp"), out_specs=P("fsdp"))
    )(w)
    back = wire_decode(*wire_encode(w), dtype=jnp.float32)
    np.testing.assert_allclose(np.asarray(out), np.asarray(back), atol=1e-6)
