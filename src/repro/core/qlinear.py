"""Quantized linear layer -- the integration point between the RaZeR numerics
and the model zoo / serving engine.

Modes (per TensorSpec):
  * ``bf16``      -- plain matmul (training / FP16 baseline rows).
  * ``fakequant`` -- quantize-dequantize W (offline semantics) and optionally A
                     (dynamic, Eq. 6 with the activation SV pair) then matmul in
                     bf16.  Bit-exact simulation of RaZeR arithmetic; used for
                     every accuracy experiment.  Optional straight-through
                     estimator for QAT (beyond-paper).
  * ``packed``    -- W stored in the format's wire container; forward runs the
                     registered matmul kernel (Pallas on TPU, jnp reference on
                     CPU).  Used by the serving engine; the Marlin analogue.

Every entry point accepts either the new ``QuantPolicy`` (core.policy) or the
legacy flat ``QuantConfig`` below, which survives as a thin back-compat
constructor: ``QuantConfig(...).to_policy()`` is called internally via
``as_policy`` so existing call sites keep working bit-exactly.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Mapping, Optional, Tuple, Union

import jax
import jax.numpy as jnp

from . import registry
from .policy import QuantPolicy, TensorSpec, as_policy
from .razer import ACT_SPECIAL_VALUES, WEIGHT_SPECIAL_VALUES

__all__ = ["QuantConfig", "QuantizedLinear", "qdq_weight", "qdq_activation", "qlinear"]

QuantLike = Union[QuantPolicy, "QuantConfig", None]


@dataclass(frozen=True)
class QuantConfig:
    """Legacy flat quantization config (hashable / static-arg friendly).

    Kept as a convenience constructor over the policy API; new code should
    build a ``QuantPolicy`` directly (per-layer rules, pluggable formats)."""

    mode: str = "bf16"  # bf16 | fakequant | packed
    weight_format: str = "razer"
    act_format: Optional[str] = None  # None = weight-only quantization
    weight_svs: Tuple[float, ...] = WEIGHT_SPECIAL_VALUES
    act_svs: Tuple[float, ...] = ACT_SPECIAL_VALUES
    block_size: int = 16
    weight_scale_fmt: str = "e3m3"  # §4.1: E3M3 for weights
    act_scale_fmt: str = "e4m3"  # §4.1: activations keep E4M3
    kv_format: Optional[str] = None  # e.g. 'razer' to quantize the KV cache
    ste: bool = False  # straight-through estimator (QAT, beyond-paper)

    def to_policy(self) -> QuantPolicy:
        """The equivalent QuantPolicy (with the default dense per-layer rules)."""
        weight = TensorSpec(
            format=self.weight_format,
            mode=self.mode,
            block_size=self.block_size,
            scale_fmt=self.weight_scale_fmt,
            special_values=self.weight_svs,
            ste=self.ste,
        )
        act = None
        if self.act_format is not None:
            act = TensorSpec(
                format=self.act_format,
                mode="fakequant",
                block_size=self.block_size,
                scale_fmt=self.act_scale_fmt,
                special_values=self.act_svs,
                ste=self.ste,
            )
        kv = TensorSpec.kv(self.kv_format) if self.kv_format is not None else None
        return QuantPolicy(weight=weight, act=act, kv=kv)

    @property
    def sv_magnitudes(self) -> Tuple[float, float]:
        """Wire-format pair magnitudes; 1 pair duplicates, >2 is an error."""
        return self.to_policy().weight.sv_magnitudes


# ---------------------------------------------------------------------------
# deprecated registry views (old private API, kept for external callers)
# ---------------------------------------------------------------------------
class _RegistryFormats(Mapping):
    """dict-like view of the format registry's quantize fns (old ``_FORMATS``)."""

    def __getitem__(self, name):
        return registry.get_format(name).quantize

    def __iter__(self):
        return iter(registry.format_names())

    def __len__(self):
        return len(registry.format_names())


_FORMATS = _RegistryFormats()


def _format_kwargs(cfg: QuantLike, weight: bool) -> dict:
    """Deprecated: quantize-fn kwargs for a legacy config's weight/act role."""
    pol = as_policy(cfg)
    spec = pol.weight if weight else pol.act
    if spec is None:
        raise ValueError("config has no activation spec (act_format=None)")
    return registry.spec_kwargs(spec.entry, spec)


# ---------------------------------------------------------------------------
# fake-quant entry points
# ---------------------------------------------------------------------------
def qdq_weight(w, cfg: QuantLike):
    """Fake-quantize a (d_in, d_out) weight along the reduction dim (axis 0)."""
    return as_policy(cfg).weight.qdq(w, axis=0)


def qdq_activation(x, cfg: QuantLike):
    """Dynamically fake-quantize activations along the feature dim (axis -1).

    Routes through the format's registered ``act_kernel`` (the fused Pallas
    dynamic-quant kernel on TPU, its jnp oracle on CPU) via
    ``kernels.ops.quantized_act_qdq``; formats without an act kernel fall back
    to the spec's qdq numerics.  Registered act kernels use the dynamic
    per-block scale with NO tensor scale (the deployable form -- a per-tensor
    absmax would need a second pass over the activation), matching the fused
    kernel and the KV-cache wire format."""
    pol = as_policy(cfg)
    spec = pol.act
    if spec is None:
        raise ValueError(
            "qdq_activation called but the policy has no activation spec "
            "(act_format=None means weight-only quantization)"
        )
    # lazy: repro.kernels imports repro.core, so core reaches ops at call time
    from repro.kernels.ops import quantized_act_qdq

    xq = quantized_act_qdq(x, spec)
    if spec.ste:
        xq = x + jax.lax.stop_gradient(xq - x)
    return xq


# ---------------------------------------------------------------------------
# the linear layer
# ---------------------------------------------------------------------------
@jax.tree_util.register_pytree_node_class
@dataclass
class QuantizedLinear:
    """A linear layer's parameter bundle under a quantization policy.

    Holds either a dense weight (bf16/fakequant modes) or a packed wire-format
    container (packed mode).  Pytree-registered so it can live inside model
    param trees, be sharded by pjit and stand in as ShapeDtypeStructs for the
    dry-run.
    """

    w: object  # jnp.ndarray | packed container (registry packed_type)
    b: Optional[jnp.ndarray] = None

    def tree_flatten(self):
        return (self.w, self.b), None

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(*children)

    @staticmethod
    def create(w, cfg: QuantLike, b=None) -> "QuantizedLinear":
        spec = as_policy(cfg).weight
        if spec.quantizes and spec.mode == "packed":
            return QuantizedLinear(w=spec.pack(jnp.asarray(w, jnp.float32)), b=b)
        return QuantizedLinear(w=w, b=b)


def _tp_packed_matmul(x, w, entry):
    """K-sharded packed matmul under the active mesh, or None when ineligible.

    When a sharding context with a tp (model) axis is live and the format
    published a K-shard plan (``shard_packed_fn``) that the weight's shape
    satisfies, run the matmul inside ``shard_map``: each device localizes its
    K/tp wire-row shard (``plan.localize`` rewrites the container's static
    shape), launches the ordinary kernel on a per-shard grid over local K,
    and the partial-sum exchange is fused into the epilogue as one last-dim
    ``psum_scatter`` -- the output leaves the boundary N/tp-sharded on the
    model axis, which is exactly the "ffn" activation layout
    (docs/parallelism.md).  Returns None to mean "run the unsharded kernel".
    """
    from repro.parallel.sharding import get_ctx, packed_weight_specs

    ctx = get_ctx()
    if ctx is None or ctx.mesh is None:
        return None
    specs = packed_weight_specs(w, ctx)
    if specs is None:
        return None
    axis = ctx.model_axis
    tp = ctx.axis_size(axis)
    _, localize = entry.shard_packed_fn(w, axis)

    from jax.sharding import PartitionSpec as P

    from repro.kernels.ops import reduce_scatter_epilogue

    io_spec = P(*([None] * (x.ndim - 1) + [axis]))  # K-sharded in, N/tp out

    def body(x_l, w_l):
        y = entry.matmul_kernel(x_l, localize(w_l, tp))
        return reduce_scatter_epilogue(y, axis)

    return jax.shard_map(
        body, mesh=ctx.mesh, in_specs=(io_spec, specs), out_specs=io_spec,
        check_vma=False,
    )(x, w)


def qlinear(x, lin, cfg: QuantLike):
    """y = quant(x) @ quant(W) + b under the configured policy.

    Packed containers dispatch to their format's registered matmul kernel by
    container type -- no string keys, no core edits for new formats.  Under
    an active mesh with a tp (model) axis, eligible packed weights run
    K-sharded with the reduce-scatter fused into the kernel epilogue
    (``_tp_packed_matmul``).  A dense weight under a ``packed`` spec runs
    DENSE: in packed mode the per-layer rules decided at pack time which
    weights stay high precision (embed, kv_b, first-layer exceptions, ...),
    and honoring that here keeps e.g. the absorbed MLA decode -- which
    contracts the dense kv_b directly -- numerically consistent with
    prefill.
    """
    w, b = (lin.w, lin.b) if isinstance(lin, QuantizedLinear) else (lin, None)
    entry = registry.packed_entry(w)
    if entry is not None:
        if entry.matmul_kernel is None:
            raise TypeError(f"format {entry.name!r} has a packed container but no matmul_kernel")
        pol = as_policy(cfg)
        if pol.act is not None:
            # W+A packed serving: dynamic activation quant ahead of the wire-
            # format matmul, through the format's registered fused act kernel.
            # Runs BEFORE the tp shard_map: qdq blocks are 16 elements along
            # K and K/tp is a 16-multiple, so no block straddles a shard.
            x = qdq_activation(x, pol)
        y = _tp_packed_matmul(x, w, entry)
        if y is None:
            y = entry.matmul_kernel(x, w)
    else:
        pol = as_policy(cfg)
        spec = pol.weight
        if spec.quantizes and spec.mode == "fakequant":
            w = spec.qdq(w, axis=0)
            if pol.act is not None:
                x = qdq_activation(x, pol)
        y = x @ w.astype(x.dtype)
    if b is not None:
        y = y + b.astype(y.dtype)
    return y
