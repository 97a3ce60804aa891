"""The general RaZeR path as an oracle for the KV wire bytes:
``KV_SPEC.quantize`` -> ``fp4_encode`` -> ``pack_scale_meta``, the composition
``serving/kvcache.kv_quantize`` must reproduce."""
import jax.numpy as jnp

from repro.core.formats import FP4_NEG_ZERO_CODE, fp4_encode
from repro.core.packing import pack_fp4_codes, pack_scale_meta
from repro.serving.kvcache import KV_SPEC


def kv_oracle(x):
    """x (..., hd) -> (codes (..., hd//2), meta (..., hd//16)) uint8."""
    bq = KV_SPEC.quantize(x.astype(jnp.float32), axis=-1, tensor_scale=jnp.asarray(1.0, jnp.float32))
    uses_sv = (bq.sv_index >= 0)[..., None] & (bq.q == bq.sv[..., None])
    codes = jnp.where(uses_sv, jnp.uint8(FP4_NEG_ZERO_CODE), fp4_encode(bq.q))
    codes = pack_fp4_codes(codes.reshape(x.shape))
    meta = pack_scale_meta(bq.block_scale, bq.sv_index, weight=False, scale_fmt="e4m3")
    return codes, meta.astype(jnp.uint8)
