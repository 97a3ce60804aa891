import os

os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=512")

"""Perf hillclimbing harness (EXPERIMENTS.md §Perf).

Re-runs a single dry-run cell under named variants (sharding rules, remat
policy, attention schedule, RaZeR-packed weights / quantized KV for serve
cells) and prints the before/after roofline terms -- the measure step of the
hypothesis -> change -> measure -> validate loop.

    PYTHONPATH=src python -m repro.launch.perf --arch deepseek_v2_236b \
        --shape train_4k --variants baseline,remat_dots,no_seq_parallel
"""
import argparse
import contextlib
import gc
import json
import time
from typing import Dict

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs import SHAPES, get_config
from repro.launch import costmodel
from repro.launch.dryrun import (
    batch_sharding_tree,
    build_lowered,
    cache_sharding_tree,
    collective_bytes,
    corrected_costs,
    make_mesh_512,
)
from repro.models import attention as attn_mod
from repro.models import transformer as tf
from repro.models.inputs import input_specs
from repro.parallel import sharding as shard_mod
from repro.parallel.sharding import param_sharding_tree, sharding_ctx


# ---------------------------------------------------------------------------
# variant context managers
# ---------------------------------------------------------------------------
@contextlib.contextmanager
def _ctx_var(var, value):
    tok = var.set(value)
    try:
        yield
    finally:
        var.reset(tok)


@contextlib.contextmanager
def _act_rule(kind, rule):
    shard_mod.set_activation_rule(kind, rule)
    try:
        yield
    finally:
        shard_mod.set_activation_rule(kind, None)


VARIANTS = {
    "baseline": lambda: contextlib.nullcontext(),
    # distribution variants (train)
    "no_seq_parallel": lambda: _act_rule("resid", ("batch", None, None)),
    "logits_vocab_sharded": lambda: _act_rule("logits", ("batch", None, "model")),
    "remat_dots": lambda: _ctx_var(tf.REMAT_POLICY, "dots"),
    "no_remat": lambda: _ctx_var(tf.REMAT_POLICY, "none"),
    "skip_masked_chunks": lambda: _ctx_var(attn_mod.SKIP_MASKED_CHUNKS, True),
    "moe_buf_replicated_d": lambda: _act_rule("moe_buf", ("batch", None, None)),
    # dispatch buffer (G,E,cap,d): E on model => EP-style a2a instead of
    # all-gathering the d dim against the expert-weight contraction
    "moe_buf_ep": lambda: _act_rule("moe_buf", ("batch", "model", None)),
    # statically-banded causal attention (tq(tq+1)/2 pair GEMMs; O(w*S) for
    # sliding-window archs)
    "triangular_attention": lambda: _ctx_var(attn_mod.ATTN_SCHEDULE, "triangular"),
}


# ---------------------------------------------------------------------------
# serve-cell weight/KV format variants (the paper's deployment artifacts)
# ---------------------------------------------------------------------------
def build_lowered_serve_variant(cfg, shape, mesh, *, packed: bool, kv_quant: bool,
                                donate: bool = False):
    """decode-step lowering with RaZeR-packed weights and/or packed KV cache."""
    from repro.core.policy import QuantPolicy
    from repro.serving.engine import pack_model_weights
    from repro.serving.kvcache import quantized_gqa_cache_init

    assert shape["kind"] == "decode"
    specs = input_specs(cfg, shape)
    params_shape = jax.eval_shape(lambda: tf.init_params(jax.random.PRNGKey(0), cfg))
    params_shape = jax.tree_util.tree_map(
        lambda s: jax.ShapeDtypeStruct(s.shape, jnp.bfloat16), params_shape
    )
    if packed:
        qc = QuantPolicy.packed()
        params_shape = jax.eval_shape(lambda p: pack_model_weights(p, cfg, qc), params_shape)
    p_shard = param_sharding_tree(params_shape, mesh)

    cache_shapes = specs["caches"]
    if kv_quant:
        b = shape["global_batch"]
        new = []
        for (ltype, count), c in zip(tf.layer_groups(cfg), cache_shapes):
            if isinstance(c, dict) and "k" in c and len(c["k"].shape) == 5:
                one = jax.eval_shape(lambda: quantized_gqa_cache_init(cfg, b, shape["seq_len"]))
                new.append(jax.tree_util.tree_map(
                    lambda s: jax.ShapeDtypeStruct((count,) + s.shape, s.dtype), one))
            else:
                new.append(c)
        cache_shapes = new
    c_shard = [cache_sharding_tree(c, mesh) for c in cache_shapes]

    def serve_step(params, token, caches, cur_len):
        with sharding_ctx(mesh):
            return tf.decode_step(params, token, caches, cur_len, cfg)

    from repro.parallel.sharding import input_sharding

    jitted = jax.jit(
        serve_step,
        in_shardings=(p_shard, input_sharding(mesh, specs["token"].shape), c_shard,
                      NamedSharding(mesh, P())),
        out_shardings=(None, c_shard),
        donate_argnums=(2,) if donate else (),
    )
    return jitted.lower(params_shape, specs["token"], cache_shapes, specs["cur_len"])


SERVE_VARIANTS = {
    "serve_baseline": dict(packed=False, kv_quant=False, donate=False),
    "donate_caches": dict(packed=False, kv_quant=False, donate=True),
    "packed_weights": dict(packed=True, kv_quant=False, donate=True),
    "packed_weights+kv_quant": dict(packed=True, kv_quant=True, donate=True),
    "kv_quant": dict(packed=False, kv_quant=True, donate=True),
}


def measure(cfg, shape, mesh, build_fn) -> Dict:
    t0 = time.time()
    lowered = build_fn()
    compiled = lowered.compile()
    rec = {"compile_s": round(time.time() - t0, 1)}
    ma = compiled.memory_analysis()
    rec["temp_gb"] = round(ma.temp_size_in_bytes / 1e9, 2)
    rec["args_gb"] = round(ma.argument_size_in_bytes / 1e9, 3)
    ca = compiled.cost_analysis()
    rec["flops_raw"] = float(ca.get("flops", 0))
    rec["bytes_raw"] = float(ca.get("bytes accessed", 0))
    rec["coll_raw"] = collective_bytes(compiled.as_text()).get("total", 0.0)
    del compiled, lowered
    jax.clear_caches()
    gc.collect()
    return rec


# config-level variants: applied via dataclasses.replace before lowering
import dataclasses as _dc

CFG_VARIANTS = {
    "capfac_1.0": lambda c: _dc.replace(c, capacity_factor=1.0),
    "capfac_2.0": lambda c: _dc.replace(c, capacity_factor=2.0),
}


def run_variant(arch, shape_name, variant) -> Dict:
    cfg = get_config(arch)
    for part in variant.split("+"):
        if part in CFG_VARIANTS:
            cfg = CFG_VARIANTS[part](cfg)
    variant_ctx_parts = [p for p in variant.split("+") if p not in CFG_VARIANTS]
    shape = SHAPES[shape_name]
    mesh = make_mesh_512(False)
    if variant in SERVE_VARIANTS:
        flags = SERVE_VARIANTS[variant]
        bf = lambda c, s, m: build_lowered_serve_variant(c, s, m, **flags)
        rec = measure(cfg, shape, mesh, lambda: bf(cfg, shape, mesh))
        cc = corrected_costs(cfg, shape, mesh, build_fn=bf)
        rec["corrected"] = cc
        rec["roofline"] = costmodel.roofline_terms(cc["flops"], cc["bytes"], cc["coll_bytes"])
    else:
        parts = variant_ctx_parts or ["baseline"]  # combos: "a+b"
        with contextlib.ExitStack() as stack:
            for part in parts:
                stack.enter_context(VARIANTS[part]())
            rec = measure(cfg, shape, mesh, lambda: build_lowered(cfg, shape, mesh))
            cc = corrected_costs(cfg, shape, mesh)
            rec["corrected"] = cc
            rec["roofline"] = costmodel.roofline_terms(cc["flops"], cc["bytes"], cc["coll_bytes"])
    rec.update(arch=arch, shape=shape_name, variant=variant)
    return rec


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--shape", required=True)
    ap.add_argument("--variants", default="baseline")
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)
    out = []
    for v in args.variants.split(","):
        print(f"=== {args.arch}/{args.shape}/{v} ===", flush=True)
        rec = run_variant(args.arch, args.shape, v)
        print(json.dumps({k: rec[k] for k in rec if k not in ("corrected",)}, default=str), flush=True)
        out.append(rec)
    if args.out:
        with open(args.out, "w") as f:
            json.dump(out, f, indent=1)


if __name__ == "__main__":
    main()
