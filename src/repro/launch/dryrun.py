import os

os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run (the brief's deliverable (e)).

For every (architecture x input shape) cell and both production meshes
(16x16 single pod, 2x16x16 two pods), lower + compile the real jitted step
(train_step / serve_prefill / serve_step) with ShapeDtypeStruct inputs -- no
allocation -- and record:

  * memory_analysis (per-device argument/output/temp bytes: the "fits" proof)
  * cost_analysis flops/bytes
  * collective bytes parsed from the post-SPMD HLO
  * an exact scan-corrected costing via small UNROLLED layer-count variants
    (XLA cost_analysis counts while bodies once; see models.transformer._scan)
  * the three roofline terms + dominant bottleneck (single-pod mesh)

Usage:
    python -m repro.launch.dryrun --arch llama3_2_3b --shape train_4k
    python -m repro.launch.dryrun --all --out results/dryrun.json
"""
import argparse
import dataclasses
import gc
import json
import re
import sys
import time
import traceback
from typing import Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.configs import ARCH_IDS, SHAPES, cells, get_config
from repro.launch import costmodel
from repro.models import transformer as tf
from repro.models.config import ArchConfig
from repro.models.inputs import input_specs
from repro.parallel.sharding import (
    input_sharding,
    param_sharding_tree,
    sharding_ctx,
)
from repro.train.optimizer import AdamWConfig, OptState, adamw_update, init_opt_state

_COLL_LINE = re.compile(
    r"=\s*(\(?[\w\[\],{}\s/]+?\)?)\s+(all-reduce|all-gather|reduce-scatter|all-to-all|collective-permute)(-start)?\("
)
_SHAPE_RE = re.compile(r"(bf16|f16|f32|f64|s8|u8|s16|u16|s32|u32|s64|u64|pred)\[([\d,]*)\]")
_DTYPE_BYTES = {"f32": 4, "f16": 2, "bf16": 2, "s32": 4, "u32": 4, "s8": 1, "u8": 1,
                "pred": 1, "f64": 8, "s64": 8, "u64": 8, "s16": 2, "u16": 2}


def make_mesh_512(multi_pod: bool) -> Mesh:
    devs = jax.devices()
    if multi_pod:
        arr = np.asarray(devs[:512]).reshape(2, 16, 16)
        return Mesh(arr, ("pod", "data", "model"))
    arr = np.asarray(devs[:256]).reshape(16, 16)
    return Mesh(arr, ("data", "model"))


def collective_bytes(hlo_text: str) -> Dict[str, float]:
    """Sum result-buffer bytes of collective ops in a (per-partition) module.

    Lines look like ``%x = bf16[3072,192]{1,0} all-gather(...)`` (possibly a
    tuple result); '-start' async forms are counted, '-done' skipped."""
    out: Dict[str, float] = {}
    for line in hlo_text.splitlines():
        m = _COLL_LINE.search(line)
        if not m:
            continue
        op = m.group(2)
        b = 0
        for dt, shape in _SHAPE_RE.findall(m.group(1)):
            n = 1
            for d in shape.split(","):
                if d:
                    n *= int(d)
            b += n * _DTYPE_BYTES.get(dt, 4)
        out[op] = out.get(op, 0.0) + b
        out["total"] = out.get("total", 0.0) + b
    return out


def cache_sharding_tree(caches, mesh: Mesh):
    """Decode caches: dim0=layer stack (replicated), dim1=batch->data(+pod),
    then the largest remaining dim divisible by the model axis (prefers the
    KV sequence dim => flash-decode style sharding)."""
    names = mesh.axis_names
    batch_axes = tuple(a for a in ("pod", "data") if a in names)
    bsz = int(np.prod([mesh.shape[a] for a in batch_axes])) if batch_axes else 1
    msz = mesh.shape["model"] if "model" in names else 1

    def spec(leaf):
        axes = [None] * len(leaf.shape)
        if len(leaf.shape) >= 2 and leaf.shape[1] % bsz == 0 and bsz > 1:
            axes[1] = batch_axes if len(batch_axes) > 1 else batch_axes[0]
        if msz > 1 and len(leaf.shape) >= 3:
            cand = [d for d in range(2, len(leaf.shape)) if leaf.shape[d] % msz == 0]
            if cand:
                best = max(cand, key=lambda d: leaf.shape[d])
                axes[best] = "model"
        return NamedSharding(mesh, P(*axes))

    return jax.tree_util.tree_map(spec, caches)


def batch_sharding_tree(specs, mesh: Mesh):
    out = {}
    for k, v in specs.items():
        if k == "positions3":
            out[k] = input_sharding(mesh, v.shape, batch_dim=1)
        elif hasattr(v, "shape") and len(v.shape) >= 1:
            out[k] = input_sharding(mesh, v.shape, batch_dim=0)
        else:
            out[k] = NamedSharding(mesh, P())
    return out


# ---------------------------------------------------------------------------
# step builders (lower + compile one cell)
# ---------------------------------------------------------------------------
def build_lowered(cfg: ArchConfig, shape: dict, mesh: Mesh):
    kind = shape["kind"]
    specs = input_specs(cfg, shape)

    if kind == "train":
        ocfg = AdamWConfig()
        params_shape = jax.eval_shape(lambda: tf.init_params(jax.random.PRNGKey(0), cfg))
        p_shard = param_sharding_tree(params_shape, mesh)
        opt_shape = jax.eval_shape(init_opt_state, params_shape)
        o_shard = OptState(
            step=NamedSharding(mesh, P()),
            m=param_sharding_tree(opt_shape.m, mesh),
            v=param_sharding_tree(opt_shape.v, mesh),
        )

        def train_step(params, opt_state, batch):
            with sharding_ctx(mesh):
                (loss, metrics), grads = jax.value_and_grad(
                    lambda p: tf.lm_loss(p, batch, cfg), has_aux=True
                )(params)
                params, opt_state, om = adamw_update(params, grads, opt_state, ocfg)
                return params, opt_state, dict(metrics, loss=loss, **om)

        jitted = jax.jit(
            train_step,
            in_shardings=(p_shard, o_shard, batch_sharding_tree(specs, mesh)),
            out_shardings=(p_shard, o_shard, None),
        )
        return jitted.lower(params_shape, opt_shape, specs)

    # serving params: bf16 copies
    params_shape = jax.eval_shape(lambda: tf.init_params(jax.random.PRNGKey(0), cfg))
    params_shape = jax.tree_util.tree_map(
        lambda s: jax.ShapeDtypeStruct(s.shape, jnp.bfloat16), params_shape
    )
    p_shard = param_sharding_tree(params_shape, mesh)

    if kind == "prefill":
        def serve_prefill(params, batch):
            with sharding_ctx(mesh):
                last, caches, enc = tf.prefill(
                    params, batch["tokens"], cfg, max_len=shape["seq_len"],
                    positions3=batch.get("positions3"),
                    frontend_embeds=batch.get("frontend_embeds"),
                    enc_frames=batch.get("enc_frames"),
                )
                return last, caches

        jitted = jax.jit(
            serve_prefill,
            in_shardings=(p_shard, batch_sharding_tree(specs, mesh)),
            out_shardings=None,
        )
        return jitted.lower(params_shape, specs)

    # decode
    cache_shapes = specs["caches"]
    c_shard = [cache_sharding_tree(c, mesh) for c in cache_shapes]
    tok_shard = input_sharding(mesh, specs["token"].shape, batch_dim=0)
    enc_in = specs.get("enc")
    enc_shard = input_sharding(mesh, enc_in.shape, batch_dim=0) if enc_in is not None else None

    def serve_step(params, token, caches, cur_len, enc=None):
        with sharding_ctx(mesh):
            return tf.decode_step(params, token, caches, cur_len, cfg, enc=enc)

    in_sh = (p_shard, tok_shard, c_shard, NamedSharding(mesh, P()))
    args = (params_shape, specs["token"], cache_shapes, specs["cur_len"])
    if enc_in is not None:
        in_sh = in_sh + (enc_shard,)
        args = args + (enc_in,)
        jitted = jax.jit(serve_step, in_shardings=in_sh, out_shardings=(None, c_shard))
        return jitted.lower(*args)
    jitted = jax.jit(serve_step, in_shardings=in_sh, out_shardings=(None, c_shard))
    return jitted.lower(*args)


# ---------------------------------------------------------------------------
# scan-corrected costing via unrolled small-layer-count variants
# ---------------------------------------------------------------------------
def _variant_cfgs(cfg: ArchConfig):
    """[(type, cfg_1layer, cfg_2layer_or_None)] per block type (DESIGN note:
    cost is affine in per-type layer counts; two points pin the line)."""
    out = []
    if cfg.block_pattern:  # recurrentgemma: separate r / a variants
        out.append(("r", dataclasses.replace(cfg, num_layers=1, block_pattern=("r",)),
                    dataclasses.replace(cfg, num_layers=2, block_pattern=("r",))))
        out.append(("a", dataclasses.replace(cfg, num_layers=1, block_pattern=("a",)), None))
        return out
    if cfg.moe and cfg.first_dense_layers:
        out.append(("m", dataclasses.replace(cfg, num_layers=1, first_dense_layers=0),
                    dataclasses.replace(cfg, num_layers=2, first_dense_layers=0)))
        out.append(("a", dataclasses.replace(cfg, num_layers=1, first_dense_layers=1), None))
        return out
    t = tf.layer_groups(cfg)[0][0]
    out.append((t, dataclasses.replace(cfg, num_layers=1, first_dense_layers=0),
                dataclasses.replace(cfg, num_layers=2, first_dense_layers=0)))
    return out


def _counts_by_type(cfg: ArchConfig) -> Dict[str, int]:
    counts: Dict[str, int] = {}
    for t, c in tf.layer_groups(cfg):
        counts[t] = counts.get(t, 0) + c
    return counts


def _cost_of(cfg, shape, mesh, build_fn=None) -> Dict[str, float]:
    build_fn = build_fn or build_lowered
    tok = tf.UNROLL_SCANS.set(True)
    try:
        lowered = build_fn(cfg, shape, mesh)
        compiled = lowered.compile()
        ca = compiled.cost_analysis()
        coll = collective_bytes(compiled.as_text())
        return {
            "flops": float(ca.get("flops", 0.0)),
            "bytes": float(ca.get("bytes accessed", 0.0)),
            "coll_bytes": float(coll.get("total", 0.0)),
        }
    finally:
        tf.UNROLL_SCANS.reset(tok)
        jax.clear_caches()
        gc.collect()


def corrected_costs(cfg: ArchConfig, shape: dict, mesh: Mesh, build_fn=None) -> Dict[str, float]:
    """base + sum_t count_t * per_t, from unrolled 1/2-layer compiles."""
    variants = _variant_cfgs(cfg)
    counts = _counts_by_type(cfg)
    # first variant pins base via two points
    t0, c1cfg, c2cfg = variants[0]
    c1 = _cost_of(c1cfg, shape, mesh, build_fn)
    c2 = _cost_of(c2cfg, shape, mesh, build_fn)
    per = {t0: {k: c2[k] - c1[k] for k in c1}}
    base = {k: c1[k] - per[t0][k] for k in c1}
    for t, vcfg, _ in variants[1:]:
        cv = _cost_of(vcfg, shape, mesh, build_fn)
        per[t] = {k: cv[k] - base[k] for k in cv}
    total = dict(base)
    for t, n in counts.items():
        for k in total:
            total[k] += per[t][k] * n
    return total


# ---------------------------------------------------------------------------
# cell runner
# ---------------------------------------------------------------------------
def run_cell(arch_id: str, shape_name: str, multi_pod: bool, *, with_cost: bool = True) -> Dict:
    cfg = get_config(arch_id)
    shape = SHAPES[shape_name]
    mesh = make_mesh_512(multi_pod)
    n_chips = int(np.prod(list(mesh.shape.values())))
    rec: Dict = {
        "arch": arch_id, "shape": shape_name,
        "mesh": "2x16x16" if multi_pod else "16x16", "chips": n_chips,
    }
    t0 = time.time()
    lowered = build_lowered(cfg, shape, mesh)
    rec["lower_s"] = round(time.time() - t0, 2)
    t0 = time.time()
    compiled = lowered.compile()
    rec["compile_s"] = round(time.time() - t0, 2)

    ma = compiled.memory_analysis()
    rec["memory"] = {
        "argument_bytes": int(ma.argument_size_in_bytes),
        "output_bytes": int(ma.output_size_in_bytes),
        "temp_bytes": int(ma.temp_size_in_bytes),
    }
    ca = compiled.cost_analysis()
    rec["cost_raw"] = {
        "flops": float(ca.get("flops", 0.0)),
        "bytes": float(ca.get("bytes accessed", 0.0)),
    }
    rec["collectives_raw"] = collective_bytes(compiled.as_text())
    del compiled, lowered
    jax.clear_caches()
    gc.collect()

    if with_cost and not multi_pod:
        cc = corrected_costs(cfg, shape, mesh)
        rec["cost_corrected"] = cc
        mf = costmodel.model_flops(cfg, shape["seq_len"], shape["global_batch"], shape["kind"])
        rec["model_flops_global"] = mf
        rec["model_flops_per_dev"] = mf / n_chips
        terms = costmodel.roofline_terms(cc["flops"], cc["bytes"], cc["coll_bytes"])
        rec["roofline"] = terms
        rec["dominant"] = costmodel.dominant(terms)
        rec["useful_ratio"] = (mf / n_chips) / cc["flops"] if cc["flops"] else None
    return rec


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--mesh", default="both", choices=["single", "multi", "both"])
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--no-cost", action="store_true", help="skip the corrected-cost pass")
    ap.add_argument("--out", default="results/dryrun.json")
    args = ap.parse_args(argv)

    todo = []
    archs = ARCH_IDS if (args.all or not args.arch) else [args.arch]
    for a in archs:
        for s in cells(a):
            if args.shape and s != args.shape:
                continue
            if args.mesh in ("single", "both"):
                todo.append((a, s, False))
            if args.mesh in ("multi", "both"):
                todo.append((a, s, True))

    os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
    results = []
    if os.path.exists(args.out):
        with open(args.out) as f:
            results = json.load(f)
    done = {(r["arch"], r["shape"], r["mesh"]) for r in results if "error" not in r}

    for a, s, mp in todo:
        key = (a, s, "2x16x16" if mp else "16x16")
        if key in done:
            print(f"skip (done): {key}", flush=True)
            continue
        print(f"=== {key} ===", flush=True)
        try:
            rec = run_cell(a, s, mp, with_cost=not args.no_cost)
            print(json.dumps({k: rec[k] for k in ("compile_s", "memory", "dominant") if k in rec}),
                  flush=True)
        except Exception as e:
            traceback.print_exc()
            rec = {"arch": a, "shape": s, "mesh": key[2], "error": f"{type(e).__name__}: {e}"}
        results = [r for r in results if (r["arch"], r["shape"], r["mesh"]) != key]
        results.append(rec)
        with open(args.out, "w") as f:
            json.dump(results, f, indent=1)
    n_err = sum("error" in r for r in results)
    print(f"done: {len(results)} cells, {n_err} errors")
    return 1 if n_err else 0


if __name__ == "__main__":
    sys.exit(main())
