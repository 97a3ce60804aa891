"""Compile a configuration's serve programs for a described TPU v5e, with no
chip attached, and print what each needs of the chip's memory.

    JAX_PLATFORMS=cpu python3 bench/rehearse.py qwen3_8b.chat [deepseek_coder_33b_pp4.code ...]

Compiles the weight-packing call, the decode step at ``max_slots``, and the
largest prefill, suffix prefill and page scatter the cell's traffic reaches.
Nothing runs: this says whether the programs compile and fit, not how fast
they are.  The Pallas path is forced on, as it is on the chip.
"""
from __future__ import annotations

import os
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]
os.environ.setdefault("TPU_LOG_DIR", "disabled")

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
from jax.sharding import SingleDeviceSharding  # noqa: E402


def main(names):
    from jax.experimental import topologies

    from bench import system, warmup, weights
    from bench.run import Cell
    from repro.kernels import ops
    from repro.models import transformer as tf
    from repro.serving.pagepool import KVPagePool, _quantize_scatter

    ops.on_tpu = lambda: True  # compile the kernels, as on the chip
    jax.config.update("jax_enable_compilation_cache", False)
    topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    dev = SingleDeviceSharding(topo.devices[0])

    def spec(x):
        return jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=dev)

    for name in names:
        cell = Cell(name)
        cfg = cell.cfg
        sv = cfg["serving"]
        full, pairs = warmup.shapes(cell.mix, cfg)
        arch = system.arch_config(cfg)
        policy = system.serve_policy()
        build = system.packed_params_fn(cfg)
        key = jax.ShapeDtypeStruct((), jax.random.key(0).dtype, sharding=dev)
        report(f"{name} pack", build.lower(key).compile())
        params = jax.tree.map(spec, jax.eval_shape(build, weights.seed_key(0)))
        _, pool_cfg = system.sched_and_pool(cfg)
        pool = jax.eval_shape(lambda: KVPagePool(arch, pool_cfg).caches)
        pool = jax.tree.map(spec, pool)
        b, pps = sv["max_slots"], pool_cfg.pages_per_seq
        i32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.int32, sharding=dev)  # noqa: E731

        def decode(p, tok, caches, pages, cur):
            return tf.decode_step(p, tok, caches, cur, arch, policy, pages=pages)

        report(f"{name} decode B={b}", jax.jit(decode, donate_argnums=(2,)).lower(
            params, i32(b), pool, i32(b, pps), i32(b)).compile())
        top = max(full)

        def prefill(p, toks, lens):
            return tf.prefill(p, toks, arch, policy, max_len=toks.shape[1],
                              last_positions=lens, qdq_kv=True)[:2]

        report(f"{name} prefill S={top}", jax.jit(prefill).lower(
            params, i32(1, top), i32(1)).compile())
        kv = jax.ShapeDtypeStruct((arch.num_layers, top, arch.num_kv_heads, arch.hd),
                                  jnp.bfloat16, sharding=dev)
        report(f"{name} page scatter S={top}", _quantize_scatter.lower(
            pool[0], kv, kv, i32(top), i32(top)).compile())
        if pairs:
            sb, npb = max(pairs)

            def suffix(p, toks, caches, row, pre, n):
                return tf.prefill_paged_suffix(p, toks, caches, row, pre, n, arch, policy,
                                               page_size=sv["page_size"])

            report(f"{name} suffix S={sb} pages={npb}", jax.jit(suffix).lower(
                params, i32(1, sb), pool, i32(npb), i32(), i32()).compile())


def report(what, compiled):
    m = compiled.memory_analysis()
    gb = 1e9
    print(f"{what}: arguments {m.argument_size_in_bytes / gb:.3f} GB, outputs "
          f"{m.output_size_in_bytes / gb:.3f} GB, temporaries {m.temp_size_in_bytes / gb:.3f} GB, "
          f"aliased {m.alias_size_in_bytes / gb:.3f} GB", flush=True)


if __name__ == "__main__":
    main(sys.argv[1:] or ["qwen3_8b.chat", "deepseek_coder_33b_pp4.code"])
