#!/usr/bin/env python3
"""On-chip smoke test: packed RaZeR serving at published widths on a TPU.

    python3 chip_smoke.py             # one chip: Llama-3.2-3B, packed weights
    python3 chip_smoke.py --chips 4   # only the ep x tp MoE path, 2x2 mesh

One chip (the default) runs four phases, and any failure exits non-zero:

  1. the device: platform, kind and count.  No TPU -> exit 1, no result.
  2. each Pallas kernel of the serve path against its jnp oracle
     (``repro.kernels.ref``) at the model's shapes.
  3. ``Engine.serve`` over the paged 4.5-bit KV pool (scheduler, prefix
     cache, paged decode, Pallas kernels): every request must finish, and the
     compiled decode step must hold the packed-matmul and paged-attention
     kernels as ``tpu_custom_call``s.
  4. the engine's logits (prefill + paged decode) against a fake-quant
     forward of the same weights under ``default_matmul_precision("highest")``.

``--chips 4`` runs dbrx-132b at its published widths, cut to one layer, on a
(2, 2) ep x tp serving mesh against the same weights with no mesh.

The last line of stdout is one JSON object naming the device.  Printed
times, token counts and cache hits are information, not measurements.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import sys
import time
from pathlib import Path

import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

# -- tolerances ---------------------------------------------------------------
# Kernels, as max|kernel - oracle| / max|oracle|.  The matmul kernel
# multiplies bf16 activations by decoded weights that are exact in bf16 (an
# FP4 value times an E3M3 scale has at most 6 significant bits), accumulates
# in f32 and rounds its output to bf16 (at most 2^-8 relative); the oracle
# runs in f32 at "highest" precision.  A wrong nibble, scale or special value
# moves a weight by a whole FP4 step: an error of order 0.1.
MATMUL_TOL = 2.0 ** -7
# The attention kernel's operands are exact in bf16 too (bf16 q, decoded
# K/V); its softmax weights may pass through bf16 on the MXU and its output
# is rounded to bf16 (2^-8 each).  A decode or page-table defect mixes in
# wrong positions: an error of order max|out|.
ATTN_TOL = 2.0 ** -6
# Logits, as ||engine - reference|| / ||reference|| over a request's checked
# positions, divided by the same norm of (reference - unquantized forward):
# the error in units of the 4-bit quantization's own effect.  The two paths
# round bf16 values at different points (the reference rounds each weight
# times its tensor scale to bf16, the kernel scales its f32 accumulator),
# and with random weights that moves the logits by a few percent even for
# one layer (0.03-0.08 measured on the CPU for 1-28 layers against a
# quantization effect of 0.3-0.57).  A wrong weight, page or position moves
# them by at least the quantization effect: a ratio of order 1.  0.5 sits
# between the two (0.13-0.16 measured, 1 for a defect).
LOGITS_TOL = 0.5
# Mesh vs no mesh, same weights and kernels, as ||d|| / ||no mesh||: the tp
# K-shards sum bf16 partial products in a psum_scatter, the rest is equal.
# bf16 rounding at different points moves random-weight logits by a few
# percent per layer (see above); a wrong expert slice or a missing
# reduction changes the MoE output, and so the logits, by their own scale.
MESH_TOL = 0.1

# the serve run: 8 requests of 100-400 prompt tokens, half of them behind one
# 64-token system prompt, 32 new tokens each, on 8 decode slots
SEED, REQUESTS, MAX_NEW, SLOTS, MAX_LEN, PREFIX = 0, 8, 32, 8, 512, 64


def rel_err(got, want) -> float:
    got = np.asarray(got, np.float32)
    want = np.asarray(want, np.float32)
    return float(np.max(np.abs(got - want)) / max(float(np.max(np.abs(want))), 1e-30))


def rel_l2(got, want) -> float:
    got = np.asarray(got, np.float32)
    want = np.asarray(want, np.float32)
    return float(np.linalg.norm(got - want) / max(float(np.linalg.norm(want)), 1e-30))


def check(name: str, err: float, tol: float, why: str, what: str = "max|d|/max|ref|") -> None:
    status = "ok" if err <= tol else "FAIL"
    print(f"  {name}: {what} = {err:.3e} (tol {tol:.3e}: {why}) {status}")
    if err > tol:
        raise SystemExit(f"chip_smoke: {name} out of tolerance")


def device_phase():
    """Phase 1: the device as JAX reports it; anything but a TPU fails."""
    import jax

    devs = jax.devices()
    dev = devs[0]
    print(f"[1] device: platform={dev.platform} kind={dev.device_kind} "
          f"count={len(devs)}")
    if dev.platform != "tpu":
        raise SystemExit(f"chip_smoke: needs a TPU, but JAX found platform "
                         f"{dev.platform!r}; no CPU fallback")
    return {"platform": dev.platform, "kind": dev.device_kind, "count": len(devs)}


def kernel_phase(cfg, seed: int, slots: int, prefill_m: int, max_len: int) -> None:
    """Phase 2: the serve path's Pallas kernels against their jnp oracles,
    at this model's shapes."""
    import jax
    import jax.numpy as jnp

    from repro.core.packing import pack_weight
    from repro.kernels import ops, ref
    from repro.serving.kvcache import kv_quantize

    print(f"[2] kernels vs oracles ({cfg.name} shapes)")
    key = jax.random.PRNGKey(seed)
    d, f = cfg.d_model, cfg.d_ff
    for k_dim, n_dim in ((d, f), (f, d)):
        kw, kx = jax.random.split(jax.random.fold_in(key, k_dim))
        pw = pack_weight(jax.random.normal(kw, (k_dim, n_dim), jnp.float32) / np.sqrt(k_dim))
        for m in (slots, prefill_m):
            x = jax.random.normal(kx, (m, k_dim), jnp.bfloat16)
            y = jax.jit(ops.razer_matmul)(x, pw)
            with jax.default_matmul_precision("highest"):
                y_ref = jax.jit(ref.razer_matmul_ref)(x, pw)
            check(f"razer_matmul M={m} K={k_dim} N={n_dim}", rel_err(y, y_ref),
                  MATMUL_TOL, "bf16 output rounding")

    ps = 16
    npg = max_len // ps
    b, h, kvh, hd = slots, cfg.num_heads, cfg.num_kv_heads, cfg.hd
    kq, kk, kv, kp, kl = jax.random.split(jax.random.fold_in(key, 7), 5)
    n_pages = b * npg + 1
    kc, km = kv_quantize(jax.random.normal(kk, (n_pages, ps, kvh, hd), jnp.float32))
    vc, vm = kv_quantize(jax.random.normal(kv, (n_pages, ps, kvh, hd), jnp.float32))
    cache = {"k_codes": kc, "k_meta": km, "v_codes": vc, "v_meta": vm}
    table = 1 + jax.random.permutation(kp, b * npg).reshape(b, npg).astype(jnp.int32)
    cur = jax.random.randint(kl, (b,), 1, max_len + 1, jnp.int32)
    q = jax.random.normal(kq, (b, 1, h, hd), jnp.bfloat16)
    out = jax.jit(ops.razer_paged_kv_attention)(q, cache, table, cur)
    with jax.default_matmul_precision("highest"):
        out_ref = jax.jit(ref.paged_kv_attention_ref)(
            q[:, 0], kc, km, vc, vm, table, cur)
    check(f"paged attention q=1 B={b} H={h} KVH={kvh} hd={hd} pages={npg}x{ps}",
          rel_err(out[:, 0], out_ref), ATTN_TOL, "bf16 softmax weights and output")


def make_traffic(cfg, seed: int, n: int, max_new: int, prefix_len: int):
    """n requests, prompts of 100-400 tokens; every other one starts with the
    same prefix_len-token system prompt, so the prefix cache hits and the
    suffix prefill runs."""
    from repro.serving.scheduler import Request

    rng = np.random.default_rng(seed)
    prefix = rng.integers(1, cfg.vocab_size, size=prefix_len).tolist()
    reqs = []
    for i in range(n):
        length = int(rng.integers(100, 401))
        if i % 2 == 0:
            prompt = prefix + rng.integers(1, cfg.vocab_size, size=length - prefix_len).tolist()
        else:
            prompt = rng.integers(1, cfg.vocab_size, size=length).tolist()
        reqs.append(Request(rid=i, prompt=prompt, max_new_tokens=max_new))
    return reqs


def serve_phase(eng, reqs, slots: int) -> None:
    """Phase 3: Engine.serve over the paged pool; every request finishes."""
    from repro.serving.scheduler import SchedulerConfig

    print(f"[3] Engine.serve: {len(reqs)} requests, prompts "
          f"{min(len(r.prompt) for r in reqs)}-{max(len(r.prompt) for r in reqs)} "
          f"tokens, max_new {reqs[0].max_new_tokens}, {slots} slots, "
          f"max_len {eng.scfg.max_len}")
    t0 = time.perf_counter()
    rep = eng.serve(reqs, sched_cfg=SchedulerConfig(max_slots=slots))
    wall = time.perf_counter() - t0
    unfinished = [r.rid for r in rep.requests
                  if r.finish_time is None or len(r.out_tokens) != r.max_new_tokens]
    if unfinished:
        raise SystemExit(f"chip_smoke: requests {unfinished} did not finish")
    print(f"  all {len(rep.requests)} requests finished: {rep.new_tokens} new tokens, "
          f"{rep.decode_steps} decode steps, {wall:.1f} s wall incl. compiles "
          f"(information only)")
    print(f"  prefix cache: {rep.cache_hits}/{rep.cache_lookups} hits, "
          f"{rep.cached_tokens} cached vs {rep.prefill_tokens} computed prompt tokens")
    if rep.cache_hits == 0:
        raise SystemExit("chip_smoke: no prefix-cache hit, the suffix prefill did not run")


def kernels_in_decode_step(eng, slots: int) -> None:
    """The compiled decode step holds the Pallas kernels, not the jnp
    references: one ``tpu_custom_call`` per kernel launch site."""
    import jax.numpy as jnp

    from repro.serving.pagepool import KVPagePool

    pool = KVPagePool(eng.cfg, eng.default_pool_cfg(slots))
    zeros = jnp.zeros((slots,), jnp.int32)
    text = eng._paged_decode_jit.lower(
        eng.params, zeros, pool.caches, pool.page_table([None] * slots), zeros
    ).compile().as_text()
    calls = [ln for ln in text.splitlines() if "tpu_custom_call" in ln]
    counts = {name: sum(f"%{name}" in ln for ln in calls)
              for name in ("razer_matmul", "razer_paged_attention")}
    print(f"  compiled decode step: {len(calls)} tpu_custom_call sites {counts}")
    missing = [name for name, c in counts.items() if c == 0]
    if missing:
        raise SystemExit(f"chip_smoke: decode step lacks Pallas kernels {missing}")


def engine_logits(eng, prompts, n_steps: int, slots: int, exact_prefill: bool = False):
    """Greedy logits of the engine's own serve path for each prompt: the
    prefill into pool pages, then n_steps - 1 paged decode steps with every
    prompt in its own slot.  Returns (logits (len(prompts), n_steps, V),
    fed tokens per prompt).  ``exact_prefill`` prefills at the prompt's own
    length instead of its power-of-two bucket."""
    import jax.numpy as jnp

    from repro.serving.pagepool import KVPagePool

    pool = KVPagePool(eng.cfg, eng.default_pool_cfg(slots))
    logits = [[] for _ in prompts]
    for rid, p in enumerate(prompts):
        pool.allocate(rid, len(p) + n_steps)
        if exact_prefill:
            last, caches = eng._bucketed_prefill(
                np.asarray([p], np.int32), np.asarray([len(p)], np.int32),
                max_len=len(p), qdq_kv=True)
        else:
            last, caches = eng._serve_prefill(p)
        pool.write_prefill(rid, caches, len(p))
        logits[rid].append(np.asarray(last[0], np.float32))
    seq_ids = list(range(len(prompts))) + [None] * (slots - len(prompts))
    table = pool.page_table(seq_ids)
    fed = [[] for _ in prompts]
    for step in range(n_steps - 1):
        toks = np.zeros((slots,), np.int32)
        cur = np.zeros((slots,), np.int32)
        for rid, p in enumerate(prompts):
            toks[rid] = int(np.argmax(logits[rid][-1]))
            cur[rid] = len(p) + step
            fed[rid].append(int(toks[rid]))
        out, pool.caches = eng._paged_decode_jit(
            eng.params, jnp.asarray(toks), pool.caches, table, jnp.asarray(cur))
        out = np.asarray(out, np.float32)
        for rid in range(len(prompts)):
            logits[rid].append(out[rid])
    return np.stack([np.stack(ls) for ls in logits]), fed


def logits_phase(eng, params, cfg, prompts, n_steps: int, slots: int) -> None:
    """Phase 4: engine logits vs the fake-quant forward of the same weights."""
    import jax
    import jax.numpy as jnp

    from repro.core.policy import QuantPolicy
    from repro.models import transformer as tf

    print(f"[4] logits: engine (prefill + {n_steps - 1} paged decode steps) vs "
          f"fake-quant forward at highest matmul precision")
    got, fed = engine_logits(eng, prompts, n_steps, slots)
    # one row per checked position: row j of prompt i holds prompt + the first
    # j fed tokens, and its logits are read at its own last position
    rows, lens = [], []
    for p, f in zip(prompts, fed):
        for j in range(n_steps):
            rows.append(p + f[:j])
            lens.append(len(p) + j)
    width = eng._bucket(max(lens))
    toks = np.zeros((len(rows), width), np.int32)
    for i, r in enumerate(rows):
        toks[i, :len(r)] = r

    def fwd(params, toks, lens, policy):
        last, _, _ = tf.prefill(params, toks, cfg, policy, max_len=width,
                                last_positions=lens, qdq_kv=True)
        return last

    fwd = jax.jit(fwd, static_argnums=3)
    with jax.default_matmul_precision("highest"):
        want, dense = (
            np.asarray(fwd(params, jnp.asarray(toks), jnp.asarray(lens, jnp.int32), pol),
                       np.float32).reshape(got.shape)
            for pol in (QuantPolicy.fakequant(), QuantPolicy.bf16()))
    agree = float(np.mean(got.argmax(-1) == want.argmax(-1)))
    for i in range(len(prompts)):
        err, gap = rel_l2(got[i], want[i]), rel_l2(want[i], dense[i])
        print(f"  request {i} ({len(prompts[i])}-token prompt), {n_steps} positions: "
              f"||engine - fakequant|| / ||fakequant|| = {err:.3e}, "
              f"||fakequant - unquantized|| / ||fakequant|| = {gap:.3e}")
        check(f"request {i}", err / gap, LOGITS_TOL,
              "bf16 rounding at different points vs the 4-bit effect",
              what="error / quantization effect")
    print(f"  top-1 agreement {agree:.3f} over {got.shape[0] * got.shape[1]} "
          f"positions (information only: random weights leave near-ties)")


def one_chip() -> None:
    import jax

    from repro.configs import get_config
    from repro.core.policy import QuantPolicy
    from repro.models import transformer as tf
    from repro.serving.engine import Engine, ServeConfig

    # published widths, no .reduced(); the checkpoint dtype is bf16
    cfg = get_config("llama3_2_3b")
    cfg = dataclasses.replace(cfg, param_dtype=cfg.compute_dtype)
    print(f"  model {cfg.name}: {cfg.num_layers} layers, d={cfg.d_model}, "
          f"{cfg.num_heads}/{cfg.num_kv_heads} heads, hd={cfg.hd}, d_ff={cfg.d_ff}, "
          f"vocab {cfg.vocab_size}; random weights from seed {SEED}")
    kernel_phase(cfg, SEED, SLOTS, prefill_m=256, max_len=MAX_LEN)

    t0 = time.perf_counter()
    params = jax.jit(tf.init_params, static_argnums=1)(jax.random.PRNGKey(SEED), cfg)
    eng = Engine(params, cfg, ServeConfig(max_len=MAX_LEN, max_new_tokens=MAX_NEW,
                                          kv_quant=True, quant=QuantPolicy.packed()))
    jax.block_until_ready(eng.params)
    print(f"  init + pack: {time.perf_counter() - t0:.1f} s")
    reqs = make_traffic(cfg, SEED, REQUESTS, MAX_NEW, prefix_len=PREFIX)
    serve_phase(eng, reqs, SLOTS)
    kernels_in_decode_step(eng, SLOTS)
    logits_phase(eng, params, cfg, [reqs[0].prompt, reqs[1].prompt], n_steps=4,
                 slots=SLOTS)


def four_chips() -> None:
    """dbrx-132b's MoE layer on a (2, 2) ep x tp mesh vs no mesh."""
    import jax

    from repro.configs import get_config
    from repro.core.policy import QuantPolicy
    from repro.launch.mesh import make_serving_mesh
    from repro.models import transformer as tf
    from repro.serving.engine import Engine, ServeConfig

    full = get_config("dbrx_132b")
    cfg = dataclasses.replace(full, num_layers=1, param_dtype=full.compute_dtype)
    print(f"  model {cfg.name}: d={cfg.d_model}, {cfg.n_experts} experts top-{cfg.topk}, "
          f"moe_d_ff={cfg.moe_d_ff}, {cfg.num_heads}/{cfg.num_kv_heads} heads, "
          f"vocab {cfg.vocab_size}; depth cut {full.num_layers} -> {cfg.num_layers} "
          f"layer so the unsharded run fits one chip")
    scfg = ServeConfig(max_len=64, max_new_tokens=4, kv_quant=True, quant=QuantPolicy.packed())
    params = jax.jit(tf.init_params, static_argnums=1)(jax.random.PRNGKey(SEED), cfg)
    # the raw expert banks (6.3 GB of bf16) feed nothing but the pack: held on
    # the host, they reach the chip one expert at a time inside
    # pack_stacked_weights, which leaves room for the quantizer's temporaries
    params = jax.tree_util.tree_map_with_path(
        lambda path, a: jax.device_get(a) if "experts" in jax.tree_util.keystr(path) else a,
        params)
    # 25 prompt tokens and one decode slot: the MoE dispatch then uses one
    # token group with and without the mesh (gcd(25, 16) == gcd(25, 2) == 1),
    # so both runs route under the same expert capacity
    prompt = np.random.default_rng(SEED).integers(1, cfg.vocab_size, size=25).tolist()
    eng0 = Engine(params, cfg, scfg)
    want, _ = engine_logits(eng0, [prompt], n_steps=3, slots=1, exact_prefill=True)
    del eng0
    mesh = make_serving_mesh(ep=2, tp=2)
    print(f"[2] (2, 2) ep x tp mesh {dict(mesh.shape)} vs no mesh, same weights")
    eng = Engine(params, cfg, scfg, mesh=mesh)
    got, _ = engine_logits(eng, [prompt], n_steps=3, slots=1, exact_prefill=True)
    check("dbrx 1-layer logits, prefill + 2 paged decode steps", rel_l2(got, want),
          MESH_TOL, "bf16 rounding at different points", what="||mesh - no mesh|| / ||no mesh||")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the ep x tp MoE mesh path and its comparison")
    args = ap.parse_args(argv)

    device = device_phase()
    if device["count"] < args.chips:
        raise SystemExit(f"chip_smoke: --chips {args.chips} needs {args.chips} "
                         f"devices, found {device['count']}")
    from repro.launch.compile_cache import enable_compile_cache

    print(f"  compile cache: {enable_compile_cache()}")
    t0 = time.perf_counter()
    four_chips() if args.chips == 4 else one_chip()
    print(f"  total {time.perf_counter() - t0:.1f} s")
    print(json.dumps({"ok": True, "device": device}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
