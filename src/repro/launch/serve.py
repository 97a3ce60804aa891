"""Production serving driver.

    PYTHONPATH=src python -m repro.launch.serve --arch qwen3_8b --reduced \
        --packed --kv-quant --requests 8

Expert-parallel packed MoE serving (docs/parallelism.md): ``--ep N`` builds
an (N, tp) mesh whose data axis shards the packed expert banks E/N rows per
device; for MoE archs N must divide n_experts (checked up front).

    XLA_FLAGS=--xla_force_host_platform_device_count=8 \
    PYTHONPATH=src python -m repro.launch.serve --arch dbrx_132b --reduced \
        --packed --ep 4

Tensor-parallel packed serving (docs/parallelism.md#k-sharding): ``--tp N``
adds a model axis that K-shards every eligible packed weight -- each device
holds K/N wire rows and the partial-sum reduce-scatter is fused into the
kernel epilogue; composes with ``--ep`` on a 2-D mesh.  For packed runs N
must split every reduction dim into whole 16-element quant blocks (checked
up front):

    XLA_FLAGS=--xla_force_host_platform_device_count=4 \
    PYTHONPATH=src python -m repro.launch.serve --arch dbrx_132b --reduced \
        --packed --ep 2 --tp 2

Continuous batching (docs/serving.md): ``--continuous`` switches from one
static batch to the scheduler-driven request-stream mode over the paged
RaZeR-quantized KV pool -- requests arrive on a Poisson trace (``--rate``
req/s) and are admitted into ``--slots`` decode slots as capacity frees up,
with per-request TTFT / latency and pool stats printed at the end:

    PYTHONPATH=src python -m repro.launch.serve --arch llama3_2_3b --reduced \
        --continuous --requests 12 --rate 20 --slots 4

Prefix caching (docs/serving.md#prefix-caching) is ON by default in
continuous mode: requests sharing a prompt prefix share its quantized KV
pages and prefill only their suffix, with bit-identical greedy outputs.
``--no-prefix-cache`` disables it; ``--shared-prefix N`` prepends an
N-token system prompt to every request to demo the hit rate:

    PYTHONPATH=src python -m repro.launch.serve --arch llama3_2_3b --reduced \
        --continuous --requests 12 --shared-prefix 32 --slots 4

Speculative decoding (docs/serving.md#speculative-decoding): ``--speculate-k``
drafts k tokens per slot per iteration with the same checkpoint under a
cheaper quantization (``--draft-policy``), verifies all k+1 positions in one
multi-query paged-attention pass, and rolls rejected drafts back -- greedy
outputs stay bit-identical at any k, only throughput changes:

    PYTHONPATH=src python -m repro.launch.serve --arch llama3_2_3b --reduced \
        --continuous --requests 12 --slots 4 --speculate-k 3 --draft-policy bf16

Disaggregated serving (docs/serving.md#disaggregated-serving): ``--disagg``
replaces the single serve loop with prefill/decode replicas and a
prefix-aware router; quantized KV pages ship between stages in the 4.5-bit
wire format (0.28x of bf16):

    PYTHONPATH=src python -m repro.launch.serve --arch llama3_2_3b --reduced \
        --disagg --prefill-replicas 2 --decode-replicas 2 --requests 12 --rate 20
"""
from __future__ import annotations

import argparse
import dataclasses
import time

import jax
import numpy as np

from repro.configs import get_config
from repro.core.policy import QuantPolicy
from repro.launch.compile_cache import enable_compile_cache
from repro.models import transformer as tf
from repro.serving.engine import Engine, ServeConfig


def _write_report(path: str, report) -> None:
    """Byte-stable quant-report JSON (same conventions as trace export)."""
    import json

    with open(path, "w") as f:
        json.dump(report, f, indent=1, sort_keys=True)
        f.write("\n")
    roll = report["rollups"]
    print(f"  quant report: {path} ({roll['layers_audited']} layers audited, "
          f"min SQNR {roll['min_sqnr_db']} dB, SV block rate "
          f"{roll['sv_block_rate']}, max drift {roll['max_drift']}; "
          f"gate: python tools/check_bench.py --report {path})")


def _export_obs(args, tracer, registry) -> None:
    """Flush --trace-out / --metrics-out artifacts after a serve run."""
    if tracer is not None:
        tracer.export(args.trace_out)
        print(f"  trace: {args.trace_out} ({len(tracer.events)} events; "
              f"validate: python tools/check_trace.py {args.trace_out})")
    if registry is not None:
        import json

        if args.metrics_out.endswith(".json"):
            with open(args.metrics_out, "w") as f:
                json.dump(registry.snapshot(), f, indent=1, sort_keys=True)
                f.write("\n")
        else:
            with open(args.metrics_out, "w") as f:
                f.write(registry.expose())
        print(f"  metrics: {args.metrics_out}")


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--packed", action="store_true", help="RaZeR 4.5-bit packed weights")
    ap.add_argument("--kv-quant", action="store_true", help="RaZeR KV cache (App. C.1)")
    ap.add_argument("--ep", type=int, default=0,
                    help="expert-parallel (data) mesh axis size; 0 = no mesh")
    ap.add_argument("--tp", type=int, default=1, help="tensor-parallel (model) axis size")
    ap.add_argument("--requests", type=int, default=4)
    ap.add_argument("--max-new", type=int, default=16)
    ap.add_argument("--max-len", type=int, default=128)
    ap.add_argument("--continuous", action="store_true",
                    help="continuous batching over the paged quantized KV pool")
    ap.add_argument("--rate", type=float, default=0.0,
                    help="Poisson request arrival rate, req/s (0 = all at t=0)")
    ap.add_argument("--slots", type=int, default=4, help="decode slots (continuous mode)")
    ap.add_argument("--prefill-budget", type=int, default=256,
                    help="max prompt tokens prefilled per engine step (continuous mode)")
    ap.add_argument("--prefix-cache", action=argparse.BooleanOptionalAction, default=True,
                    help="share prompt-prefix pages between requests via the radix "
                         "prefix cache (continuous mode; bit-identical outputs either "
                         "way -- docs/serving.md#prefix-caching)")
    ap.add_argument("--shared-prefix", type=int, default=0,
                    help="prepend this many identical system-prompt tokens to every "
                         "request (demo traffic for the prefix cache)")
    ap.add_argument("--speculate-k", type=int, default=0,
                    help="self-speculative decoding: draft this many tokens per slot "
                         "per iteration with the --draft-policy model, verify all k+1 "
                         "in one paged-attention pass (continuous mode; greedy outputs "
                         "stay bit-identical -- docs/serving.md#speculative-decoding)")
    ap.add_argument("--draft-policy", default=None,
                    help="draft-side quantization: a registry format name (nvfp4, "
                         "fouroversix, ...) fake-quantizing the SAME checkpoint, or "
                         "'bf16' for the raw weights (default: nvfp4)")
    ap.add_argument("--disagg", action="store_true",
                    help="disaggregated prefill/decode serving (implies a request "
                         "stream like --continuous; docs/serving.md#disaggregated-serving)")
    ap.add_argument("--prefill-replicas", type=int, default=1,
                    help="prefill workers, each with its own pool + prefix cache (--disagg)")
    ap.add_argument("--decode-replicas", type=int, default=1,
                    help="decode workers, each with its own pool + slots (--disagg)")
    ap.add_argument("--chunk-tokens", type=int, default=64,
                    help="max prompt tokens per prefill chunk (--disagg queue fairness)")
    ap.add_argument("--transfer-gbps", type=float, default=0.0,
                    help="modelled prefill->decode wire bandwidth (0 = instantaneous)")
    ap.add_argument("--ckpt", default=None, help="restore params from a training checkpoint dir")
    ap.add_argument("--trace-out", default=None,
                    help="write a Chrome trace-event JSON of the serve run "
                         "(open in https://ui.perfetto.dev; validate with "
                         "tools/check_trace.py -- docs/observability.md)")
    ap.add_argument("--metrics-out", default=None,
                    help="write the metrics registry at exit: Prometheus text "
                         "exposition, or a JSON snapshot when the path ends "
                         "in .json")
    ap.add_argument("--quant-report", default=None, metavar="OUT.json",
                    help="emit the per-layer quantization audit (SQNR, FP4 "
                         "code histograms, SV-remap hit rates, packed-vs-"
                         "fakequant drift) before serving -- requires "
                         "--packed; validate/gate with tools/check_bench.py "
                         "(docs/observability.md#numerics-audit)")
    ap.add_argument("--kv-audit", type=int, default=0, metavar="N",
                    help="sample KV quantization error every Nth prefill "
                         "write into the quant report's 'kv' section (0 = "
                         "off; read-only hook, greedy outputs bit-identical "
                         "either way; requires --continuous and "
                         "--quant-report)")
    ap.add_argument("--jax-profile", default=None, metavar="DIR",
                    help="bracket the serve loop with jax.profiler traces "
                         "into DIR (continuous mode)")
    ap.add_argument("--dry", action="store_true",
                    help="CI smoke mode: reduced config, tiny request count "
                         "and generation budget")
    args = ap.parse_args(argv)

    if args.dry:
        args.reduced = True
        args.requests = min(args.requests, 4)
        args.max_new = min(args.max_new, 4)
        args.max_len = min(args.max_len, 64)
    if (args.trace_out or args.metrics_out or args.jax_profile) and not (
            args.continuous or args.disagg):
        ap.error("--trace-out/--metrics-out/--jax-profile instrument the "
                 "serving loops; add --continuous or --disagg")
    if args.quant_report and not args.packed:
        # the audit reads wire bytes; a fakequant/bf16 run has none to read
        ap.error("--quant-report audits the packed wire format, but this run "
                 "serves bf16 weights (no wire bytes to audit); add --packed, "
                 "or use tools/quant_report.py --mode fakequant for "
                 "accuracy-experiment policies")
    if args.kv_audit:
        if not args.continuous:
            ap.error("--kv-audit samples KVPagePool prefill writes; add "
                     "--continuous")
        if not args.quant_report:
            ap.error("--kv-audit results land in the quant report's 'kv' "
                     "section; add --quant-report OUT.json")

    enable_compile_cache()
    cfg = get_config(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
    # serve from a checkpoint in the compute dtype (published checkpoints are
    # bf16): an f32 tree of a 3B model alone fills most of a 16 GB chip.
    # Jitted, so the per-layer inits are written straight into the stacked
    # leaves instead of being stacked from eager copies
    cfg = dataclasses.replace(cfg, param_dtype=cfg.compute_dtype)
    params = jax.jit(tf.init_params, static_argnums=1)(jax.random.PRNGKey(0), cfg)
    if args.ckpt:
        from repro.train.checkpoint import restore_checkpoint

        state = {"params": params}
        try:
            state, step = restore_checkpoint(args.ckpt, state)
            params = state["params"]
            print(f"restored params from step {step}")
        except (KeyError, ValueError):
            # checkpoint may hold {"params", "opt"}: restore params subtree only
            full, step = restore_checkpoint(args.ckpt, {"params": params, "opt": None})
            params = full["params"]

    mesh = None
    if args.ep or args.tp > 1:
        from repro.launch.mesh import make_serving_mesh
        from repro.parallel.sharding import expert_shard_size, kshard_size

        if cfg.moe and args.packed and args.ep > 1:
            # fail fast with the divisibility rule instead of silently
            # replicating a bank the user asked to shard
            expert_shard_size(cfg.n_experts, args.ep)
        if args.packed and args.tp > 1:
            # same fail-fast for the tp axis: every packed reduction dim the
            # K-shard path touches (d_model everywhere; the expert trio also
            # reduces over the ffn width) must split into whole quant blocks
            kshard_size(cfg.d_model, args.tp)
            kshard_size(cfg.moe_d_ff if cfg.moe else cfg.d_ff, args.tp)
        mesh = make_serving_mesh(ep=args.ep or None, tp=args.tp)

    scfg = ServeConfig(
        max_len=args.max_len,
        max_new_tokens=args.max_new,
        kv_quant=args.kv_quant,
        quant=QuantPolicy.packed() if args.packed else QuantPolicy.bf16(),
    )
    eng = Engine(params, cfg, scfg, mesh=mesh)

    report = kv_auditor = None
    if args.quant_report:
        report = eng.quant_audit(model=args.arch)
        _write_report(args.quant_report, report)
        if args.kv_audit:
            from repro.obs import KVAuditor

            kv_auditor = KVAuditor(sample_every=args.kv_audit)

    rng = np.random.default_rng(0)
    sys_prompt = rng.integers(1, cfg.vocab_size, size=args.shared_prefix).tolist()
    reqs = [sys_prompt + rng.integers(1, cfg.vocab_size, size=int(rng.integers(4, 16))).tolist()
            for _ in range(args.requests)]
    if cfg.ssm or cfg.block_pattern:
        reqs = [r[:4] for r in reqs]  # recurrent archs: equal lengths
    extras = {}
    if cfg.encoder_decoder:
        import jax.numpy as jnp

        extras["enc_frames"] = jnp.asarray(
            rng.standard_normal((len(reqs), cfg.enc_frames, cfg.d_model)), jnp.bfloat16)

    if args.continuous or args.disagg:
        from repro.serving.scheduler import Request, SchedulerConfig

        # observability sinks (docs/observability.md): a Tracer when the run
        # should leave a Chrome trace, a MetricsRegistry when it should leave
        # a Prometheus/JSON dump.  None = the zero-overhead disabled path.
        tracer = registry = None
        if args.trace_out:
            from repro.obs import Tracer

            tracer = Tracer()
        if args.metrics_out:
            from repro.obs import MetricsRegistry

            registry = MetricsRegistry()

        # Poisson arrival trace: exponential inter-arrival gaps at --rate req/s
        gaps = rng.exponential(1.0 / args.rate, size=len(reqs)) if args.rate > 0 else \
            np.zeros(len(reqs))
        arrivals = np.cumsum(gaps)
        stream = [Request(rid=i, prompt=p, max_new_tokens=args.max_new,
                          arrival=float(arrivals[i]))
                  for i, p in enumerate(reqs)]
        if args.disagg:
            if args.speculate_k:
                ap.error("--speculate-k applies to single-engine --continuous "
                         "serving; disaggregated decode workers do not speculate yet")
            from repro.serving.disagg import serve_disagg

            rep = serve_disagg(
                eng, stream, trace=tracer, metrics=registry,
                n_prefill=args.prefill_replicas,
                n_decode=args.decode_replicas, chunk_tokens=args.chunk_tokens,
                max_slots=args.slots, prefix_cache=args.prefix_cache,
                transfer_gbps=args.transfer_gbps)
            print(f"{rep.new_tokens} tokens / {rep.wall_time:.2f}s makespan | "
                  f"{rep.n_prefill}P x {rep.n_decode}D | "
                  f"prefill {rep.prefill_tokens_per_s:.1f} tok/s, "
                  f"decode {rep.decode_tokens_per_s:.1f} tok/s")
            print(f"  mean TTFT {rep.mean_ttft * 1e3:.1f} ms | mean latency "
                  f"{rep.mean_latency * 1e3:.1f} ms | {rep.shipments} shipments, "
                  f"{rep.transfer_bytes / 1024:.1f} KiB shipped "
                  f"({rep.transfer_ratio:.3f}x of bf16)")
            print(f"  router: {rep.router_placements} placements, "
                  f"{rep.router_hit_rate:.0%} predicted hit rate | realized "
                  f"{rep.cache_hit_rate:.0%} ({rep.cached_tokens} cached vs "
                  f"{rep.prefill_tokens} computed prompt tokens)")
            for r in rep.requests[:3]:
                print(f"  prompt[{len(r.prompt)}] @t={r.arrival:.2f}s -> {r.out_tokens}")
            _export_obs(args, tracer, registry)
            return
        rep = eng.serve(stream, sched_cfg=SchedulerConfig(
            max_slots=args.slots, prefill_token_budget=args.prefill_budget),
            prefix_cache=args.prefix_cache,
            speculate_k=args.speculate_k, draft_policy=args.draft_policy,
            trace=tracer, metrics=registry, kv_audit=kv_auditor,
            profile_dir=args.jax_profile)
        if kv_auditor is not None:
            # re-emit with the live-serving KV error section filled in
            report["kv"] = kv_auditor.snapshot()
            _write_report(args.quant_report, report)
        print(f"{rep.new_tokens} tokens / {rep.wall_time:.2f}s = "
              f"{rep.tokens_per_s:.1f} tok/s over {rep.decode_steps} decode steps "
              f"(slots={args.slots}, packed={args.packed})")
        if rep.speculate_k:
            print(f"  speculative k={rep.speculate_k}: accept rate "
                  f"{rep.accept_rate:.0%} ({rep.accepted_drafts}/{rep.drafted_tokens} "
                  f"drafts) | {rep.tokens_per_step:.2f} tokens/step | draft overhead "
                  f"{rep.draft_overhead:.0%} of decode time")
        print(f"  mean TTFT {rep.mean_ttft * 1e3:.1f} ms | mean latency "
              f"{rep.mean_latency * 1e3:.1f} ms | peak {rep.peak_slots} slots, "
              f"{rep.peak_pages} pages ({rep.peak_pages * rep.page_bytes / 1024:.1f} KiB KV)")
        if args.prefix_cache:
            print(f"  prefix cache: {rep.cache_hits}/{rep.cache_lookups} hits | "
                  f"{rep.cached_tokens} cached vs {rep.prefill_tokens} computed prompt "
                  f"tokens ({rep.cache_hit_rate:.0%} hit rate) | "
                  f"{rep.cache_evictions} evictions")
        for r in rep.requests[:3]:
            print(f"  prompt[{len(r.prompt)}] @t={r.arrival:.2f}s -> {r.out_tokens}")
        _export_obs(args, tracer, registry)
        return

    t0 = time.perf_counter()
    out = eng.generate(reqs, extras=extras)
    dt = time.perf_counter() - t0
    new = sum(len(o) - len(r) for o, r in zip(out, reqs))
    print(f"{new} tokens / {dt:.2f}s = {new / dt:.1f} tok/s "
          f"(packed={args.packed}, kv_quant={args.kv_quant})")
    for o, r in zip(out[:3], reqs[:3]):
        print(f"  prompt[{len(r)}] -> {o[len(r):]}")


if __name__ == "__main__":
    main()
