"""The benchmark: one cell of ``BENCHMARK.json``, one run.

    python3 bench/run.py --workload qwen3_8b.chat --seed 7 --seconds 30 --trace 0

Set-up (weights packed on the device from the seed, every program shape the
cell's traffic reaches compiled and run once, the preloaded requests
served) is timed as ``setup_s``.  Then ``Engine.serve`` runs the cell's open-
loop traffic for ``--seconds``; ``--trace 0`` prints the cell's end-to-end
metrics, ``--trace 1`` its per-layer metrics from the program's spans and a
device trace of the same window.  After the window, a sample of the served
tokens is checked against the plain reference (``bench/reference.py``):
``correct`` is whether the mean gap between a served token's reference
logit and the reference's best stays within the cell's limit.

The last line of stdout is the result as one JSON object.  Without a TPU,
or with fewer chips than the cell asks for, it exits 2 and prints none.
"""
from __future__ import annotations

import time

PROCESS_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from pathlib import Path  # noqa: E402
from typing import Dict, List, Optional  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
for p in (ROOT, ROOT / "src"):
    if str(p) not in sys.path:
        sys.path.insert(0, str(p))

BENCH = Path(__file__).resolve().parent


def load_json(path: Path) -> Dict:
    with open(path) as f:
        return json.load(f)


class Cell:
    """A workload of ``BENCHMARK.json`` with its configuration, traffic mix
    and cell file, found by name."""

    def __init__(self, name: str, benchmark: Optional[Dict] = None, *,
                 cfg: Optional[Dict] = None, mix: Optional[Dict] = None,
                 params: Optional[Dict] = None):
        from bench import traffic

        bm = benchmark if benchmark is not None else load_json(ROOT / "BENCHMARK.json")
        wl = next((w for w in bm["workloads"] if w["name"] == name), None)
        if wl is None:
            raise SystemExit(f"no workload {name!r} in BENCHMARK.json")
        self.name, self.chips = name, wl["chips"]
        conf = next(c for c in bm["configs"] if c["name"] == wl["config"])
        self.cfg = cfg if cfg is not None else load_json(ROOT / conf["file"])
        self.mix = mix if mix is not None else traffic.load(wl["traffic"])
        self.params = params if params is not None else load_json(BENCH / "cells" / f"{name}.json")

        def applies(m):
            return name in m.get("workloads", [name])

        self.end_to_end = [m for m in bm["end_to_end"] if applies(m)]
        self.per_layer = [m for m in bm["per_layer"] if applies(m)]


def device_info(chips: int) -> Dict:
    """The device as JAX reports it; anything but enough TPU chips exits 2."""
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu" or len(devs) < chips:
        print(f"bench: needs {chips} TPU chip(s), JAX found {len(devs)} "
              f"{devs[0].platform} device(s); no result", file=sys.stderr)
        raise SystemExit(2)
    return {"platform": devs[0].platform, "kind": devs[0].device_kind, "count": len(devs)}


def enable_compile_cache() -> None:
    import jax

    from repro.launch.compile_cache import enable_compile_cache as enable

    enable()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


class CompileCounter:
    """Counts programs traced or compiled while ``active``."""

    def __init__(self):
        import jax

        self.active, self.count = False, 0

        def on(event, _secs, **_kw):
            if self.active and event in ("/jax/core/compile/jaxpr_trace_duration",
                                         "/jax/core/compile/backend_compile_duration"):
                self.count += 1

        jax.monitoring.register_event_duration_secs_listener(on)


def memory_peak() -> int:
    import jax

    stats = jax.devices()[0].memory_stats() or {}
    return int(stats.get("peak_bytes_in_use", 0))


def sample(reqs, seed: int, target: int, rows: int):
    """Served requests for the check, drawn from the seed: the one with the
    most served tokens, then finished ones, then unfinished ones, until
    ``target`` served tokens or ``rows`` requests."""
    import numpy as np

    rng = np.random.default_rng(seed ^ 0xC0FFEE)
    served = [r for r in reqs if r.out_tokens]
    if not served:
        return []
    first = max(served, key=lambda r: len(r.out_tokens))
    rest = [r for r in served if r is not first]
    done = [r for r in rest if r.finish_time is not None]
    live = [r for r in rest if r.finish_time is None]
    order = [first] + [done[i] for i in rng.permutation(len(done))] + \
        [live[i] for i in rng.permutation(len(live))]
    out, toks = [], 0
    for r in order[:rows]:
        out.append(r)
        toks += len(r.out_tokens)
        if toks >= target:
            break
    return out


def judge(gaps, limit: float) -> Dict:
    """The comparison that decides ``correct``: the mean of the served
    tokens' gaps (``reference.served_gaps``) against the cell's limit."""
    import numpy as np

    gaps = np.asarray(gaps)
    value = float(gaps.mean()) if gaps.size else float("inf")
    return {"value": value, "tokens": int(gaps.size),
            "correct": bool(gaps.size > 0 and value <= limit)}


def check(cell: Cell, seed: int, reqs) -> Dict:
    """Reference logits for a sample of served tokens; the compared number is
    the mean gap by which a served token's reference logit lies below the
    reference's best (0 where the program picked the reference's token)."""
    import numpy as np

    from bench import reference

    p = cell.params
    picked = sample(reqs, seed, p["check_tokens"], p["check_requests"])
    seqs, pos, toks = reference.teacher_forced(
        [(list(r.prompt), list(r.out_tokens)) for r in picked])
    if not seqs:
        return dict(judge([], p["mean_logit_gap"]), requests=0)
    ref = reference.logits_at(cell.cfg, seed, seqs, pos, rows=p["check_requests"],
                              length=cell.cfg["serving"]["max_len"])
    gaps = reference.served_gaps(ref, toks)
    print(f"bench: served tokens that are the reference's best {np.mean(gaps == 0):.4f}, "
          f"widest gap {gaps.max():.4f}, quantiles 50/90/99 "
          f"{np.quantile(gaps, [0.5, 0.9, 0.99]).tolist()}", file=sys.stderr)
    return dict(judge(gaps, p["mean_logit_gap"]), requests=len(seqs))


def host_metrics(seen, end: float, setup_s: float) -> Dict[str, float]:
    from bench.window import percentile, tpot_values, ttft_values, window_tokens

    out = {"setup_s": setup_s, "output_tok_s": window_tokens(seen) / end}
    ttft = ttft_values(seen, end)
    tpot = tpot_values(seen, end)
    if ttft:
        out["ttft_p95_ms"] = percentile(ttft, 95) * 1e3
    if tpot:
        out["tpot_p95_ms"] = percentile(tpot, 95) * 1e3
    return out


def run(cell: Cell, *, seed: int, seconds: float, trace: bool,
        device: Dict, engine_hook=None, keep: Optional[Dict] = None) -> Dict:
    """One run of a cell; the result object.  ``engine_hook(engine)`` lets
    the harness's tests break the served path underneath; ``keep``, where
    given, receives the run's requests under ``"requests"``."""
    import jax

    from bench import measure, system, traffic, trace as tr, warmup
    from bench.window import Seen, WindowClock, WindowClosed
    from repro.obs import Clock, Tracer
    from repro.obs.metrics import MetricsRegistry

    cfg, mix = cell.cfg, cell.mix
    peaks = load_json(BENCH / "peaks.json").get(device["kind"])
    if peaks is None:
        raise SystemExit(f"bench: no peaks for device kind {device['kind']!r} in bench/peaks.json")
    counter = CompileCounter()

    t = time.perf_counter()
    params = system.make_params(cfg, seed)
    jax.block_until_ready(params)
    engine = system.make_engine(cfg, params)
    if engine_hook is not None:
        engine_hook(engine)
    t_weights = time.perf_counter()
    system.serve(engine, cfg, system.requests(warmup.plan(mix, cfg, seed)), clock=Clock())
    t_warm = time.perf_counter()

    plan = traffic.generate(mix, seed=seed, seconds=seconds, rate=cell.params["rate"],
                            max_slots=cfg["serving"]["max_slots"], vocab=cfg["vocab_size"])
    reqs = system.requests(plan)
    preload = [r for r, p in zip(reqs, plan) if p.preload]
    tmp = tempfile.TemporaryDirectory(prefix="bench_trace_") if trace else None

    def open_window():
        counter.active = True
        if trace:
            opts = jax.profiler.ProfileOptions()
            opts.host_tracer_level, opts.python_tracer_level = 1, 0
            jax.profiler.start_trace(tmp.name, profiler_options=opts)
            with jax.profiler.TraceAnnotation("bench.window_open"):
                pass

    clock = WindowClock(preload, seconds, reqs, on_open=open_window)
    tracer = Tracer() if trace else None
    registry = MetricsRegistry()
    try:
        system.serve(engine, cfg, reqs, clock=clock, trace=tracer, metrics=registry)
        clock.drained()
    except WindowClosed:
        pass
    finally:
        counter.active = False
        if trace and clock.t_open is not None:
            jax.profiler.stop_trace()
    if clock.t_open is None:
        raise RuntimeError("the window never opened: the preload did not finish")
    setup_s = clock.t_open - PROCESS_START
    if keep is not None:
        keep["requests"] = reqs
    pool = system.pool_use(registry, reqs, cfg["serving"]["page_size"])
    end = float(seconds)
    seen = [Seen.of(r, p.preload, clock) for r, p in zip(reqs, plan)]

    metrics: Dict[str, Dict] = {}
    result: Dict = {}
    if not trace:
        values = host_metrics(seen, end, setup_s)
        for m in cell.end_to_end:
            if m["name"] not in values:
                raise RuntimeError(f"{cell.name}: no value for {m['name']}")
            metrics[m["name"]] = {"value": values[m["name"]], "unit": m["unit"]}
    else:
        data = tr.load(tmp.name)
        tmp.cleanup()
        dev = tr.first_device(data)
        ops = dev["ops"] if dev else []
        anchor = next((s for n, s, _ in data["host"] if n == "bench.window_open"), None)
        if anchor is not None:
            ops = tr.clip(ops, anchor, anchor + end * 1e9)
        busy = tr.busy_ns(ops) * 1e-9 if ops else 0.0
        ctx = measure.Context(cfg=cfg, peaks=peaks, seen=seen, end=end,
                              spans=measure.spans_of(tracer),
                              device=dict(dev, ops=ops) if dev else None,
                              window_s=end, busy_s=busy)
        for m in cell.per_layer:
            v = measure.reader(m["name"])(ctx)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
        host = []
        if anchor is not None:
            host = [(n, anchor + s * 1e9, (e - s) * 1e9) for n, s, e in ctx.spans]
        result["breakdown"] = {
            "device_ops": [[n, s] for n, s in tr.time_by_name(ops)[:10]],
            "idle_gaps": [[n, s] for n, s in tr.label_gaps(tr.idle_gaps(ops), host)],
        }
        device = dict(device, busy_s=busy, window_s=end)
    device = dict(device, memory_peak_bytes=memory_peak())
    attempted = sum(1 for s in seen if s.preload or s.arrival <= end)
    print(f"bench: set-up: start {t - PROCESS_START:.1f} s, weights {t_weights - t:.1f} s, "
          f"warm-up {t_warm - t_weights:.1f} s, preload {clock.t_open - t_warm:.1f} s",
          file=sys.stderr)
    print(f"bench: {cell.name} seed {seed}: setup {setup_s:.3f} s, "
          f"{attempted} requests, {sum(s.in_window for s in seen)} "
          f"tokens in the window, {counter.count} programs traced or compiled in it",
          file=sys.stderr)
    print(f"bench: page pool at the window's end: {pool['in_use']} of {pool['pages']} pages "
          f"in use; {pool['live_requests']} live requests fill {pool['filled_by_live']} "
          f"and reserved {pool['reserved_by_live']}", file=sys.stderr)

    # the reference runs on a chip that holds nothing of the program
    del engine, params
    gc.collect()
    t = time.perf_counter()
    got = check(cell, seed, reqs)
    limit = cell.params["mean_logit_gap"]
    print(f"bench: reference over {got['requests']} requests, {got['tokens']} served "
          f"tokens in {time.perf_counter() - t:.1f} s", file=sys.stderr)
    print(f"check mean_logit_gap {got['value']!r} limit {limit!r}", file=sys.stderr)
    result.update({
        "correct": got["correct"],
        "attempted": attempted, "failed": 0, "metrics": metrics, "device": device,
        "window_compiles": counter.count, "pool": pool,
    })
    result["checks"] = {"mean_logit_gap": {"value": got["value"], "limit": limit}}
    return result


def main(argv: Optional[List[str]] = None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    cell = Cell(args.workload)
    device = device_info(cell.chips)
    enable_compile_cache()
    result = run(cell, seed=args.seed, seconds=args.seconds, trace=bool(args.trace),
                 device=device)
    sys.stdout.flush()
    print(json.dumps(result))


if __name__ == "__main__":
    main()
