"""Observability wired through the serving stack: fake-clock exact latency
stats, bit-identical outputs with tracing on vs off, deterministic disagg
traces, span coverage, and the pool/cache/router metric exports."""
import glob
import importlib.util
import json
from pathlib import Path

import jax
import pytest
from jax.profiler import ProfileData

from repro.configs import get_config
from repro.models import transformer as tf
from repro.obs import FakeClock, MetricsRegistry, Tracer
from repro.obs import trace as obs_trace
from repro.serving.disagg import serve_disagg
from repro.serving.engine import Engine, ServeConfig
from repro.serving.pagepool import KVPagePool, PagePoolConfig, install_pool_metrics
from repro.serving.prefixcache import PrefixCache, install_cache_metrics
from repro.serving.scheduler import Request, SchedulerConfig

REPO = Path(__file__).resolve().parents[1]


def _engine(**kw):
    cfg = get_config("llama3_2_3b").reduced()
    params = tf.init_params(jax.random.PRNGKey(0), cfg)
    kw.setdefault("max_len", 64)
    kw.setdefault("max_new_tokens", 4)
    return Engine(params, cfg, ServeConfig(**kw)), cfg


def _reqs(arrivals=(0.0, 0.0)):
    return [Request(rid=i, prompt=[5 + i, 6, 7, 8], max_new_tokens=4,
                    arrival=a) for i, a in enumerate(arrivals)]


def _check_trace(path):
    spec = importlib.util.spec_from_file_location(
        "check_trace", REPO / "tools" / "check_trace.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.check_trace(Path(path))


# ---------------------------------------------------------------------------
# fake-clock serve: exact latency stats, no sleeps
# ---------------------------------------------------------------------------
def test_serve_fake_clock_exact_ttft_and_latency():
    eng, _ = _engine()
    # r1 arrives 5 virtual seconds after r0; tick=0 makes every measured
    # duration exactly zero, so the only time that passes is the idle wait
    rep = eng.serve(_reqs(arrivals=(0.0, 5.0)), clock=FakeClock())
    r0, r1 = rep.requests
    assert r0.first_token_time == 0.0 and r0.finish_time == 0.0
    assert r1.first_token_time == 5.0 and r1.finish_time == 5.0
    assert rep.wall_time == 5.0  # the serve loop slept to the arrival, virtually
    assert rep.ttft_values() == [0.0, 0.0]
    assert rep.latency_values() == [0.0, 0.0]
    assert rep.mean_ttft == 0.0 and rep.latency_p99 == 0.0


def test_serve_report_percentiles_exact():
    eng, _ = _engine()
    rep = eng.serve(_reqs(arrivals=(0.0, 0.0, 0.0, 2.0)), clock=FakeClock())
    # all requests admitted at their arrival with zero-duration compute:
    # latency == 0 exactly, and the percentile machinery is nearest-rank
    assert rep.ttft_p50 == rep.ttft_p95 == rep.ttft_p99 == 0.0
    assert rep.tpot_values() == [0.0] * 4  # 4 tokens each -> 3 gaps, all zero
    with pytest.raises(ValueError):
        rep.ttft_percentile(101)


# ---------------------------------------------------------------------------
# tracing on vs off: identical outputs, sane spans
# ---------------------------------------------------------------------------
def test_serve_outputs_bit_identical_tracing_on_vs_off():
    eng, _ = _engine()
    base = eng.serve(_reqs())
    tracer, registry = Tracer(), MetricsRegistry()
    traced = eng.serve(_reqs(), trace=tracer, metrics=registry,
                       clock=FakeClock())
    assert [r.out_tokens for r in traced.requests] == \
        [r.out_tokens for r in base.requests]
    assert tracer.events  # and the traced run actually recorded


def test_serve_trace_span_coverage_and_validity(tmp_path):
    eng, _ = _engine()
    tracer = Tracer()
    eng.serve(_reqs(arrivals=(0.0, 1.0)), trace=tracer, clock=FakeClock())
    names = {e[1] for e in tracer.events}
    assert {"admit", "prefill", "decode_step", "retire"} <= names
    out = tmp_path / "trace.json"
    tracer.export(str(out))
    assert _check_trace(out)[0] == []
    # admits land on the serve-relative timeline: r1's admit at its arrival
    admits = [e for e in tracer.events if e[1] == "admit"]
    assert [e[5]["rid"] for e in admits] == [0, 1]
    assert admits[1][2] == 1.0


# ---------------------------------------------------------------------------
# the engine loop's host phases: child spans, profiler annotations, and a
# disabled tracer that costs nothing
# ---------------------------------------------------------------------------
PREFILL_CHILDREN = ("prefill_forward", "page_write", "prefill_sync")
DECODE_CHILDREN = ("page_table", "decode_dispatch", "decode_sync")
LOOP_SPANS = ("sched_admit", "prefill", "decode_step", "sched_post_decode")


def _spans(tracer):
    """Each B/E pair as (name, start, end, attrs, parent's index), in order."""
    out, stack = [], []
    for ph, name, ts, _, _, attrs in tracer.events:
        if ph == "B":
            stack.append(len(out))
            out.append([name, ts, None, attrs, stack[-2] if len(stack) > 1 else None])
        elif ph == "E":
            i = stack.pop()
            assert out[i][0] == name
            out[i][2] = ts
    assert not stack
    return out


class _CountingClock(FakeClock):
    def __init__(self, tick=0.0):
        super().__init__(tick=tick)
        self.reads = 0

    def now(self):
        self.reads += 1
        return super().now()


def test_serve_loop_child_spans_nest_in_their_parents():
    eng, _ = _engine()
    tracer = Tracer()
    # tick > 0: every reading is distinct, so nesting shows strictly
    rep = eng.serve(_reqs(arrivals=(0.0, 1.0)), trace=tracer,
                    clock=FakeClock(tick=0.25))
    spans = _spans(tracer)
    names = {s[0] for s in spans}
    assert set(PREFILL_CHILDREN + DECODE_CHILDREN + LOOP_SPANS) <= names
    for i, (name, start, end, _, parent) in enumerate(spans):
        if name in PREFILL_CHILDREN + DECODE_CHILDREN:
            pname, pstart, pend = spans[parent][:3]
            assert pname == ("prefill" if name in PREFILL_CHILDREN else "decode_step")
            assert pstart < start < end < pend
        elif name in LOOP_SPANS:
            assert parent is None, name  # the loop's phases do not overlap
    children = {i: [spans[j][0] for j in range(len(spans)) if spans[j][4] == i]
                for i in range(len(spans))}
    steps = [i for i, s in enumerate(spans) if s[0] == "decode_step"]
    assert len(steps) == rep.decode_steps
    for i in steps:
        kids = children[i]
        assert kids.count("decode_sync") == 1 and kids.count("decode_dispatch") == 1
        assert kids[-1] == "decode_sync" and kids.count("page_table") <= 1
    # the page table is rebuilt only after an admission or a retirement
    assert 0 < sum(s[0] == "page_table" for s in spans) < len(steps)
    prefills = [i for i, s in enumerate(spans) if s[0] == "prefill"]
    assert len(prefills) == len(rep.requests)
    for i in prefills:
        assert children[i] == list(PREFILL_CHILDREN)
        req = rep.requests[spans[i][3]["rid"]]
        # the readback is inside the span: the first token comes after it
        assert req.first_token_time > spans[i][2]


def test_tracer_spans_land_in_the_profiler_host_plane(tmp_path):
    eng, _ = _engine()
    tracer = Tracer()
    eng.serve(_reqs(), trace=tracer, profile_dir=str(tmp_path))
    (path,) = glob.glob(f"{tmp_path}/**/*.xplane.pb", recursive=True)
    host = [e.name for plane in ProfileData.from_file(path).planes
            if plane.name == "/host:CPU" for line in plane.lines for e in line.events]
    recorded = [s[0] for s in _spans(tracer)]
    assert set(PREFILL_CHILDREN + DECODE_CHILDREN + LOOP_SPANS) <= set(recorded)
    for name in set(recorded):
        assert host.count(name) == recorded.count(name), name


@pytest.mark.parametrize("metrics", [False, True])
def test_null_tracer_opens_no_annotation_and_reads_no_extra_clock(monkeypatch, metrics):
    entered = []

    class Counting:
        def __init__(self, name):
            self.name = name

        def __enter__(self):
            entered.append(self.name)

        def __exit__(self, *exc):
            pass

    monkeypatch.setattr(obs_trace, "TraceAnnotation", Counting)
    eng, _ = _engine()
    clock = _CountingClock()
    rep = eng.serve(_reqs(), clock=clock,
                    metrics=MetricsRegistry() if metrics else None)
    assert entered == []
    # the loop's readings with tracing off, as they were before the host
    # phases had spans: serve's start and end, one per admission pass (3),
    # one per first token (2) and one per decode step's tokens (3); metrics
    # time each prefill (2 x 2) and each decode step (3 x 2) besides
    assert rep.decode_steps == 3
    assert clock.reads == (20 if metrics else 10)
    eng.serve(_reqs(), trace=Tracer(), clock=FakeClock())
    assert "decode_sync" in entered  # the patched name is the one spans use


def test_serve_speculative_trace_has_draft_verify_spans(tmp_path):
    eng, _ = _engine()
    tracer = Tracer()
    rep = eng.serve(_reqs(), trace=tracer, clock=FakeClock(),
                    speculate_k=2, draft_policy="bf16")
    names = {e[1] for e in tracer.events}
    assert {"draft", "verify", "retire"} <= names
    out = tmp_path / "spec.json"
    tracer.export(str(out))
    assert _check_trace(out)[0] == []
    assert rep.speculate_k == 2


def test_serve_metrics_registry_populated():
    eng, _ = _engine()
    registry = MetricsRegistry()
    rep = eng.serve(_reqs(), metrics=registry, clock=FakeClock())
    assert registry.get("serve_ttft_seconds").count(stage="engine") == 2
    assert registry.get("serve_tokens_total").value(stage="engine") == \
        rep.new_tokens
    assert registry.get("serve_decode_step_seconds").count(stage="engine") == \
        rep.decode_steps
    # pool drained at end of serve: all pages free, none live
    pool_pages = registry.get("pool_pages")
    free = pool_pages.value(stage="engine", replica="0", state="free")
    assert free > 0
    assert pool_pages.value(stage="engine", replica="0", state="live") == 0
    # exposition renders end to end
    text = registry.expose()
    assert "serve_ttft_seconds_bucket" in text and "pool_pages{" in text


# ---------------------------------------------------------------------------
# disagg: deterministic virtual-time traces
# ---------------------------------------------------------------------------
def _disagg_trace():
    eng, _ = _engine()
    tracer = Tracer()
    registry = MetricsRegistry()
    rep = serve_disagg(eng, _reqs(arrivals=(0.0, 0.5)),
                       clock=FakeClock(tick=0.001), trace=tracer,
                       metrics=registry, n_prefill=2, n_decode=2,
                       chunk_tokens=2, max_slots=2)
    return rep, tracer, registry


def test_disagg_trace_deterministic_and_valid(tmp_path):
    rep1, tr1, _ = _disagg_trace()
    rep2, tr2, _ = _disagg_trace()
    # FakeClock(tick) makes every measured duration an exact constant, the
    # event interleave is deterministic, so two runs export identical bytes
    j1, j2 = tmp_path / "1.json", tmp_path / "2.json"
    tr1.export(str(j1))
    tr2.export(str(j2))
    assert j1.read_bytes() == j2.read_bytes()
    assert _check_trace(j1)[0] == []
    assert [r.out_tokens for r in rep1.requests] == \
        [r.out_tokens for r in rep2.requests]
    # full fleet span taxonomy on the three processes
    names = {e[1] for e in tr1.events}
    assert {"route", "prefill_chunk", "ship", "insert", "decode_step",
            "retire"} <= names
    pids = {e[3] for e in tr1.events}
    assert pids == {0, 1, 2}  # router / prefill / decode


def test_disagg_virtual_clock_makes_stats_exact():
    rep, _, registry = _disagg_trace()
    # every measured duration is exactly one tick (1 ms); busy seconds are
    # event counts * tick, to the float
    assert rep.prefill_busy == pytest.approx(0.001 * round(rep.prefill_busy / 0.001))
    assert rep.decode_busy == pytest.approx(0.002 * round(rep.decode_busy / 0.002))
    assert rep.wall_time < 1.0  # virtual: far below any real serve run
    # per-stage registry exports
    assert registry.get("stage_busy_seconds").value(stage="prefill") == \
        rep.prefill_busy
    assert registry.get("disagg_shipments_total").value() == rep.shipments
    assert registry.get("serve_ttft_seconds").count(stage="disagg") == 2
    snap = registry.snapshot()
    assert snap["router_placements"]["series"][0]["value"] == 2.0
    assert rep.decode_stage_values() == [
        r.finish_time - r.first_token_time for r in rep.requests]
    assert rep.decode_stage_percentile(50) >= 0.0


# ---------------------------------------------------------------------------
# pool / cache metric installers (unit-level)
# ---------------------------------------------------------------------------
def test_install_pool_metrics_tracks_events():
    cfg = get_config("llama3_2_3b").reduced()
    pool = KVPagePool(cfg, PagePoolConfig(num_pages=6, page_size=8, max_len=48))
    reg = MetricsRegistry()
    install_pool_metrics(reg, pool, stage="t", replica="1")
    pages = reg.get("pool_pages")
    assert pages.value(stage="t", replica="1", state="free") == 6
    pool.allocate(0, 17)  # 3 pages
    assert pages.value(stage="t", replica="1", state="free") == 3
    assert pages.value(stage="t", replica="1", state="live") == 3
    ev = reg.get("pool_page_events_total")
    assert ev.value(stage="t", replica="1", event="alloc") == 3
    pool.append(0, 25)
    pool.truncate(0, 17)
    pool.release(0)
    assert ev.value(stage="t", replica="1", event="append") == 1
    assert ev.value(stage="t", replica="1", event="truncate") == 1
    assert ev.value(stage="t", replica="1", event="release") == 3
    assert pages.value(stage="t", replica="1", state="free") == 6


def test_install_cache_metrics_tracks_inserts():
    cfg = get_config("llama3_2_3b").reduced()
    pool = KVPagePool(cfg, PagePoolConfig(num_pages=8, page_size=4, max_len=32))
    cache = PrefixCache(pool)
    reg = MetricsRegistry()
    install_cache_metrics(reg, cache, stage="t")
    pool.allocate(0, 8)
    cache.insert([1, 2, 3, 4, 5, 6, 7, 8], pool.sequence_pages(0))
    assert reg.get("cache_radix_nodes").value(stage="t", replica="0") == \
        cache.nodes
    assert cache.nodes == 2
    # one event per publish call (the full path), however many nodes it added
    assert reg.get("cache_events_total").value(
        stage="t", replica="0", event="insert") == 1


def test_multiple_pool_listeners_coexist():
    cfg = get_config("llama3_2_3b").reduced()
    pool = KVPagePool(cfg, PagePoolConfig(num_pages=4, page_size=8, max_len=32))
    seen = []
    pool.add_listener(lambda ev, n: seen.append((ev, n)))
    install_pool_metrics(MetricsRegistry(), pool)
    pool.allocate(0, 8)
    assert seen == [("alloc", 1)]
