"""Observability unit tests: injectable clocks, the span recorder and its
Chrome-trace export (golden file + validator), and the metrics registry
(label discipline, bucket edges, exposition format, exact percentiles)."""
import importlib.util
import json
import math
from pathlib import Path

import pytest

from repro.obs import (
    DEFAULT_BUCKETS,
    NULL_COUNTER,
    NULL_TRACER,
    Clock,
    FakeClock,
    MetricsRegistry,
    NullTracer,
    Tracer,
    percentile,
)
from repro.obs import trace as obs_trace

REPO = Path(__file__).resolve().parents[1]
GOLDEN = Path(__file__).parent / "data" / "trace_golden.json"


def _load_check_trace():
    """Import tools/check_trace.py (a script, not a package module)."""
    spec = importlib.util.spec_from_file_location(
        "check_trace", REPO / "tools" / "check_trace.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


# ---------------------------------------------------------------------------
# clocks
# ---------------------------------------------------------------------------
def test_clock_is_monotonic_and_sleep_guards_nonpositive():
    c = Clock()
    a, b = c.now(), c.now()
    assert b >= a
    c.sleep(0.0)  # must not raise (time.sleep(-x) would)
    c.sleep(-1.0)


def test_fake_clock_tick_and_virtual_sleep():
    c = FakeClock(start=2.0, tick=0.5)
    assert c.now() == 2.0
    assert c.now() == 2.5  # advanced by tick per read
    c.sleep(10.0)  # virtual: no wall time passes
    assert c.now() == 13.0
    c.advance(1.0)
    assert c.now() == 14.5
    with pytest.raises(ValueError, match="backwards"):
        c.advance(-1.0)


def test_fake_clock_default_stands_still():
    c = FakeClock()
    assert c.now() == c.now() == 0.0


# ---------------------------------------------------------------------------
# tracer
# ---------------------------------------------------------------------------
def _sample_tracer() -> Tracer:
    """A deterministic little trace exercising every event shape."""
    clk = FakeClock(tick=0.001)
    tr = Tracer(clock=clk)
    tr.set_track(0, 0, process="engine", thread="serve")
    tr.set_track(1, 3, process="prefill", thread="prefill/3")
    tr.instant("admit", rid=7, prompt=12)
    with tr.span("prefill", rid=7, tokens=12):
        with tr.span("chunk", idx=0):
            pass
    tr.complete("prefill_chunk", 0.25, 0.125, pid=1, tid=3, rid=7)
    tr.instant("ship", ts=0.375, pid=1, tid=3, nbytes=4096)
    tr.instant("retire", rid=7, new_tokens=4)
    return tr


def test_tracer_golden_export(tmp_path):
    """The exported Chrome trace JSON is byte-stable (golden file)."""
    out = tmp_path / "trace.json"
    _sample_tracer().export(str(out))
    assert out.read_text() == GOLDEN.read_text()


def test_tracer_export_is_deterministic_and_valid(tmp_path):
    a, b = tmp_path / "a.json", tmp_path / "b.json"
    _sample_tracer().export(str(a))
    _sample_tracer().export(str(b))
    assert a.read_bytes() == b.read_bytes()
    ct = _load_check_trace()
    bad, summary = ct.check_trace(a)
    assert bad == []
    assert "admit" in summary and "prefill_chunk" in summary


def test_tracer_event_shapes():
    doc = _sample_tracer().to_json()
    assert doc["displayTimeUnit"] == "ms"
    evs = doc["traceEvents"]
    by_ph = {}
    for e in evs:
        by_ph.setdefault(e["ph"], []).append(e)
    # 2 named tracks -> 4 metadata events
    assert len(by_ph["M"]) == 4
    assert {e["args"]["name"] for e in by_ph["M"]} == {
        "engine", "serve", "prefill", "prefill/3"}
    # balanced B/E pair per span, innermost-first E
    assert [e["name"] for e in by_ph["B"]] == ["prefill", "chunk"]
    assert [e["name"] for e in by_ph["E"]] == ["chunk", "prefill"]
    # X carries integer-us dur, i carries a scope
    (x,) = by_ph["X"]
    assert x["dur"] == 125000 and x["ts"] == 250000
    assert all(e["s"] == "t" for e in by_ph["i"])
    # attrs land under args
    admit = next(e for e in by_ph["i"] if e["name"] == "admit")
    assert admit["args"] == {"rid": 7, "prompt": 12}


def test_tracer_us_conversion_integer_when_exact():
    assert Tracer._us(0.001) == 1000 and isinstance(Tracer._us(0.001), int)
    assert Tracer._us(1.5e-9) == 0.002  # sub-us stays fractional


def test_tracer_complete_rejects_negative_duration():
    with pytest.raises(ValueError, match="negative duration"):
        Tracer(clock=FakeClock()).complete("x", 1.0, -0.5)


def test_tracer_accepts_clock_object_or_callable():
    assert Tracer(clock=FakeClock(start=3.0))._now() == 3.0
    assert Tracer(clock=lambda: 9.0)._now() == 9.0


def test_null_tracer_is_allocation_free_noop():
    assert isinstance(NULL_TRACER, NullTracer) and not NULL_TRACER.enabled
    # one cached context manager: the disabled hot path allocates nothing
    assert NULL_TRACER.span("a", rid=1) is NULL_TRACER.span("b")
    with NULL_TRACER.span("a"):
        NULL_TRACER.instant("x", rid=1)
        NULL_TRACER.complete("y", 0.0, -1.0)  # not even validated
        NULL_TRACER.set_track(0, 0, process="p")
    assert NULL_TRACER.events == []


@pytest.mark.parametrize("raises_at", ["enter", "exit"])
def test_tracer_span_leaves_no_annotation_open_when_the_clock_raises(
        monkeypatch, raises_at):
    live = []

    class Annotation:
        def __init__(self, name):
            self.name = name

        def __enter__(self):
            live.append(self.name)

        def __exit__(self, *exc):
            live.remove(self.name)

    class WindowOver(Exception):
        pass

    readings = [] if raises_at == "enter" else [0.5]

    def clock():
        if not readings:
            raise WindowOver
        return readings.pop(0)

    monkeypatch.setattr(obs_trace, "TraceAnnotation", Annotation)
    t = Tracer(clock=clock)
    with pytest.raises(WindowOver):
        with t.span("step"):
            assert live == ["step"]
    assert live == []
    assert t.events == ([] if raises_at == "enter"
                        else [("B", "step", 0.5, 0, 0, {})])


# ---------------------------------------------------------------------------
# check_trace validator
# ---------------------------------------------------------------------------
def _check(tmp_path, events):
    ct = _load_check_trace()
    p = tmp_path / "t.json"
    p.write_text(json.dumps({"traceEvents": events}))
    bad, _ = ct.check_trace(p)
    return bad


def _ev(ph, name, ts, pid=0, tid=0, **extra):
    return {"name": name, "ph": ph, "ts": ts, "pid": pid, "tid": tid, **extra}


def test_check_trace_flags_violations(tmp_path):
    assert _check(tmp_path, [_ev("B", "a", 0)]) != []  # unclosed B
    assert any("unclosed" in b for b in _check(tmp_path, [_ev("B", "a", 0)]))
    # E without B, and mismatched nesting
    assert any("no open B" in b for b in _check(tmp_path, [_ev("E", "a", 0)]))
    bad = _check(tmp_path, [_ev("B", "a", 0), _ev("B", "b", 1),
                            _ev("E", "a", 2), _ev("E", "b", 3)])
    assert any("unbalanced" in b for b in bad)
    # non-monotonic ts on one track; separate tracks are independent
    assert any("non-monotonic" in b for b in _check(
        tmp_path, [_ev("i", "a", 5, s="t"), _ev("i", "b", 4, s="t")]))
    assert _check(tmp_path, [_ev("i", "a", 5, s="t"),
                             _ev("i", "b", 4, tid=1, s="t")]) == []
    # X needs dur >= 0; i needs a scope
    assert any("dur" in b for b in _check(tmp_path, [_ev("X", "a", 0)]))
    assert any("dur" in b for b in _check(tmp_path, [_ev("X", "a", 0, dur=-1)]))
    assert any("scope" in b for b in _check(tmp_path, [_ev("i", "a", 0)]))
    assert any("missing keys" in b for b in _check(tmp_path, [{"ph": "i"}]))


def test_check_trace_rejects_malformed_files(tmp_path):
    ct = _load_check_trace()
    p = tmp_path / "bad.json"
    p.write_text("not json")
    assert ct.check_trace(p)[0]
    p.write_text(json.dumps([1, 2]))
    assert any("traceEvents" in b for b in ct.check_trace(p)[0])
    assert ct.main([str(p)]) == 1
    good = tmp_path / "good.json"
    _sample_tracer().export(str(good))
    assert ct.main([str(good)]) == 0


# ---------------------------------------------------------------------------
# metrics: percentile helper
# ---------------------------------------------------------------------------
def test_percentile_exact_nearest_rank():
    vals = [0.1, 0.2, 0.3, 0.4, 0.5, 0.6, 0.7, 0.8, 0.9, 1.0]
    assert percentile(vals, 50) == 0.5
    assert percentile(vals, 95) == 1.0
    assert percentile(vals, 99) == 1.0
    assert percentile(vals, 0) == 0.1
    assert percentile(vals, 100) == 1.0
    assert percentile([], 50) == 0.0
    assert percentile([3.0], 99) == 3.0
    with pytest.raises(ValueError):
        percentile(vals, 101)


# ---------------------------------------------------------------------------
# metrics: registry
# ---------------------------------------------------------------------------
def test_counter_basics_and_monotonicity():
    reg = MetricsRegistry()
    c = reg.counter("reqs_total", "requests", labels=("stage",))
    c.inc(stage="prefill")
    c.inc(2.5, stage="prefill")
    assert c.value(stage="prefill") == 3.5
    assert c.value(stage="decode") == 0.0
    with pytest.raises(ValueError, match="cannot decrease"):
        c.inc(-1, stage="prefill")


def test_label_discipline():
    reg = MetricsRegistry()
    c = reg.counter("x_total", labels=("stage",))
    with pytest.raises(ValueError, match="labels"):
        c.inc()  # missing declared label
    with pytest.raises(ValueError, match="labels"):
        c.inc(stage="a", extra="b")  # undeclared label
    with pytest.raises(ValueError, match="invalid metric name"):
        reg.counter("bad-name")
    with pytest.raises(ValueError, match="invalid label"):
        reg.counter("ok_total", labels=("bad-label",))


def test_label_cardinality_guard():
    from repro.obs.metrics import Counter

    c = Counter("x_total", labels=("rid",), max_series=3)
    for i in range(3):
        c.inc(rid=i)
    with pytest.raises(ValueError, match="cardinality"):
        c.inc(rid=99)
    c.inc(rid=1)  # existing series still fine


def test_registry_idempotent_and_schema_checked():
    reg = MetricsRegistry()
    a = reg.counter("x_total", "help", labels=("s",))
    assert reg.counter("x_total", labels=("s",)) is a
    with pytest.raises(ValueError, match="already registered"):
        reg.gauge("x_total", labels=("s",))
    with pytest.raises(ValueError, match="already registered"):
        reg.counter("x_total", labels=("other",))
    assert reg.get("x_total") is a and reg.get("missing") is None


def test_gauge_set_inc_and_function_backed():
    reg = MetricsRegistry()
    g = reg.gauge("depth", labels=("q",))
    g.set(4, q="a")
    g.inc(q="a")
    g.dec(0.5, q="a")
    assert g.value(q="a") == 4.5
    box = {"v": 7}
    g.set_function(lambda: box["v"], q="b")
    assert g.value(q="b") == 7.0
    box["v"] = 9  # read at collection time, not at registration
    assert g.value(q="b") == 9.0
    with pytest.raises(ValueError, match="function-backed"):
        g.inc(q="b")


def test_histogram_bucket_edges_le_semantics():
    reg = MetricsRegistry()
    h = reg.histogram("lat_seconds", buckets=(0.01, 0.1, 1.0))
    # le semantics: a value equal to an edge lands in that bucket
    h.observe(0.01)
    h.observe(0.05)
    h.observe(1.0)
    h.observe(50.0)  # +Inf bucket
    assert h.cumulative() == [1, 2, 3, 4]
    assert h.count() == 4
    assert h.sum() == pytest.approx(51.06)
    with pytest.raises(ValueError, match="strictly increasing"):
        reg.histogram("bad_seconds", buckets=(0.1, 0.1))
    with pytest.raises(ValueError, match="strictly increasing"):
        reg.histogram("bad2_seconds", buckets=())


def test_histogram_exact_percentiles_and_default_buckets():
    reg = MetricsRegistry()
    h = reg.histogram("ttft_seconds", labels=("stage",))
    assert h.buckets == DEFAULT_BUCKETS
    for v in (0.010, 0.020, 0.030, 0.040):
        h.observe(v, stage="e")
    assert h.percentile(50, stage="e") == 0.020  # exact, not a bucket edge
    assert h.percentile(99, stage="e") == 0.040
    assert h.percentile(50, stage="missing") == 0.0


def test_expose_prometheus_format_exact():
    reg = MetricsRegistry()
    reg.counter("reqs_total", "served requests", labels=("stage",)).inc(
        3, stage="prefill")
    reg.gauge("depth", "queue depth").set(2)
    reg.histogram("lat_seconds", "latency", buckets=(0.1, 1.0)).observe(0.5)
    assert reg.expose() == (
        "# HELP reqs_total served requests\n"
        "# TYPE reqs_total counter\n"
        'reqs_total{stage="prefill"} 3\n'
        "# HELP depth queue depth\n"
        "# TYPE depth gauge\n"
        "depth 2\n"
        "# HELP lat_seconds latency\n"
        "# TYPE lat_seconds histogram\n"
        'lat_seconds_bucket{le="0.1"} 0\n'
        'lat_seconds_bucket{le="1"} 1\n'
        'lat_seconds_bucket{le="+Inf"} 1\n'
        "lat_seconds_sum 0.5\n"
        "lat_seconds_count 1\n"
    )


def test_expose_escapes_label_values():
    reg = MetricsRegistry()
    reg.counter("x_total", labels=("p",)).inc(p='a"b\\c\nd')
    assert r'x_total{p="a\"b\\c\nd"} 1' in reg.expose()


def test_snapshot_json_shape():
    reg = MetricsRegistry()
    h = reg.histogram("lat_seconds", "latency", labels=("stage",),
                      buckets=(0.1, 1.0))
    for v in (0.05, 0.2, 0.9):
        h.observe(v, stage="e")
    reg.gauge("depth").set_function(lambda: 5)
    snap = reg.snapshot()
    json.dumps(snap)  # JSON-able (function gauges resolved)
    (series,) = snap["lat_seconds"]["series"]
    assert series["labels"] == {"stage": "e"}
    assert series["count"] == 3 and series["p50"] == 0.2 and series["p99"] == 0.9
    assert series["buckets"] == {"0.1": 1, "1": 3, "inf": 3}
    assert snap["depth"]["series"][0]["value"] == 5.0
    assert math.isfinite(series["sum"])


def test_null_metrics_accept_everything():
    NULL_COUNTER.inc(5, anything="goes")
    NULL_COUNTER.observe(1.0)
    NULL_COUNTER.set(2)
    NULL_COUNTER.set_function(lambda: 1)
    assert NULL_COUNTER.value() == 0.0
    assert NULL_COUNTER.count() == 0
    assert NULL_COUNTER.percentile(99) == 0.0
