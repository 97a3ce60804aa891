"""The per-layer readers of the engine loop's tracer spans against hand
counts, on spans as ``measure.spans_of`` takes them from a tracer.

    PYTHONPATH=src python -m pytest -q bench/tests
"""
from types import SimpleNamespace

import pytest

from bench import measure


def events(*spans):
    """B/E events of nested ``(name, start, end, children...)`` spans, as an
    ``obs.Tracer`` records them (seconds on the window's clock)."""
    out = []
    for name, start, end, *children in spans:
        out.append(("B", name, start, 0, 0, {}))
        out.extend(events(*children))
        out.append(("E", name, end, 0, 0, None))
    return out


def context(evs, end=10.0):
    spans = measure.spans_of(SimpleNamespace(events=evs))
    return measure.Context(cfg={}, peaks={}, seen=[], end=end, spans=spans)


def step(t, dispatch, sync, table=0.0):
    """A decode iteration at ``t``: admission 1 ms, the step, bookkeeping 2 ms."""
    kids = [("page_table", t + 0.001, t + 0.001 + table)] if table else []
    d0 = t + 0.001 + table
    kids += [("decode_dispatch", d0, d0 + dispatch),
             ("decode_sync", d0 + dispatch, d0 + dispatch + sync)]
    e = d0 + dispatch + sync
    return [("sched_admit", t, t + 0.001), ("decode_step", t + 0.001, e, *kids),
            ("sched_post_decode", e, e + 0.002)]


def prefill(start, forward, write, sync):
    return ("prefill", start, start + forward + write + sync,
            ("prefill_forward", start, start + forward),
            ("page_write", start + forward, start + forward + write),
            ("prefill_sync", start + forward + write, start + forward + write + sync))


# the window opens after the preloaded request's prefill, which ended while
# the window's clock stood at 0 and is no span of the window
PRELOAD = prefill(0.0, 0.0, 0.0, 0.0)


def test_readers_against_hand_counts():
    evs = events(
        PRELOAD,
        *step(0.0, dispatch=0.004, sync=0.9, table=0.003),
        ("sched_admit", 0.91, 0.912), prefill(0.912, 0.05, 0.01, 6.4),
        *step(7.5, dispatch=0.006, sync=0.9),
        ("sched_admit", 8.5, 8.501), prefill(8.501, 0.04, 0.01, 5.45),
        *step(14.1, dispatch=0.005, sync=0.9, table=0.002))
    ctx = context(evs, end=20.0)
    assert sum(n == "prefill" for n, _, _ in ctx.spans) == 2  # the preload is left out
    assert measure.reader("prefill_stall_ms")(ctx) == pytest.approx((6460 + 5500) / 2)
    # per step: admission 1 ms, dispatch, table, bookkeeping 2 ms; plus the
    # two admission passes that admitted the arrivals (2 ms and 1 ms)
    host = 3 * (1 + 2) + (4 + 6 + 5) + (3 + 2) + (2 + 1)
    assert measure.reader("loop_host_ms")(ctx) == pytest.approx(host / 3)
    assert measure.reader("decode_step_ms")(ctx) == pytest.approx(
        (907 + 906 + 907) / 3)


def test_a_window_without_decode_steps_or_host_phases_reads_nothing():
    only_prefill = context(events(PRELOAD, ("sched_admit", 1.0, 1.001),
                                  prefill(1.001, 0.05, 0.01, 6.0)))
    assert measure.reader("loop_host_ms")(only_prefill) is None
    assert measure.reader("prefill_stall_ms")(only_prefill) == pytest.approx(6060)
    # a program that records decode_step and prefill, but no host phase and
    # no readback inside prefill: nothing to read for either metric
    bare = context(events(("decode_step", 0.0, 0.9), ("prefill", 0.9, 0.95),
                          ("decode_step", 0.95, 1.85)))
    assert measure.reader("loop_host_ms")(bare) is None
    assert measure.reader("prefill_stall_ms")(bare) is None
    assert measure.reader("decode_step_ms")(bare) == pytest.approx(900)
