"""CPU tests of the benchmark's own arithmetic: traffic, the window's
metrics, operation and byte counts, and the trace reduction.

    PYTHONPATH=src python -m pytest -q bench/tests
"""
import json
from pathlib import Path

import numpy as np
import pytest

from bench import flops, trace as tr, traffic, warmup
from bench.run import load_json
from bench.window import (Seen, WindowClock, WindowClosed, percentile, queue_wait_values,
                          tpot_values, ttft_values, window_tokens)

ROOT = Path(__file__).resolve().parents[2]
DATA = Path(__file__).resolve().parent / "data"


def config(name):
    return load_json(ROOT / "bench" / "configs" / f"{name}.json")


# -- traffic ------------------------------------------------------------------
@pytest.mark.parametrize("mix", ["chat", "chat_overload", "code_sessions"])
def test_same_seed_same_requests(mix):
    m = traffic.load(mix)
    a = traffic.generate(m, seed=2**33 + 5, seconds=30, rate=0.5, max_slots=16, vocab=32256)
    b = traffic.generate(m, seed=2**33 + 5, seconds=30, rate=0.5, max_slots=16, vocab=32256)
    c = traffic.generate(m, seed=6, seconds=30, rate=0.5, max_slots=16, vocab=32256)
    assert [(r.prompt, r.max_new_tokens, r.arrival) for r in a] == \
           [(r.prompt, r.max_new_tokens, r.arrival) for r in b]
    assert [r.prompt for r in a] != [r.prompt for r in c]
    assert all(0 <= r.arrival < 30 for r in a)
    # another seed does the same work: the schedule is fixed, the tokens differ
    assert [(len(r.prompt), r.max_new_tokens, r.arrival) for r in a] == \
           [(len(r.prompt), r.max_new_tokens, r.arrival) for r in c]


@pytest.mark.parametrize("dist,median", [
    ({"dist": "lognormal", "median": 1020, "sigma": 0.7, "min": 16, "max": 1536}, 1020),
    ({"dist": "lognormal", "median": 129, "sigma": 1.0, "min": 4, "max": 512}, 129),
    ({"dist": "lognormal", "median": 13, "sigma": 1.0, "min": 1, "max": 128}, 13),
])
def test_lengths_match_stated_medians_and_clips(dist, median):
    x = traffic.lengths(dist, 401, np.random.default_rng(0))
    assert x.min() >= dist["min"] and x.max() <= dist["max"]
    assert abs(np.median(x) - median) <= 0.02 * median + 1


def test_rate_is_exact_and_first_tokens_distinct():
    m = traffic.load("chat")
    reqs = traffic.generate(m, seed=9, seconds=40, rate=0.5, max_slots=24, vocab=151936)
    pre = [r for r in reqs if r.preload]
    win = [r for r in reqs if not r.preload]
    assert len(pre) == round(m["preload_share"] * 24) and len(win) == 20
    assert len({r.prompt[0] for r in reqs}) == len(reqs)


def test_sweep_staircase_shifts_each_rate_into_its_own_step():
    from bench import sweep

    m = traffic.load("chat")
    plan = sweep.staircase(m, seed=2**31 + 9, seconds=50, rates=[0.1, 0.2, 0.3],
                           max_slots=32, vocab=151936)
    pre = [p for p in plan if p.preload]
    assert len(pre) == round(m["preload_share"] * 32)
    assert [p.rid for p in plan] == list(range(len(plan)))
    assert len({p.prompt[0] for p in plan}) == len(plan)
    for i, n in enumerate([5, 10, 15]):
        step = [p for p in plan if not p.preload and i * 50 < p.arrival <= (i + 1) * 50]
        assert len(step) == n
    # the queue: arrived and not admitted
    s = [seen(10.0, start=12.0), seen(20.0), seen(30.0, start=80.0), seen(60.0), seen(70.0)]
    assert [sweep.queue_at(s, t) for t in (11.0, 25.0, 50.0, 75.0)] == [1, 1, 2, 4]
    lines = sweep.steps(s, 50.0, [0.1, 0.2])
    assert [(x["queue_start"], x["queue_mid"], x["queue_end"], x["held_so_far"])
            for x in lines] == [(0, 1, 2, False), (2, 4, 3, False)]
    # a queue that rose and fell back within a step held it
    s = [seen(10.0, start=30.0), seen(20.0, start=40.0), seen(60.0, start=61.0)]
    assert [x["held_so_far"] for x in sweep.steps(s, 50.0, [0.1, 0.2])] == [True, True]
    # the queue a step starts with counts: it grew 2 -> 4 in the second step
    s = [seen(30.0, start=52.0), seen(40.0, start=54.0), seen(60.0), seen(70.0),
         seen(80.0), seen(90.0)]
    assert [x["held_so_far"] for x in sweep.steps(s, 50.0, [0.1, 0.2])] == [False, False]
    s = [seen(30.0), seen(40.0, start=45.0), seen(60.0, start=99.0), seen(70.0), seen(80.0)]
    assert [x["held_so_far"] for x in sweep.steps(s, 50.0, [0.1, 0.2])] == [True, False]


def test_sessions_grow_their_prompt():
    m = traffic.load("code_sessions")
    reqs = traffic.generate(m, seed=4, seconds=60, rate=0.4, max_slots=16, vocab=32256)
    by = {}
    for r in reqs:
        by.setdefault(r.session, []).append(r)
    grown = 0
    for rs in by.values():
        for a, b in zip(rs, rs[1:]):
            assert b.prompt[: len(a.prompt)] == a.prompt
            assert 16 <= len(b.prompt) - len(a.prompt) <= 256 and b.arrival > a.arrival
            grown += 1
    assert grown > 0
    assert max(len(r.prompt) for r in reqs) <= m["max_prompt"]


def test_warmup_reaches_every_suffix_shape():
    # the session mix on DeepSeek-Coder's widths, with room for its prompts
    cfg = config("deepseek_coder_33b_pp4")
    cfg["serving"].update(max_len=2176, num_pages=3072)
    full, pairs = warmup.shapes(traffic.load("code_sessions"), cfg)
    assert full == {64, 128, 256, 512, 1024, 2048}
    assert pairs == {(s, p) for s in (16, 32, 64, 128, 256, 512) for p in (4, 8, 16, 32, 64, 128)}
    plan = warmup.plan(traffic.load("code_sessions"), cfg, seed=1)
    assert len(plan) == 6 + 6 + 36 + 1 and all(len(p.prompt) <= 2048 for p in plan)


# -- the window ---------------------------------------------------------------
def seen(arrival, *, preload=False, tokens=0, start=None, first=None, finish=None,
         prompt=100, cached=0, k=0, t0=0.0, t1=0.0, at_open=0):
    return Seen(arrival, prompt, preload, at_open, tokens, start, first, finish, cached,
                k, t0, t1)


def test_censored_ttft_and_queue_wait():
    s = [seen(1.0, start=1.5, first=2.0, tokens=3),
         seen(5.0, start=9.0),                 # admitted, no first token yet
         seen(8.0),                            # never admitted
         seen(0.0, preload=True, first=0.0, tokens=5)]
    assert ttft_values(s, 10.0) == [1.0, 5.0, 2.0]
    assert queue_wait_values(s, 10.0) == [0.5, 4.0, 2.0]
    assert percentile([1.0, 5.0, 2.0], 95) == 5.0
    assert percentile(list(range(1, 21)), 95) == 19


def test_tpot_and_window_tokens():
    s = [seen(1.0, first=2.0, finish=6.0, k=5, t0=2.0, t1=6.0),  # (6 - 2) / 4
         seen(3.0, first=4.0, k=3, t0=4.0, t1=8.0),   # still decoding: to the end, (12 - 4) / 2
         seen(4.0, first=5.0, k=1, t0=5.0, t1=5.0),   # waits for its second: (12 - 5) / 1
         seen(4.5, first=5.0, finish=5.0, k=1, t0=5.0, t1=5.0),  # one token, no gap
         seen(0.0, preload=True, at_open=2, k=6, t0=0.5, t1=10.5),  # from the start, 12 / 6
         seen(0.0, preload=True, at_open=3, finish=3.0, k=3, t0=1.0, t1=3.0),  # 3 / 3
         seen(0.0, preload=True, at_open=2),          # stalled all window: 12 / 1
         seen(0.0, preload=True, at_open=5, finish=0.0),  # done before the window
         seen(11.0)]                                  # never admitted
    assert tpot_values(s, 12.0) == [1.0, 4.0, 7.0, 2.0, 1.0, 12.0]
    assert window_tokens(s) == 5 + 3 + 1 + 1 + 6 + 3


class Req:
    def __init__(self, rid, first=None):
        self.rid, self.first_token_time, self.out_tokens = rid, first, []


def test_window_clock_stamps_tokens_with_their_step():
    pre = [Req(0), Req(1)]
    clock = WindowClock(pre, 0.2, pre, on_open=lambda: None)
    assert clock.now() == 0.0 and clock.t_open is None
    pre[0].first_token_time = pre[1].first_token_time = 0.0
    pre[0].out_tokens = [1, 2]
    assert clock.now() == 0.0 and clock.t_open is not None
    pre[0].out_tokens.append(3)  # a step the still clock timed: not in the window
    t1 = clock.now()
    assert clock.tokens_at_open == {0: 3, 1: 0} and clock.token_times == {}
    pre[0].out_tokens.append(4)  # the step read the clock at t1, then appended
    pre[1].out_tokens.append(9)
    clock.now()
    assert clock.token_times == {0: [1, t1, t1], 1: [1, t1, t1]}
    with pytest.raises(WindowClosed):
        clock.sleep(1.0)
        clock.now()


# -- operations and bytes -----------------------------------------------------
def test_counts_at_qwen3_8b_shapes():
    cfg = config("qwen3_8b")
    assert flops.layer_params(cfg) == 4096 * (4096 + 2 * 1024 + 4096 + 3 * 12288)
    assert flops.kv_bytes_per_token(cfg) * 36 == 41472
    f, b = flops.matmul_call(32, 4096, 12288)
    assert (f, b) == (2 * 32 * 4096 * 12288,
                      4096 * 12288 // 2 + 4096 * 12288 // 16 + 2 * 32 * 4096 + 2 * 32 * 12288)
    # one decode step of one live slot at length 1000
    f, b = flops.decode_steps(cfg, 1, 1, 1000)
    weights_b = 36 * flops.layer_params(cfg) * 9 // 16
    assert b == weights_b + 2 * 151936 * 4096 + 41472 * 1000
    assert f == 2 * (36 * flops.layer_params(cfg) + 151936 * 4096) + 4 * 32 * 128 * 1000 * 36


def test_counts_at_deepseek_coder_shapes():
    cfg = config("deepseek_coder_33b_pp4")
    assert flops.layer_params(cfg) == 530_317_312
    assert flops.kv_bytes_per_token(cfg) * 16 == 18432
    # prefill of 3 tokens after 2 cached: attention over 3, 4 and 5 positions
    f = flops.prefill(cfg, 3, 2)
    assert f == (2 * 3 * 530_317_312 * 16 + 2 * 32256 * 7168 + 4 * 56 * 128 * 12 * 16)
    # two steps of 16 slots at 300 positions each: weights twice, KV once
    f, b = flops.decode_steps(cfg, 2, 32, 32 * 300)
    assert b == 2 * (16 * 530_317_312 * 9 // 16 + 2 * 32256 * 7168) + 18432 * 32 * 300


# -- trace reduction ----------------------------------------------------------
def test_busy_gaps_and_kernel_time():
    ops = [("fusion.1", 0, 10), ("razer_matmul.3", 5, 10), ("fusion.2", 30, 5),
           ("razer_matmul.4", 50, 10), ("copy.7", 100, 1)]
    modules = [("jit__paged_decode_step(1)", 0, 40), ("jit__prefill(2)", 45, 20)]
    assert tr.merge([(0, 10), (5, 15), (30, 35)]) == [(0, 15), (30, 35)]
    assert tr.busy_ns(ops) == 15 + 5 + 10 + 1
    assert tr.clip(ops, 7, 55) == [("fusion.1", 7, 3), ("razer_matmul.3", 7, 8),
                                   ("fusion.2", 30, 5), ("razer_matmul.4", 50, 5)]
    assert tr.idle_gaps(ops) == [(60, 40), (15, 15), (35, 15)]
    decode = tr.modules_named(modules, "_paged_decode_step")
    assert tr.kernel_ns(ops, "razer_matmul", decode) == (10, 1)
    assert tr.time_by_name(ops)[0] == ("razer_matmul", 20e-9)
    host = [("decode_step", 55, 50), ("prefill", 10, 30)]
    labels = tr.label_gaps(tr.idle_gaps(ops), host)
    assert [n for n, _ in labels] == ["decode_step", "prefill", "prefill"]
    assert [s for _, s in labels] == pytest.approx([40e-9, 15e-9, 15e-9])


def test_reduction_of_a_recorded_chip_trace():
    data = json.loads((DATA / "trace_fixture.json").read_text())
    dev = data["devices"][0]
    busy = tr.busy_ns(dev["ops"])
    span = max(s + d for _, s, d in dev["ops"]) - min(s for _, s, _ in dev["ops"])
    assert 0 < busy <= span
    decode = tr.modules_named(dev["modules"], "_paged_decode_step")
    assert decode, "the recorded window holds a decode step"
    t, n = tr.kernel_ns(dev["ops"], "razer_matmul", decode)
    t_att, n_att = tr.kernel_ns(dev["ops"], "razer_paged_attention", decode)
    # each layer of the step runs its 7 packed projections around one paged
    # attention; the recording stops inside a layer
    assert n_att >= 1 and 7 * (n_att - 1) <= n <= 7 * (n_att + 1)
    assert 0 < t + t_att <= sum(d for _, _, d in decode)
    names = [k for k, _ in tr.time_by_name(dev["ops"])]
    assert "razer_matmul" in names and "while" not in names
    assert tr.label_gaps(tr.idle_gaps(dev["ops"]), [])[0][0] == "idle"


def test_column_sliced_packing_is_bit_identical():
    import jax
    import jax.numpy as jnp

    from bench import system

    spec = system.serve_policy().resolve("layers_0/mlp/gate")
    w = jax.random.normal(jax.random.key(3), (64, 1024), jnp.float32)
    w = w.at[5, 700].set(9.0)  # the largest magnitude sits in one slice only
    whole = spec.pack(w)
    sliced = jax.jit(lambda x: system.pack(spec, x, chunk=64 * 256))(w)
    assert sliced.shape == whole.shape
    for a, b in ((sliced.codes, whole.codes), (sliced.scale_meta, whole.scale_meta),
                 (sliced.tensor_scale, whole.tensor_scale)):
        assert jnp.array_equal(a, b)


def test_decode_readers_against_hand_counts():
    from bench import flops, measure

    cfg = config("qwen3_8b")
    peaks = load_json(ROOT / "bench" / "peaks.json")["TPU v5 lite"]
    layers, ms = cfg["num_hidden_layers"], 1e6  # ns per ms
    ops, modules = [], []
    for step in range(2):  # two decode programs of 900 ms: 7 x 5 ms + 1 x 10 ms per layer
        t0 = step * 1000 * ms
        modules.append(("jit__paged_decode_step(1)", t0, 900 * ms))
        for i in range(layers):
            for j in range(7):
                ops.append((f"razer_matmul.{j}", t0 + (i * 20 + j) * ms, 5 * ms))
            ops.append(("razer_paged_attention.1", t0 + (i * 20 + 8) * ms, 10 * ms))
    # 16 requests of 300 prompt tokens that each gained 2 tokens in the 2 steps
    seen = [Seen(0.0, 300, True, 10, 12, None, 0.0, None, 0, 2, 0.5, 1.5)] * 16
    spans = [("decode_step", 0.0, 0.9), ("decode_step", 1.0, 1.9)]
    ctx = measure.Context(cfg=cfg, peaks=peaks, seen=seen, end=2.0, spans=spans,
                          device={"ops": ops, "modules": modules}, window_s=2.0,
                          busy_s=1.8)
    assert ctx.decode_work() == (32, 16 * (310 + 311), 2)
    need_mm = layers * sum(max(f / peaks["bf16_flops"], b / peaks["hbm_bytes_per_s"])
                           for f, b in (flops.matmul_call(32, k, n)
                                        for k, n in flops.matrix_shapes(cfg).values()))
    got = measure.reader("razer_matmul_roofline")(ctx)
    assert got == pytest.approx(100 * need_mm / (layers * 35e-3))
    attended = 16 * (310 + 311)
    need_att = flops.decode_attention_bytes(cfg, [attended]) / peaks["hbm_bytes_per_s"] / 2
    assert measure.reader("paged_attention_roofline")(ctx) == pytest.approx(
        100 * need_att / (layers * 10e-3))
    f, b = flops.decode_steps(cfg, 2, 32, attended)
    assert measure.reader("mfu.decode")(ctx) == pytest.approx(
        100 * max(f / peaks["bf16_flops"], b / peaks["hbm_bytes_per_s"]) / 1.8)
    assert measure.reader("decode_step_ms")(ctx) == pytest.approx(900)
    assert measure.reader("idle_share")(ctx) == pytest.approx(10)
