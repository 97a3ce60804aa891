"""Continuous-batching subsystem tests: paged KV pool, paged decode kernel,
scheduler, and static-vs-continuous numerical fidelity."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from _hyp import given, settings, st  # optional-hypothesis shim
from _kv_oracle import kv_oracle

from repro.configs import get_config
from repro.core.policy import QuantPolicy
from repro.kernels import ops
from repro.models import transformer as tf
from repro.serving.engine import Engine, ServeConfig
from repro.serving.kvcache import kv_dequantize, kv_quantize
from repro.serving.pagepool import NULL_PAGE, KVPagePool, PagePoolConfig
from repro.serving.scheduler import Request, Scheduler, SchedulerConfig


def _cfg(arch="llama3_2_3b"):
    return get_config(arch).reduced()


def _engine(arch="llama3_2_3b", **kw):
    cfg = _cfg(arch)
    params = tf.init_params(jax.random.PRNGKey(0), cfg)
    kw.setdefault("max_len", 64)
    kw.setdefault("max_new_tokens", 8)
    return Engine(params, cfg, ServeConfig(**kw)), cfg


# ---------------------------------------------------------------------------
# page pool
# ---------------------------------------------------------------------------
def test_pool_alloc_free_append_cycle():
    pool = KVPagePool(_cfg(), PagePoolConfig(num_pages=6, page_size=8, max_len=48))
    assert pool.num_free_pages == 6
    pages = pool.allocate(0, 17)  # 3 pages of 8
    assert len(pages) == 3 and NULL_PAGE not in pages
    assert pool.num_free_pages == 3 and pool.pages_in_use == 3
    added = pool.append(0, 25)  # 4th page
    assert len(added) == 1 and pool.num_free_pages == 2
    assert pool.append(0, 26) == []  # still fits page 4
    pool.allocate(1, 8)
    pool.release(0)
    assert pool.num_free_pages == 5
    # freed pages are reusable
    again = pool.allocate(2, 40)
    assert set(again) & set(pages)


def test_pool_exhaustion_and_misuse_errors():
    pool = KVPagePool(_cfg(), PagePoolConfig(num_pages=2, page_size=8, max_len=48))
    pool.allocate(0, 16)
    with pytest.raises(RuntimeError, match="exhausted"):
        pool.allocate(1, 9)
    with pytest.raises(ValueError, match="already holds pages"):
        pool.allocate(0, 8)
    with pytest.raises(ValueError, match="max_len"):
        pool.release(0) or pool.allocate(3, 64)


def test_pool_page_table_layout():
    pool = KVPagePool(_cfg(), PagePoolConfig(num_pages=8, page_size=8, max_len=48))
    pool.allocate(7, 20)
    row = pool.page_row(7)
    assert row.shape == (6,)  # ceil(48/8)
    assert (row[:3] != NULL_PAGE).all() and (row[3:] == NULL_PAGE).all()
    # idle slots map entirely to the null page
    table = pool.page_table([7, None])
    assert (np.asarray(table[1]) == NULL_PAGE).all()


def test_pool_rejects_non_gqa_archs():
    # modality-frontend archs (qwen2_vl) reject too: serve() has no extras
    # path, so their frontend embeddings would silently drop
    for arch in ("deepseek_v2_236b", "mamba2_370m", "recurrentgemma_2b",
                 "whisper_base", "qwen2_vl_7b"):
        with pytest.raises(ValueError, match="GQA"):
            KVPagePool(_cfg(arch), PagePoolConfig(num_pages=4))


def test_pool_prefill_roundtrip_matches_contiguous_quant():
    """write_prefill + gather_sequence must reproduce kv_quantize/dequantize of
    the same tokens: pages are whole quant blocks, the wire format is shared."""
    cfg = _cfg()
    pool = KVPagePool(cfg, PagePoolConfig(num_pages=8, page_size=8, max_len=64))
    rng = np.random.default_rng(0)
    s = 13  # non-multiple of page_size
    count = tf.layer_groups(cfg)[0][1]
    caches = [{
        "k": jnp.asarray(rng.standard_normal((count, 1, 16, cfg.num_kv_heads, cfg.hd)),
                         jnp.float32),
        "v": jnp.asarray(rng.standard_normal((count, 1, 16, cfg.num_kv_heads, cfg.hd)),
                         jnp.float32),
    } for _ in tf.layer_groups(cfg)]
    pool.allocate(0, s)
    pool.write_prefill(0, caches, s)
    k_pg, v_pg = pool.gather_sequence(0, s, group=0)
    kc, km = kv_quantize(caches[0]["k"][:, 0, :s])
    want_k = kv_dequantize(kc, km, cfg.hd)
    np.testing.assert_array_equal(np.asarray(k_pg), np.asarray(want_k))
    vc, vm = kv_quantize(caches[0]["v"][:, 0, :s])
    np.testing.assert_array_equal(np.asarray(v_pg), np.asarray(kv_dequantize(vc, vm, cfg.hd)))


@pytest.mark.parametrize("start,length,bucket", [
    (0, 13, 16),   # a whole prompt; the padded tail runs past its last page
    (16, 29, 16),  # a suffix after a page-aligned cached prefix
    (11, 30, 32),  # a suffix from mid-page: the page's head is the prefix's
    (3, 6, 8),     # a suffix inside one page, padded tail in the same page
    (5, 13, 16),   # a suffix over two partial pages
], ids=["prompt", "suffix-aligned", "suffix-mid-page", "suffix-one-page", "suffix-two-pages"])
def test_write_prefill_pages_match_reference_scatter(start, length, bucket):
    """The pages ``write_prefill`` writes hold, byte for byte, the general
    RaZeR path's wire bytes of tokens ``[start, length)`` at their (page,
    slot); every slot no token lands in keeps its bytes, in every page but
    the null page (a sink)."""
    cfg = _cfg()
    ps = 8
    pool = KVPagePool(cfg, PagePoolConfig(num_pages=12, page_size=ps, max_len=64))
    rng = np.random.default_rng(start * 100 + length)
    pool.caches = [{key: jnp.asarray(rng.integers(0, 256, buf.shape), jnp.uint8)
                    for key, buf in group.items()} for group in pool.caches]
    before = [{key: np.asarray(buf) for key, buf in group.items()} for group in pool.caches]
    pool.allocate(0, length)
    caches = [{
        name: jnp.asarray(rng.standard_normal((count, 1, bucket, cfg.num_kv_heads, cfg.hd)),
                          jnp.bfloat16)
        for name in ("k", "v")
    } for _, count in tf.layer_groups(cfg)]
    pool.write_prefill(0, caches, length, start=start)
    row = pool.page_row(0)
    for gi, c in enumerate(caches):
        want = {key: buf.copy() for key, buf in before[gi].items()}
        for name in ("k", "v"):
            codes, meta = (np.asarray(a) for a in kv_oracle(c[name][:, 0]))
            for j in range(length - start):
                page, slot = row[(start + j) // ps], (start + j) % ps
                want[f"{name}_codes"][:, page, slot] = codes[:, j]
                want[f"{name}_meta"][:, page, slot] = meta[:, j]
        for key, buf in want.items():
            got = np.asarray(pool.caches[gi][key])
            np.testing.assert_array_equal(got[:, NULL_PAGE + 1:], buf[:, NULL_PAGE + 1:], err_msg=key)


# ---------------------------------------------------------------------------
# paged decode kernel
# ---------------------------------------------------------------------------
def test_paged_kernel_matches_ref_interpret():
    rng = np.random.default_rng(1)
    b, h, kvh, hd, ps, p, npg = 3, 4, 2, 32, 8, 9, 4
    q = jnp.asarray(rng.standard_normal((b, h, hd)).astype(np.float32))
    kc, km = kv_quantize(jnp.asarray(rng.standard_normal((p, ps, kvh, hd)), jnp.float32))
    vc, vm = kv_quantize(jnp.asarray(rng.standard_normal((p, ps, kvh, hd)), jnp.float32))
    cache = {"k_codes": kc, "k_meta": km, "v_codes": vc, "v_meta": vm}
    pt = jnp.asarray([[1, 2, 3, 0], [4, 5, 0, 0], [6, 7, 8, 2]], jnp.int32)
    cl = jnp.asarray([25, 9, 30], jnp.int32)
    out_ref = ops.razer_paged_kv_attention(q, cache, pt, cl)
    out_pal = ops.razer_paged_kv_attention(q, cache, pt, cl, force_pallas=True, interpret=True)
    np.testing.assert_allclose(np.asarray(out_pal), np.asarray(out_ref), atol=2e-5, rtol=2e-5)


def test_paged_ref_matches_contiguous_ref():
    """A paged cache whose pages happen to be laid out contiguously must score
    identically to the contiguous packed-KV attention (same wire bytes)."""
    rng = np.random.default_rng(2)
    b, h, kvh, hd, ps = 2, 4, 2, 32, 8
    s = 3 * ps
    k = jnp.asarray(rng.standard_normal((b, s, kvh, hd)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((b, s, kvh, hd)), jnp.float32)
    kc, km = kv_quantize(k)
    vc, vm = kv_quantize(v)
    q = jnp.asarray(rng.standard_normal((b, h, hd)).astype(np.float32))
    cl = jnp.asarray([19, 11], jnp.int32)
    contiguous = ops.razer_kv_attention(
        q, {"k_codes": kc, "k_meta": km, "v_codes": vc, "v_meta": vm}, cl)
    # pool: one sequence's pages stacked (+ null page 0)
    def pooled(x):
        pages = x.reshape(b * 3, ps, kvh, x.shape[-1])
        return jnp.concatenate([jnp.zeros_like(pages[:1]), pages])
    pt = jnp.asarray([[1, 2, 3], [4, 5, 6]], jnp.int32)
    paged = ops.razer_paged_kv_attention(
        q, {"k_codes": pooled(kc), "k_meta": pooled(km),
            "v_codes": pooled(vc), "v_meta": pooled(vm)}, pt, cl)
    np.testing.assert_array_equal(np.asarray(contiguous), np.asarray(paged))


# ---------------------------------------------------------------------------
# multi-query verify kernel (speculative decode)
# ---------------------------------------------------------------------------
def _verify_fixture(rng, b, kvh, hd, ps, npg):
    """Random pool + DISJOINT per-sequence page tables in scrambled physical
    order (each sequence owns its pages, like the real allocator)."""
    p = b * npg + 1
    kc, km = kv_quantize(jnp.asarray(rng.standard_normal((p, ps, kvh, hd)), jnp.float32))
    vc, vm = kv_quantize(jnp.asarray(rng.standard_normal((p, ps, kvh, hd)), jnp.float32))
    cache = {"k_codes": kc, "k_meta": km, "v_codes": vc, "v_meta": vm}
    perm = rng.permutation(np.arange(1, p))
    pt = perm.reshape(b, npg).astype(np.int32)
    return cache, jnp.asarray(pt)


@pytest.mark.parametrize("ps", [3, 8, 16])
@pytest.mark.parametrize("t", [1, 2, 4])
def test_verify_kernel_matches_ref_interpret(ps, t):
    """Pallas verify kernel (interpret) vs the jnp reference across page sizes
    and draft lengths, with cur_len values straddling page boundaries."""
    rng = np.random.default_rng(ps * 10 + t)
    b, h, kvh, hd, npg = 3, 4, 2, 32, 4
    cache, pt = _verify_fixture(rng, b, kvh, hd, ps, npg)
    # one slot right at a boundary, one mid-page, one near the table's end
    cl = jnp.asarray([ps - 1, ps + ps // 2, npg * ps - t - 1], jnp.int32)
    q = jnp.asarray(rng.standard_normal((b, t, h, hd)).astype(np.float32))
    out_ref = ops.razer_paged_kv_attention_verify(q, cache, pt, cl)
    out_pal = ops.razer_paged_kv_attention_verify(
        q, cache, pt, cl, force_pallas=True, interpret=True)
    np.testing.assert_allclose(np.asarray(out_pal), np.asarray(out_ref),
                               atol=2e-5, rtol=2e-5)


def test_verify_t1_matches_single_query_decode():
    """T=1 verify at committed length c IS a decode step at cur_len c+1: the
    one query attends positions < c+1, exactly the single-query kernel's
    masking -- the identity that makes speculative decode bit-exact."""
    rng = np.random.default_rng(7)
    b, h, kvh, hd, ps, npg = 2, 4, 2, 32, 8, 3
    cache, pt = _verify_fixture(rng, b, kvh, hd, ps, npg)
    cl = jnp.asarray([13, 20], jnp.int32)
    q = jnp.asarray(rng.standard_normal((b, 1, h, hd)).astype(np.float32))
    verify = ops.razer_paged_kv_attention_verify(q, cache, pt, cl)
    single = ops.razer_paged_kv_attention(q[:, 0], cache, pt, cl + 1)
    np.testing.assert_allclose(np.asarray(verify[:, 0]), np.asarray(single),
                               atol=1e-6, rtol=1e-6)


@pytest.mark.parametrize("ps", [3, 8])
def test_verify_masks_rollback_shaped_tails(ps):
    """Rollback leaves stale wire bytes past cur_len (append k, truncate
    j < k): positions >= cur_len + t + 1 must never leak into the output, so
    scribbling garbage there cannot change any query's result."""
    rng = np.random.default_rng(11)
    b, h, kvh, hd, npg, t = 2, 4, 2, 32, 4, 3
    cache, pt = _verify_fixture(rng, b, kvh, hd, ps, npg)
    cl = jnp.asarray([ps + 1, 2 * ps - 1], jnp.int32)
    q = jnp.asarray(rng.standard_normal((b, t, h, hd)).astype(np.float32))
    clean = ops.razer_paged_kv_attention_verify(q, cache, pt, cl)
    # scribble every position past the last attended one (cur_len + t) in
    # each sequence's own pages -- the rolled-back speculative tail
    dirty = {k: np.asarray(v).copy() for k, v in cache.items()}
    for i in range(b):
        for pos in range(int(cl[i]) + t, npg * ps):
            pg, slot = int(pt[i, pos // ps]), pos % ps
            for key in dirty:
                dirty[key][pg, slot] = rng.integers(0, 256, dirty[key].shape[2:])
    dirty = {k: jnp.asarray(v) for k, v in dirty.items()}
    out_ref = ops.razer_paged_kv_attention_verify(q, dirty, pt, cl)
    out_pal = ops.razer_paged_kv_attention_verify(
        q, dirty, pt, cl, force_pallas=True, interpret=True)
    np.testing.assert_array_equal(np.asarray(out_ref), np.asarray(clean))
    np.testing.assert_allclose(np.asarray(out_pal), np.asarray(clean),
                               atol=2e-5, rtol=2e-5)


@settings(max_examples=20, deadline=None)
@given(st.integers(0, 2 ** 30), st.integers(1, 4), st.sampled_from([3, 8, 16]),
       st.integers(1, 3))
def test_verify_kernel_fuzz(seed, t, ps, b):
    """Hypothesis sweep: random shapes/lengths, Pallas-interpret vs ref."""
    rng = np.random.default_rng(seed)
    h, kvh, hd = 4, 2, 32
    npg = int(rng.integers(2, 5))
    cache, pt = _verify_fixture(rng, b, kvh, hd, ps, npg)
    hi = npg * ps - t  # keep every query position inside the page table
    cl = jnp.asarray(rng.integers(0, hi + 1, size=b), jnp.int32)
    q = jnp.asarray(rng.standard_normal((b, t, h, hd)).astype(np.float32))
    out_ref = ops.razer_paged_kv_attention_verify(q, cache, pt, cl)
    out_pal = ops.razer_paged_kv_attention_verify(
        q, cache, pt, cl, force_pallas=True, interpret=True)
    np.testing.assert_allclose(np.asarray(out_pal), np.asarray(out_ref),
                               atol=2e-5, rtol=2e-5)


# ---------------------------------------------------------------------------
# scheduler
# ---------------------------------------------------------------------------
def _mk_sched(max_slots=2, budget=512, num_pages=32, ps=8, max_len=48):
    pool = KVPagePool(_cfg(), PagePoolConfig(num_pages=num_pages, page_size=ps,
                                             max_len=max_len))
    return Scheduler(SchedulerConfig(max_slots=max_slots, prefill_token_budget=budget), pool)


def test_scheduler_slots_and_fifo():
    sched = _mk_sched(max_slots=2)
    for i in range(3):
        sched.submit(Request(rid=i, prompt=[1] * 4, max_new_tokens=4))
    admitted = sched.admit(0.0)
    assert [r.rid for r in admitted] == [0, 1]  # 2 slots
    for r in admitted:
        sched.start(r, first_token=5, now=0.0)
    assert sched.admit(0.0) == []  # no slot free
    sched.post_decode([9] * 2, now=0.1)  # not done yet (max_new 4)
    for _ in range(2):
        sched.post_decode([9] * 2, now=0.2)
    assert all(r.state == "finished" for r in sched.finished)
    assert [r.rid for r in sched.admit(0.3)] == [2]  # freed slot reused


def test_scheduler_token_budget_and_arrivals():
    # distinct prompts: identical ones would dedup (free) instead of queueing
    sched = _mk_sched(max_slots=4, budget=10)
    sched.submit(Request(rid=0, prompt=[1] * 6, max_new_tokens=2))
    sched.submit(Request(rid=1, prompt=[2] * 6, max_new_tokens=2))
    sched.submit(Request(rid=2, prompt=[1] * 2, max_new_tokens=2, arrival=5.0))
    admitted = sched.admit(0.0)
    assert [r.rid for r in admitted] == [0]  # 6 + 6 > budget 10
    admitted = sched.admit(0.0)
    assert [r.rid for r in admitted] == [1]  # next step
    assert sched.admit(0.0) == []  # rid 2 not arrived yet
    assert [r.rid for r in sched.admit(6.0)] == [2]


def test_scheduler_submit_validation():
    sched = _mk_sched(max_len=16, num_pages=2, ps=8)
    with pytest.raises(ValueError, match="empty prompt"):
        sched.submit(Request(rid=0, prompt=[], max_new_tokens=2))
    with pytest.raises(ValueError, match="max_len"):
        sched.submit(Request(rid=1, prompt=[1] * 12, max_new_tokens=8))
    with pytest.raises(ValueError, match="num_pages"):
        big = _mk_sched(max_len=48, num_pages=2, ps=8)
        big.submit(Request(rid=2, prompt=[1] * 30, max_new_tokens=10))


def test_scheduler_pool_backpressure():
    """Admission waits for pages, not just slots: worst-case reservation."""
    sched = _mk_sched(max_slots=4, num_pages=3, ps=8, max_len=48)
    sched.submit(Request(rid=0, prompt=[1] * 10, max_new_tokens=6))  # 2 pages
    sched.submit(Request(rid=1, prompt=[2] * 10, max_new_tokens=6))  # 2 pages > 1 free
    admitted = sched.admit(0.0)
    assert [r.rid for r in admitted] == [0]
    sched.start(admitted[0], 7, 0.0)
    assert sched.admit(0.0) == []  # only 1 page free
    for _ in range(5):
        sched.post_decode([3, 0, 0, 0], now=0.1)
    assert [r.rid for r in sched.admit(0.2)] == [1]  # pages released


# ---------------------------------------------------------------------------
# end-to-end fidelity: continuous == static greedy decode
# ---------------------------------------------------------------------------
def test_continuous_matches_static_greedy():
    """Acceptance criterion: greedy tokens for a mixed-length prompt set are
    IDENTICAL between static-batch generate (quantized KV) and the
    scheduler-driven paged path."""
    eng, _ = _engine(kv_quant=True)
    prompts = [[1, 2, 3, 4], [5, 6, 7, 8, 9, 10], [11, 12, 13], [14, 15, 16, 17, 18]]
    static = eng.generate(prompts)
    rep = eng.serve(prompts)
    assert rep.outputs == static
    assert all(r.state == "finished" for r in rep.requests)
    assert rep.new_tokens == sum(len(o) - len(p) for o, p in zip(static, prompts))
    assert rep.peak_pages > 0 and rep.tokens_per_s > 0


def test_continuous_matches_static_across_page_boundaries():
    """Small pages force mid-decode page-boundary crossings and multi-page
    gathers; tokens must still match the static path exactly."""
    eng, _ = _engine(kv_quant=True, max_len=48, max_new_tokens=10)
    prompts = [[1, 2, 3, 4, 5, 6, 7, 8, 9, 10], [11, 12, 13]]
    static = eng.generate(prompts)
    rep = eng.serve(prompts, pool_cfg=PagePoolConfig(num_pages=16, page_size=4, max_len=48))
    assert rep.outputs == static


def test_continuous_matches_static_packed_moe():
    """Packed MoE (wire-format expert banks) through the continuous path."""
    eng, _ = _engine("dbrx_132b", max_len=48, max_new_tokens=5,
                     quant=QuantPolicy.packed(kv_quant=True))
    prompts = [[1, 2, 3, 4, 5], [6, 7, 8]]
    static = eng.generate(prompts)
    rep = eng.serve(prompts)
    assert rep.outputs == static


def test_continuous_slot_reuse_smaller_than_load():
    """More requests than slots: slots must be reused as requests finish and
    every request still decodes correctly (vs its solo static decode)."""
    eng, _ = _engine(kv_quant=True)
    prompts = [[i + 1, i + 2, i + 3] for i in range(5)]
    rep = eng.serve(prompts, sched_cfg=SchedulerConfig(max_slots=2))
    assert rep.peak_slots <= 2
    for p, out in zip(prompts, rep.outputs):
        assert out == eng.generate([p])[0]


def test_continuous_eos_and_heterogeneous_max_new():
    eng, _ = _engine(kv_quant=True)
    reqs = [Request(rid=0, prompt=[1, 2, 3], max_new_tokens=6),
            Request(rid=1, prompt=[4, 5, 6, 7], max_new_tokens=2),
            Request(rid=2, prompt=[8, 9], max_new_tokens=7)]
    rep = eng.serve(reqs)
    assert [len(r.out_tokens) for r in rep.requests] == [6, 2, 7]
    # eos stops a request early and frees its slot
    base = rep.requests[0].out_tokens
    eos = base[2]
    reqs2 = [Request(rid=0, prompt=[1, 2, 3], max_new_tokens=6, eos_id=int(eos))]
    rep2 = eng.serve(reqs2)
    assert rep2.requests[0].out_tokens == base[: base.index(eos) + 1]


def test_serve_rejects_unsupported_archs():
    eng, _ = _engine("deepseek_v2_236b", max_len=32, max_new_tokens=4)
    with pytest.raises(ValueError, match="GQA"):
        eng.serve([[1, 2, 3]])


def test_serve_rid_uniqueness_and_stale_reuse():
    """Mixed Request/raw-prompt submissions get non-colliding rids (rids key
    page-pool ownership); reusing consumed Request objects is rejected
    instead of silently returning stale tokens."""
    eng, _ = _engine(kv_quant=True)
    reqs = [Request(rid=1, prompt=[1, 2, 3], max_new_tokens=3), [4, 5, 6]]
    rep = eng.serve(reqs)
    assert all(r.state == "finished" for r in rep.requests)
    assert len({r.rid for r in rep.requests}) == 2
    with pytest.raises(ValueError, match="stale"):
        eng.serve(rep.requests)
    # a generator argument serves every request (serve iterates twice)
    rep_gen = eng.serve(p for p in [[1, 2, 3], [4, 5]])
    assert len(rep_gen.outputs) == 2 and all(r.state == "finished" for r in rep_gen.requests)
    sched = _mk_sched()
    sched.submit(Request(rid=0, prompt=[1], max_new_tokens=2))
    with pytest.raises(ValueError, match="duplicate"):
        sched.submit(Request(rid=0, prompt=[2], max_new_tokens=2))


def test_serve_out_of_order_arrivals():
    """Regression: requests submitted out of arrival order must serve (the
    scheduler orders admission by arrival, not submission), not trip the
    stall guard."""
    eng, _ = _engine(kv_quant=True)
    reqs = [Request(rid=0, prompt=[1, 2, 3], max_new_tokens=3, arrival=0.3),
            Request(rid=1, prompt=[4, 5, 6, 7], max_new_tokens=3, arrival=0.0)]
    rep = eng.serve(reqs)
    assert all(r.state == "finished" for r in rep.requests)
    # the later-submitted, earlier-arriving request was admitted first
    assert rep.requests[1].first_token_time < rep.requests[0].first_token_time
    assert rep.outputs[0][3:] == eng.generate([[1, 2, 3]], max_new_tokens=3)[0][3:]
