"""Batched serving engine: prefill + greedy decode, optional RaZeR-packed
weights (the paper's weight-only deployment path) and RaZeR-quantized KV
cache (App. C.1).

Two serving modes:

  * ``Engine.generate`` -- static batching: one ragged batch runs to
    completion over fixed ``(batch, max_len)`` caches (continuous-batching
    lite: per-sequence lengths, right-padded).
  * ``Engine.serve``    -- continuous batching: a ``serving.scheduler``
    admission/decode loop over the paged RaZeR KV pool
    (``serving.pagepool``), decoding a dynamic batch of slots each iteration
    and refilling slots the moment a request finishes.

The engine is the deployment-side counterpart of the training driver: it takes
a param tree, optionally packs every linear weight into the 4.5-bit wire
format (offline, once), and serves token prompts.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict, List, Optional, Sequence, Union

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.policy import QuantPolicy, TensorSpec, as_policy
from repro.core.qlinear import QuantConfig
from repro.models import transformer as tf
from repro.models.config import ArchConfig
from repro.parallel.sharding import sharding_ctx


@dataclasses.dataclass
class ServeConfig:
    max_len: int = 256
    max_new_tokens: int = 32
    kv_quant: bool = False  # RaZeR KV cache (App. C.1)
    quant: Union[QuantPolicy, QuantConfig] = QuantConfig(mode="bf16")
    eos_id: int = -1  # -1: never stop early


# weights large enough to be worth packing (skip tiny projections)
_MIN_PACK = 16 * 16


def _packable(spec: TensorSpec, leaf, block_axis: int) -> bool:
    """Structural eligibility: blocked axis divisible by the block size the
    format will actually use, and big enough to matter."""
    return (
        hasattr(leaf, "ndim")
        and leaf.shape[block_axis] % spec.effective_block_size == 0
        and leaf.size >= _MIN_PACK
    )


def _apply_policy_to_weights(params, quant, leaf_fn):
    """Shared rule-resolving tree walk: ``leaf_fn(spec, leaf)`` transforms
    every leaf whose '/'-joined path resolves to a quantizing spec."""
    policy = as_policy(quant)

    def walk(tree, path=""):
        if isinstance(tree, dict):
            return {k: walk(v, f"{path}/{k}" if path else str(k)) for k, v in tree.items()}
        spec = policy.resolve(path)
        return tree if spec is None else leaf_fn(spec, tree)

    return walk(params)


def pack_model_weights(params, cfg: ArchConfig, quant: Union[QuantPolicy, QuantConfig]):
    """Offline PTQ: replace every eligible 2-D linear weight with its format's
    wire container, per the policy's per-layer rules.

    Which tensors stay dense is decided by ``QuantPolicy.resolve`` on the
    '/'-joined param path (default rules: embed/lm_head/router/norms/biases/
    SSM state high precision, paper convention) -- not by name-substring
    guesses, so a ``bottleneck`` projection packs like any other weight.
    Scan-stacked weights (leading layer dim) are packed per layer and the
    containers restacked leaf-wise, which works for any registered format's
    container.  Specs carrying the ``stacked`` marker (MoE expert banks, the
    default ``*experts*`` rule) pack the whole (E, d_in, d_out) bank into the
    format's stacked container so ``moe_forward`` can run the grouped packed
    kernel; a scan-stacked bank (L, E, d_in, d_out) packs one stacked
    container per scan layer, restacked leaf-wise.
    """

    def pack_leaf(spec, leaf):
        if spec.mode != "packed":
            return leaf
        if spec.stacked:
            # BOTH trailing dims must be block multiples: an MoE FFN trio has
            # reduction dims {d_model, moe_d_ff} split across gate/up (E,d,f)
            # and down (E,f,d), and moe_forward needs the whole trio packed
            # or the whole trio dense -- the symmetric condition guarantees
            # all three leaves decide identically (all-or-none per bank).
            bs = spec.effective_block_size
            # (the bank widens to f32 one expert at a time inside the pack:
            # a whole f32 bank of a full-width MoE layer alone fills a chip)
            if leaf.ndim == 3 and _packable(spec, leaf, 1) and leaf.shape[2] % bs == 0:
                return spec.pack_stacked(leaf)
            if leaf.ndim == 4 and _packable(spec, leaf, 2) and leaf.shape[3] % bs == 0:
                # scan-stacked (L, E, d_in, d_out): one grouped container per
                # scan layer, restacked leaf-wise (scan slices them back out)
                packed = [spec.pack_stacked(leaf[i]) for i in range(leaf.shape[0])]
                return jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *packed)
            return leaf
        if leaf.ndim == 2 and _packable(spec, leaf, 0):
            return spec.pack(leaf.astype(jnp.float32))
        if leaf.ndim == 3 and _packable(spec, leaf, 1):
            # scan-stacked (L, d_in, d_out): pack per layer, stack the pieces
            packed = [spec.pack(leaf[i].astype(jnp.float32)) for i in range(leaf.shape[0])]
            return jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *packed)
        return leaf

    return _apply_policy_to_weights(params, quant, pack_leaf)


def fakequant_model_weights(params, cfg: ArchConfig, quant: Union[QuantPolicy, QuantConfig]):
    """Offline per-layer fake-quant: quantize-dequantize every eligible weight
    under the policy's per-layer rules (the accuracy-experiment analogue of
    ``pack_model_weights`` -- this is how rule-driven mixed precision, e.g.
    calibrated per-layer SV magnitudes or first/last-layer higher precision,
    enters a fakequant evaluation)."""

    def qdq_leaf(spec, leaf):
        if spec.stacked:
            # expert banks fake-quantize at forward time (moe_forward, along
            # d_in) -- qdq'ing here too would double-round through two absmax
            # normalizations and drift from the packed path's numerics
            return leaf
        if leaf.ndim == 2 and _packable(spec, leaf, 0):
            return spec.qdq(leaf, axis=0)
        if leaf.ndim == 3 and _packable(spec, leaf, 1):
            return spec.qdq(leaf, axis=1)
        return leaf

    return _apply_policy_to_weights(params, quant, qdq_leaf)


class Engine:
    def __init__(self, params, cfg: ArchConfig, serve_cfg: ServeConfig, mesh=None):
        self.cfg = cfg
        self.scfg = serve_cfg
        self.mesh = mesh
        self.quant = serve_cfg.quant
        self.policy = as_policy(serve_cfg.quant)
        # policy.kv implies a quantized cache even without the legacy flag
        self.kv_quant = bool(serve_cfg.kv_quant or self.policy.kv is not None)
        # keep the dense tree reachable for self-speculative serving: the
        # draft side re-quantizes the SAME checkpoint under a cheaper policy
        # (serving/speculative.py), which needs pre-packing weights
        self._raw_params = params
        if self.policy.mode == "packed":
            params = pack_model_weights(params, cfg, serve_cfg.quant)
        if mesh is not None:
            # place params by the resolver rules (docs/parallelism.md): dense
            # weights FSDP/TP-shard, packed stacked expert banks split E/ep
            # over the data axis (each device holds only its expert rows --
            # moe_forward then shard_maps the grouped kernel over that axis)
            from repro.parallel.sharding import param_sharding_tree

            params = jax.device_put(params, param_sharding_tree(params, mesh))
        self.params = params
        self._decode_jit = jax.jit(self._decode_step)
        # the pool buffers are donated: serve() immediately replaces
        # pool.caches with the step's output, and without donation every
        # decode step would materialize a second full copy of the pool
        # (doubling peak KV HBM -- exactly what the pool exists to avoid)
        self._paged_decode_jit = jax.jit(self._paged_decode_step, donate_argnums=(2,))
        # one shared jitted prefill for both serving modes (compiled per
        # power-of-two bucket shape); + the prefix-cache suffix continuation
        self._prefill_jit = None
        self._suffix_jit = None
        # speculative decoders keyed by resolved draft policy (jits + draft
        # params are reused across serve() calls)
        self._spec_cache: Dict[Any, Any] = {}

    def quant_audit(self, *, model: Optional[str] = None, metrics=None,
                    trace=None, kv_audit=None):
        """Per-layer quantization audit of this engine's weights: the
        ``obs.numerics.audit_model`` report over the raw bf16 reference tree
        and (in packed mode) the exact wire-format tree the engine serves
        from.  See docs/observability.md#numerics-audit."""
        from repro.obs.numerics import audit_model

        packed = self.params if self.policy.mode == "packed" else None
        return audit_model(self._raw_params, self.policy, packed=packed,
                           model=model, metrics=metrics, tracer=trace,
                           kv_audit=kv_audit)

    # -- internals ----------------------------------------------------------
    def _decode_step(self, params, token, caches, cur_len, enc):
        with sharding_ctx(self.mesh):
            return tf.decode_step(params, token, caches, cur_len, self.cfg, self.quant, enc=enc)

    def _prefill(self, tokens, lengths, extras):
        with sharding_ctx(self.mesh):
            # single pass: caches + per-sequence last logits (ragged batches)
            last, caches, enc = tf.prefill(
                self.params, tokens, self.cfg, self.quant, max_len=self.scfg.max_len,
                frontend_embeds=extras.get("frontend_embeds"),
                enc_frames=extras.get("enc_frames"),
                last_positions=lengths,
            )
            if self.kv_quant:
                caches = self._quantize_caches(caches)
            return last, caches, enc

    def _quantize_caches(self, caches):
        """Convert bf16 GQA caches to the packed layout (App. C.1)."""
        from repro.serving.kvcache import kv_quantize

        spec = self.policy.kv
        out = []
        for c in caches:
            if isinstance(c, dict) and "k" in c and c["k"].ndim == 5:
                kc, km = kv_quantize(c["k"], spec=spec)
                vc, vm = kv_quantize(c["v"], spec=spec)
                out.append({"k_codes": kc, "k_meta": km, "v_codes": vc, "v_meta": vm})
            else:
                out.append(c)
        return out

    def _check_prompts(self, prompts: Sequence[Sequence[int]], n_new: int) -> None:
        """Fail fast on requests the fixed caches cannot hold -- silent
        truncation or an opaque shape error downstream would be worse.

        Pure-SSM archs carry recurrent state, not a (max_len,) cache, so only
        the empty-prompt check applies to them."""
        if not prompts:
            raise ValueError("Engine.generate needs at least one prompt")
        for i, p in enumerate(prompts):
            if len(p) == 0:
                raise ValueError(
                    f"prompt {i} is empty; every prompt needs >= 1 token "
                    f"(prefill gathers logits at position len-1)"
                )
            if not self.cfg.ssm and len(p) + n_new > self.scfg.max_len:
                raise ValueError(
                    f"prompt {i} ({len(p)} tokens) + max_new_tokens ({n_new}) "
                    f"exceeds ServeConfig.max_len ({self.scfg.max_len}); raise "
                    f"max_len to >= {len(p) + n_new}, shorten the prompt, or "
                    f"request fewer new tokens"
                )

    def _bucketed_prefill(self, toks: np.ndarray, lens: np.ndarray, *,
                          max_len: int, qdq_kv: bool):
        """The shared jitted prefill: compiled once per (batch, bucket) shape
        -- ``toks`` must already be padded to a power-of-two bucket.  Both
        serving modes use it: ``serve`` per request (B=1, ``max_len`` = the
        bucket, ``qdq_kv`` always on -- pool pages hold wire bytes), and
        ``generate`` per batch (``max_len`` = the cache width, ``qdq_kv`` on
        when the KV cache is quantized).  Causal masking makes the padded
        positions inert, so bucket size never changes the valid tokens'
        values."""
        if self._prefill_jit is None:
            def _prefill(params, tokens, lens, *, max_len, qdq_kv):
                with sharding_ctx(self.mesh):
                    last, caches, _ = tf.prefill(
                        params, tokens, self.cfg, self.quant, max_len=max_len,
                        last_positions=lens, qdq_kv=qdq_kv)
                return last, caches

            self._prefill_jit = jax.jit(_prefill, static_argnames=("max_len", "qdq_kv"))
        return self._prefill_jit(self.params, jnp.asarray(toks),
                                 jnp.asarray(lens, jnp.int32),
                                 max_len=max_len, qdq_kv=qdq_kv)

    @staticmethod
    def _bucket(n: int, cap: Optional[int] = None) -> int:
        b = max(8, 1 << (n - 1).bit_length())
        return b if cap is None else min(b, cap)

    # -- public API ---------------------------------------------------------
    def generate(self, prompts: Sequence[Sequence[int]], extras: Optional[Dict] = None,
                 max_new_tokens: Optional[int] = None) -> List[List[int]]:
        """Greedy-decode a batch of token prompts (continuous-batching lite:
        ragged prompt lengths are right-padded and tracked per sequence)."""
        extras = extras or {}
        n_new = max_new_tokens or self.scfg.max_new_tokens
        self._check_prompts(prompts, n_new)
        b = len(prompts)
        lens = np.array([len(p) for p in prompts], np.int32)
        if self.cfg.ssm or self.cfg.block_pattern:
            assert len(set(lens.tolist())) == 1, "recurrent archs need equal prompt lengths"
        # pure-attention stacks reuse the continuous path's jitted
        # power-of-two-bucketed prefill (one compile per bucket instead of an
        # eager retrace per prompt-length mix); recurrent state (SSM/RG-LRU)
        # is corrupted by padded steps and modality frontends need the extras
        # channel, so those archs keep the exact-length eager prefill
        bucketed = not (self.cfg.ssm or self.cfg.block_pattern
                        or self.cfg.encoder_decoder or self.cfg.frontend != "none"
                        or extras)
        s = self._bucket(int(lens.max()), cap=self.scfg.max_len) if bucketed \
            else int(lens.max())
        toks = np.zeros((b, s), np.int32)
        for i, p in enumerate(prompts):
            toks[i, : len(p)] = p
        tokens = jnp.asarray(toks)
        lengths = jnp.asarray(lens)

        if bucketed:
            last, caches = self._bucketed_prefill(
                toks, lens, max_len=self.scfg.max_len, qdq_kv=self.kv_quant)
            enc = None
            if self.kv_quant:
                caches = self._quantize_caches(caches)
        else:
            last, caches, enc = self._prefill(tokens, lengths, extras)
        out = [list(p) for p in prompts]
        cur = lengths
        done = np.zeros(b, bool)
        tok = jnp.argmax(last, axis=-1).astype(jnp.int32)
        for step in range(n_new):
            for i in range(b):
                if not done[i]:
                    t = int(tok[i])
                    out[i].append(t)
                    if t == self.scfg.eos_id:
                        done[i] = True
            if done.all() or step == n_new - 1:
                break
            logits, caches = self._decode_jit(self.params, tok, caches, cur, enc)
            tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)
            cur = cur + 1
        return out

    # -- continuous batching (paged KV pool + scheduler) ---------------------
    def _paged_decode_step(self, params, token, caches, pages, cur_len):
        with sharding_ctx(self.mesh):
            return tf.decode_step(params, token, caches, cur_len, self.cfg, self.quant,
                                  pages=pages)

    def draft_source_params(self):
        """Param tree the speculative draft side re-quantizes: the dense
        (pre-packing) tree for a packed engine, else the served params
        themselves (already placed; fakequant policies apply at forward
        time)."""
        return self._raw_params if self.policy.mode == "packed" else self.params

    def _speculator(self, draft_policy):
        """Build (or reuse) the ``SpeculativeDecoder`` for a draft policy --
        keyed by the resolved policy so repeated ``serve`` calls share jits
        and draft params.  Callable drafts (test seam) key by identity."""
        from repro.serving.speculative import SpeculativeDecoder, resolve_draft_policy

        resolved = resolve_draft_policy(draft_policy)
        key = resolved if isinstance(resolved, QuantPolicy) else id(resolved)
        if key not in self._spec_cache:
            self._spec_cache[key] = SpeculativeDecoder(self, draft_policy)
        return self._spec_cache[key]

    def _serve_prefill(self, prompt: Sequence[int]):
        """Prefill ONE request, padded to a power-of-two bucket so the jitted
        prefill compiles once per bucket, not once per prompt length.

        The serve path always prefills with ``qdq_kv=True``: attention reads
        the same wire bytes the pool pages will hold, which is what makes a
        prefix-cached continuation (``_serve_prefill_suffix``) bit-identical
        to this uncached pass at any split point."""
        s = len(prompt)
        toks = np.zeros((1, self._bucket(s)), np.int32)
        toks[0, :s] = prompt
        return self._bucketed_prefill(toks, np.asarray([s], np.int32),
                                      max_len=toks.shape[1], qdq_kv=True)

    def _prefill_range(self, prompt, start: int, end: int, pool, rid: int):
        """Prefill tokens ``[start, end)`` of ``prompt`` against the
        sequence's pool pages covering ``[0, start)`` -- the shared primitive
        behind BOTH prefix-cache suffix continuation (``start`` = the cached
        length, ``end`` = the prompt length) and disagg chunked prefill
        (successive ``[done, done + chunk)`` windows; every chunk past the
        first attends the pages earlier chunks just wrote).

        ``start == 0`` is the plain bucketed prefill.  Otherwise the range
        tokens (bucketed) attend the sequence's written pages -- gathered and
        dequantized per layer -- plus themselves.  The gathered prefix is
        bucketed to a power-of-two PAGE count (one compile per
        (range, prefix) bucket pair), not the full page-table width: per-layer
        dequant of untouched pages would otherwise dominate the very prefill
        work caching/chunking saves.  Returns (last logits of position
        ``end - 1``, K/V caches to scatter with
        ``write_prefill(..., length=end, start=start)``); both are
        bit-identical to a single full prefill's at any split points
        (docs/serving.md#why-hits-are-bit-identical)."""
        if start == 0:
            return self._serve_prefill(prompt[:end])
        c, s = start, end - start
        ps = pool.pool_cfg.page_size
        npb = min(1 << (-(-c // ps) - 1).bit_length(), pool.pool_cfg.pages_per_seq)
        toks = np.zeros((1, self._bucket(s)), np.int32)
        toks[0, :s] = prompt[c:end]
        if self._suffix_jit is None:
            def _suffix(params, tokens, pool_caches, row, pre_len, sfx_len, *, page_size):
                with sharding_ctx(self.mesh):
                    return tf.prefill_paged_suffix(
                        params, tokens, pool_caches, row, pre_len, sfx_len,
                        self.cfg, self.quant, page_size=page_size)

            self._suffix_jit = jax.jit(_suffix, static_argnames=("page_size",))
        return self._suffix_jit(
            self.params, jnp.asarray(toks), pool.caches,
            jnp.asarray(pool.page_row(rid)[:npb]),
            jnp.asarray(c, jnp.int32), jnp.asarray(s, jnp.int32),
            page_size=ps)

    def default_pool_cfg(self, max_slots: int):
        """The pool ``serve`` builds when given none: 16-token pages, room for
        every slot at ``max_len``."""
        from repro.serving.pagepool import PagePoolConfig

        ps = 16
        pages_per_seq = -(-self.scfg.max_len // ps)
        return PagePoolConfig(num_pages=max_slots * pages_per_seq, page_size=ps,
                              max_len=self.scfg.max_len)

    def _as_requests(self, requests, n_new: int):
        """Normalize a request stream (``scheduler.Request`` or raw token-id
        prompts, freely mixed) into a list of ``Request``.  Raw prompts get
        arrival 0, the engine's eos, and fresh rids past any explicit
        Request's (rids key page-pool ownership; duplicates are rejected
        downstream).  Shared by ``serve`` and ``disagg.serve_disagg``."""
        from repro.serving.scheduler import Request

        requests = list(requests)  # may be a generator; iterated twice below
        next_rid = max((r.rid for r in requests if isinstance(r, Request)), default=-1) + 1
        reqs: List[Request] = []
        for r in requests:
            if isinstance(r, Request):
                reqs.append(r)
            else:
                reqs.append(Request(rid=next_rid, prompt=list(r), max_new_tokens=n_new,
                                    eos_id=self.scfg.eos_id))
                next_rid += 1
        return reqs

    def serve(self, requests, *, sched_cfg=None, pool_cfg=None,
              max_new_tokens: Optional[int] = None, prefix_cache: bool = True,
              speculate_k: int = 0, draft_policy=None,
              clock=None, trace=None, metrics=None, kv_audit=None,
              profile_dir: Optional[str] = None):
        """Continuous batching: serve a stream of requests over the paged
        RaZeR-quantized KV pool, decoding a dynamic batch each iteration.

        ``requests`` is a sequence of ``scheduler.Request`` or raw token-id
        prompts (converted with arrival 0 and the engine's ``max_new_tokens``
        / ``eos_id``).  Requests are admitted when their ``arrival`` offset
        (seconds, relative to the call) has elapsed, a decode slot and pool
        pages are free, and the prefill token budget allows -- see
        ``serving/scheduler.py``.  Greedy decode, numerically identical to
        ``generate`` with a quantized KV cache (the pool pages hold the same
        wire format the contiguous quantized cache does).

        ``prefix_cache`` (default on) shares prompt-prefix pages between
        requests through a radix tree over page-aligned token chunks
        (``serving/prefixcache.py``): a hit prefills only the uncached
        suffix, and greedy outputs are BIT-IDENTICAL to the uncached run --
        prefill attention reads the same wire bytes either way.

        ``speculate_k > 0`` turns on self-speculative decoding: each decode
        iteration drafts ``k`` tokens per running slot with the same
        checkpoint under ``draft_policy`` (a cheaper ``QuantPolicy`` / format
        name; default fakequant nvfp4), then verifies all ``k+1`` positions
        in ONE multi-query paged-attention pass, rolling rejected tail pages
        back via ``pool.truncate`` -- see ``serving/speculative.py``.  Greedy
        outputs stay bit-identical to ``speculate_k=0`` for ANY draft policy;
        only throughput changes (with the accept rate).

        Observability (docs/observability.md), all off by default and
        zero-overhead when off:

          * ``clock``   -- an ``obs.Clock``; every timestamp and sleep in the
            loop goes through it (``obs.FakeClock`` makes latency stats exact
            and deterministic in tests).  Greedy OUTPUTS never depend on it.
          * ``trace``   -- an ``obs.Tracer``; the loop records the request
            lifecycle (admit / prefill / decode_step / draft / verify /
            retire) and the host phases inside each iteration
            (docs/observability.md) on the serve-relative timeline (the
            tracer's clock is rebound to it, so trace timestamps line up
            with arrivals), and, through the tracer's profiler
            annotations, on the device trace's clock when ``profile_dir``
            is set.
          * ``metrics`` -- an ``obs.MetricsRegistry``; pool/cache occupancy
            export as function-backed gauges, and TTFT / latency / per-token
            latency / step-duration histograms populate as requests finish.
          * ``kv_audit`` -- an ``obs.KVAuditor``; samples KV quantization
            error per page at prefill-write time (read-only: greedy outputs
            are bit-identical with the hook on or off).
          * ``profile_dir`` -- bracket the serve loop with
            ``jax.profiler.start_trace/stop_trace`` for kernel deep dives.

        Returns a ``ServeReport`` (outputs in submission order + latency /
        throughput / pool / prefix-cache / speculation stats, with exact
        p50/p95/p99 TTFT / latency / per-token-latency properties)."""
        from repro.obs import NULL_TRACER, Clock
        from repro.serving.pagepool import KVPagePool, install_pool_metrics
        from repro.serving.prefixcache import PrefixCache, install_cache_metrics
        from repro.serving.scheduler import Request, Scheduler, SchedulerConfig

        sched_cfg = sched_cfg or SchedulerConfig()
        if speculate_k:
            sched_cfg = dataclasses.replace(sched_cfg, speculate_k=speculate_k)
        k = sched_cfg.speculate_k
        if k < 0:
            raise ValueError(f"speculate_k must be >= 0, got {k}")
        spec = self._speculator(draft_policy) if k else None
        n_new = max_new_tokens or self.scfg.max_new_tokens
        reqs = self._as_requests(requests, n_new)
        pool = KVPagePool(self.cfg, pool_cfg or self.default_pool_cfg(sched_cfg.max_slots))
        if kv_audit is not None:
            pool.set_kv_audit(kv_audit)
        cache = PrefixCache(pool) if prefix_cache else None
        clock = clock if clock is not None else Clock()
        tracer = trace if trace is not None else NULL_TRACER
        sched = Scheduler(sched_cfg, pool, cache=cache, tracer=tracer)
        for r in reqs:
            sched.submit(r)

        t0 = clock.now()

        def now() -> float:
            return clock.now() - t0

        mx = metrics is not None
        if tracer.enabled:
            # trace timestamps on the serve-relative timeline: admits line up
            # with request arrival offsets, and a FakeClock run is diffable
            tracer.clock = now
            tracer.set_track(tracer.pid, tracer.tid,
                             process="engine", thread="serve")
        if spec is not None:
            spec.clock, spec.tracer = clock, tracer
        if mx:
            install_pool_metrics(metrics, pool)
            if cache is not None:
                install_cache_metrics(metrics, cache)
            metrics.histogram("serve_decode_step_seconds",
                              "Wall seconds per decode step", labels=("stage",))
            metrics.histogram("serve_prefill_seconds",
                              "Wall seconds per prefill, through its first token",
                              labels=("stage",))
        # the cached speculator accumulates stats across serve() calls;
        # report this run's delta against a snapshot
        spec_base = dataclasses.replace(spec.stats) if spec else None
        if profile_dir:
            jax.profiler.start_trace(profile_dir)
        try:
            self._serve_loop(sched, pool, spec, k, now, clock, tracer,
                             metrics if mx else None)
        finally:
            if profile_dir:
                jax.profiler.stop_trace()
        decode_steps, prefill_tokens, cached_tokens, peak_pages, peak_slots = (
            self._loop_stats)

        wall = now()
        new_tokens = sum(len(r.out_tokens) for r in reqs)
        report = ServeReport(
            requests=reqs, wall_time=wall, new_tokens=new_tokens,
            decode_steps=decode_steps, prefill_tokens=prefill_tokens,
            peak_pages=peak_pages, peak_slots=peak_slots,
            page_bytes=pool.bytes_per_page(), pool_bytes=pool.total_bytes(),
            cached_tokens=cached_tokens,
            cache_lookups=cache.lookups if cache else 0,
            cache_hits=cache.hits if cache else 0,
            cache_evictions=cache.evictions if cache else 0,
            speculate_k=k,
            drafted_tokens=spec.stats.drafted - spec_base.drafted if spec else 0,
            accepted_drafts=spec.stats.accepted - spec_base.accepted if spec else 0,
            draft_steps=spec.stats.draft_steps - spec_base.draft_steps if spec else 0,
            draft_time=spec.stats.draft_time - spec_base.draft_time if spec else 0.0,
            verify_time=spec.stats.verify_time - spec_base.verify_time if spec else 0.0,
        )
        if mx:
            report.observe_into(metrics)
        return report

    def _serve_loop(self, sched, pool, spec, k: int, now, clock, tracer,
                    metrics) -> None:
        """The continuous-batching event loop (see ``serve``, which owns
        setup and the report).  Loop totals land in ``self._loop_stats``."""
        mx = metrics is not None
        if mx:
            step_h = metrics.get("serve_decode_step_seconds")
            prefill_h = metrics.get("serve_prefill_seconds")
        decode_steps = prefill_tokens = cached_tokens = 0
        peak_pages = peak_slots = 0
        # slot->pages assignments only change on admission/retirement, so the
        # device page table is cached between scheduler events instead of
        # being rebuilt + re-uploaded on every decode step
        page_table = None
        idle_retries = 0
        while sched.has_work:
            with tracer.span("sched_admit"):
                admitted = sched.admit(now())
            if not admitted and not sched.running:
                # nothing runnable yet: sleep until the next arrival, then
                # retry admission (the scheduler keeps waiting sorted by
                # arrival; an arrival landing mid-iteration just retries).
                # With nothing running the pool is empty, so an ARRIVED head
                # always admits (submit() validated it fits) -- repeated
                # no-progress retries past its arrival mean invariant breakage
                nxt = sched.next_arrival()
                idle_retries = idle_retries + 1 if (nxt is None or nxt <= now()) else 0
                if nxt is None or idle_retries > 1000:
                    raise RuntimeError(
                        "scheduler stalled: an arrived request cannot be admitted "
                        "into an idle engine"
                    )
                clock.sleep(max(nxt - now(), 0.0))
                continue
            idle_retries = 0
            # prefill phase (token-budgeted by the scheduler; a prefix-cache
            # hit prefills only the uncached suffix and scatter-writes just
            # the pages past the shared boundary)
            by_rid = {r.rid: r for r in admitted}
            for req in admitted:
                if req.dedup_of is not None:
                    # same-batch duplicate: its donor (earlier in this very
                    # list) has prefilled and sampled, so the shared pages are
                    # written and the COW copy of the partial last page can be
                    # taken; the first token is the donor's -- identical
                    # prompts sample identical greedy tokens
                    pool.flush_forks(req.rid)
                    cached_tokens += req.cached_tokens
                    sched.start(req, by_rid[req.dedup_of].out_tokens[0], now())
                    continue
                if mx:
                    pt = now()
                # the span ends with the first token on the host: the device
                # runs the forward and then the page scatter in order, so the
                # readback waits for both -- the time decode stands still
                with tracer.span("prefill", rid=req.rid,
                                 tokens=len(req.prompt) - req.cached_tokens,
                                 cached=req.cached_tokens):
                    with tracer.span("prefill_forward"):
                        if req.cached_tokens:
                            pool.flush_forks(req.rid)  # COW copy, after donors' writes
                            last, caches = self._prefill_range(
                                req.prompt, req.cached_tokens, len(req.prompt), pool,
                                req.rid)
                        else:
                            last, caches = self._serve_prefill(req.prompt)
                    with tracer.span("page_write"):
                        pool.write_prefill(req.rid, caches, len(req.prompt),
                                           start=req.cached_tokens)
                    with tracer.span("prefill_sync"):
                        first = int(jnp.argmax(last[0]))
                if mx:
                    prefill_h.observe(now() - pt, stage="engine")
                prefill_tokens += len(req.prompt) - req.cached_tokens
                cached_tokens += req.cached_tokens
                sched.start(req, first, now())
            if admitted:
                page_table = None
            peak_pages = max(peak_pages, pool.pages_in_use)
            peak_slots = max(peak_slots, len(sched.running))
            # decode phase: one dynamic-batch step over the active slots
            batch = sched.decode_batch()
            if batch is None:
                continue
            if mx:
                st = now()
            if spec is not None:
                # draft-k-verify-1: the speculator appends/truncates pages
                # every iteration, so the cached table is useless here (the
                # draft/verify spans record inside decode_iteration)
                spec.decode_iteration(pool, sched, batch, k, now)
                decode_steps += 1
                page_table = None
                peak_pages = max(peak_pages, pool.pages_in_use)
                if mx:
                    step_h.observe(now() - st, stage="engine")
                continue
            seq_ids, tokens, cur_lens = batch
            with tracer.span("decode_step", batch=len(sched.running)):
                if page_table is None:
                    with tracer.span("page_table"):
                        page_table = pool.page_table(seq_ids)
                # returns once the step is enqueued; decode_sync is the host
                # blocked on the device
                with tracer.span("decode_dispatch"):
                    logits, pool.caches = self._paged_decode_jit(
                        self.params, jnp.asarray(tokens, jnp.int32), pool.caches,
                        page_table, jnp.asarray(cur_lens, jnp.int32))
                with tracer.span("decode_sync"):
                    toks = np.asarray(jnp.argmax(logits, axis=-1), np.int32)
            decode_steps += 1
            if mx:
                step_h.observe(now() - st, stage="engine")
            with tracer.span("sched_post_decode"):
                retired = sched.post_decode(toks.tolist(), now())
            if retired:
                page_table = None  # a retirement freed a slot

        self._loop_stats = (decode_steps, prefill_tokens, cached_tokens,
                            peak_pages, peak_slots)


@dataclasses.dataclass
class ServeReport:
    """Outcome of one ``Engine.serve`` run: outputs + serving metrics."""

    requests: List[Any]
    wall_time: float
    new_tokens: int
    decode_steps: int
    prefill_tokens: int
    peak_pages: int
    peak_slots: int
    page_bytes: int
    pool_bytes: int
    # page-sharing outcome: ``prefill_tokens`` counts only COMPUTED prompt
    # tokens; ``cached_tokens`` counts prompt tokens served from shared /
    # copied pages instead -- prefix-cache hits AND same-batch duplicate
    # dedup (scheduler._admit_dedup), so it can be nonzero with the cache
    # off.  Every field defaults to a real zero: with ``prefix_cache=False``
    # the cache_* stats are populated zeros, never stale Nones
    cached_tokens: int = 0
    cache_lookups: int = 0
    cache_hits: int = 0
    cache_evictions: int = 0
    # speculative decoding (serving/speculative.py): with ``speculate_k > 0``
    # each decode_step is one draft-k-verify-1 iteration; ``drafted_tokens``
    # counts draft proposals, ``accepted_drafts`` the ones the target's argmax
    # agreed with (an iteration commits 1 + accepted tokens).  draft_time /
    # verify_time split the decode wall clock into overhead vs target work
    speculate_k: int = 0
    drafted_tokens: int = 0
    accepted_drafts: int = 0
    draft_steps: int = 0
    draft_time: float = 0.0
    verify_time: float = 0.0

    @property
    def outputs(self) -> List[List[int]]:
        """prompt + generated tokens per request, submission order (the same
        shape ``Engine.generate`` returns)."""
        return [list(r.prompt) + list(r.out_tokens) for r in self.requests]

    @property
    def cache_hit_rate(self) -> float:
        """Fraction of prompt tokens served from the prefix cache."""
        total = self.cached_tokens + self.prefill_tokens
        return self.cached_tokens / total if total else 0.0

    @property
    def accept_rate(self) -> float:
        """Fraction of draft proposals the target's argmax accepted."""
        return self.accepted_drafts / self.drafted_tokens if self.drafted_tokens else 0.0

    @property
    def draft_overhead(self) -> float:
        """Fraction of speculative decode wall time spent drafting."""
        total = self.draft_time + self.verify_time
        return self.draft_time / total if total else 0.0

    @property
    def tokens_per_step(self) -> float:
        """Mean committed tokens per decode iteration (1.0 for vanilla decode;
        speculation pushes this toward ``1 + k * accept_rate``)."""
        return self.new_tokens / self.decode_steps if self.decode_steps else 0.0

    @property
    def tokens_per_s(self) -> float:
        return self.new_tokens / max(self.wall_time, 1e-9)

    # -- latency distributions ------------------------------------------------
    # raw per-request samples; percentiles are exact nearest-rank
    # (obs.percentile), so tests can pin them to the digit under a FakeClock
    def ttft_values(self) -> List[float]:
        """Per-request time-to-first-token (s), finished requests only."""
        return [r.first_token_time - r.arrival for r in self.requests
                if r.first_token_time is not None]

    def latency_values(self) -> List[float]:
        """Per-request total latency (s), finished requests only."""
        return [r.finish_time - r.arrival for r in self.requests
                if r.finish_time is not None]

    def tpot_values(self) -> List[float]:
        """Per-request mean per-token latency after the first token (s);
        requests generating a single token carry no decode interval."""
        return [(r.finish_time - r.first_token_time) / (len(r.out_tokens) - 1)
                for r in self.requests
                if r.finish_time is not None and len(r.out_tokens) > 1]

    @property
    def mean_ttft(self) -> float:
        """Mean time-to-first-token (s) over finished requests."""
        ts = self.ttft_values()
        return sum(ts) / len(ts) if ts else 0.0

    @property
    def mean_latency(self) -> float:
        ts = self.latency_values()
        return sum(ts) / len(ts) if ts else 0.0

    def ttft_percentile(self, q: float) -> float:
        from repro.obs import percentile

        return percentile(self.ttft_values(), q)

    def latency_percentile(self, q: float) -> float:
        from repro.obs import percentile

        return percentile(self.latency_values(), q)

    def tpot_percentile(self, q: float) -> float:
        from repro.obs import percentile

        return percentile(self.tpot_values(), q)

    @property
    def ttft_p50(self) -> float:
        return self.ttft_percentile(50)

    @property
    def ttft_p95(self) -> float:
        return self.ttft_percentile(95)

    @property
    def ttft_p99(self) -> float:
        return self.ttft_percentile(99)

    @property
    def latency_p50(self) -> float:
        return self.latency_percentile(50)

    @property
    def latency_p95(self) -> float:
        return self.latency_percentile(95)

    @property
    def latency_p99(self) -> float:
        return self.latency_percentile(99)

    @property
    def tpot_p50(self) -> float:
        return self.tpot_percentile(50)

    @property
    def tpot_p95(self) -> float:
        return self.tpot_percentile(95)

    @property
    def tpot_p99(self) -> float:
        return self.tpot_percentile(99)

    def observe_into(self, registry, stage: str = "engine") -> None:
        """Feed the per-request latency samples into a MetricsRegistry's
        ``serve_ttft_seconds`` / ``serve_latency_seconds`` /
        ``serve_tpot_seconds`` histograms and bump the token counters --
        the registry-side mirror of the report's exact percentiles.
        ``DisaggReport`` reuses this per stage."""
        ttft = registry.histogram(
            "serve_ttft_seconds", "Time to first token", labels=("stage",))
        lat = registry.histogram(
            "serve_latency_seconds", "Request total latency", labels=("stage",))
        tpot = registry.histogram(
            "serve_tpot_seconds", "Per-token latency after the first",
            labels=("stage",))
        for v in self.ttft_values():
            ttft.observe(v, stage=stage)
        for v in self.latency_values():
            lat.observe(v, stage=stage)
        for v in self.tpot_values():
            tpot.observe(v, stage=stage)
        registry.counter(
            "serve_tokens_total", "Committed new tokens",
            labels=("stage",)).inc(self.new_tokens, stage=stage)
        registry.counter(
            "serve_prefill_tokens_total", "Prompt tokens computed by prefill",
            labels=("stage",)).inc(self.prefill_tokens, stage=stage)
        registry.counter(
            "serve_requests_total", "Requests finished",
            labels=("stage",)).inc(len(self.requests), stage=stage)
