"""Paged RaZeR-quantized KV pool for continuous batching.

The static engine allocates one contiguous ``(batch, max_len)`` cache per
sequence slot; at mixed prompt lengths most of that HBM is padding.  The pool
instead carves KV storage into fixed-size **pages** of ``page_size`` tokens
shared by all sequences, with a per-sequence page table mapping logical token
positions to physical pages -- the vLLM PagedAttention layout, applied to the
4.5-bit wire format.

The page layout IS the existing KV wire format (serving/kvcache.py, paper
App. C.1): per (token, kv-head), the head dim splits into 16-element quant
blocks stored as ``hd//2`` code bytes + ``hd//16`` scale-meta bytes.  Blocks
never span tokens, so ANY page of whole tokens is an integer number of quant
blocks and ``kv_quantize`` / ``kv_dequantize`` apply per page unchanged:

    k_codes[page, slot, kvh, hd//2]   two FP4 codes per byte
    k_meta [page, slot, kvh, hd//16]  E4M3 scale (7 bits) + SV-sign bit

Physical page 0 is reserved as the **null page**: page-table rows of inactive
decode slots (and the tails of short sequences) point at it, so masked lanes
of the fixed-shape decode step scatter their garbage writes somewhere harmless
instead of needing a dynamic shape.

Device buffers mirror the engine's per-layer-group cache list (one stacked
``(count, num_pages, page_size, kvh, ...)`` dict per scan group) so the paged
decode step slices them exactly like the contiguous caches.  Allocation
(free-list, per-sequence page lists) is host-side Python: it runs between jit
steps, never inside them.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.models.config import ArchConfig
from repro.models.transformer import layer_groups

NULL_PAGE = 0


@functools.partial(jax.jit, donate_argnums=(0,))
def _copy_page(pool, src, dst):
    """Copy one physical page's wire bytes src -> dst across a layer group's
    buffers (the copy-on-write fork of a partially reused cached page)."""
    return {key: buf.at[:, dst].set(buf[:, src]) for key, buf in pool.items()}


@functools.partial(jax.jit, donate_argnums=(0,))
def _scatter_pages(pool, src, dst):
    """Write shipped page bytes (one layer group's ``(count, n, ps, kvh, X)``
    buffers) into physical pages ``dst`` of a donated pool -- the device half
    of ``KVPagePool.import_pages``."""
    return {key: buf.at[:, dst].set(src[key]) for key, buf in pool.items()}


@functools.partial(jax.jit, donate_argnums=(0,))
def _quantize_scatter(pool, k, v, pids, sids):
    """Quantize a prefill's K/V (count, S, kvh, hd) and write token j into pool
    page ``pids[j]`` slot ``sids[j]`` -- one compiled call per prefill bucket
    shape (padded tokens ride along into the null page).  The pool buffers
    are donated: the caller replaces them with the result, so the update
    happens in place instead of copying the pool.

    The tokens are consecutive positions: token j sits in slot
    ``(sids[0] + j) % ps`` of the span's page ``(sids[0] + j) // ps``.  The
    write is a chain of ``dynamic_update_slice``: one whole page per page that
    tokens fill completely, and slot by slot for the span's first and last
    page, where slots no token fills (the forked head of a suffix prefill, the
    slots past the prompt) keep their bytes -- a slot write with no token
    goes to the null page.  A scatter, or a read of a page to merge with,
    would make XLA relayout each whole pool buffer, whose pages are the TPU's
    minor axis: 1.2 GB of temporaries at Qwen3-8B's pool."""
    from repro.serving.kvcache import kv_quantize

    kc, km = kv_quantize(k)
    vc, vm = kv_quantize(v)
    ps = pool["k_codes"].shape[2]
    s = pids.shape[0]
    n = -(-(s + ps - 1) // ps)  # pages a span of s tokens at any offset touches

    def paged(x):  # (count, S, ...) -> (count, n, ps, ...) on the span's pages
        pad = [(0, 0)] * x.ndim
        pad[1] = (ps, n * ps - s)  # pads pids with NULL_PAGE (0): no token there
        x = jax.lax.dynamic_slice_in_dim(jnp.pad(x, pad), ps - sids[0], n * ps, axis=1)
        return x.reshape(x.shape[0], n, ps, *x.shape[2:])

    slot_page = paged(pids[None])[0]  # (n, ps)
    page = jnp.max(slot_page, axis=1)  # the page's id, NULL_PAGE if no token
    whole = jnp.where((slot_page != NULL_PAGE).all(axis=1), page, NULL_PAGE)
    last = (sids[0] + jnp.sum(pids != NULL_PAGE) - 1) // ps
    out = {}
    for key, x in (("k_codes", kc), ("k_meta", km), ("v_codes", vc), ("v_meta", vm)):
        buf, new = pool[key], paged(x)
        zeros = (0,) * (buf.ndim - 3)
        for i in range(n):
            buf = jax.lax.dynamic_update_slice(buf, new[:, i:i + 1], (0, whole[i], 0) + zeros)
        for edge in (0, last):
            row = jax.lax.dynamic_index_in_dim(new, edge, axis=1)  # (count, 1, ps, ...)
            for t in range(ps):
                at = (0, slot_page[edge, t], t) + zeros
                buf = jax.lax.dynamic_update_slice(buf, row[:, :, t:t + 1], at)
        out[key] = buf
    return out


@dataclasses.dataclass(frozen=True)
class PagePoolConfig:
    """Sizing knobs for the paged KV pool.

    ``num_pages`` counts usable pages EXCLUDING the reserved null page;
    ``max_len`` bounds any single sequence (prompt + generated) and fixes the
    page-table width ``ceil(max_len / page_size)`` the decode step is
    compiled for.
    """

    num_pages: int
    page_size: int = 16
    max_len: int = 256

    def __post_init__(self):
        if self.page_size < 1:
            raise ValueError(f"page_size must be >= 1, got {self.page_size}")
        if self.num_pages < 1:
            raise ValueError(f"num_pages must be >= 1, got {self.num_pages}")

    @property
    def pages_per_seq(self) -> int:
        """Page-table width: worst-case pages one sequence can touch."""
        return -(-self.max_len // self.page_size)


@dataclasses.dataclass
class PageShipment:
    """Wire-format KV pages of one sequence, on host, ready to cross a
    process/host boundary (serving/disagg).

    ``buffers`` mirrors the pool's per-layer-group cache list: one
    ``{"k_codes": (count, n_pages, ps, kvh, hd//2) u8, "k_meta": ..., ...}``
    dict per scan group, gathered in LOGICAL page order -- entry ``i`` along
    the page axis holds tokens ``[i * ps, (i+1) * ps)``.  The payload IS the
    App. C.1 wire format, so shipping KV between replicas costs 4.5 bits per
    element (``nbytes``) instead of 16 (``bf16_bytes``) -- the 3.56x transfer
    saving that makes prefill/decode disaggregation cheap.  ``n_tokens``
    counts the valid leading positions (the tail of the last page is
    uninitialized wire bytes the importer's decode overwrites/masks).
    """

    seq_id: int
    n_tokens: int
    page_size: int
    buffers: List[Dict[str, np.ndarray]]

    @property
    def n_pages(self) -> int:
        return self.buffers[0]["k_codes"].shape[1]

    @property
    def nbytes(self) -> int:
        """Transfer payload: wire-format bytes actually shipped."""
        return sum(int(a.nbytes) for g in self.buffers for a in g.values())

    @property
    def bf16_bytes(self) -> int:
        """What the same KV pages would cost in bf16 (2 bytes/element)."""
        hd = self.buffers[0]["k_codes"].shape[-1] * 2
        return sum(
            int(np.prod(g["k_codes"].shape[:-1])) * hd * 2 * 2  # K+V, 2 B each
            for g in self.buffers
        )


def _check_paged_arch(cfg: ArchConfig) -> None:
    """The pool stores the GQA wire format; archs whose decode state is not a
    per-token GQA cache cannot page it (they keep the static engine path)."""
    # modality frontends are rejected too: Engine.serve has no extras path,
    # so a VLM/audio prefill would silently drop its frontend embeddings
    if cfg.mla or cfg.ssm or cfg.block_pattern or cfg.encoder_decoder or cfg.frontend != "none":
        raise ValueError(
            "paged KV serving supports pure GQA-attention stacks (dense or MoE); "
            f"arch {cfg.name!r} has "
            + ", ".join(
                k for k, v in [
                    ("mla", cfg.mla), ("ssm", cfg.ssm),
                    ("block_pattern", bool(cfg.block_pattern)),
                    ("encoder_decoder", cfg.encoder_decoder),
                    (f"a {cfg.frontend} frontend", cfg.frontend != "none"),
                ] if v
            )
            + " -- use Engine.generate (static batching) for it"
        )
    if cfg.hd % 16 != 0:
        raise ValueError(f"quantized KV pages need head_dim % 16 == 0, got hd={cfg.hd}")


class KVPagePool:
    """Block-quantized KV page pool + free-list allocator + page tables.

    Device state lives in ``self.caches`` (functionally updated by the jitted
    decode step -- the engine writes the new buffers back after each step);
    everything else is host bookkeeping.
    """

    def __init__(self, cfg: ArchConfig, pool_cfg: PagePoolConfig):
        _check_paged_arch(cfg)
        self.cfg = cfg
        self.pool_cfg = pool_cfg
        hd, kvh, ps = cfg.hd, cfg.num_kv_heads, pool_cfg.page_size
        p = pool_cfg.num_pages + 1  # + reserved null page 0
        self.caches: List[Dict[str, jnp.ndarray]] = []
        for _, count in layer_groups(cfg):
            self.caches.append({
                "k_codes": jnp.zeros((count, p, ps, kvh, hd // 2), jnp.uint8),
                "k_meta": jnp.zeros((count, p, ps, kvh, hd // 16), jnp.uint8),
                "v_codes": jnp.zeros((count, p, ps, kvh, hd // 2), jnp.uint8),
                "v_meta": jnp.zeros((count, p, ps, kvh, hd // 16), jnp.uint8),
            })
        self._free: List[int] = list(range(p - 1, NULL_PAGE, -1))  # pop() -> lowest first
        self._seq_pages: Dict[int, List[int]] = {}
        self._pending_forks: Dict[int, tuple] = {}  # seq -> (dst, src), see flush_forks
        # physical page -> owner count.  Owners are sequences (one ref per
        # sequence whose page list holds the page) plus, for pages published
        # into a prefix cache, the cache itself (serving/prefixcache.py takes
        # one ref per radix node).  A page returns to the free list when its
        # last owner lets go; refcount > 1 means SHARED, and shared pages are
        # immutable by construction (prefill/decode only ever write positions
        # past the shared prefix, which live in sequence-private pages).
        self._refs: Dict[int, int] = {}
        # optional observability hooks, called as listener(event, n_pages)
        # with event in {"alloc", "append", "release", "truncate",
        # "cow_fork"} -- see install_pool_metrics / docs/observability.md.
        # A list (not a single callable): the engine's metrics wiring and a
        # test probe can both subscribe without displacing each other.
        self._listeners: List = []
        # optional numerics-audit hook (obs/numerics.KVAuditor): read-only
        # observer of prefill K/V, NULL-style no-op when None (the default)
        self._kv_audit = None

    def set_kv_audit(self, auditor) -> None:
        """Attach a ``KVAuditor`` (or None to detach).  The auditor only
        reads the bf16 prefill caches out-of-band -- pool contents and serve
        outputs are bit-identical with or without it."""
        self._kv_audit = auditor

    def add_listener(self, fn) -> None:
        """Subscribe ``fn(event, n_pages)`` to allocator events."""
        self._listeners.append(fn)

    def _notify(self, event: str, n: int) -> None:
        for fn in self._listeners:
            fn(event, n)

    # -- accounting ----------------------------------------------------------
    @property
    def num_free_pages(self) -> int:
        return len(self._free)

    @property
    def pages_in_use(self) -> int:
        return self.pool_cfg.num_pages - len(self._free)

    def pages_for(self, n_tokens: int) -> int:
        return -(-n_tokens // self.pool_cfg.page_size)

    def can_allocate(self, n_tokens: int) -> bool:
        return self.pages_for(n_tokens) <= len(self._free)

    def bytes_per_page(self) -> int:
        """Wire-format bytes one page holds across all layers (K+V)."""
        hd, kvh, ps = self.cfg.hd, self.cfg.num_kv_heads, self.pool_cfg.page_size
        layers = sum(count for _, count in layer_groups(self.cfg))
        return layers * ps * kvh * 2 * (hd // 2 + hd // 16)

    def total_bytes(self) -> int:
        return self.bytes_per_page() * (self.pool_cfg.num_pages + 1)

    # -- refcounting ---------------------------------------------------------
    def refcount(self, page: int) -> int:
        return self._refs.get(page, 0)

    def incref(self, page: int) -> None:
        """Add an owner to a live page (prefix-cache publication)."""
        if page not in self._refs:
            raise ValueError(
                f"page {page} is not allocated; only live pages can gain owners"
            )
        self._refs[page] += 1

    def decref(self, page: int) -> None:
        """Drop one owner; the last owner's decref frees the page."""
        n = self._refs.get(page, 0)
        if n <= 0:
            raise ValueError(f"page {page} has no owners to release (double free?)")
        if n == 1:
            del self._refs[page]
            self._free.append(page)
        else:
            self._refs[page] = n - 1

    # -- alloc / free --------------------------------------------------------
    def allocate(self, seq_id: int, n_tokens: int,
                 shared: Sequence[int] = (), cow_src: Optional[int] = None) -> List[int]:
        """Reserve pages covering ``n_tokens`` logical positions for a (new)
        sequence.  Raises if the pool cannot fit it -- the scheduler gates
        admission on ``can_allocate`` so this only fires on misuse.

        ``shared`` are live pages (a cached prefix, in logical order) the
        sequence joins as a co-owner -- they cost no free pages.  ``cow_src``
        forks one more page: a fresh page is popped and the sequence owns the
        COPY (the partially reused cached page stays immutable; the sequence
        overwrites the copied tail in place).  The device-side byte copy is
        DEFERRED to ``flush_forks``: at admission time a same-batch donor may
        not have prefilled the source page yet -- the engine flushes right
        before this sequence's own prefill, by which point every
        earlier-admitted write has landed.  The source holds an extra ref
        until the flush so eviction cannot recycle it in between.  The
        remainder comes fresh from the free list."""
        if seq_id in self._seq_pages:
            raise ValueError(
                f"double allocation: sequence {seq_id} already holds pages "
                f"{self._seq_pages[seq_id]}; release() it first (decode growth "
                f"goes through append())"
            )
        need = self.pages_for(n_tokens)
        if n_tokens > self.pool_cfg.max_len:
            raise ValueError(
                f"sequence {seq_id} wants {n_tokens} tokens > pool max_len "
                f"{self.pool_cfg.max_len} (page-table width is fixed at compile time)"
            )
        n_fresh = need - len(shared)
        if n_fresh < 0 or (cow_src is not None and n_fresh < 1):
            raise ValueError(
                f"sequence {seq_id}: {len(shared)} shared pages"
                + ("" if cow_src is None else " + a COW fork")
                + f" exceed the {need} pages {n_tokens} tokens need"
            )
        if n_fresh > len(self._free):
            raise RuntimeError(
                f"KV pool exhausted: need {n_fresh} fresh pages, {len(self._free)} "
                f"free (admit fewer sequences or grow num_pages)"
            )
        for pg in shared:
            self.incref(pg)
        pages = list(shared)
        if cow_src is not None:
            dst = self._free.pop()
            self._refs[dst] = 1
            self.incref(cow_src)  # pin the source until the copy happens
            self._pending_forks[seq_id] = (dst, cow_src)
            pages.append(dst)
        while len(pages) < need:
            pg = self._free.pop()
            self._refs[pg] = 1
            pages.append(pg)
        self._seq_pages[seq_id] = pages
        if self._listeners:
            self._notify("alloc", need - len(shared))
        return pages

    def append(self, seq_id: int, new_len: int) -> List[int]:
        """Grow a sequence's page list to cover ``new_len`` tokens (decode
        append path).  Returns the newly added physical pages."""
        if seq_id not in self._seq_pages:
            raise ValueError(
                f"append() for unknown sequence {seq_id}: it holds no pages "
                f"(allocate() it first, or it was already released)"
            )
        pages = self._seq_pages[seq_id]
        need = self.pages_for(new_len)
        added: List[int] = []
        if need > self.pool_cfg.pages_per_seq:
            raise ValueError(
                f"sequence {seq_id} grew past pool max_len {self.pool_cfg.max_len}"
            )
        while len(pages) < need:
            if not self._free:
                raise RuntimeError(
                    f"KV pool exhausted appending to sequence {seq_id}; the "
                    f"scheduler must reserve decode headroom at admission"
                )
            pages.append(self._free.pop())
            self._refs[pages[-1]] = 1
            added.append(pages[-1])
        if added and self._listeners:
            self._notify("append", len(added))
        return added

    def truncate(self, seq_id: int, new_len: int) -> List[int]:
        """Shrink a sequence's page list to cover ``new_len`` tokens, dropping
        ownership of the tail pages (speculative-decode rollback: rejected
        draft positions sit past the committed length, so the pages holding
        only them pop off the page-table tail and -- when this sequence was
        their last owner -- return to the free list).  The wire bytes are NOT
        erased: stale positions >= ``new_len`` never attend (``cur_len``
        masking), exactly like null-page garbage writes.  Returns the popped
        physical pages (newest first).

        A tail page another owner still holds (a prefix cache, a sharing
        sequence) merely loses this sequence as an owner -- though in the
        serving loop rollback only ever pops sequence-private speculative
        pages: shared prefix pages cover prompt tokens, and ``cur_len`` never
        rolls back below the prompt."""
        if seq_id not in self._seq_pages:
            raise ValueError(
                f"truncate() for unknown sequence {seq_id}: it holds no pages "
                f"(never allocated, or already released)"
            )
        if new_len < 0:
            raise ValueError(f"truncate() to negative length {new_len}")
        pages = self._seq_pages[seq_id]
        keep = self.pages_for(new_len)
        popped: List[int] = []
        while len(pages) > keep:
            pg = pages.pop()
            if self._pending_forks.get(seq_id, (None,))[0] == pg:
                # the deferred COW copy targeted this page: cancel the fork
                # and unpin its source (property-suite interleavings; the
                # serve loop never truncates into the prompt's COW page)
                self.decref(self._pending_forks.pop(seq_id)[1])
            self.decref(pg)
            popped.append(pg)
        if popped and self._listeners:
            self._notify("truncate", len(popped))
        return popped

    def flush_forks(self, seq_id: int) -> None:
        """Execute the sequence's deferred copy-on-write page copy (no-op if
        none pending).  Called right before the sequence's own prefill reads
        the copy -- every earlier-admitted prefill has written by then."""
        if seq_id in self._pending_forks:
            dst, src = self._pending_forks.pop(seq_id)
            for gi, c in enumerate(self.caches):
                self.caches[gi] = _copy_page(
                    c, jnp.asarray(src, jnp.int32), jnp.asarray(dst, jnp.int32))
            self.decref(src)
            if self._listeners:
                self._notify("cow_fork", 1)

    def release(self, seq_id: int) -> None:
        """Drop a finished/evicted sequence's ownership of its pages.  Private
        pages return to the free list; pages a prefix cache (or another
        sequence) still owns merely lose one owner."""
        if seq_id in self._pending_forks:  # evicted before it ever prefilled
            self.decref(self._pending_forks.pop(seq_id)[1])
        if seq_id not in self._seq_pages:
            raise ValueError(
                f"release() for unknown sequence {seq_id}: it holds no pages "
                f"(never allocated, or already released)"
            )
        free_before = len(self._free)
        for pg in self._seq_pages.pop(seq_id):
            self.decref(pg)
        if self._listeners:
            # count pages actually returned to the free list, not decrefs --
            # cache-shared pages survive their donor and are not "released"
            self._notify("release", len(self._free) - free_before)

    def sequence_pages(self, seq_id: int) -> List[int]:
        return list(self._seq_pages[seq_id])

    # -- page tables ---------------------------------------------------------
    def page_row(self, seq_id: Optional[int]) -> np.ndarray:
        """(pages_per_seq,) i32 physical-page row; unused tail (and a ``None``
        sequence, i.e. an idle decode slot) points at the null page."""
        row = np.full((self.pool_cfg.pages_per_seq,), NULL_PAGE, np.int32)
        if seq_id is not None:
            pages = self._seq_pages[seq_id]
            row[: len(pages)] = pages
        return row

    def page_table(self, seq_ids: Sequence[Optional[int]]) -> jnp.ndarray:
        """(len(seq_ids), pages_per_seq) i32 table for one decode step."""
        return jnp.asarray(np.stack([self.page_row(s) for s in seq_ids]))

    # -- prefill writes ------------------------------------------------------
    def write_prefill(self, seq_id: int, caches: List[Dict[str, jnp.ndarray]],
                      length: int, start: int = 0) -> None:
        """Scatter a prefill's quantized K/V into the sequence's pages.

        ``caches`` is the engine prefill output restricted to batch index 0:
        one ``{"k": (count, 1, S, kvh, hd), "v": ...}`` dict per layer group
        (bf16), where S is the engine's padded prefill bucket.  Every position
        quantizes per token -- the page is an integer number of quant blocks,
        so this is ``kv_quantize`` applied page-wise unchanged -- and valid
        positions ``[start, length)`` (cache index j holds token ``start + j``;
        a prefix-cached suffix prefill passes ``start = cached_len``) are
        written to ``(page_of(start + j), (start + j) % page_size)`` while the
        padded tail goes to the null page; every other slot keeps its bytes.
        A nonzero ``start`` never touches the shared prefix pages: they cover
        tokens ``[0, start)`` only.  Quantize + write run as ONE jitted call
        (cached per bucket shape): the eager per-op path recompiles per prompt
        shape and dominates serving wall time.
        """
        ps = self.pool_cfg.page_size
        row = np.asarray(self.page_row(seq_id))
        s = caches[0]["k"].shape[2]
        pos = start + np.arange(s)
        logical = np.minimum(pos // ps, row.shape[0] - 1)
        pids = jnp.asarray(np.where(pos < length, row[logical], NULL_PAGE))
        sids = jnp.asarray(pos % ps)
        for gi, c in enumerate(self.caches):
            self.caches[gi] = _quantize_scatter(
                c, caches[gi]["k"][:, 0], caches[gi]["v"][:, 0], pids, sids)
        if self._kv_audit is not None:
            self._kv_audit.observe_prefill(seq_id, caches, length, start, ps)

    # -- wire-format page transfer (serving/disagg) --------------------------
    def export_pages(self, seq_id: Optional[int] = None, *,
                     page_ids: Optional[Sequence[int]] = None,
                     n_tokens: Optional[int] = None) -> PageShipment:
        """Gather a sequence's pages (or an explicit logical-order ``page_ids``
        list) to host as a ``PageShipment``.

        A prefill replica calls this after the last prefill chunk lands: the
        shipment holds exactly the bytes its pool pages do, so a decode
        replica that ``import_pages`` it attends bit-identical KV.  Pending
        copy-on-write forks for the sequence are flushed first -- a shipment
        must capture the sequence's OWN last-page bytes, not its donor's
        still-shared source page.  ``n_tokens`` bounds the export to the pages
        covering that many leading tokens (default: every page the sequence
        holds, valid to its full page span)."""
        if (seq_id is None) == (page_ids is None):
            raise ValueError("export_pages: pass exactly one of seq_id / page_ids")
        if seq_id is not None:
            self.flush_forks(seq_id)
            pages = self._seq_pages.get(seq_id)
            if pages is None:
                raise ValueError(
                    f"export_pages() for unknown sequence {seq_id}: it holds no "
                    f"pages (never allocated, or already released)"
                )
            if n_tokens is None:
                n_tokens = len(pages) * self.pool_cfg.page_size
            pages = pages[: self.pages_for(n_tokens)]
        else:
            pages = list(page_ids)
            if n_tokens is None:
                n_tokens = len(pages) * self.pool_cfg.page_size
            if self.pages_for(n_tokens) != len(pages):
                raise ValueError(
                    f"export_pages: {len(pages)} pages cannot cover n_tokens="
                    f"{n_tokens} (need {self.pages_for(n_tokens)} at page_size "
                    f"{self.pool_cfg.page_size})"
                )
        ids = jnp.asarray(np.asarray(pages, np.int32))
        buffers = [
            {key: np.asarray(jax.device_get(buf[:, ids])) for key, buf in c.items()}
            for c in self.caches
        ]
        return PageShipment(seq_id=seq_id if seq_id is not None else -1,
                            n_tokens=int(n_tokens),
                            page_size=self.pool_cfg.page_size, buffers=buffers)

    def import_pages(self, shipment: PageShipment, *, seq_id: Optional[int] = None,
                     reserve_tokens: Optional[int] = None) -> List[int]:
        """Inject a shipment into THIS pool: allocate fresh pages for the
        sequence and write the shipped wire bytes into the leading ones.

        ``reserve_tokens`` (default ``shipment.n_tokens``) sizes the
        allocation -- a decode replica reserves the worst case
        ``len(prompt) + max_new_tokens`` up front, exactly like single-engine
        admission, so decode never deadlocks on pool growth.  Returns the
        sequence's new page list (logical order); the shipment's page ``i``
        bytes now live in physical page ``pages[i]`` and ``page_table`` /
        ``paged_kv_attention`` work unchanged."""
        sid = shipment.seq_id if seq_id is None else seq_id
        n_tok = shipment.n_tokens if reserve_tokens is None else reserve_tokens
        if shipment.page_size != self.pool_cfg.page_size:
            raise ValueError(
                f"shipment page_size {shipment.page_size} != pool page_size "
                f"{self.pool_cfg.page_size}; replicas must agree on the page layout"
            )
        if len(shipment.buffers) != len(self.caches) or any(
            s[k].shape[0] != c[k].shape[0] or s[k].shape[2:] != c[k].shape[2:]
            for s, c in zip(shipment.buffers, self.caches) for k in c
        ):
            raise ValueError(
                "shipment layer-group/head layout does not match this pool "
                "(different arch?)"
            )
        if n_tok < shipment.n_tokens:
            raise ValueError(
                f"reserve_tokens={n_tok} < shipment.n_tokens={shipment.n_tokens}: "
                f"the reservation must cover every shipped page"
            )
        pages = self.allocate(sid, n_tok)
        dst = jnp.asarray(np.asarray(pages[: shipment.n_pages], np.int32))
        for gi, host in enumerate(shipment.buffers):
            src = {k: jnp.asarray(v) for k, v in host.items()}
            self.caches[gi] = _scatter_pages(self.caches[gi], src, dst)
        return pages

    # -- debug / tests -------------------------------------------------------
    def gather_sequence(self, seq_id: int, length: int, group: int = 0):
        """Dequantized (count, length, kvh, hd) K/V of one sequence -- test
        and fallback path; the decode hot loop never materializes this."""
        from repro.serving.kvcache import kv_dequantize

        ps = self.pool_cfg.page_size
        row = np.asarray(self.page_row(seq_id))
        pos = np.arange(length)
        pids, sids = row[pos // ps], pos % ps
        c = self.caches[group]
        k = kv_dequantize(c["k_codes"][:, pids, sids], c["k_meta"][:, pids, sids], self.cfg.hd)
        v = kv_dequantize(c["v_codes"][:, pids, sids], c["v_meta"][:, pids, sids], self.cfg.hd)
        return k, v


def install_pool_metrics(registry, pool: KVPagePool, *,
                         stage: str = "engine", replica: str = "0") -> None:
    """Export a pool's occupancy and allocator traffic into ``registry``.

    Occupancy is function-backed gauges (``pool_pages{state=...}``,
    ``pool_refcount_total``): the registry reads the pool at collection
    time and the allocator hot path never touches a metric.  Allocator
    traffic (``pool_page_events_total{event=...}``) rides the listener
    hook.  ``stage``/``replica`` distinguish disagg fleet members sharing
    one registry ("prefill"/"decode" x worker id); the single engine uses
    the defaults.
    """
    pages = registry.gauge(
        "pool_pages", "KV pool pages by state", labels=("stage", "replica", "state"))
    pages.set_function(lambda: pool.num_free_pages,
                       stage=stage, replica=replica, state="free")
    pages.set_function(lambda: pool.pages_in_use,
                       stage=stage, replica=replica, state="live")
    pages.set_function(lambda: 1,  # the reserved null page
                       stage=stage, replica=replica, state="null")
    refs = registry.gauge(
        "pool_refcount_total",
        "Sum of page owner counts (> live pages means prefix sharing)",
        labels=("stage", "replica"))
    refs.set_function(lambda: sum(pool._refs.values()),
                      stage=stage, replica=replica)
    events = registry.counter(
        "pool_page_events_total",
        "Allocator events by type (pages moved per event)",
        labels=("stage", "replica", "event"))
    pool.add_listener(
        lambda event, n: events.inc(n, stage=stage, replica=replica, event=event))
