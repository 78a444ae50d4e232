"""Paged takum-wire KV pool: free-list allocator + per-sequence block
tables over the pooled cache of ``transformer.init_paged_cache``.

The contiguous serving cache allocates ``batch x max_len`` KV slots up
front, so every sequence pays ``max(prompt) + max_new`` whether it uses
them or not. The :class:`PagePool` instead owns one
``[num_pages, Hkv, page_size, hd]`` wire-word array per layer (float for
the identity codec) and hands out *pages* — ``page_size`` consecutive KV
positions — from a free list. A sequence's pages are glued together by
its row of the block table (``[batch_slots, max_pages]`` int32 page
ids), which rides into the paged attention kernel as a scalar-prefetch
operand. Page size should match the kernel's KV tile
(``kernels.takum_attention.DEFAULT_BK`` or ``ModelConfig.kv_block``):
one page = one decode-and-accumulate step of the flash loop.

This is where the codec's compression becomes *capacity*: the pool's
HBM budget is ``num_pages * page_bytes`` with ``page_bytes`` derived
from the registry spec's bytes-per-element, so a takum8 pool holds 4x
the pages of an f32 pool in the same HBM (``hbm_bytes``,
``docs/serving.md``).

Conventions:

* **Page 0 is reserved** as the scratch page: idle decode-batch slots
  keep riding the compiled step with ``table`` row 0 / ``pos`` 0, so
  their garbage writes and reads land on a page no live sequence owns.
* The allocator is host-side, strict, and **refcounted**: ``alloc``
  hands out pages at refcount 1, ``ref`` adds an owner (a prefix-cache
  node or another block table sharing the page), ``unref`` drops one —
  the page returns to the free list only when its last owner lets go.
  ``free`` is an unref loop, so release code predating sharing keeps
  working. ``unref`` of a page that is not allocated (double free,
  never allocated, the scratch page) raises, and ``alloc`` beyond
  capacity raises — callers are expected to check :meth:`pages_free`
  first (the scheduler's admission gate).
* Recycled pages are **not** zeroed: positions past a sequence's
  ``pos`` hold stale words from previous owners, and containment comes
  from the causal mask (see ``ops.paged_attention``), not from
  zero-fill.
* The pool also owns the host mirrors of ``table``/``pos``/``start``
  and pushes them into every layer's cache leaves (:meth:`push_tables`)
  — only needed when the active set changes (admit/release), since the
  compiled step advances the device-side ``pos`` itself.
"""

from __future__ import annotations

import dataclasses
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

from repro.configs.base import ModelConfig

__all__ = ["PagePool", "PagePoolError", "AdmissionError"]


class PagePoolError(RuntimeError):
    """Allocator misuse: double free, foreign page, over-allocation."""


class AdmissionError(PagePoolError):
    """A request can never be admitted under the pool's page budget.

    Raised at ``submit`` time — with the cache format and the page
    budget in the message — instead of letting an oversized request OOM
    or index out of bounds inside the compiled step.
    """


def pages_for(positions: int, page_size: int) -> int:
    """Pages needed to hold ``positions`` KV positions."""
    return -(-positions // page_size)


# jitted page-copy kernels (built lazily: pools with alloc_device=False
# must not import jax). One compiled executable per (shape, page-count)
# signature — page *ids* are traced operands, so moving pages around
# never retraces. Keeping each copy a single compiled call matters for
# latency: the gather runs on every prefix-hit admission, and eager
# dispatch overhead there serializes straight into later requests' TTFT.
_JIT_COPY: Dict[str, object] = {}


def _copy_kernels():
    if not _JIT_COPY:
        import functools

        import jax

        def gather(pool_kv, dst, pages):
            # pool pages (n_rep, P, Hkv, ps, hd) -> head of a batch-1
            # contiguous cache (n_rep, 1, Hkv, T, hd)
            tiles = pool_kv[:, pages]           # (n_rep, npg, Hkv, ps, hd)
            n_rep, npg, hkv, ps, hd = tiles.shape
            span = tiles.swapaxes(1, 2).reshape(n_rep, 1, hkv, npg * ps, hd)
            return dst.at[:, :, :, :npg * ps].set(span)

        @functools.partial(jax.jit, static_argnames=("first_page",))
        def scatter(pool_kv, src, pages, *, first_page):
            # contiguous pages [first_page, first_page+npg) -> pool pages
            n_rep, _, hkv, _, hd = src.shape
            ps = pool_kv.shape[3]
            npg = pages.shape[0]
            span = src[:, 0, :, first_page * ps:(first_page + npg) * ps]
            tiles = span.reshape(n_rep, hkv, npg, ps, hd).swapaxes(1, 2)
            return pool_kv.at[:, pages].set(tiles)

        _JIT_COPY["gather"] = jax.jit(gather)
        _JIT_COPY["scatter"] = scatter
    return _JIT_COPY


@dataclasses.dataclass(frozen=True)
class PageStats:
    """One snapshot of the allocator (``PagePool.stats()``)."""
    num_pages: int          # total pages, scratch page included
    page_size: int
    free: int
    in_use: int             # unique pages with refcount >= 1
    peak_in_use: int
    hbm_bytes: int          # whole pool, all layers, K and V
    shared_pages: int       # pages with refcount > 1 (prefix dedup)
    prefix_hit_tokens: int  # prompt tokens served from shared pages
    quarantined: int        # corrupted pages retired from circulation
    # summed over decode steps and their active rows (``advance``): KV
    # positions written, and the positions of the pages the rows hold
    # (a shared page counts once per row); their ratio is the pool's fill
    fill_tokens: int
    fill_capacity: int
    # summed over decode steps of a mixture-of-experts model
    # (``note_experts``): routed (token, held expert) rows, and held
    # experts that got rows, over every MoE layer
    moe_rows: int = 0
    moe_calls: int = 0


class PagePool:
    """Free-list page allocator + block tables over the pooled KV cache.

    ``batch`` is the decode-batch width (scheduler slots), ``max_pages``
    the block-table width (pages per sequence cap). With
    ``alloc_device=False`` no device arrays are built — the allocator
    and accounting run standalone (property tests, capacity planning).
    """

    def __init__(self, cfg: ModelConfig, *, batch: int, num_pages: int,
                 page_size: int, max_pages: int, dtype=None,
                 alloc_device: bool = True):
        from repro import formats
        if num_pages < 2:
            raise ValueError(f"num_pages must be >= 2 (page 0 is the "
                             f"reserved scratch page), got {num_pages}")
        if page_size < 8 or page_size % 8:
            raise ValueError(f"page_size must be a positive multiple of "
                             f"8 (kernel tile alignment), got {page_size}")
        self.cfg = cfg
        self.spec = formats.resolve(cfg.kv_quant)
        self.batch = batch
        self.num_pages = num_pages
        self.page_size = page_size
        self.max_pages = max_pages
        self._dtype = dtype
        # LIFO free list: hot pages get reused first (page 0 reserved)
        self._free: List[int] = list(range(num_pages - 1, 0, -1))
        self._refs: Dict[int, int] = {}
        self._quarantined: set = set()
        self._peak = 0
        self._prefix_hit_tokens = 0
        self._fill_tokens = 0
        self._fill_capacity = 0
        self._moe_rows = 0
        self._moe_calls = 0
        # pages given to each slot by assign (0 for an idle slot)
        self._slot_pages = [0] * batch
        # host mirrors of the per-slot table state (pushed on change)
        self.table = np.zeros((batch, max_pages), np.int32)
        self.pos = np.zeros((batch,), np.int32)
        self.start = np.zeros((batch,), np.int32)
        self.cache = None
        if alloc_device:
            from repro.models import model
            self.cache = model.init_paged_cache(
                cfg, batch=batch, num_pages=num_pages, page_size=page_size,
                max_pages=max_pages, dtype=dtype)

    # -- allocator ---------------------------------------------------------

    def pages_free(self) -> int:
        """Pages available for admission (scratch page excluded)."""
        return len(self._free)

    def pages_in_use(self) -> int:
        """Unique allocated pages (a shared page counts once)."""
        return len(self._refs)

    def peak_pages_in_use(self) -> int:
        """High-water mark of concurrently allocated pages."""
        return self._peak

    def shared_pages(self) -> int:
        """Pages held by more than one owner (prefix deduplication)."""
        return sum(1 for c in self._refs.values() if c > 1)

    def refcount(self, page: int) -> int:
        """Current owner count of ``page`` (0 = free)."""
        return self._refs.get(page, 0)

    def alloc(self, n: int) -> Tuple[int, ...]:
        """Take ``n`` pages off the free list at refcount 1 (strict:
        raises if short — admission checks :meth:`pages_free` first)."""
        if n < 0:
            raise ValueError(f"alloc({n})")
        if n > len(self._free):
            raise PagePoolError(
                f"page pool exhausted: requested {n} pages with "
                f"{len(self._free)} free "
                f"(budget {self.num_pages - 1} x {self.page_size} "
                f"{self.spec.name} KV positions)")
        pages = tuple(self._free.pop() for _ in range(n))
        for p in pages:
            self._refs[p] = 1
        self._peak = max(self._peak, len(self._refs))
        return pages

    def ref(self, page: int) -> None:
        """Add an owner to an allocated page — how a prefix-cache node
        or a second block table shares it (strict: the page must be
        allocated; you cannot resurrect a free page by reference)."""
        if page not in self._refs:
            raise PagePoolError(
                f"ref of page {page} which is not allocated "
                f"(free, scratch page, or foreign id)")
        self._refs[page] += 1

    def unref(self, page: int) -> None:
        """Drop one owner; the last owner's unref returns the page to
        the free list (strict: double frees, the scratch page, and
        never-allocated ids raise)."""
        if page not in self._refs:
            raise PagePoolError(
                f"free of page {page} which is not allocated "
                f"(double free, scratch page, or foreign id)")
        self._refs[page] -= 1
        if self._refs[page] == 0:
            del self._refs[page]
            # quarantined pages never rejoin the free list: a corrupted
            # wire page must not be handed to the next admission
            if page not in self._quarantined:
                self._free.append(page)

    def free(self, pages: Sequence[int]) -> None:
        """Unref each page (kept as the bulk-release spelling: with no
        sharing in play refcounts are 1 and this frees outright)."""
        for p in pages:
            self.unref(p)

    def note_prefix_hits(self, n_tokens: int) -> None:
        """Account ``n_tokens`` prompt positions served from shared
        pages instead of recomputed (``PageStats.prefix_hit_tokens``)."""
        self._prefix_hit_tokens += int(n_tokens)

    # -- quarantine (fault containment) ------------------------------------

    def quarantine(self, page: int) -> None:
        """Mark ``page`` as corrupted: it is pulled out of circulation
        permanently (until :meth:`release_quarantined`). A currently
        free page leaves the free list now; an allocated page is left
        to its remaining owners — their final ``unref`` retires it
        instead of recycling it. Idempotent."""
        if not 0 < page < self.num_pages:
            raise PagePoolError(
                f"quarantine of page {page}: not a poolable page id "
                f"(scratch page 0 and ids >= {self.num_pages} excluded)")
        if page in self._quarantined:
            return
        self._quarantined.add(page)
        if page in self._free:
            self._free.remove(page)

    def pages_quarantined(self) -> int:
        return len(self._quarantined)

    def quarantined_pages(self) -> frozenset:
        return frozenset(self._quarantined)

    def release_quarantined(self) -> int:
        """Operator repair hook: return quarantined pages that have no
        remaining owners to the free list (their words are stale-but-
        harmless once recycled — positions past ``pos`` are never read,
        and a fresh owner overwrites from position 0). Pages still
        referenced stay quarantined. Returns the count released."""
        released = [p for p in self._quarantined if p not in self._refs]
        for p in released:
            self._quarantined.discard(p)
            self._free.append(p)
        return len(released)

    # -- memory accounting (registry bytes-per-element) --------------------

    def _n_kv_layers(self) -> int:
        from repro.models.transformer import layer_plan
        return sum(len(pat) * n_rep for pat, n_rep in layer_plan(self.cfg))

    def page_hbm_bytes(self) -> int:
        """Bytes one page costs across all layers (K and V), from the
        registered format's bytes-per-element — the one source of truth
        shared with ``docs/serving.md``'s capacity math."""
        cfg = self.cfg
        from repro.models.transformer import DTYPES
        dtype = self._dtype or DTYPES[cfg.dtype]
        per_elem = self.spec.bytes_per_elem(dtype)
        return (2 * self.page_size * cfg.n_kv_heads * cfg.hd
                * self._n_kv_layers() * per_elem)

    def hbm_bytes(self) -> int:
        """Total pool HBM footprint (every layer's K and V pages)."""
        return self.num_pages * self.page_hbm_bytes()

    def scan_nar(self, pages: Optional[Sequence[int]] = None) -> int:
        """Count stored NaR words across ``pages`` (default: every
        allocated page), all layers, K and V — the pool's numeric-health
        scan (``REPRO_OBS=2`` samples it once per scheduler tick).

        The count is an **over-approximation of live corruption**:
        positions past a sequence's ``pos`` may hold stale words from
        previous owners (recycled pages are not zeroed), and a stale NaR
        there is never read. A count that *rises* while the allocated
        set is stable is the actionable signal — fresh NaR words are
        landing in pages someone owns. Reads device arrays (one sync per
        call); for the identity codec NaN plays the NaR role.
        """
        if self.cache is None:
            raise PagePoolError("pool built with alloc_device=False has "
                                "no device cache")
        import jax.numpy as jnp
        ids = sorted(self._refs) if pages is None \
            else sorted({int(p) for p in pages})
        if not ids:
            return 0
        idx = jnp.asarray(np.asarray(ids, np.int32))
        counts = []
        for attn in self._attn_nodes(self.cache):
            for key in ("k", "v"):
                arr = attn[key][:, idx]
                counts.append(jnp.isnan(arr).sum() if self.spec.is_identity
                              else (arr == self.spec.nar_word).sum())
        return int(sum(counts))

    def stats(self) -> PageStats:
        return PageStats(num_pages=self.num_pages, page_size=self.page_size,
                         free=self.pages_free(), in_use=self.pages_in_use(),
                         peak_in_use=self._peak,
                         hbm_bytes=self.hbm_bytes(),
                         shared_pages=self.shared_pages(),
                         prefix_hit_tokens=self._prefix_hit_tokens,
                         quarantined=self.pages_quarantined(),
                         fill_tokens=self._fill_tokens,
                         fill_capacity=self._fill_capacity,
                         moe_rows=self._moe_rows,
                         moe_calls=self._moe_calls)

    def note_experts(self) -> tuple:
        """Add the last decode step's MoE counters, which the step wrote
        into each MoE layer's ``"moe"`` leaf of the cache (routed rows,
        expert calls), to ``PageStats.moe_rows`` / ``moe_calls``; returns
        the step's ``(rows, calls)``. Read after the step's tokens, so the
        counters are ready; a model without MoE layers has no leaf and
        reads nothing."""
        rows = calls = 0
        for group in self.cache or ():
            for bname in sorted(group):
                node = group[bname]
                if isinstance(node, dict) and "moe" in node:
                    r, c = np.asarray(node["moe"]).sum(axis=0)
                    rows, calls = rows + int(r), calls + int(c)
        self._moe_rows += rows
        self._moe_calls += calls
        return rows, calls

    # -- block tables ------------------------------------------------------

    def assign(self, slot: int, pages: Sequence[int], *, pos: int,
               start: int = 0) -> None:
        """Point decode-batch ``slot`` at ``pages`` (rest of the row
        stays on the scratch page) from position ``pos`` onward."""
        self.table[slot] = 0
        self.table[slot, :len(pages)] = pages
        self.pos[slot] = pos
        self.start[slot] = start
        self._slot_pages[slot] = len(pages)

    def clear(self, slot: int) -> None:
        """Idle a slot: scratch-page table row, pos/start 0."""
        self.table[slot] = 0
        self.pos[slot] = 0
        self.start[slot] = 0
        self._slot_pages[slot] = 0

    def advance(self, slots: Sequence[int]) -> None:
        """Mirror one compiled decode step: the device cache advanced
        every slot's ``pos`` by 1; track the active ones here (idle
        slots drift on device — harmless, see the kernel's table
        clamp — and are resynced by the next :meth:`push_tables`).
        Adds the active rows' written positions and held capacity to
        the fill counters (``PageStats.fill_tokens``/``fill_capacity``)."""
        tokens = pages = 0
        for s in slots:
            self.pos[s] += 1
            tokens += int(self.pos[s])
            pages += self._slot_pages[s]
        self._fill_tokens += tokens
        self._fill_capacity += pages * self.page_size

    # -- device-cache plumbing --------------------------------------------

    def _attn_nodes(self, caches):
        """Yield every stacked per-group attention-cache dict."""
        for group in caches:
            for bname in sorted(group):
                node = group[bname]
                if isinstance(node, dict) and "attn" in node:
                    yield node["attn"]

    def push_tables(self) -> None:
        """Install the host ``table``/``pos``/``start`` mirrors into
        every layer's cache leaves (replicated across the scan dim).
        Called when the active set changes; between changes the device
        step keeps ``pos`` advancing on its own."""
        import jax.numpy as jnp
        if self.cache is None:
            raise PagePoolError("pool built with alloc_device=False has "
                                "no device cache")
        # snapshot the host mirrors: device_put of a numpy array can be
        # zero-copy on CPU, and these buffers are mutated in place by
        # assign/clear/advance — an aliased transfer would let a later
        # host write race an in-flight async step
        table = jnp.asarray(self.table.copy())
        pos = jnp.asarray(self.pos.copy())
        start = jnp.asarray(self.start.copy())
        for attn in self._attn_nodes(self.cache):
            n_rep = attn["table"].shape[0]
            attn["table"] = jnp.broadcast_to(table, (n_rep,) + table.shape)
            attn["pos"] = jnp.broadcast_to(pos, (n_rep,) + pos.shape)
            attn["start"] = jnp.broadcast_to(start, (n_rep,) + start.shape)

    def scatter_prefill(self, contig_caches, pages: Sequence[int], *,
                        first_page: int = 0) -> None:
        """Copy a prefilled *contiguous* single-sequence cache
        (``model.init_cache(batch=1, ...)``) into the pool at ``pages``
        — contiguous page ``first_page + k`` lands on pool page
        ``pages[k]``, for every layer. A prefix-cache hit scatters only
        the suffix pages it computed (``first_page`` > 0, the shared
        head pages already live in the pool); the cache may carry slack
        positions past the scattered range (chunk-padding scratch)."""
        import jax.numpy as jnp
        if self.cache is None:
            raise PagePoolError("pool built with alloc_device=False has "
                                "no device cache")
        ps = self.page_size
        pages_arr = jnp.asarray(np.asarray(pages, np.int32))
        npg = len(pages)
        need = (first_page + npg) * ps
        scatter = _copy_kernels()["scatter"]
        for pool_attn, contig_attn in zip(self._attn_nodes(self.cache),
                                          self._attn_nodes(contig_caches)):
            for key in ("k", "v"):
                src = contig_attn[key]          # (n_rep, 1, Hkv, T, hd)
                b1, t = src.shape[1], src.shape[3]
                if b1 != 1 or t < need:
                    raise ValueError(
                        f"scatter_prefill expects a batch-1 contiguous "
                        f"cache of at least {first_page + npg} x {ps} "
                        f"positions, got {src.shape}")
                pool_attn[key] = scatter(pool_attn[key], src, pages_arr,
                                         first_page=first_page)

    def gather_prefix(self, contig_caches, pages: Sequence[int], *,
                      pos: int) -> None:
        """Inverse of :meth:`scatter_prefill`: copy pool ``pages`` into
        the head of a contiguous single-sequence cache (page k of the
        sequence comes from pool page ``pages[k]``) and set every
        layer's ``pos`` leaf to ``pos`` — the prefix-hit seam. The
        suffix chunks then prefill *on top of* the shared prefix KV
        (they must attend to it), and only suffix pages are scattered
        back. Wire words are copied as words: a gather + scatter
        round-trip is bit-exact, no re-quantisation."""
        import jax.numpy as jnp
        if self.cache is None:
            raise PagePoolError("pool built with alloc_device=False has "
                                "no device cache")
        ps = self.page_size
        npg = len(pages)
        pages_arr = jnp.asarray(np.asarray(pages, np.int32))
        gather = _copy_kernels()["gather"]
        for pool_attn, contig_attn in zip(self._attn_nodes(self.cache),
                                          self._attn_nodes(contig_caches)):
            if npg:
                for key in ("k", "v"):
                    dst = contig_attn[key]      # (n_rep, 1, Hkv, T, hd)
                    if dst.shape[1] != 1 or dst.shape[3] < npg * ps:
                        raise ValueError(
                            f"gather_prefix needs a batch-1 contiguous "
                            f"cache of at least {npg} x {ps} positions, "
                            f"got {dst.shape}")
                    contig_attn[key] = gather(pool_attn[key], dst,
                                              pages_arr)
            contig_attn["pos"] = jnp.full_like(contig_attn["pos"], pos)
