"""Paged KV pool: ONE donated device allocation per tier + a host-side
REFERENCE-COUNTED page allocator with a content-addressed prefix index and
copy-on-write.

The drain-path engine keeps a donated contiguous cache per COMPILED SHAPE —
every (batch, bucket) pair owns a full (L, B, cache_len, K, Dh) buffer.  The
pool replaces all of them with a single physical allocation per tier,
``model_zoo.init_paged_cache``: attention K/V is cut into ``num_pages`` pages
of ``page_size`` positions, and an int32 block table maps each decode slot's
logical pages to physical ones.  Buckets stop being a compile-time property
of the cache: every prompt length shares the same buffers and therefore the
same executable.

Ownership model (the PR-3 refactor)
-----------------------------------
Pages are no longer slot-owned: a physical page carries a REFCOUNT — one per
decode slot whose block row references it, one per prefix-index entry that
retains it.  A page returns to the free list only when its refcount reaches
zero.  Three reference kinds exist:

* slot references — the classic "this slot's block row points here";
* page-index references — full prompt pages are content-addressed by a
  ROLLING CHAIN HASH (``h_i = H(h_{i-1} || tokens of page i)``), which
  encodes the whole trie of prompt prefixes in one flat dict: looking up a
  new prompt walks its chain until the first miss, and every hit page is
  aliased read-only into the new slot's block row (refcount bump, no copy,
  no prefill);
* full-entry references — a completed prompt additionally registers a
  FULL-PROMPT entry (same chain hash extended over the partial tail page)
  that pins every prompt page plus one row of the device-side prefix cache
  (last-position logits, and recurrent state + conv window for the SSM
  families).  A later identical prompt restores from it and skips prefill
  entirely.

COPY-ON-WRITE: a slot may only write pages it holds EXCLUSIVELY (no other
slot referencing them).  Shared pages are read-only; when a full-prompt
restore would have to append decode tokens into a retained partial tail
page, admission allocates a fresh page and schedules an on-device page copy
(``cow`` pairs executed at the top of the scheduler tick) — the index keeps
the original, the slot appends into its private copy.  ``write_block`` gives
the decode step a table with every non-exclusive page masked to the null
page, so a violation of the invariant drops the write harmlessly instead of
corrupting another request's cache.

Eviction is LRU over index entries (pages pinned only by the index are
reclaimable; pages referenced by live slots never move).  The allocator
remains deliberately host-side — allocation happens at request admission
(milliseconds), not inside the device program (microseconds).

Page 0 is the NULL page: freed block-table rows and idle slots point at it,
it receives the (benign, raced) writes of idle slots, and no positional mask
ever exposes its contents.

QUANTIZED POOLS (``dtype=jnp.int8``): the K/V pages are stored as int8 with
symmetric per-page-per-head fp32 scales.  The scale tensors (``ks``/``vs``,
shape ``(L, num_pages, K)``) live in the SAME ``buffers`` pytree as the page
pools — they are part of the one donated allocation and move with it through
every tick.  Scale rows are indexed by PHYSICAL page, so everything the
allocator does to a page (alias, COW copy, free, evict) applies to its scale
row by construction: aliasing shares the row, ``copy_pages`` moves it with
the page (the kernel is rank/dtype generic), and freeing leaves it stale but
unobservable — the first write into a recycled page resets its scale before
quantizing (prefill overwrites it wholesale; the decode append zeroes it at
page offset 0).  Dequantization is fused into the Pallas page-gather kernels
(a ``(1, 1)`` scale block rides the same block-table index_map as its page),
so the gathered K/V never exists in HBM at full precision.  The bf16 default
keeps the cache pytree exactly ``{"kp", "vp"}`` — bitwise identical to the
unquantized build.

SSM-family tiers have constant-size per-slot state instead of pages; the
pool still tracks slot occupancy through the same interface so the scheduler
is family-agnostic (the block table is simply ignored by the SSM decode),
and their prefix reuse runs entirely through the full-entry snapshots.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig
from repro.kernels.decode_attention import MAX_PREFETCH_PAGES
from repro.models import model_zoo


@dataclass
class _PageEntry:
    """One content-addressed full prompt page retained by the index."""
    page: int
    ready: int          # first tick whose lookups may alias this page
    used: int           # LRU stamp


@dataclass
class _FullEntry:
    """One full-prompt snapshot: pinned prompt pages + a prefix-cache row."""
    row: int            # row in the device prefix cache (logits / state)
    pages: List[int]    # every prompt page incl. the partial tail (pinned)
    bucket: int
    ready: int
    used: int


@dataclass
class AdmitPlan:
    """Host-side admission decision for one request (consumed by the tick).

    ``start``     — first token position the admit lane must prefill (page
                    aligned for partial hits; == bucket for full restores);
    ``restore_row`` — prefix-cache row to restore from (-1 = none);
    ``save_row``    — prefix-cache row this admission fills (-1 = none);
    ``cow``         — (src, dst) physical page copy to run before prefill.
    """
    slot: int
    start: int = 0
    restore_row: int = -1
    save_row: int = -1
    cow: Optional[Tuple[int, int]] = None

    @property
    def is_restore(self) -> bool:
        return self.restore_row >= 0


class KVPool:
    """Device page pool + block tables + refcounted allocator for one tier.

    ``buffers`` is the family cache pytree the scheduler threads (donated)
    through every tick; ``prefix_buffers`` (present when
    ``prefix_entries > 0``) holds the device-side prefix cache rows;
    ``block`` is the host-side (num_slots, n_pages) int32 block table passed
    as a small operand each tick.
    """

    def __init__(self, cfg: ModelConfig, num_slots: int, max_context: int,
                 page_size: int = 16, num_pages: Optional[int] = None,
                 dtype=jnp.bfloat16, prefix_entries: int = 0,
                 alloc: bool = True):
        if max_context % page_size:
            raise ValueError(f"max_context {max_context} must be a multiple "
                             f"of page_size {page_size}")
        from repro.configs.base import DENSE, MOE, VLM
        # page-granular partial hits need per-position attention pages; the
        # recurrent families (SSM, and the hybrid's mamba half) have no state
        # snapshot at mid-prompt boundaries, so they share whole prompts only
        self.partial_prefix = cfg.family in (DENSE, VLM, MOE)
        self.recurrent = not self.partial_prefix
        self.cfg = cfg
        self.num_slots = num_slots
        self.page_size = page_size
        self.n_pages_per_slot = max_context // page_size
        if self.n_pages_per_slot > MAX_PREFETCH_PAGES:
            # the decode kernels scalar-prefetch one block-table row into
            # SMEM; a wider row than the kernels' bound would silently read
            # out of the prefetch block, so fail loudly at construction —
            # every alloc/admit_prefix row is bounded by n_pages_per_slot
            raise ValueError(
                f"max_context {max_context} / page_size {page_size} implies "
                f"a {self.n_pages_per_slot}-page block-table row, wider than "
                f"the kernels' scalar-prefetch bound MAX_PREFETCH_PAGES="
                f"{MAX_PREFETCH_PAGES}; raise page_size or lower max_context")
        if num_pages is None:
            # enough for every slot to hold a full-context sequence, + null
            num_pages = num_slots * self.n_pages_per_slot + 1
        if num_pages < 2:
            raise ValueError("need at least one non-null page")
        self.num_pages = num_pages
        # ``alloc=False`` (mesh-sharded serving): this pool is one REPLICA's
        # host-side allocator — free list, block table, refcounts, prefix
        # index — while the device allocation lives in the scheduler's ONE
        # stacked, data-sharded, donated pool tree.  The buffers here are
        # ShapeDtypeStructs (shape/dtype only), which every consumer that
        # stays host-side (gauges, kv_bytes_total, check_invariants) already
        # tolerates: they only read ``.shape`` / ``.dtype``.
        self._alloc = bool(alloc)
        if alloc:
            self.buffers = model_zoo.init_paged_cache(
                cfg, num_slots, num_pages, page_size, dtype)
        else:
            self.buffers = jax.eval_shape(
                lambda: model_zoo.init_paged_cache(cfg, num_slots, num_pages,
                                                   page_size, dtype))
        self.kv_dtype = str(jnp.dtype(dtype))
        # byte accounting over the donated pool allocation (pages + scale
        # rows + recurrent state), fixed at construction — gauges() reports
        # these without touching the device
        self.kv_bytes_total = int(sum(
            int(np.prod(b.shape)) * jnp.dtype(b.dtype).itemsize
            for b in self.buffers.values()))
        self.bytes_per_slot = self.kv_bytes_total // num_slots
        self.prefix_entries = prefix_entries
        if prefix_entries > 0:
            self.prefix_buffers = (
                model_zoo.init_prefix_cache(cfg, prefix_entries, dtype)
                if alloc else jax.eval_shape(
                    lambda: model_zoo.init_prefix_cache(cfg, prefix_entries,
                                                        dtype)))
        else:
            self.prefix_buffers = None
        self.block = np.zeros((num_slots, self.n_pages_per_slot), np.int32)
        # LIFO free list; physical page 0 is the null page, never allocated
        self._free: List[int] = list(range(num_pages - 1, 0, -1))
        self._refs = np.zeros((num_pages,), np.int32)       # all references
        self._slot_refs = np.zeros((num_pages,), np.int32)  # slot refs only
        self._slot_pages: Dict[int, List[int]] = {}
        self._page_index: Dict[bytes, _PageEntry] = {}
        self._full_index: Dict[bytes, _FullEntry] = {}
        self._row_free: List[int] = list(range(prefix_entries - 1, -1, -1))
        # recurrent state held on the device: every slot's, plus the prefix
        # rows' full-prompt snapshots of it
        self.state_bytes = sum(
            int(np.prod(b.shape)) * jnp.dtype(b.dtype).itemsize
            for bufs in (self.buffers, self.prefix_buffers or {})
            for k, b in bufs.items() if k in ("state", "conv"))
        # snapshots_saved / _restored: admissions planned to write a prompt's
        # recurrent state into a prefix row, and to restore a slot from one
        self.stats: Dict[str, int] = {
            "hits": 0, "full_hits": 0, "tokens_saved": 0, "cow_copies": 0,
            "evictions": 0, "snapshots_saved": 0, "snapshots_restored": 0}

    # -- allocator ----------------------------------------------------------

    @property
    def free_pages(self) -> int:
        return len(self._free)

    def gauges(self) -> Dict[str, int]:
        """Telemetry snapshot of allocator state the host already holds —
        sampled once per scheduler tick, no device traffic."""
        return {
            "free_pages": len(self._free),
            "refcount_total": int(self._refs.sum()),
            "prefix_index": len(self._page_index) + len(self._full_index),
            "cow_copies": self.stats["cow_copies"],
            # pool-footprint gauges (constant per pool; numeric so the
            # Chrome-trace exporter tracks them as counter series)
            "kv_bytes_total": self.kv_bytes_total,
            "bytes_per_slot": self.bytes_per_slot,
            "state_bytes": self.state_bytes,
            "kv_bits": 8 if self.kv_dtype == "int8" else 16,
        }

    @property
    def held_slots(self) -> List[int]:
        """Slots currently holding pages — empty after a clean drain.  The
        fault-path tests assert this: every abandoned escalation (lost,
        expired, outage-aborted, or in flight when the run drains) must have
        released its pages through ``free``."""
        return sorted(self._slot_pages)

    def pages_needed(self, context_len: int) -> int:
        return -(-context_len // self.page_size)        # ceil div

    def can_alloc(self, context_len: int, tick: Optional[int] = None) -> bool:
        """Optimistic capacity check: free pages plus what eviction could
        reclaim (index-retained pages with no slot refs; entries still
        PENDING at ``tick`` are excluded, matching eviction's own rule).
        ``alloc``/``admit_prefix`` remain the authority — admission paths
        treat their failure as backpressure and retry."""
        n = self.pages_needed(context_len)
        return n <= len(self._free) + self._reclaimable(tick)

    def _pop_page(self, slot: int) -> int:
        p = self._free.pop()
        self._refs[p] += 1
        self._slot_refs[p] += 1
        self._slot_pages[slot].append(p)
        return p

    def _alias_page(self, slot: int, page: int) -> int:
        self._refs[page] += 1
        self._slot_refs[page] += 1
        self._slot_pages[slot].append(page)
        return page

    def alloc(self, slot: int, context_len: int,
              tick: Optional[int] = None) -> None:
        """Give ``slot`` enough EXCLUSIVE pages for ``context_len`` positions;
        the rest of its block-table row points at the null page."""
        if not 0 <= slot < self.num_slots:
            raise ValueError(f"slot {slot} out of range (0..{self.num_slots - 1})")
        if slot in self._slot_pages:
            raise ValueError(f"slot {slot} already holds pages")
        n = self.pages_needed(context_len)
        if n > self.n_pages_per_slot:
            raise ValueError(
                f"context {context_len} needs {n} pages > per-slot maximum "
                f"{self.n_pages_per_slot}")
        if n > len(self._free) and not self._evict_pages(n, tick=tick):
            raise ValueError(
                f"pool exhausted: need {n} pages, {len(self._free)} free")
        self._slot_pages[slot] = []
        pages = [self._pop_page(slot) for _ in range(n)]
        self.block[slot, :] = 0
        self.block[slot, :n] = pages

    def free(self, slot: int) -> None:
        """Drop ``slot``'s references; pages whose refcount hits zero return
        to the free list.  Stale page contents are never scrubbed — the
        positional mask plus the prefill overwrite make them unobservable to
        the next owner.  Double frees and frees of foreign/unknown slots
        raise instead of corrupting the free list."""
        if not 0 <= slot < self.num_slots:
            raise ValueError(f"slot {slot} out of range (0..{self.num_slots - 1})")
        pages = self._slot_pages.pop(slot, None)
        if pages is None:
            raise ValueError(f"double free: slot {slot} holds no pages")
        for p in reversed(pages):
            if self._refs[p] <= 0 or self._slot_refs[p] <= 0:
                raise ValueError(
                    f"foreign free: page {p} of slot {slot} is not held "
                    f"(refcount underflow)")
            self._refs[p] -= 1
            self._slot_refs[p] -= 1
            if self._refs[p] == 0:
                self._free.append(p)
        self.block[slot, :] = 0

    def owned(self, slot: int) -> List[int]:
        return list(self._slot_pages.get(slot, []))

    # -- prefix index -------------------------------------------------------

    def _reclaimable(self, tick: Optional[int] = None) -> int:
        """Pages that eviction could free: index-retained, no slot refs.
        Pending entries (``ready > tick``) are not evictable, so their pages
        don't count when a tick is given."""
        pages = set()
        pending = set()
        for e in self._page_index.values():
            (pages if tick is None or e.ready <= tick else pending).add(e.page)
        for e in self._full_index.values():
            (pages if tick is None or e.ready <= tick
             else pending).update(e.pages)
        return sum(1 for p in pages - pending if self._slot_refs[p] == 0
                   and self._refs[p] > 0)

    def _evict_pages(self, need: int, tick: Optional[int] = None) -> bool:
        """Evict LRU index entries until at least ``need`` pages are free.
        Entries whose pages are still slot-referenced release only the index
        pin (the pages stay with their slots).  PENDING entries (registered
        this tick, device write still in flight) are never evicted — their
        prefix-cache row would be double-booked mid-dispatch."""
        if len(self._free) >= need:
            return True
        # merged LRU over both index kinds, least-recently-used first
        cand: List[Tuple[int, int, Any]] = []
        for h, e in self._page_index.items():
            if tick is None or e.ready <= tick:
                cand.append((e.used, 0, h))
        for h, e in self._full_index.items():
            if tick is None or e.ready <= tick:
                cand.append((e.used, 1, h))
        cand.sort()
        for _, kind, h in cand:
            if len(self._free) >= need:
                break
            # only evict entries that can contribute pages: an entry whose
            # every page is still slot-referenced frees nothing — dropping it
            # would wipe retention without making progress (the slots, not
            # the index, are what's holding the pool)
            if kind == 0:
                if self._slot_refs[self._page_index[h].page] > 0:
                    continue
                self._drop_page_entry(h)
            else:
                if all(self._slot_refs[p] > 0
                       for p in self._full_index[h].pages):
                    continue
                self._drop_full_entry(h)
            self.stats["evictions"] += 1
        return len(self._free) >= need

    def _unref(self, page: int) -> None:
        self._refs[page] -= 1
        if self._refs[page] == 0:
            self._free.append(page)

    def _drop_page_entry(self, h: bytes) -> None:
        e = self._page_index.pop(h)
        self._unref(e.page)

    def _drop_full_entry(self, h: bytes) -> None:
        e = self._full_index.pop(h)
        for p in e.pages:
            self._unref(p)
        self._row_free.append(e.row)

    def lookup(self, page_hashes: Sequence[bytes], full_hash: bytes,
               bucket: int, tick: int) -> Tuple[Optional[_FullEntry], List[int]]:
        """Longest cached prefix of a prompt.  Returns (full_entry | None,
        hit pages).  Only entries whose fill tick has completed are visible
        (``ready <= tick``), so two identical prompts admitted in the same
        tick never alias pages still being written.  The hit walk is capped
        so at least one prompt position is always left to prefill — the
        admit lane must produce last-position logits for a partial hit."""
        fe = self._full_index.get(full_hash)
        if fe is not None and fe.ready <= tick and fe.bucket == bucket:
            fe.used = tick
            return fe, list(fe.pages)
        max_pages = ((bucket - 1) // self.page_size if self.partial_prefix
                     else 0)
        pages: List[int] = []
        for h in page_hashes[:max_pages]:
            e = self._page_index.get(h)
            if e is None or e.ready > tick:
                break
            e.used = tick
            pages.append(e.page)
        return None, pages

    def admit_prefix(self, slot: int, context_len: int, bucket: int,
                     page_hashes: Optional[Sequence[bytes]],
                     full_hash: Optional[bytes], tick: int, *,
                     register: bool = True) -> Optional[AdmitPlan]:
        """Admission with prefix reuse: alias the longest cached prefix into
        ``slot``'s block row, allocate fresh pages for the rest, and decide
        restore / save / copy-on-write.  Returns None (no side effects) when
        even eviction cannot produce enough fresh pages.

        ``register=False`` (chunked-prefill admission): the prompt's pages
        fill over SEVERAL ticks, so neither the page index nor a full-prompt
        entry may advertise them at ``tick + 1`` — the admission still READS
        cached prefixes (aliasing, ``plan.start``) but retains nothing."""
        if not 0 <= slot < self.num_slots:
            raise ValueError(f"slot {slot} out of range (0..{self.num_slots - 1})")
        if slot in self._slot_pages:
            raise ValueError(f"slot {slot} already holds pages")
        if page_hashes is None or full_hash is None:
            try:
                self.alloc(slot, context_len, tick=tick)
            except ValueError:
                return None
            return AdmitPlan(slot=slot)
        n_ctx = self.pages_needed(context_len)
        if n_ctx > self.n_pages_per_slot:
            raise ValueError(
                f"context {context_len} needs {n_ctx} pages > per-slot "
                f"maximum {self.n_pages_per_slot}")
        fe, hit = self.lookup(page_hashes, full_hash, bucket, tick)
        n_alias = len(hit) if fe is None else bucket // self.page_size
        # alias the hit pages FIRST — the slot reference pins them so the
        # eviction pass below can never reclaim a page we are about to use
        self._slot_pages[slot] = []
        row: List[int] = []
        plan = AdmitPlan(slot=slot)
        for p in (hit if fe is None else fe.pages[: n_alias]):
            row.append(self._alias_page(slot, p))
        if not self._evict_pages(n_ctx - n_alias, tick=tick):
            for p in reversed(self._slot_pages.pop(slot)):   # rollback
                self._refs[p] -= 1
                self._slot_refs[p] -= 1
                if self._refs[p] == 0:
                    self._free.append(p)
            return None
        if fe is not None:
            # full restore: every FULL prompt page is aliased; the partial
            # tail page (decode appends into it) is copy-on-write; fresh
            # pages cover the decode region
            tail = bucket % self.page_size
            if tail:
                dst = self._pop_page(slot)
                row.append(dst)
                plan.cow = (fe.pages[-1], dst)
                self.stats["cow_copies"] += 1
            while len(row) < n_ctx:
                row.append(self._pop_page(slot))
            plan.start = bucket
            plan.restore_row = fe.row
            if self.recurrent:
                self.stats["snapshots_restored"] += 1
            self.stats["full_hits"] += 1
            self.stats["hits"] += 1
            self.stats["tokens_saved"] += bucket
        else:
            plan.start = len(hit) * self.page_size
            if hit:
                self.stats["hits"] += 1
                self.stats["tokens_saved"] += plan.start
            # fresh pages for the uncached prompt suffix; register the FULL
            # ones in the page index (their content lands this tick, usable
            # from the next)
            n_full = (bucket // self.page_size
                      if self.partial_prefix and register else 0)
            for i in range(len(row), n_ctx):
                p = self._pop_page(slot)
                row.append(p)
                if i < n_full and page_hashes[i] not in self._page_index:
                    self._refs[p] += 1
                    self._page_index[page_hashes[i]] = \
                        _PageEntry(p, ready=tick + 1, used=tick)
            if register:
                plan.save_row = self._reserve_full_entry(
                    full_hash, row, bucket, tick)
                if self.recurrent and plan.save_row >= 0:
                    self.stats["snapshots_saved"] += 1
        self.block[slot, :] = 0
        self.block[slot, : len(row)] = row
        return plan

    def _reserve_full_entry(self, full_hash: bytes, row: List[int],
                            bucket: int, tick: int) -> int:
        """Pin this admission's prompt pages + one prefix-cache row so the
        whole prompt can be restored later.  Returns the row or -1 when no
        row is available (all in use and nothing evictable)."""
        if self.prefix_entries == 0 or full_hash in self._full_index:
            return -1
        if not self._row_free:
            # evict the least-recently-used NON-PENDING full entry to
            # recycle its row (a pending row has a device write in flight)
            cand = [kv for kv in self._full_index.items()
                    if kv[1].ready <= tick]
            if not cand:
                return -1
            lru = min(cand, key=lambda kv: kv[1].used)
            self._drop_full_entry(lru[0])
            self.stats["evictions"] += 1
        r = self._row_free.pop()
        pages = row[: self.pages_needed(bucket)]
        for p in pages:
            self._refs[p] += 1
        self._full_index[full_hash] = _FullEntry(
            row=r, pages=list(pages), bucket=bucket, ready=tick + 1,
            used=tick)
        return r

    def write_block(self) -> np.ndarray:
        """Block table for the DECODE WRITE path: pages referenced by more
        than one slot are masked to the null page, so an (invariant-breaking)
        append into a shared page drops instead of corrupting a co-resident
        request.  Host admission guarantees the written page is exclusive
        (COW), making this pure defense in depth."""
        shared = self._slot_refs[self.block] > 1
        return np.where(shared, 0, self.block).astype(np.int32)

    def check_invariants(self) -> None:
        """Debug/test hook: refcount conservation — every page's refcount
        equals its slot references + index retentions, free pages carry no
        references, and live + free pages partition the pool.  Quantized
        pools additionally check scale-row accounting: every page pool has
        fp32 scale tensors with one row per PHYSICAL page, so every
        allocator move of a page implicitly moves its scale row."""
        if "ks" in self.buffers:
            for pool_key, scale_key in (("kp", "ks"), ("vp", "vs")):
                pool, scale = self.buffers[pool_key], self.buffers[scale_key]
                assert jnp.dtype(pool.dtype) == jnp.int8, \
                    f"quantized pool {pool_key} must be int8, got {pool.dtype}"
                assert jnp.dtype(scale.dtype) == jnp.float32, \
                    f"scale {scale_key} must be fp32, got {scale.dtype}"
                # pools are (..., P, page, K·Dh), scales (..., P, K): one
                # scale row per physical page and head
                assert scale.shape[-2] == self.num_pages, \
                    f"scale {scale_key} has {scale.shape[-2]} rows for " \
                    f"{self.num_pages} pages"
                assert scale.shape[:-2] == pool.shape[:-3] and \
                    scale.shape[-1] * self.cfg.resolved_head_dim \
                    == pool.shape[-1], \
                    f"scale {scale_key} shape {scale.shape} does not match " \
                    f"pool {pool_key} shape {pool.shape}"
        else:
            assert "vs" not in self.buffers, "vs scale without ks"
        refs = np.zeros((self.num_pages,), np.int32)
        slot_refs = np.zeros((self.num_pages,), np.int32)
        for pages in self._slot_pages.values():
            for p in pages:
                refs[p] += 1
                slot_refs[p] += 1
        for e in self._page_index.values():
            refs[e.page] += 1
        for e in self._full_index.values():
            for p in e.pages:
                refs[p] += 1
        assert (refs == self._refs).all(), "refcount conservation violated"
        assert (slot_refs == self._slot_refs).all(), \
            "slot refcount conservation violated"
        free = set(self._free)
        assert len(free) == len(self._free), "free list holds duplicates"
        assert 0 not in free, "null page freed"
        assert self._refs[0] == 0, "null page referenced"
        live = {p for p in range(self.num_pages) if self._refs[p] > 0}
        assert not (free & live), "page free AND referenced"
        assert len(free) + len(live) == self.num_pages - 1, "pages leaked"
        for slot in range(self.num_slots):
            row = set(self.block[slot][self.block[slot] > 0].tolist())
            assert row <= set(self._slot_pages.get(slot, [])), \
                f"slot {slot} block row references unheld pages"
        rows = [e.row for e in self._full_index.values()]
        assert len(set(rows)) == len(rows), "prefix-cache row double-booked"
        assert not (set(rows) & set(self._row_free)), \
            "prefix-cache row free AND in use"
        if self.prefix_entries:
            assert len(rows) + len(self._row_free) == self.prefix_entries, \
                "prefix-cache rows leaked"
