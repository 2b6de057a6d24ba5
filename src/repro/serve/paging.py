"""Paged KV-cache subsystem: page pool allocator + cache commit/sync ops.

The serving-side analogue of the paper's row-grouping (DESIGN.md §6):
fixed-size pages trade bounded per-slot padding (at most ``page_size - 1``
dead token slots per request, inside its last page) for perfectly regular
addressing, exactly as RgCSR's uniform groups trade per-group padding for
regular strides — and, following the adaptive-format follow-up
(arXiv:1203.5737), residency is sized to *actual* sequence lengths instead
of the worst case: a slot holding a 37-token request owns
``ceil(37 / page_size)`` pages, not ``S_max`` rows.

Split of responsibilities:

* **Device side** (``models/attention.py``): each attention layer's cache is
  a shared page pool ``(n_pages, page_size, ...)`` plus per-slot
  ``block_table`` / ``index`` vectors; ``attend()`` gathers K/V through the
  block table and masks per slot, so slots at different positions decode in
  one batch.
* **Host side** (this module): :class:`PageAllocator` owns the free list
  and the authoritative block table.  Pages are allocated lazily — prompt
  pages at prefill-commit, one page at a time as decode crosses page
  boundaries — under one of two **admission policies** (DESIGN.md §6.4):

  - ``policy="worst_case"`` reserves each request's worst-case page count
    up front, so mid-decode allocation can never fail — pools sized below
    aggregate worst-case *defer* admissions (FIFO) until pages free;
  - ``policy="prompt"`` (the engine's default) reserves only the pages
    the resident tokens actually need, admitting more concurrent
    requests; when decode then crosses a page boundary with the pool dry,
    :meth:`ensure` raises :class:`PoolExhausted` and the engine
    recompute-preempts a victim slot (``release(evicted=True)``) —
    graceful overload instead of head-of-line blocking.

  Page 0 is reserved as the null page: free slots' table rows point at
  it, so their (ignored) decode writes land there instead of corrupting
  reallocated pages.

``commit_prefill`` bridges the two: prefill runs on an ordinary dense
batch-1 cache (the prompt-length-specialized jit the engine already has),
then its K/V slab is scattered into the slot's pages — ring buffers,
recurrent state, and dense-mode caches are spliced at the slot axis by the
same call, so the engine is layout-agnostic.
"""
from __future__ import annotations

import dataclasses
import zlib
from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.models.attention import PageGeometry
from repro.obs import metrics as obs_metrics

__all__ = ["PageGeometry", "PageAllocator", "PoolExhausted", "geometry",
           "commit_prefill", "sync_block_tables", "page_fingerprints",
           "corrupt_page", "SERVE_MERGE_SPEC"]

# cache keys that live in page pools (everything else is per-slot dense)
_POOL_KEYS = ("k", "v", "k_scale", "v_scale", "ckv", "krope")


def geometry(max_seq: int, page_size: int, n_slots: int,
             n_pages: int = 0) -> PageGeometry:
    """Resolve a :class:`PageGeometry`.  ``n_pages=0`` auto-sizes the pool
    to dense capacity (every slot can reach ``max_seq``) plus the null
    page — admission then never defers; smaller pools trade deferrals for
    memory."""
    pages_per_slot = -(-max_seq // page_size)
    if n_pages <= 0:
        n_pages = 1 + n_slots * pages_per_slot
    return PageGeometry(n_pages=n_pages, page_size=page_size,
                        pages_per_slot=pages_per_slot)


class PoolExhausted(RuntimeError):
    """Raised by :meth:`PageAllocator.ensure` under ``policy="prompt"``
    when a slot must grow but the free list is empty — the engine's
    signal to recompute-preempt a victim slot and retry."""


class PageAllocator:
    """Host-side page bookkeeping for one serve() run.

    Invariants (asserted on every mutation, see :meth:`_check`):

    * ``sum(reserved) <= usable_pages`` — admission control;
    * ``len(free) + pages_in_use == usable_pages`` — pages are never lost
      or double-owned (a double :meth:`release` would otherwise hand the
      same page to two slots);
    * each slot's physical pages never exceed its own worst-case cap.

    ``policy="worst_case"`` reserves the request's whole worst case at
    admission, so :meth:`ensure` can always pop a free page and decode
    never stalls.  ``policy="prompt"`` reserves only what the resident
    tokens need (the reservation tracks the allocation); :meth:`ensure`
    then raises :class:`PoolExhausted` when the pool runs dry and the
    caller must evict a victim (``release(evicted=True)``) before
    retrying.

    **Integrity extensions** (DESIGN.md §7.6): :meth:`quarantine` takes a
    page out of circulation permanently (suspected device-memory
    corruption) — a quarantined page shrinks :attr:`usable` so the
    accounting invariant keeps holding; :meth:`record_checksum` /
    :attr:`checksums` store per-page ``(committed_tokens, crc32)``
    fingerprints recorded by the engine at chunk-commit boundaries.
    ``strict=True`` upgrades the (counted) idempotent double-release
    near-miss into a hard error.
    """

    POLICIES = ("worst_case", "prompt")

    def __init__(self, geom: PageGeometry, n_slots: int,
                 policy: str = "worst_case", strict: bool = False):
        if policy not in self.POLICIES:
            raise ValueError(f"unknown admission policy {policy!r}: "
                             f"expected one of {self.POLICIES}")
        self.geom = geom
        self.n_slots = n_slots
        self.policy = policy
        self.strict = strict
        # LIFO free list over pages 1..n_pages-1 (page 0 = null page);
        # popping the lowest id first keeps allocation deterministic
        self.free: List[int] = list(range(geom.n_pages - 1, 0, -1))
        self.table = np.zeros((n_slots, geom.pages_per_slot), np.int32)
        self.slot_pages: List[List[int]] = [[] for _ in range(n_slots)]
        self.reserved = [0] * n_slots
        self.worst_cap = [geom.pages_per_slot] * n_slots
        self.high_water = 0
        # eviction accounting (preemption observability, DESIGN.md §6.4)
        self.evictions = 0
        self.pages_evicted = 0
        # integrity accounting (DESIGN.md §7.6)
        self.double_release = 0
        self.quarantined: set = set()          # out of circulation for good
        self._pending_quarantine: set = set()  # owned by a slot; withheld
        #                                        from the free list at release
        self.checksums: Dict[int, Tuple[int, int]] = {}
        # observability hook (DESIGN.md §13): the owning session points
        # these at its tracer so quarantines land on the replica's track.
        # None while tracing is off (and during restore-replay, where the
        # quarantines were already traced by the process that found them).
        self.tracer = None
        self.trace_track = None

    # ------------------------------------------------------------- queries
    @property
    def usable(self) -> int:
        """Pages the allocator may hand out: the geometric pool minus
        pages quarantined after corruption (pending ones still sit in a
        slot, so they count as in-use until released)."""
        return self.geom.usable_pages - len(self.quarantined)

    @property
    def pages_in_use(self) -> int:
        return sum(len(p) for p in self.slot_pages)

    @property
    def free_pages(self) -> int:
        """Pages available right now — the router's load-balance signal."""
        return len(self.free)

    def pages_for(self, n_tokens: int) -> int:
        return self.geom.pages_for(n_tokens)

    def admission_pages(self, n_tokens: int, worst_pages: int) -> int:
        """Pages admission will reserve for a request under this policy:
        the full worst case, or just the resident prompt's pages."""
        if self.policy == "prompt":
            return self.pages_for(n_tokens)
        return worst_pages

    def can_admit(self, pages: int) -> bool:
        return sum(self.reserved) + pages <= self.usable

    def _check(self) -> None:
        assert sum(self.reserved) <= self.usable, \
            "admission invariant violated: reservations exceed the pool"
        assert len(self.free) + self.pages_in_use == self.usable, \
            "page accounting violated: free list + in-use != usable " \
            "(double release or leaked page)"
        for s, pages in enumerate(self.slot_pages):
            assert len(pages) <= self.worst_cap[s], \
                f"slot {s} holds more pages than its worst case"

    # ------------------------------------------------------------- updates
    def admit(self, slot: int, n_tokens: int, worst_pages: int) -> bool:
        """Reserve pages for the slot per the admission policy and
        allocate the prompt's pages.  Returns False (nothing changed) when
        the pool can't cover the reservation — the caller defers the
        request."""
        need = self.admission_pages(n_tokens, worst_pages)
        if not self.can_admit(need):
            return False
        self.worst_cap[slot] = worst_pages
        self.reserved[slot] = need
        self.ensure(slot, n_tokens)
        return True

    def ensure(self, slot: int, n_tokens: int) -> bool:
        """Grow the slot's pages to cover ``n_tokens``; True if the block
        table changed (the engine then re-syncs device tables).  Under
        ``policy="prompt"`` the reservation grows with the allocation, and
        :class:`PoolExhausted` is raised if the free list runs dry — the
        partial growth is kept (the slot owns what it got) so the caller
        can evict a victim and retry the same call."""
        need = self.pages_for(n_tokens)
        if self.policy == "prompt":
            assert need <= self.worst_cap[slot], \
                f"slot {slot} grew past its worst-case cap"
        else:
            assert need <= self.reserved[slot], \
                f"slot {slot} grew past its admission reservation"
        changed = False
        pages = self.slot_pages[slot]
        try:
            while len(pages) < need:
                if self.policy == "prompt" and not self.free:
                    raise PoolExhausted(
                        f"slot {slot} needs page {len(pages) + 1}/{need} "
                        f"but the pool is dry")
                page = self.free.pop()
                self.table[slot, len(pages)] = page
                pages.append(page)
                if self.policy == "prompt":
                    self.reserved[slot] = len(pages)
                changed = True
        finally:
            if self.pages_in_use > self.high_water:
                self.high_water = self.pages_in_use
            self._check()
        return changed

    def release(self, slot: int, evicted: bool = False) -> int:
        """Free the slot on completion/eviction: pages return to the pool,
        the table row points back at the null page, the reservation lifts.
        The *cache contents* are untouched — slot reuse needs no reset.

        Idempotent: releasing an already-free slot is a no-op (it must
        not re-extend the free list — that would hand the same page to
        two slots).  Returns the number of pages freed; ``evicted=True``
        additionally counts the free toward the preemption accounting."""
        freed = len(self.slot_pages[slot])
        if freed == 0 and self.reserved[slot] == 0:
            # near-miss: harmless today, but a second release of a live
            # slot would double-own pages — count it so accounting bugs
            # upstream are observable (raise when strict)
            self.double_release += 1
            if self.strict:
                raise RuntimeError(
                    f"double release of already-free slot {slot}")
            return 0
        for page in reversed(self.slot_pages[slot]):
            self.checksums.pop(page, None)
            if page in self._pending_quarantine:
                self._pending_quarantine.discard(page)
                self.quarantined.add(page)
            else:
                self.free.append(page)
        self.slot_pages[slot] = []
        self.table[slot] = 0
        self.reserved[slot] = 0
        self.worst_cap[slot] = self.geom.pages_per_slot
        if evicted:
            self.evictions += 1
            self.pages_evicted += freed
        self._check()
        return freed

    # ---------------------------------------------------------- integrity
    def owner_of(self, page: int) -> Optional[int]:
        """Slot currently holding ``page``, or None (free/quarantined)."""
        for slot, pages in enumerate(self.slot_pages):
            if page in pages:
                return slot
        return None

    def quarantine(self, page: int) -> bool:
        """Take a (suspected-corrupt) page out of circulation for the
        rest of this allocator's life.  A free page leaves the free list
        immediately; a page still owned by a slot is marked pending and
        withheld from the free list when that slot releases.  Returns
        False if the page was already quarantined (idempotent)."""
        if not 0 < page < self.geom.n_pages:
            raise ValueError(f"page {page} outside pool "
                             f"(1..{self.geom.n_pages - 1})")
        if page in self.quarantined or page in self._pending_quarantine:
            return False
        self.checksums.pop(page, None)
        if page in self.free:
            self.free.remove(page)
            self.quarantined.add(page)
        else:
            self._pending_quarantine.add(page)
        if self.tracer is not None and self.tracer.enabled:
            self.tracer.instant("page_quarantine", self.trace_track,
                                page=page)
        self._check()
        return True

    @property
    def pages_quarantined(self) -> int:
        return len(self.quarantined) + len(self._pending_quarantine)

    def record_checksum(self, page: int, n_tokens: int, crc: int) -> None:
        """Record the fingerprint of a page's committed contents (engine
        calls this at chunk-commit boundaries; n_tokens is how many of
        the page's token rows the crc covers)."""
        self.checksums[page] = (int(n_tokens), int(crc))

    def stats(self) -> dict:
        return {
            "n_pages": self.geom.n_pages,
            "page_size": self.geom.page_size,
            "usable_pages": self.usable,
            "pages_in_use": self.pages_in_use,
            "page_high_water": self.high_water,
            "reserved_pages": sum(self.reserved),
            "admission_policy": self.policy,
            "evictions": self.evictions,
            "pages_evicted": self.pages_evicted,
            "double_release": self.double_release,
            "pages_quarantined": self.pages_quarantined,
        }


# ---------------------------------------------------------------------------
# cache tree ops (host-driven, eager — run once per admission / table change)
# ---------------------------------------------------------------------------


def _splice(full, one, slot: int, stacked: bool):
    """Write the batch-1 leaf into the full cache at the slot axis
    (axis 1 under the body stack's leading (layers,) dim)."""
    return jax.lax.dynamic_update_slice_in_dim(
        full, one.astype(full.dtype), slot, axis=1 if stacked else 0)


def _commit_entry(full, one, slot: int, length: int, table_dev,
                  page_ids, offs, stacked: bool):
    if isinstance(full, dict) and "self" in full:   # dec_attn: nested self
        out = dict(full)
        out["self"] = _commit_entry(full["self"], one["self"], slot, length,
                                    table_dev, page_ids, offs, stacked)
        for key in ("ck", "cv"):
            if key in full:
                out[key] = _splice(full[key], one[key], slot, stacked)
        return out
    if isinstance(full, dict) and "block_table" in full:
        # paged entry: scatter the dense prefill slab (1, S_max, ...) into
        # the slot's pages — token t -> (table[slot, t // ps], t % ps)
        out = dict(full)
        for key in _POOL_KEYS:
            if key not in full:
                continue
            pool, slab = full[key], one[key]
            if stacked:
                tok = slab[:, 0, :length].astype(pool.dtype)
                out[key] = pool.at[:, page_ids, offs].set(tok)
            else:
                tok = slab[0, :length].astype(pool.dtype)
                out[key] = pool.at[page_ids, offs].set(tok)
        if stacked:
            out["index"] = full["index"].at[:, slot].set(length)
            out["block_table"] = jnp.broadcast_to(
                table_dev, full["block_table"].shape)
        else:
            out["index"] = full["index"].at[slot].set(length)
            out["block_table"] = table_dev
        return out
    # dense slab / ring / recurrent state: per-slot splice of every leaf
    # (the prefill cache's index leaf carries the prompt length)
    return jax.tree_util.tree_map(
        lambda f, o: _splice(f, o, slot, stacked), full, one)


def commit_prefill(caches, slot_cache, slot: int, length: int,
                   table: Optional[np.ndarray] = None,
                   page_size: Optional[int] = None):
    """Install a batch-1 prefill cache into slot ``slot`` of the live
    decode caches.  Paged entries scatter into pages via ``table`` (the
    allocator's authoritative block table); everything else splices at the
    slot axis.  In dense mode pass ``table=None`` — no paged entries exist
    and the arguments are unused."""
    if table is not None:
        pos = np.arange(length)
        row = np.asarray(table)[slot]
        page_ids = jnp.asarray(row[pos // page_size], jnp.int32)
        offs = jnp.asarray(pos % page_size, jnp.int32)
        table_dev = jnp.asarray(table, jnp.int32)
    else:
        page_ids = offs = table_dev = None
    new = {}
    for part, stacked in (("prefix", False), ("body", True)):
        new[part] = {
            name: _commit_entry(full, slot_cache[part][name], slot, length,
                                table_dev, page_ids, offs, stacked)
            for name, full in caches[part].items()}
    return new


# Authoritative merge schema for session stats (DESIGN.md §13.1).
# Counters sum across replicas; capacity gauges take the fleet-wide
# extreme (with per-replica lists kept so a skewed router policy shows up
# in the bench JSON, not just in the max); pool geometry comes from the
# first replica (replicas share one config); latency histograms merge by
# sample concatenation.  peak_live_tokens rides the page_high_water gate:
# it is reported whenever any replica reports paging high-water figures,
# even for sessions that never recorded a live peak.
SERVE_MERGE_SPEC: Dict[str, obs_metrics.MergeRule] = {
    **{k: obs_metrics.MergeRule("sum") for k in (
        "requests", "completed", "preemptions", "recompute_tokens",
        "rejected", "failed", "timed_out", "decode_steps",
        "decode_dispatches", "admission_deferrals", "evictions",
        "pages_evicted", "double_release", "pages_quarantined",
        "nonfinite_logits", "restores", "restore_recompute_tokens",
        "decode_enqueue_s", "decode_wait_s", "decode_commit_s")},
    "straggler_decode_steps": obs_metrics.MergeRule(
        "sum", list_as="straggler_decode_steps_per_replica"),
    **{k: obs_metrics.MergeRule("first") for k in (
        "n_pages", "page_size", "usable_pages", "admission_policy",
        "kv_layout", "dense_equiv_tokens")},
    "page_high_water": obs_metrics.MergeRule(
        "max", list_as="page_high_water_per_replica"),
    "peak_live_tokens": obs_metrics.MergeRule(
        "max", gate="page_high_water"),
    "request_timing": obs_metrics.MergeRule("hist_map"),
}


def merge_replica_stats(per_replica: list) -> dict:
    """Aggregate per-replica session stats into one router-level view —
    a straight application of :data:`SERVE_MERGE_SPEC` through
    :func:`repro.obs.metrics.merge_stats` (which replaced the ad-hoc
    sum/max/first loops this function used to hand-roll)."""
    return obs_metrics.merge_stats(per_replica, SERVE_MERGE_SPEC)


def _paged_entries(caches):
    """Yield ``(entry, stacked)`` for every paged cache entry in the tree
    (mirrors the traversal in :func:`commit_prefill`)."""
    def walk(entry, stacked):
        if isinstance(entry, dict) and "self" in entry:
            yield from walk(entry["self"], stacked)
        elif isinstance(entry, dict) and "block_table" in entry:
            yield entry, stacked

    for part, stacked in (("prefix", False), ("body", True)):
        for entry in caches.get(part, {}).values():
            yield from walk(entry, stacked)


def page_fingerprints(caches, committed: Dict[int, int]) -> Dict[int, int]:
    """crc32 fingerprint of each page's committed contents.

    ``committed`` maps page id -> number of token rows committed into
    that page; the crc covers exactly those rows (a page's tail beyond
    the committed length holds garbage from slot reuse, so it must not
    feed the fingerprint).  The crc chains over every pool leaf of every
    paged entry, so corruption in any layer/head is caught.
    """
    crcs = {page: 0 for page in committed}
    if not crcs:
        return crcs
    for entry, stacked in _paged_entries(caches):
        for key in _POOL_KEYS:
            if key not in entry:
                continue
            pool = np.asarray(jax.device_get(entry[key]))
            for page, ntok in committed.items():
                slab = pool[:, page, :ntok] if stacked else pool[page, :ntok]
                crcs[page] = zlib.crc32(
                    np.ascontiguousarray(slab).tobytes(), crcs[page])
    return crcs


def pages_nonfinite(caches, pages) -> set:
    """Subset of ``pages`` holding any NaN/Inf in a float pool leaf —
    precise localization for the commit-loop logit screen (NaN leaks
    through the attention mask from *any* position of a touched page, so
    detection can't rely on the committed-region checksums alone)."""
    bad: set = set()
    pages = [p for p in pages]
    for entry, stacked in _paged_entries(caches):
        for key in _POOL_KEYS:
            if key not in entry:
                continue
            pool = entry[key]
            if not jnp.issubdtype(pool.dtype, jnp.floating):
                continue
            arr = np.asarray(jax.device_get(pool))
            for page in pages:
                if page in bad:
                    continue
                slab = arr[:, page] if stacked else arr[page]
                if not np.isfinite(slab).all():
                    bad.add(page)
    return bad


def corrupt_page(caches, page: int, nan: bool = False):
    """Scribble over KV page ``page`` in every pool leaf — the
    ``("page", idx)`` fault payload (simulated device-memory corruption).
    ``nan=True`` writes NaN into float pools (poisons logits, caught by
    the engine's commit-time screen); otherwise writes finite garbage
    (silent — caught only by the checksum verify)."""
    def fix(entry, stacked):
        if isinstance(entry, dict) and "self" in entry:
            out = dict(entry)
            out["self"] = fix(entry["self"], stacked)
            return out
        if isinstance(entry, dict) and "block_table" in entry:
            out = dict(entry)
            for key in _POOL_KEYS:
                if key not in entry:
                    continue
                pool = entry[key]
                if jnp.issubdtype(pool.dtype, jnp.floating):
                    val = jnp.nan if nan else 1e4
                else:
                    val = jnp.iinfo(pool.dtype).max
                fill = jnp.asarray(val, pool.dtype)
                out[key] = (pool.at[:, page].set(fill) if stacked
                            else pool.at[page].set(fill))
            return out
        return entry

    return {part: {name: fix(entry, part == "body")
                   for name, entry in caches[part].items()}
            for part in ("prefix", "body")}


def sync_block_tables(caches, table: np.ndarray):
    """Push the allocator's host block table into every layer's
    ``block_table`` leaf (decode-boundary page allocations, slot frees)."""
    t = jnp.asarray(table, jnp.int32)

    def fix(path, leaf):
        keys = [str(getattr(k, "key", getattr(k, "idx", k))) for k in path]
        if keys and keys[-1] == "block_table":
            return jnp.broadcast_to(t, leaf.shape)
        return leaf

    return jax.tree_util.tree_map_with_path(fix, caches)
