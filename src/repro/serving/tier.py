"""Host-side KV page store: the spill/restore tier under the device pool.

The paper's scarce resource is device HBM for multi-million-token KV
histories; serving/pool.py rations it, but until now the only responses to
pool pressure were "queue" or "preempt and recompute everything".  The
``HostPageStore`` adds the missing tier: page-granularity save/restore of
KV state in host memory, so

  * preemption **spills** a request's live pool pages (int8 payloads and
    f32 scale planes included — exact bytes, not a re-quantized copy)
    before the pool releases them, and resume becomes a block-table
    rebuild plus one H2D scatter with zero re-prefill chunks;
  * a retired request's pages can persist keyed by session id, so turn
    N+1 of a multi-turn conversation restores its history instead of
    re-prefilling it (``DecodeEngine`` session KV);
  * the PrefixIndex's host fp K/V blobs (PR 7 kept them forever) ride the
    same LRU so prefix-restore host memory is capped.

Integrity is never assumed: every stored page carries a CRC32 checksum
and a generation stamp, both verified before any byte is handed back — a
corrupt or stale entry is detected, dropped and reported, and the engine
falls back to the re-prefill path (graceful degradation, never divergent
tokens).  ``serving/faults.py`` injects the failure modes
deterministically so CI can prove that contract (scripts/chaos_smoke.py).

The store is layout-agnostic pure host python + numpy: an entry is a dict
of page-stacked planes with the page axis at position 1 (pool spills use
``[L, P, Kh, block_s, hsz]``; prefix blobs reshape their carry-buffer
layout the same way).  Capacity is counted in pages across all planes'
page axis; eviction is LRU over whole entries (sessions), mirroring the
device pool's accounting style so the property suite
(tests/serving/test_tier_props.py) can model it exactly.
"""
from __future__ import annotations

import dataclasses
import zlib
from collections import OrderedDict

import numpy as np

from repro.serving.faults import FaultPlan

__all__ = ["HostPageStore", "HostEntry"]


@dataclasses.dataclass
class HostEntry:
    """One stored KV snapshot: page-stacked planes + integrity metadata.

    ``planes`` maps plane name -> host array with the page axis at
    position 1; ``tokens`` is the token prefix the pages represent (the
    restore-applicability check); ``gen`` is the entry's generation stamp
    with ``page_gens[p]`` expected to equal it for every page — a
    mismatch means the page was recycled under us; ``sums[p]`` is the
    CRC32 over page ``p``'s bytes across all planes."""

    key: str
    tokens: tuple
    planes: dict[str, np.ndarray]
    n_pages: int
    gen: int
    page_gens: list[int]
    sums: list[int]


def _page_crc(planes: dict[str, np.ndarray], p: int) -> int:
    # chained CRC over every plane's page-p slice, in sorted plane order
    acc = 0
    for name in sorted(planes):
        acc = zlib.crc32(np.ascontiguousarray(planes[name][:, p]).tobytes(),
                         acc)
    return acc


class HostPageStore:
    """Capacity-bounded host KV store with checksums, generations and LRU.

    ``capacity_pages`` bounds the total page count across live entries;
    ``put`` evicts least-recently-used entries to make room (whole
    entries — a half-restored session is useless).  ``faults`` (a
    ``serving/faults.FaultPlan``) deterministically injects the tier's
    failure modes; with no plan the store is exact and loss-free.

    Counters (all monotonic): ``saves``/``restores``/``restores_failed``,
    ``checksum_mismatches`` (corrupt bytes), ``stale_generations``
    (recycled pages), ``evictions``/``evicted_pages`` (LRU),
    ``store_full`` (refused saves, genuine or injected).
    """

    def __init__(self, capacity_pages: int,
                 faults: FaultPlan | None = None):
        assert capacity_pages > 0, "host store needs >= 1 page"
        self.capacity = capacity_pages
        self._faults = (faults or FaultPlan()).injector()
        self._entries: "OrderedDict[str, HostEntry]" = OrderedDict()
        self._gen = 0
        self.pages_used = 0
        self.saves = 0
        self.restores = 0
        self.restores_failed = 0
        self.checksum_mismatches = 0
        self.stale_generations = 0
        self.evictions = 0
        self.evicted_pages = 0
        self.store_full = 0

    def __len__(self) -> int:
        return len(self._entries)

    def has(self, key: str) -> bool:
        """Whether an entry for ``key`` is currently live (no LRU touch,
        no fault draw, no integrity verification — a cheap existence
        probe; the restore itself may still fail)."""
        return key in self._entries

    def tokens(self, key: str) -> tuple | None:
        """The token prefix stored under ``key`` (None when absent) — the
        engine's restore-applicability check.  No LRU touch, no fault
        draw."""
        e = self._entries.get(key)
        return None if e is None else e.tokens

    # ----------------------------------------------------------- mutation
    def put(self, key: str, planes: dict, tokens=()) -> bool:
        """Save one snapshot under ``key`` (overwriting any previous one).

        ``planes`` must be non-empty arrays sharing the page axis (axis 1)
        extent; they are copied to host memory, stamped with a fresh
        generation, and checksummed per page.  Returns False — allocator
        untouched beyond counters — when the save is refused: injected
        ``store_full`` fault, or the entry alone exceeds capacity.
        Otherwise LRU entries are evicted until the entry fits."""
        assert planes, "empty snapshot"
        n_pages = {int(v.shape[1]) for v in planes.values()}
        assert len(n_pages) == 1, f"ragged page axes: {n_pages}"
        n = n_pages.pop()
        assert n > 0, "zero-page snapshot"
        if self._faults.draw("store_full") or n > self.capacity:
            self.store_full += 1
            return False
        self.drop(key)
        while self.pages_used + n > self.capacity:
            old_key, old = next(iter(self._entries.items()))
            self._entries.pop(old_key)
            self.pages_used -= old.n_pages
            self.evictions += 1
            self.evicted_pages += old.n_pages
        host = {name: np.array(v, copy=True) for name, v in planes.items()}
        gen = self._gen
        self._gen += 1
        entry = HostEntry(key=key, tokens=tuple(int(t) for t in tokens),
                          planes=host, n_pages=n, gen=gen,
                          page_gens=[gen] * n,
                          sums=[_page_crc(host, p) for p in range(n)])
        if self._faults.draw("corrupt"):
            self._corrupt(entry)
        self._entries[key] = entry
        self.pages_used += n
        self.saves += 1
        return True

    def _corrupt(self, entry: HostEntry) -> None:
        # damage AFTER checksumming, so verification catches it: either a
        # byte flip in one page (checksum mismatch) or a bumped page
        # generation (stale-tenancy mismatch)
        p = self._faults.pick(entry.n_pages)
        if self._faults.pick(2) == 0:
            name = sorted(entry.planes)[0]
            arr = entry.planes[name]
            # the page slice is strided (page axis 1), so mutate a
            # contiguous copy and write it back — a view-reshape would
            # silently flip a throwaway buffer instead
            page = np.ascontiguousarray(arr[:, p])
            flat = page.view(np.uint8).reshape(-1)
            flat[self._faults.pick(flat.size)] ^= 0xFF
            arr[:, p] = page
        else:
            entry.page_gens[p] += 1

    def drop(self, key: str) -> bool:
        """Remove ``key``'s entry (no-op on absence); True when dropped."""
        e = self._entries.pop(key, None)
        if e is None:
            return False
        self.pages_used -= e.n_pages
        return True

    # ------------------------------------------------------------ restore
    def _verify(self, entry: HostEntry) -> str | None:
        for p in range(entry.n_pages):
            if entry.page_gens[p] != entry.gen:
                self.stale_generations += 1
                return "generation"
            if _page_crc(entry.planes, p) != entry.sums[p]:
                self.checksum_mismatches += 1
                return "checksum"
        return None

    def restore(self, key: str) -> tuple[dict | None, int, str | None]:
        """Fetch ``key``'s planes for an H2D restore, with fault draws.

        Returns ``(planes, delay_steps, why)``: on success planes is the
        stored dict, ``delay_steps`` how many engine steps the injected
        ``delay`` fault withholds them (0 normally), ``why`` None.  On
        failure planes is None and ``why`` one of ``"missing"`` (no
        entry), ``"injected"`` (restore_fail fault), ``"checksum"`` /
        ``"generation"`` (integrity verification — the entry is dropped so
        corrupt bytes can never be served later)."""
        entry = self._entries.get(key)
        if entry is None:
            return None, 0, "missing"
        if self._faults.draw("restore_fail"):
            self.restores_failed += 1
            return None, 0, "injected"
        why = self._verify(entry)
        if why is not None:
            self.restores_failed += 1
            self.drop(key)
            return None, 0, why
        delay = self._faults.plan.delay_steps \
            if self._faults.draw("delay") else 0
        self._entries.move_to_end(key)
        self.restores += 1
        return entry.planes, delay, None

    def fetch(self, key: str) -> dict | None:
        """Integrity-verified payload WITHOUT injected restore faults.

        The prefix-sharing admission path calls this up to three times per
        decision (fits / can_admit_now / reserve) and all three must agree,
        so only deterministic failures apply: a corrupt/stale entry is
        dropped (counted) and every subsequent call consistently misses.
        Touches LRU recency; does not count as a restore."""
        entry = self._entries.get(key)
        if entry is None:
            return None
        if self._verify(entry) is not None:
            self.drop(key)
            return None
        self._entries.move_to_end(key)
        return entry.planes

    # -------------------------------------------------------------- stats
    def stats(self) -> dict:
        """Counter snapshot (plus occupancy) for metrics summaries."""
        return {
            "host_pages_capacity": self.capacity,
            "host_pages_used": self.pages_used,
            "host_entries": len(self._entries),
            "host_saves": self.saves,
            "host_restores": self.restores,
            "restores_failed": self.restores_failed,
            "checksum_mismatches": self.checksum_mismatches,
            "stale_generations": self.stale_generations,
            "store_evictions": self.evictions,
            "store_full": self.store_full,
        }

    # --------------------------------------------------------- invariants
    def check_invariants(self) -> None:
        """Assert the accounting the property suite pins: page usage
        equals the sum over entries, never exceeds capacity, and every
        healthy entry's checksums verify."""
        total = sum(e.n_pages for e in self._entries.values())
        assert total == self.pages_used, (total, self.pages_used)
        assert total <= self.capacity, (total, self.capacity)
        for e in self._entries.values():
            assert e.n_pages == next(iter(e.planes.values())).shape[1]
