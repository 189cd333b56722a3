"""Admission scheduling for the continuous-batching engine (pure python).

The ``Scheduler`` owns everything the engine must *decide* (who runs where,
and when) without touching device state: the admission queue, the slot
table, per-slot committed cache lengths, and the cache-pressure gate.  It is
deliberately jax-free so its invariants can be property-tested exhaustively
(tests/serving/test_scheduler_props.py) with simulated request streams —
the ``DecodeEngine`` mirrors its decisions onto the device arrays.

Request lifecycle (docs/serving.md):

    QUEUED --admit--> PREFILL --last chunk--> DECODE --retire--> DONE
       ^                  |                      |
       +----preempt-------+----------preempt-----+

Policies: ``"fcfs"`` (arrival order) and ``"sjf"`` (shortest remaining
prefill first — cheap requests jump the queue, bounding their TTFT under
load).  Both apply the cache-pressure gate: a request whose prefill alone
cannot fit the per-slot cache capacity is rejected up front instead of
being admitted and immediately capacity-retired.  Preempted requests
re-enter at the front of the queue so they resume promptly.

Tenancy (``tenants=`` / ``slo_aware=``, docs/serving.md): every request
carries a ``tenant`` name and an SLO class (``interactive`` — TTL-bound,
``batch`` — throughput-bound).  With tenancy on, ``_pick`` layers a
deficit-weighted-fair-queueing admission filter over the base policy:

  * eligibility — a tenant at its slot quota, or a batch-class request
    while ``batch_cap`` batch slots already run, is skipped (never
    blocking an eligible interactive request behind it);
  * class priority — eligible interactive requests admit before eligible
    batch ones;
  * weighted fairness — among the eligible class, the tenant with the
    least *normalized service* (served tokens / weight) goes first, so
    backlogged tenants' served-token shares converge to their weight
    shares (tests/serving/test_tenant_props.py);
  * bounded credit — a tenant returning from idle has its service floored
    to the least-served active tenant's, so idle time never banks an
    unbounded catch-up burst.

``batch_cap`` (default ``max_batch``) is the dynamic ceiling on running
batch-class slots the TTL governor (serving/governor.py) trades against
interactive latency.  Without tenancy every knob is inert and admission
is byte-for-byte the legacy FCFS/SJF behavior.
"""
from __future__ import annotations

import dataclasses
from typing import Any

import numpy as np

# lifecycle states (plain strings so they serialize/log cleanly)
QUEUED = "queued"
PREFILL = "prefill"
# awaiting a host-tier page restore (serving/tier.py): the slot is held
# and other slots keep decoding, but this one neither prefills nor
# decodes until the (possibly fault-delayed) H2D restore commits
RESTORING = "restoring"
DECODE = "decode"
DONE = "done"

POLICIES = ("fcfs", "sjf")

# SLO classes (serving/workload.py traces tag every request with one):
# interactive work is TTL-bound (the paper's budget), batch work is
# throughput-bound and the first to be shed under TTL pressure
SLO_INTERACTIVE = "interactive"
SLO_BATCH = "batch"
SLO_CLASSES = (SLO_INTERACTIVE, SLO_BATCH)


@dataclasses.dataclass(frozen=True)
class TenantConfig:
    """Per-tenant admission knobs for the DWFQ layer.

    ``weight`` sets the tenant's fair share of served tokens while
    backlogged (normalized service = served / weight; the least-served
    tenant admits first).  ``max_slots`` > 0 caps the tenant's concurrent
    engine slots (0 = no quota)."""
    name: str
    weight: float = 1.0
    max_slots: int = 0


@dataclasses.dataclass
class Request:
    """One generation request.

    ``prompt`` is the token list to prefill; the engine appends generated
    tokens to ``out_tokens`` and sets ``done``/``finish_reason`` on
    retirement (``"eos"`` | ``"max_tokens"`` | ``"capacity"`` |
    ``"rejected"``).  ``state`` tracks the scheduler lifecycle."""
    rid: int
    prompt: list[int]
    max_new_tokens: int = 32
    eos_id: int | None = None
    out_tokens: list[int] = dataclasses.field(default_factory=list)
    done: bool = False
    state: str = QUEUED
    finish_reason: str | None = None
    preempted: bool = False                   # awaiting resume (front of queue)
    admit_seq: int = -1                       # admission order stamp
    session_id: str | None = None             # multi-turn session KV key
    tenant: str = "default"                   # DWFQ accounting bucket
    slo_class: str = SLO_INTERACTIVE          # interactive (TTL) | batch
    sampling: Any = None                      # per-request SamplingParams
                                              # (None = engine default)
    # --- chunked-prefill bookkeeping (engine-internal) ---
    prefill_tokens: list[int] | None = None   # prompt (+ generated on resume)
    prefill_pos: int = 0                      # next chunk offset
    buffers: Any = None                       # K/V carry buffers (device)
    # --- prefix-sharing bookkeeping (engine-internal, set at admission) ---
    shared_len: int = 0                       # matched prefix tokens (restore)
    shared_pages: int = 0                     # leading logical pages shared
    shared_kv: Any = None                     # host fp K/V of [0, shared_len)
    # --- host-tier spill/restore bookkeeping (engine-internal) ---
    spill_key: str | None = None              # host store key of spilled pages
    spill_len: int = 0                        # committed tokens when spilled
    forced_tokens: list[int] | None = None    # restore catch-up token queue
    resume_fallback: bool = False             # restore failed -> re-prefill

    def resume_tokens(self) -> list[int]:
        """Tokens to (re-)prefill: the prompt plus anything already
        generated (preempted requests recompute their full context)."""
        return list(self.prompt) + list(self.out_tokens)


def _kv_to_pages(arr, block_s: int):
    """Carry-layout host K/V ``[L, t, Kp, hsz]`` -> page stack
    ``[L, P, block_s, Kp, hsz]`` (zero-padded tail) for the host store."""
    arr = np.asarray(arr)
    l, t = arr.shape[:2]
    p = -(-t // block_s)
    if p * block_s != t:
        pad = [(0, 0)] * arr.ndim
        pad[1] = (0, p * block_s - t)
        arr = np.pad(arr, pad)
    return arr.reshape(l, p, block_s, *arr.shape[2:])


def _pages_to_kv(pages, t: int):
    """Inverse of ``_kv_to_pages``: drop the padding back to ``t`` rows."""
    l, p, bs = pages.shape[:3]
    return pages.reshape(l, p * bs, *pages.shape[3:])[:, :t]


class PrefixIndex:
    """Hash trie over token ids, at page granularity, mapping prompts onto
    already-committed KV prefixes (the prefix-sharing index).

    Registration happens when a request finishes chunked prefill: the engine
    hands over the token sequence, the request's physical page list (with
    the pool's generation stamps), and a **host fp copy** of the carried
    K/V buffers.  An arriving prompt then walks the trie — one node per
    full page of ``block_s`` token ids — to its longest registered prefix:

      * the matched length ``m`` gates *compute*: the engine restores the
        host fp K/V for ``[0, m)`` into the new request's prefill buffers
        and chunk-prefills only the suffix (TTFT ~ suffix-only).  The host
        copy is captured before quantization, so restoration is bit-exact
        for fp and kv8 alike and never needs to invert the pool layout.
      * the entry's still-live leading pages gate *memory*:
        ``valid_leading_pages`` checks refcount + generation per page, and
        the scheduler ``share()``s exactly that many full pages instead of
        charging fresh ones (see ``Scheduler.fits``).

    Entries never go "wrong", only stale: the host K/V is a pure function
    of the token prefix, so a fully-recycled entry still saves prefill
    compute even when no pages are shareable any more.  ``max_entries``
    bounds the entry count with FIFO eviction; with ``store`` (a
    ``serving/tier.HostPageStore``) the K/V blobs themselves live under
    the store's page-capacity LRU instead of inline — an evicted or
    corrupt blob degrades that entry to pages-only sharing (the suffix
    prefill falls back to a full prefill, still bit-exact)."""

    def __init__(self, block_s: int, pool, max_entries: int = 64,
                 store=None):
        assert block_s > 0
        self.block_s = block_s
        self.pool = pool
        self.store = store
        self.max_entries = max_entries
        self._root: dict = {"children": {}, "entries": []}
        self._order: list[dict] = []          # FIFO eviction order
        self._seq = 0
        self.lookups = 0
        self.hits = 0

    def __len__(self) -> int:
        return len(self._order)

    def register(self, tokens, pages, kv=None) -> None:
        """Insert one committed prefix: ``tokens`` (the full prefilled
        sequence), its physical ``pages`` (snapshotted with the pool's
        current generation stamps), and ``kv`` — host fp
        ``(k, v)`` arrays of shape ``[L, len(tokens), Kp, hsz]`` captured
        from the prefill carry buffers before quantization.

        With a host page store, the blob is deposited there under a
        ``prefix:<seq>`` key (page-reshaped, checksummed, LRU-bounded) and
        the entry keeps only the key; a refused save (store-full fault)
        registers the entry pages-only."""
        toks = tuple(int(t) for t in tokens)
        if kv is not None and self.store is not None:
            key = f"prefix:{self._seq}"
            planes = {"k": _kv_to_pages(kv[0], self.block_s),
                      "v": _kv_to_pages(kv[1], self.block_s)}
            kv = key if self.store.put(key, planes, tokens=toks) else None
        entry = {"tokens": toks, "pages": list(pages),
                 "gens": [self.pool.generation(p) for p in pages],
                 "kv": kv, "seq": self._seq, "nodes": []}
        self._seq += 1
        node = self._root
        node["entries"].append(entry)
        entry["nodes"].append(node)
        bs = self.block_s
        for d in range(len(toks) // bs):
            key = toks[d * bs:(d + 1) * bs]
            nxt = node["children"].get(key)
            if nxt is None:
                nxt = {"children": {}, "entries": []}
                node["children"][key] = nxt
            node = nxt
            node["entries"].append(entry)
            entry["nodes"].append(node)
        self._order.append(entry)
        while len(self._order) > self.max_entries:
            old = self._order.pop(0)
            for n in old["nodes"]:
                n["entries"].remove(old)
            if self.store is not None and isinstance(old["kv"], str):
                self.store.drop(old["kv"])

    def match(self, tokens, limit: int) -> tuple[int, dict | None]:
        """Longest registered prefix of ``tokens``: returns ``(m, entry)``
        with ``m <= limit`` matched token ids (0, None on miss).  Walks the
        page-key trie to the deepest node, then extends token-by-token into
        the partial page against that node's entries; equal-length matches
        break toward the entry with the most still-live (shareable) leading
        pages — a retired twin's entry saves the same prefill compute but
        no memory — then toward the earliest-registered (determinism)."""
        self.lookups += 1
        toks = tuple(int(t) for t in tokens)
        bs = self.block_s
        path = [self._root]
        node = self._root
        for d in range(len(toks) // bs):
            nxt = node["children"].get(toks[d * bs:(d + 1) * bs])
            if nxt is None:
                break
            node = nxt
            path.append(node)
        best_m, best, best_key = 0, None, None
        for depth in range(len(path) - 1, -1, -1):
            for e in sorted(path[depth]["entries"], key=lambda e: e["seq"]):
                m = depth * bs
                et = e["tokens"]
                hi = min(len(toks), len(et), limit)
                while m < hi and toks[m] == et[m]:
                    m += 1
                m = min(m, limit)
                key = (m, self.valid_leading_pages(e), -e["seq"])
                if best_key is None or key > best_key:
                    best_m, best, best_key = m, e, key
            if best_m > 0:
                break       # shallower nodes can only match shorter prefixes
        if best_m <= 0:
            return 0, None
        self.hits += 1
        return best_m, best

    def resolve_kv(self, entry: dict):
        """The entry's host fp ``(k, v)`` arrays for a buffer restore, or
        None when unavailable.  Inline blobs return as stored; store-backed
        blobs fetch through the ``HostPageStore`` with integrity
        verification but no injected restore faults (this runs inside the
        admission decision, which must be internally consistent) — an
        evicted or corrupt blob clears the entry's reference and the
        admission proceeds pages-only with a full prefill."""
        kv = entry["kv"]
        if kv is None or not isinstance(kv, str):
            return kv
        planes = None if self.store is None else self.store.fetch(kv)
        if planes is None:
            entry["kv"] = None
            return None
        t = len(entry["tokens"])
        return (_pages_to_kv(planes["k"], t), _pages_to_kv(planes["v"], t))

    def valid_leading_pages(self, entry: dict) -> int:
        """How many of ``entry``'s leading pages are still the same tenancy
        they were at registration (refcount > 0 and unchanged generation) —
        the shareable page span.  Later pages may have been recycled; the
        host K/V stays usable regardless."""
        n = 0
        for p, g in zip(entry["pages"], entry["gens"]):
            if self.pool.refcount(p) <= 0 or self.pool.generation(p) != g:
                break
            n += 1
        return n

    def hit_rate(self) -> float:
        """Fraction of lookups that matched a non-empty prefix."""
        return self.hits / max(self.lookups, 1)


class Scheduler:
    """FCFS/SJF admission queue + slot table with cache-pressure gating.

    ``cap`` is the per-slot KV capacity; a slot's committed length may
    never reach it (the engine retires the request one token earlier —
    ``at_capacity``).  All methods are O(queue) python; the engine calls
    ``admit()`` once per step and mirrors the returned placements.

    **Capacity oracle** (the single authority both admission paths and the
    retirement check consult, so they can never disagree): with ``pool``
    (a ``serving/pool.BlockAllocator`` — the shared-pool paged KV cache)
    capacity is the *global* free-page count — ``fits`` asks whether the
    request could ever hold its prompt + one token in ``max_pages`` pages,
    ``can_admit_now`` whether that many pages are free *now* (otherwise the
    request stays queued instead of being rejected), and
    ``grow_for_next_token`` reserves the next decode token's page on
    demand.  Without ``pool`` the same three methods fall back to the
    per-slot ``cap`` gate (always-admissible once a slot is free)."""

    def __init__(self, max_batch: int, cap: int, policy: str = "fcfs",
                 pool=None, max_pages: int = 0, prefix_index=None,
                 tenants=None, slo_aware: bool | None = None):
        if policy not in POLICIES:
            raise ValueError(f"unknown sched policy {policy!r}; "
                             f"choose from {POLICIES}")
        self.policy = policy
        self.cap = cap
        self.max_batch = max_batch
        self.pool = pool
        self.max_pages = max_pages or (pool.capacity if pool else 0)
        self.prefix_index = prefix_index
        self.queue: list[Request] = []
        self.slot_rids: list[int | None] = [None] * max_batch
        self.slot_len: list[int] = [0] * max_batch
        self.rejected: list[Request] = []
        self._admit_seq = 0
        # --- tenancy / DWFQ state (inert unless slo_aware) ---
        if tenants is not None and not isinstance(tenants, dict):
            tenants = {t.name: t for t in tenants}
        self.tenants: dict[str, TenantConfig] | None = tenants
        # slo_aware turns on class priority + DWFQ + batch_cap in _pick;
        # default: on iff tenants are configured (the TTL governor turns it
        # on without tenant configs — every tenant then weighs 1.0)
        self.slo_aware = bool(tenants) if slo_aware is None else slo_aware
        self.batch_cap = max_batch              # governor-adjusted ceiling
        self.slot_tenant: list[str | None] = [None] * max_batch
        self.slot_slo: list[str | None] = [None] * max_batch
        self.served_tokens: dict[str, int] = {}
        self._service: dict[str, float] = {}    # served / weight per tenant

    # ----------------------------------------------------------- tenancy
    def _weight(self, tenant: str) -> float:
        cfg = (self.tenants or {}).get(tenant)
        return max(cfg.weight, 1e-9) if cfg is not None else 1.0

    def _running(self, tenant: str | None = None,
                 slo_class: str | None = None) -> int:
        return sum(1 for s, r in enumerate(self.slot_rids)
                   if r is not None
                   and (tenant is None or self.slot_tenant[s] == tenant)
                   and (slo_class is None or self.slot_slo[s] == slo_class))

    def _eligible(self, req: Request) -> bool:
        """DWFQ admission filter: the tenant's slot quota and the dynamic
        batch-class cap.  Ineligible requests are *skipped* by ``_pick``
        (they stay queued), never head-of-line blocking eligible work —
        in particular an interactive request is never stuck behind an
        over-cap batch one."""
        if not self.slo_aware:
            return True
        cfg = (self.tenants or {}).get(req.tenant)
        if cfg is not None and cfg.max_slots > 0 \
                and self._running(tenant=req.tenant) >= cfg.max_slots:
            return False
        if req.slo_class == SLO_BATCH \
                and self._running(slo_class=SLO_BATCH) >= self.batch_cap:
            return False
        return True

    def record_served(self, slot: int, n: int = 1) -> None:
        """Charge ``n`` generated tokens to ``slot``'s tenant — the DWFQ
        service accounting ``_pick`` balances against tenant weights."""
        t = self.slot_tenant[slot]
        if t is None:
            return
        self.served_tokens[t] = self.served_tokens.get(t, 0) + n
        self._service[t] = self._service.get(t, 0.0) + n / self._weight(t)

    # ------------------------------------------------------------- queue
    def submit(self, req: Request, front: bool = False) -> None:
        """Enqueue ``req`` (``front=True`` = preemption resume priority).

        With tenancy on, a tenant returning from idle (no queued or
        running work) has its normalized service floored to the
        least-served *active* tenant's: idle time banks no catch-up
        credit, so the returning tenant re-enters the fair rotation at
        the current frontier instead of monopolizing admissions."""
        if self.slo_aware and not req.preempted:
            active = ({r.tenant for r in self.queue}
                      | {t for t in self.slot_tenant if t is not None})
            if req.tenant not in active:
                floor = min((self._service.get(t, 0.0) for t in active),
                            default=0.0)
                self._service[req.tenant] = max(
                    self._service.get(req.tenant, 0.0), floor)
        req.state = QUEUED
        if front:
            self.queue.insert(0, req)
        else:
            self.queue.append(req)

    def _pick(self) -> Request | None:
        # preempted requests resume first under EVERY policy — their
        # already-spent prefill/decode work must not be stranded behind a
        # stream of fresh short arrivals (they sit at the queue front)
        if not self.slo_aware:
            for r in self.queue:
                if r.preempted:
                    return r
            if self.policy == "sjf":
                # min() is stable: earliest-queued wins among equal lengths
                return min(self.queue, key=lambda r: len(r.resume_tokens()))
            return self.queue[0]
        # DWFQ layer: same preempted-first / fcfs / sjf skeleton, but only
        # over *eligible* requests (quota + batch_cap), interactive before
        # batch, and the least-normalized-service tenant first.  None when
        # nothing is eligible (the admit loop stops; queued work waits for
        # slots to free or the governor to raise the cap).
        elig = [r for r in self.queue if self._eligible(r)]
        if not elig:
            return None
        for r in elig:
            if r.preempted:
                return r
        inter = [r for r in elig if r.slo_class != SLO_BATCH]
        pool = inter or elig
        tenant = min({r.tenant for r in pool},
                     key=lambda t: (self._service.get(t, 0.0), t))
        cand = [r for r in pool if r.tenant == tenant]
        if self.policy == "sjf":
            return min(cand, key=lambda r: len(r.resume_tokens()))
        return cand[0]

    def _stamp(self, req: Request) -> None:
        # first admission only: a preempted request keeps its original
        # stamp, so it also keeps its seniority in the engine's
        # oldest-first prefill-chunk scheduling when it resumes
        if req.admit_seq < 0:
            req.admit_seq = self._admit_seq
            self._admit_seq += 1
        req.preempted = False

    def free_slot(self) -> int | None:
        """Lowest free slot index, or None when the batch is full."""
        try:
            return self.slot_rids.index(None)
        except ValueError:
            return None

    def _prefix_plan(self, req: Request) -> tuple[int, dict | None, int, int]:
        """One consistent prefix-share decision for all oracle methods:
        ``(m, entry, shared_full, total_pages)`` — ``m`` matched tokens,
        ``shared_full`` full pages the pool can ``share()`` (still-live
        leading pages of the entry), ``total_pages`` the request's full
        table width (prompt + one token).  With no pool or no index:
        ``(0, None, 0, total)``."""
        need = len(req.resume_tokens())
        if self.pool is None:
            return 0, None, 0, 0
        total = self.pool.pages_for(need + 1)
        if self.prefix_index is None or need < 2:
            return 0, None, 0, total
        m, entry = self.prefix_index.match(req.resume_tokens(),
                                           limit=need - 1)
        if entry is None:
            return 0, None, 0, total
        valid = self.prefix_index.valid_leading_pages(entry)
        shared_full = min(m // self.pool.block_s, valid)
        return m, entry, shared_full, total

    def fits(self, req: Request) -> bool:
        """Cache-pressure gate: could ``req``'s prefill plus one generated
        token *ever* fit — the per-slot capacity (fixed layout), or the
        shared pool (paged)?  False means reject.  Paged admission charges
        only the **unshared suffix**: pages the prefix index can satisfy
        from live shared pages are not counted against the pool (a batch of
        same-prefix requests that exceeds the pool unshared still admits
        shared), while the *full* table width still must respect
        ``max_pages``."""
        need = len(req.resume_tokens()) + 1
        if self.pool is None:
            return need <= self.cap
        _, _, shared_full, total = self._prefix_plan(req)
        return (total <= self.max_pages
                and total - shared_full <= self.pool.capacity)

    def can_admit_now(self, req: Request) -> bool:
        """Whether the capacity oracle can grant ``req``'s admission
        reservation *right now*.  Fixed layout: always (the free slot IS
        the reservation).  Paged: the **unshared** pages — the suffix after
        the prefix index's live shared span — must be on the free list;
        otherwise the request waits in the queue for running requests to
        retire and release pages."""
        if self.pool is None:
            return True
        _, _, shared_full, total = self._prefix_plan(req)
        return total - shared_full <= self.pool.free_count

    def grow_for_next_token(self, slot: int) -> list[int] | None:
        """Reserve whatever the *next* decode token needs for ``slot``.

        Returns the newly granted physical pages ([] when the committed
        length + 1 still fits the reservation — always, in the fixed
        layout, until ``cap``), or None when the request cannot grow:
        per-slot ``cap`` reached, ``max_pages`` reached, or the pool's free
        list is empty — the engine then retires it with
        ``finish_reason="capacity"``.  This is the paged twin of
        ``at_capacity`` with the reservation made atomically, so a
        concurrent admission cannot snatch the page between check and
        commit."""
        if self.pool is None:
            return None if self.slot_len[slot] + 1 >= self.cap else []
        rid = self.slot_rids[slot]
        assert rid is not None, slot
        need = self.pool.pages_for(self.slot_len[slot] + 1)
        have = len(self.pool.pages(rid))
        if need <= have:
            return []
        if need > self.max_pages:
            return None
        return self.pool.extend(rid, need - have)

    def grow_ahead(self, slot: int) -> list[int]:
        """``grow_for_next_token`` one token early: reserve now the page
        that the token *after* the one in flight needs, if the pool can
        grant it now.  Returns the new pages, [] otherwise; it never
        refuses a request — ``grow_for_next_token`` decides that once the
        token in flight is committed."""
        if self.pool is None:
            return []
        rid = self.slot_rids[slot]
        need = self.pool.pages_for(self.slot_len[slot] + 2)
        have = len(self.pool.pages(rid))
        if need <= have or need > self.max_pages:
            return []
        return self.pool.extend(rid, need - have) or []

    def grow_for_window(self, slot: int, want: int) -> int:
        """Reserve capacity for up to ``want`` more decode tokens of
        ``slot`` in one shot — the multi-token twin of
        ``grow_for_next_token`` for the windowed decode path
        (``DecodeEngine --decode-window``).

        Returns the granted step budget ``g <= want`` (0 = the slot cannot
        take a single step; the engine retires it with
        ``finish_reason="capacity"`` before dispatch).  Fixed layout:
        bounded by the per-slot ``cap`` exactly like
        ``grow_for_next_token``'s ``slot_len + 1 >= cap`` retire rule.
        Paged: bounded by ``max_pages`` and the pool free list, with every
        needed page taken in ONE atomic ``extend`` *before* the device
        window launches — no allocation can happen mid-window, so a
        concurrent admission at the next boundary sees an exact free
        list.  A grant ``g < want`` that the in-window EOS / max-tokens
        replay doesn't consume means the request hit capacity, matching
        the single-step engine's retire point to the token."""
        if want <= 0:
            return 0
        if self.pool is None:
            return max(0, min(want, self.cap - 1 - self.slot_len[slot]))
        rid = self.slot_rids[slot]
        assert rid is not None, slot
        have = len(self.pool.pages(rid))
        grantable = min(self.max_pages, have + self.pool.free_count)
        g = min(want, grantable * self.pool.block_s - self.slot_len[slot])
        if g <= 0:
            return 0
        need = self.pool.pages_for(self.slot_len[slot] + g)
        if need > have:
            got = self.pool.extend(rid, need - have)
            assert got is not None, "free_count lied"
        return g

    def _reserve(self, req: Request) -> None:
        """Perform the paged admission reservation ``can_admit_now`` just
        approved: ``share()`` the prefix index's live leading pages,
        ``cow()`` the trailing partial page (the request's first appended
        token diverges right after the shared prefix — resolved before any
        write, so a shared page is never mutated), then ``alloc``/``extend``
        fresh pages for the unshared suffix.  Records the match on the
        request (``shared_len``/``shared_pages``/``shared_kv``) for the
        engine's buffer restore and scatter."""
        if self.pool is None:
            return
        req.shared_len = 0          # stale match from a prior admission
        req.shared_pages = 0
        req.shared_kv = None
        m, entry, shared_full, total = self._prefix_plan(req)
        if entry is None:
            got = self.pool.alloc(req.rid, total)
            assert got is not None, "can_admit_now lied"
            return
        bs = self.pool.block_s
        valid = self.prefix_index.valid_leading_pages(entry)
        partial = (shared_full == m // bs and m % bs != 0
                   and valid > shared_full
                   and len(entry["pages"]) > shared_full)
        take = shared_full + 1 if partial else shared_full
        self.pool.share(req.rid, entry["pages"][:take])
        if partial:
            got = self.pool.cow(req.rid, shared_full)
            assert got is not None, "can_admit_now lied"
        if total > take:
            got = self.pool.extend(req.rid, total - take)
            assert got is not None, "can_admit_now lied"
        req.shared_len = m
        req.shared_pages = shared_full
        req.shared_kv = self.prefix_index.resolve_kv(entry)

    def reject(self, req: Request) -> None:
        """Retire ``req`` unplaced with ``finish_reason="rejected"``."""
        req.state = DONE
        req.done = True
        req.finish_reason = "rejected"
        self.rejected.append(req)

    # --------------------------------------------------------- admission
    def admit(self) -> list[tuple[Request, int]]:
        """Admit queued requests into free slots per policy.

        Returns the ``(request, slot)`` placements made this call.  The
        cache-pressure gate rejects requests whose prefill can never fit
        ``cap`` (they land in ``self.rejected`` with state DONE /
        ``finish_reason="rejected"`` and are NOT placed)."""
        placed: list[tuple[Request, int]] = []
        while self.queue:
            slot = self.free_slot()
            if slot is None:
                break
            req = self._pick()
            if req is None:                   # nothing eligible (DWFQ)
                break
            if not self.fits(req):            # can't even hold one new token
                self.queue.remove(req)
                self.reject(req)
                continue
            if not self.can_admit_now(req):
                # pool pressure: the pick waits (stays queued) for running
                # requests to release pages — no skip-ahead, so a big
                # request can't be starved by a stream of small ones
                break
            self.queue.remove(req)
            need = len(req.resume_tokens())
            # reserve prompt + first-token pages up front (shared prefix
            # pages + fresh suffix pages): the chunked prefill carries K/V
            # in side buffers and commits them to the pool only at
            # finalize, so full reservation here keeps multi-step prefills
            # deadlock-free (no partial holds)
            self._reserve(req)
            req.state = PREFILL
            self._stamp(req)
            self.slot_rids[slot] = req.rid
            self.slot_len[slot] = need
            self.slot_tenant[slot] = req.tenant
            self.slot_slo[slot] = req.slo_class
            placed.append((req, slot))
        return placed

    def assign_direct(self, req: Request) -> int | None:
        """Bypass the queue: place ``req`` into a free slot now (the
        engine's legacy one-shot ``add_request`` path).  Returns the slot,
        or None when full — or when the cache-pressure gate rejects the
        request (``req.finish_reason == "rejected"``; same behavior as the
        ``admit()`` path, and it keeps ``slot_len < cap`` invariant-true).
        Both admission paths share the same capacity oracle (``fits`` /
        ``can_admit_now``), so they cannot disagree on what is admissible;
        under pool pressure (paged, pages busy *now*) the request is
        neither placed nor rejected — None, like a full batch."""
        slot = self.free_slot()
        if slot is None:
            return None
        if not self.fits(req):
            self.reject(req)
            return None
        if not self.can_admit_now(req):
            return None
        need = len(req.resume_tokens())
        self._reserve(req)
        req.state = PREFILL
        self._stamp(req)
        self.slot_rids[slot] = req.rid
        self.slot_len[slot] = need
        self.slot_tenant[slot] = req.tenant
        self.slot_slo[slot] = req.slo_class
        return slot

    # ----------------------------------------------------------- running
    def on_token(self, slot: int) -> None:
        """Record one generated token committed to ``slot``'s cache."""
        self.slot_len[slot] += 1

    def at_capacity(self, slot: int) -> bool:
        """True when ``slot`` cannot hold another token (retire now).
        Read-only twin of ``grow_for_next_token`` — fixed: the per-slot
        ``cap`` is reached; paged: the next token's page can neither be
        covered by the reservation nor granted from the free list."""
        if self.pool is None:
            return self.slot_len[slot] + 1 >= self.cap
        rid = self.slot_rids[slot]
        need = self.pool.pages_for(self.slot_len[slot] + 1)
        have = len(self.pool.pages(rid)) if rid is not None else 0
        return need > have and (need > self.max_pages
                                or need - have > self.pool.free_count)

    def release(self, slot: int) -> None:
        """Free ``slot`` (request retired or preempted); paged mode also
        returns the request's pool pages to the free list — copy-free."""
        rid = self.slot_rids[slot]
        if self.pool is not None and rid is not None:
            self.pool.free(rid)
        self.slot_rids[slot] = None
        self.slot_len[slot] = 0
        self.slot_tenant[slot] = None
        self.slot_slo[slot] = None

    def preempt(self, slot: int, req: Request) -> None:
        """Release ``slot`` and requeue ``req`` at the front; ``_pick``
        resumes preempted requests before anything else under every
        policy."""
        assert self.slot_rids[slot] == req.rid, (slot, req.rid)
        self.release(slot)
        req.preempted = True
        self.submit(req, front=True)

    # -------------------------------------------------------- invariants
    def check_invariants(self) -> None:
        """Assert the scheduling invariants the property suite pins:
        no rid in two slots, queue and slots disjoint, committed lengths
        within capacity; paged mode additionally checks page conservation
        and that every slot's reservation covers its committed length;
        tenancy adds slot tenant/SLO tag consistency and non-negative
        service accounting."""
        live = [r for r in self.slot_rids if r is not None]
        assert len(live) == len(set(live)), f"slot double-assignment: {live}"
        for s, rid in enumerate(self.slot_rids):
            assert (rid is None) == (self.slot_tenant[s] is None), \
                f"slot {s} tenant tag out of sync with its rid"
            assert (rid is None) == (self.slot_slo[s] is None), \
                f"slot {s} slo tag out of sync with its rid"
        assert all(v >= 0 for v in self.served_tokens.values()), \
            self.served_tokens
        assert all(v >= 0.0 for v in self._service.values()), self._service
        assert 0 <= self.batch_cap <= self.max_batch, self.batch_cap
        qrids = [r.rid for r in self.queue]
        assert len(qrids) == len(set(qrids)), f"queue duplicates: {qrids}"
        assert not set(qrids) & set(live), "request both queued and placed"
        for s, (rid, ln) in enumerate(zip(self.slot_rids, self.slot_len)):
            if rid is None:
                continue
            if self.pool is None:
                assert 0 < ln < self.cap, \
                    f"slot {s} length {ln} violates capacity {self.cap}"
            else:
                have = len(self.pool.pages(rid))
                assert 0 < ln <= have * self.pool.block_s, \
                    f"slot {s} length {ln} exceeds its {have} pages"
                assert have <= self.max_pages, (s, have, self.max_pages)
        if self.pool is not None:
            self.pool.check_invariants()
            holders = {r for r in self.pool._pages if self.pool.pages(r)}
            assert holders <= set(live), \
                f"pages held by unplaced requests: {holders - set(live)}"
