"""Scheduler-driven continuous-batching engine over the Helix serve_step.

Slot-based continuous batching with **chunked prefill**: a fixed
``[max_batch]`` decode state holds one request per slot with *per-request*
lengths ([B] total_len — the helix attention mask, rope positions and
round-robin appends are all per-request).  Admission runs through a
``Scheduler`` (serving/scheduler.py: FCFS/SJF + cache-pressure gating);
pending prompts prefill in ``chunk_tokens``-sized slices interleaved with
decode steps, so a multi-million-token prompt no longer stalls every
in-flight decode stream — the TTL blowup Helix exists to avoid (PAPER.md
§1).  Per-request lifecycle metrics (queue wait, TTFT, per-step TTL) are
collected in ``EngineMetrics``.

One engine ``step()`` is bounded work:

  1. admission      — move queued requests into free slots (Scheduler);
  2. prefill chunk  — ONE ``chunk_tokens``-sized slice for one group of
                      same-progress prefills (batched chunk packing);
  3. decode step    — one token for every decoding slot, retiring finished
                      requests (EOS / max-tokens / capacity).

Chunked prefill is bit-exact with the one-shot path: each chunk attends to
the already-cached prefix through flash_prefill's runtime ``q_offset``
contract over a carry buffer sized to the request's full prompt, and the
finalize handoff shares ``make_prefill_step``'s cache->round-robin
conversion (models/model_zoo.py).  See docs/serving.md for the dataflow.
"""
from __future__ import annotations

import functools
import time
from typing import Any, Callable

import numpy as np
import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig
from repro.core.kvcache import (cache_capacity, cache_to_pages,
                                gather_pool_pages, init_decode_state,
                                page_positions, quantize_decode_state,
                                scatter_pool_pages)
from repro.core.sharding import HelixConfig
from repro.runtime.trace import span, step_span
from repro.serving.governor import GovernorConfig, TTLGovernor
from repro.serving.metrics import EngineMetrics, VirtualClock
from repro.serving.pool import BlockAllocator
from repro.serving.scheduler import (DECODE, DONE, PREFILL, QUEUED,
                                     RESTORING, SLO_BATCH, Request,
                                     Scheduler)
from repro.serving.tier import HostPageStore

__all__ = ["DecodeEngine", "Request"]


class DecodeEngine:
    """Scheduler-driven continuous-batching decode engine (see module doc).

    Two admission APIs:

      * ``submit(req)`` + ``step()`` — the scheduler path: queued
        admission, chunked prefill (when ``chunk_tokens`` is set and the
        arch supports it), metrics.  ``step()`` returns the requests
        retired that step.
      * ``add_request(req)`` — legacy immediate one-shot prefill into a
        free slot (returns False when full); still the fast path for
        latency-insensitive bulk decoding.

    ``hx`` (when given) pins the round-robin block size, pre-quantizes the
    lm_head (``prepare_decode_params``), switches the cache to int8 when
    ``hx.kv_cache_bits == 8``, and is validated against the kernel registry
    so unavailable backends fail fast.  ``chunk_prefill_step`` comes from
    ``make_chunk_prefill_step`` (required when ``chunk_tokens`` is set) and
    ``tp_width`` must match its mesh's 'model' axis size (it shapes the
    carry buffers' padded GQA head count); ``clock`` is the metrics clock
    (injectable for deterministic tests).

    ``hx.paged_kv`` switches the decode state to the shared-pool paged
    layout (serving/pool.py, docs/serving.md): K/V pool planes + a
    ``block_tables`` state leaf, a ``BlockAllocator`` owning page
    assignment, and the scheduler consulting the *global* free-page count
    for admission/growth/retirement instead of the per-slot cap.
    ``pool_blocks`` sizes the pool (pages of ``kvp * rr_block`` positions,
    including the reserved sink page 0); the default matches the HBM the
    fixed layout would reserve.  ``max_pages`` caps one request's block
    table (default: the whole pool; cap it when serving with the ``ref``
    backend or pruning off, whose per-request cost scales with the table
    width).  Token streams are bit-exact vs the fixed layout
    (tests/serving/test_paged_engine.py).

    Multi-tenant SLO front end (docs/serving.md): ``tenants`` (a
    ``TenantConfig`` dict or iterable) layers deficit-weighted-fair
    admission over the scheduler policy; ``slo_ttl_s`` (or a full
    ``GovernorConfig`` via ``governor``) arms the TTL governor — per step
    it reads the windowed interactive TTL p95 and sheds the youngest
    decoding batch-class request through the spill path (resume: zero
    re-prefill chunks) when the target is missed, raising the dynamic
    batch cap back once latency recovers.  Pair with a ``VirtualClock``
    metrics clock for deterministic, replayable latency summaries
    (scripts/trace_smoke.py).

    ``mesh`` (the mesh the step functions were built on) places the
    engine's device data: parameters by ``helix_param_specs`` and the
    decode state by ``decode_state_specs`` (KV pool sharded over the KVP
    axes), and pins the decode steps' state output to the same shardings,
    so nothing is gathered onto one device or resharded between steps.
    Without it everything stays on the default device.
    """

    def __init__(self, cfg: ArchConfig, params, serve_step: Callable,
                 prefill_step: Callable, *, max_batch: int, max_seq: int,
                 kvp: int = 1, rr_block: int = 16,
                 hx: HelixConfig | None = None, dtype=jnp.float32,
                 chunk_tokens: int | None = None,
                 chunk_prefill_step: Callable | None = None,
                 tp_width: int = 1,
                 sched_policy: str = "fcfs", clock=time.monotonic,
                 pool_blocks: int | None = None,
                 max_pages: int | None = None,
                 prefix_share: bool = False,
                 host_pages: int = 0,
                 session_kv: bool = False,
                 fault_plan=None,
                 tenants=None,
                 slo_ttl_s: float | None = None,
                 governor: GovernorConfig | None = None,
                 sampling=None,
                 decode_window: int = 1,
                 serve_multistep: Callable | None = None,
                 mesh=None):
        # ``hx`` (when given) wins over the bare rr_block arg so engine and
        # serve_step can't disagree on the round-robin block size.  kvp still
        # depends on the mesh (hx.kvp(mesh)), which the engine never sees —
        # that half stays the caller's contract.
        if hx is not None:
            rr_block = hx.rr_block
            # fail fast on unavailable kernel backends (e.g. 'pallas'
            # requested on a CPU host) instead of erroring steps later
            # inside the first jit'd prefill
            from repro.kernels import registry
            for field, family in registry.FAMILY_FIELDS.items():
                ok, why = registry.available(family, getattr(hx, field))
                if not ok:
                    raise RuntimeError(
                        f"{field}={getattr(hx, field)!r} unavailable: {why}")
        self.hx = hx
        self.cfg = cfg
        # quantize the lm_head once up front; otherwise serve_step
        # re-quantizes the whole [H, V] matrix every decode step
        from repro.models.decode_model import prepare_decode_params
        self.params = prepare_decode_params(params, hx)
        self.prefill_step = jax.jit(prefill_step)
        # on-device sampling (serving/sampling.py): ``sampling`` is the
        # engine-default SamplingParams; per-request policies ride
        # Request.sampling.  None keeps the historical pure-argmax path
        # (no sampling leaves in the state, nothing new traced).
        if sampling is not None:
            sampling.validate()
        self.sampling = sampling
        # windowed decode (--decode-window): N tokens per device dispatch
        # through serve_multistep (build_serve_multistep), ONE [B, N]
        # blocking transfer per window.  window=1 keeps the single-step
        # path bit-exactly.
        if decode_window < 1:
            raise ValueError(f"decode_window must be >= 1 ({decode_window})")
        if decode_window > 1 and serve_multistep is None:
            raise ValueError("decode_window > 1 needs serve_multistep "
                             "(build one with build_serve_multistep)")
        self.decode_window = decode_window
        # host-sync accounting for sync_stats(): blocking decode-loop
        # device->host transfers vs decode tokens emitted
        self.decode_syncs = 0
        self.decoded_tokens = 0
        self.max_batch = max_batch
        self.cap = cache_capacity(max_seq, kvp, rr_block)
        self.kvp, self.rr = kvp, rr_block
        self.kv8 = hx is not None and hx.kv_cache_bits == 8
        # shared-pool paged KV cache (hx.paged_kv, serving/pool.py): K/V in
        # pool planes + per-slot block-table rows; ``pool_blocks`` sizes the
        # pool (default: the same HBM the fixed layout would reserve, plus
        # the sink page 0 that idle rows' appends land in)
        self.paged = hx is not None and hx.paged_kv
        self.block_s = page_positions(kvp, rr_block)
        if self.paged:
            if not pool_blocks:
                pool_blocks = max_batch * (self.cap // self.block_s) + 1
            self.pool_blocks = pool_blocks
            self.pool = BlockAllocator(pool_blocks, self.block_s)
            # max_pages caps ONE request's table width (and so its logical
            # capacity).  Default: the whole pool — maximum flexibility,
            # but note the dense-sweep cost scales with it on the ref
            # backend (gather_pages materializes max_pages*block_s
            # positions per request) and on Pallas with pruning off; the
            # default Pallas+prune path only ever visits valid pages.
            self.max_pages = min(max_pages or self.pool.capacity,
                                 self.pool.capacity)
        else:
            self.pool = None
            self.pool_blocks = self.max_pages = 0
        # grouped shared-prefix decode (hx.grouped_decode): requests whose
        # tables share leading pages decode those pages once per *group*
        # instead of once per request; _set_groups refreshes the
        # group_id/group_np leaves from the pool's refcounts each step.
        self.grouped = self.paged and hx is not None and hx.grouped_decode
        if self.grouped and decode_window > 1:
            raise ValueError("decode_window > 1 is incompatible with "
                             "hx.grouped_decode: group_id/group_np are "
                             "host-recomputed every token and would go "
                             "stale mid-window")
        self._state_shardings = self._decode_state_shardings(mesh)
        self.state = init_decode_state(
            cfg, max_batch, self.cap, kvp, rr_block, dtype=dtype,
            kv_bits=8 if self.kv8 else 16,
            pool_blocks=self.pool_blocks if self.paged else 0,
            max_pages=self.max_pages, grouped=self.grouped,
            sampling=self.sampling is not None,
            shardings=self._state_shardings)
        # per-request lengths: [B]; empty slots keep 0
        self.state["total_len"] = jnp.zeros((max_batch,), jnp.int32)
        self.slots: list[Request | None] = [None] * max_batch
        self.cur_tokens = jnp.zeros((max_batch,), jnp.int32)
        self._place(mesh, serve_step, serve_multistep)

        from repro.models.model_zoo import chunked_prefill_supported
        self.chunk_tokens = (chunk_tokens or None) \
            if chunked_prefill_supported(cfg) else None
        if self.chunk_tokens and chunk_prefill_step is None:
            raise ValueError("chunk_tokens set but no chunk_prefill_step "
                             "(build one with make_chunk_prefill_step)")
        self.chunk_step = (jax.jit(chunk_prefill_step)
                           if chunk_prefill_step is not None else None)
        self.tp_width = tp_width
        # chunked-prefill carry buffers: in the dtype the chunk computes
        # K/V in, and split over the mesh (prefill_buffer_spec)
        self.buf_dtype = self.buf_sharding = None
        if self.chunk_tokens:
            self.buf_dtype = jnp.result_type(
                self.params["embed"], self.params["layers"]["attn"]["wk"])
            if mesh is not None:
                from jax.sharding import NamedSharding
                from repro.models.model_zoo import prefill_buffer_spec
                self.buf_sharding = NamedSharding(
                    mesh, prefill_buffer_spec(cfg, mesh, tp_width))
        # host KV tier (serving/tier.py, docs/serving.md): spill live
        # pages on preemption for a zero-re-prefill resume (host_pages
        # sizes it), persist retired requests' pages per session_id for
        # multi-turn restore, and cap the prefix index's host K/V blobs
        # under the same LRU.  fault_plan (serving/faults.py) injects the
        # tier's failure modes deterministically — every injected fault
        # degrades to the re-prefill fallback, never to divergent tokens.
        self.session_kv = session_kv
        self.spill_enabled = host_pages > 0
        if (host_pages or session_kv) and not self.paged:
            raise ValueError("the host KV tier (host_pages / session_kv) "
                             "needs hx.paged_kv — spill/restore is "
                             "page-granularity")
        if (host_pages or session_kv) and any(
                k in self.state
                for k in ("ssm_conv", "ssm_state", "xk", "xv")):
            raise ValueError("the host KV tier only spills pool planes; "
                             "this arch keeps non-paged state leaves "
                             "(ssm/enc-dec) a restore could not rebuild")
        self.store = None
        if self.paged and (host_pages or session_kv or prefix_share):
            cap = host_pages or max(4 * self.pool.capacity, 256)
            self.store = HostPageStore(cap, faults=fault_plan)
        self._restores: dict[int, dict] = {}    # slot -> in-flight restore
        # prefix sharing (docs/serving.md): a PrefixIndex matches new
        # prompts against committed prefixes; matched pages are mapped
        # refcounted into the new request's table and only the suffix
        # chunk-prefills.  Needs the paged pool (pages to share) and
        # chunked prefill (a suffix-only prefill is just a resumed one).
        self.prefix_index = None
        if prefix_share:
            if not (self.paged and self.chunk_tokens):
                raise ValueError("prefix_share needs hx.paged_kv and "
                                 "chunk_tokens (suffix-only prefill rides "
                                 "the chunked-prefill q_offset contract)")
            from repro.serving.scheduler import PrefixIndex
            self.prefix_index = PrefixIndex(self.block_s, self.pool,
                                            store=self.store)
        self._prefix_admits = 0
        self._prefix_hits = 0
        # multi-tenant SLO-aware front end (docs/serving.md): ``tenants``
        # (TenantConfig dict/iterable) turns on DWFQ admission; ``slo_ttl_s``
        # (or a full GovernorConfig) arms the TTL governor, which replaces
        # the static batch cap with measured-TTL feedback — batch-class
        # work sheds through the spill path when interactive p95 TTL
        # drifts past target (serving/governor.py).
        if governor is None and slo_ttl_s is not None:
            governor = GovernorConfig(ttl_target_s=slo_ttl_s)
        self.governor = (TTLGovernor(governor, max_batch)
                         if governor is not None else None)
        self.sched = Scheduler(max_batch=max_batch, cap=self.cap,
                               policy=sched_policy, pool=self.pool,
                               max_pages=self.max_pages,
                               prefix_index=self.prefix_index,
                               tenants=tenants,
                               slo_aware=(True if (tenants or governor)
                                          else None))
        self.metrics = EngineMetrics(
            clock=clock,
            ttl_target_s=governor.ttl_target_s if governor else None)
        self._admission_retired: list[Request] = []
        # running sum and count of the pool's fragmentation samples
        self._frag_sum, self._frag_n = 0.0, 0
        self._step_num = 0

    def _decode_state_shardings(self, mesh):
        """The decode state's shardings on ``mesh`` (None without one):
        the state is made in them, so no device holds a whole pool."""
        if mesh is None:
            return None
        from jax.sharding import PartitionSpec as P
        from repro.core.kvcache import decode_state_specs
        from repro.core.sharding import to_shardings
        hx = self.hx if self.hx is not None else _default_hx(self.rr)
        specs = decode_state_specs(self.cfg, hx, self.max_batch, mesh,
                                   sampling=self.sampling is not None)
        specs["total_len"] = P(None)
        return to_shardings(mesh, specs)

    def _place(self, mesh, serve_step, serve_multistep) -> None:
        """Place params/state on ``mesh`` (see the class doc) and jit the
        decode steps; the windowed step donates the state, so the KV pool
        is not double-buffered across a window dispatch (CPU backends
        don't implement donation and warn, so gate on the platform)."""
        state_out = self._state_shardings
        self._pool_shardings = None
        if mesh is not None:
            from jax.sharding import NamedSharding, PartitionSpec as P
            from repro.core.sharding import helix_param_specs, to_shardings
            hx = self.hx if self.hx is not None else _default_hx(self.rr)
            self.state = jax.device_put(self.state, state_out)
            if self.paged:
                self._pool_shardings = {k: state_out[k]
                                        for k in ("kcache", "vcache")}
            self.params = jax.device_put(self.params, to_shardings(
                mesh, helix_param_specs(self.cfg, self.params, hx, mesh)))
            rep = NamedSharding(mesh, P())
            self.cur_tokens = jax.device_put(self.cur_tokens, rep)
            self.serve_step = jax.jit(serve_step,
                                      out_shardings=(rep, state_out))
        else:
            self.serve_step = jax.jit(serve_step)
        from repro.models.model_zoo import prefill_to_pool
        self._to_pool = jax.jit(
            functools.partial(
                prefill_to_pool, self.cfg,
                self.hx if self.hx is not None else _default_hx(self.rr),
                kvp=self.kvp),
            static_argnames=("t", "s0"),
            donate_argnums=(0,) if jax.default_backend() != "cpu" else (),
            out_shardings=self._pool_shardings)
        self._set_table_row = jax.jit(
            _set_row, out_shardings=(state_out["block_tables"]
                                     if self.paged and state_out is not None
                                     else None))
        self.serve_multistep = None
        if serve_multistep is not None:
            kw = {}
            if state_out is not None:
                kw["out_shardings"] = (rep, rep, state_out)
            if jax.default_backend() != "cpu":
                kw["donate_argnums"] = (1,)
            self.serve_multistep = jax.jit(serve_multistep, **kw)

    # ------------------------------------------------------------- requests
    def submit(self, req: Request) -> None:
        """Queue ``req`` for scheduled admission (the chunked-prefill
        path); ``step()`` admits it when a slot frees up."""
        if req.sampling is not None and self.sampling is None:
            raise ValueError("request carries SamplingParams but the "
                             "engine was built without sampling= (the "
                             "decode state has no sampling leaves)")
        self.metrics.on_submit(req.rid, tenant=req.tenant,
                               slo_class=req.slo_class)
        self.sched.submit(req)

    def pending(self) -> bool:
        """True while any request is queued, prefilling or decoding — or
        retired at admission but not yet reported by ``step()``."""
        return (bool(self.sched.queue) or any(self.slots)
                or bool(self._admission_retired))

    def add_request(self, req: Request) -> bool:
        """Legacy immediate admission: one-shot prefill ``req`` into a free
        slot right now; False if the engine is full (no queueing).  A
        request whose prompt can never fit the slot capacity is accepted
        (True) but retired immediately with ``finish_reason="rejected"``
        and reported by the next ``step()``."""
        if req.rid not in self.metrics.requests:
            self.metrics.on_submit(req.rid, tenant=req.tenant,
                                   slo_class=req.slo_class)
        if req.sampling is not None and self.sampling is None:
            raise ValueError("request carries SamplingParams but the "
                             "engine was built without sampling= (the "
                             "decode state has no sampling leaves)")
        slot = self.sched.assign_direct(req)
        if slot is None:
            if self.sched.rejected and self.sched.rejected[-1] is req:
                self.sched.rejected.pop()
                self.metrics.on_finish(req.rid, "rejected")
                self._admission_retired.append(req)
                return True
            return False
        self.metrics.on_admit(req.rid)
        self.slots[slot] = req
        # a first token that already retires (eos / max_new=1 / capacity)
        # is reported by the next step() call
        self._admission_retired += self._oneshot_prefill(req, slot)
        return True

    def preempt(self, rid: int) -> bool:
        """Release ``rid``'s slot mid-flight and requeue it at the queue
        front.  With a host tier (``host_pages``) a decoding request's
        live pool pages are **spilled** to the ``HostPageStore`` first, so
        resume is a block-table rebuild + H2D restore with zero re-prefill
        chunks and a bit-exact continued stream; without one (or when the
        store refuses the save) the pages drop and the resumed request
        re-prefills its prompt plus everything generated so far — greedy
        decoding continues with identical output tokens either way.
        Returns False when ``rid`` holds no slot."""
        for slot, req in enumerate(self.slots):
            if req is not None and req.rid == rid:
                spilled = False
                if slot in self._restores:
                    # restore still in flight: nothing committed on the
                    # device; cancel the job (the store entry survives, so
                    # the next resume retries the restore)
                    self._restores.pop(slot)
                elif (req.state == DECODE and self.spill_enabled
                        and self.store is not None):
                    spilled = self._spill(req, slot)
                req.buffers = None
                req.prefill_pos = 0
                req.prefill_tokens = None
                req.forced_tokens = None
                self.slots[slot] = None
                self.state["total_len"] = \
                    self.state["total_len"].at[slot].set(0)
                if self.paged:
                    # pages go back to the free list copy-free
                    # (sched.preempt -> release -> pool.free); park the row
                    # on the sink page
                    self._write_table_row(
                        slot, np.zeros((self.max_pages,), np.int32))
                self.sched.preempt(slot, req)
                self.metrics.on_preempt(rid, spilled=spilled)
                return True
        return False

    def _spill(self, req: Request, slot: int) -> bool:
        """Save ``req``'s live pool pages (exact bytes: int8 payloads and
        scale planes included) into the host store before the pool
        releases them.  One device-side page gather + ONE batched
        device->host transfer per preemption — the sanctioned spill site
        (ANALYSIS_BASELINE.json); never a per-page transfer in a loop,
        which the ``sync.device-get-loop`` lint flags."""
        committed = self.sched.slot_len[slot]
        phys = self.pool.pages(req.rid)[:self.pool.pages_for(committed)]
        if committed <= 0 or not phys:
            return False
        planes = gather_pool_pages(self.state, phys)
        with span("serve.readback"):
            host = jax.device_get(planes)
        ok = self.store.put(f"spill:{req.rid}", host,
                            tokens=req.resume_tokens()[:committed])
        req.spill_key = f"spill:{req.rid}" if ok else None
        req.spill_len = committed if ok else 0
        if ok:
            self.metrics.bump("spills")
        self._sync_store_counters()
        return ok

    # ----------------------------------------------------------------- step
    def step(self) -> list[Request]:
        """One bounded engine iteration: admission, at most one prefill
        chunk, one decode step for every decoding slot, then the TTL
        governor's control decision (when armed).  Returns the requests
        retired this step.  Each phase is a profiler span
        (runtime/trace.py) inside the step's ``serve.step``."""
        self._step_num += 1
        with step_span(self._step_num):
            with span("serve.admit"):
                self._tick(steps=1)
                self._advance_restores()
                finished = self._admission_retired + self._admit()
                self._admission_retired = []
            finished += self._prefill_chunk()
            with span("serve.decode"):
                if self.decode_window > 1:
                    finished += self._decode_window()
                else:
                    finished += self._decode_step()
            with span("serve.govern"):
                self._govern()
        return finished

    def _tick(self, **work) -> None:
        """Advance a ``VirtualClock`` metrics clock by one tranche of
        modeled work (no-op on wall clocks): the base step cost, then
        each phase's decode-slot / prefill-token contribution as it
        happens — so TTFT/TTL samples taken inside a phase already
        include that phase's modeled cost."""
        if isinstance(self.metrics.clock, VirtualClock):
            self.metrics.clock.advance(**work)

    def _govern(self) -> None:
        """One TTL-governor decision per step: feed it the decoding
        batch-class requests youngest-first and execute the shed it
        returns through ``preempt`` — the host-tier spill path, so shed
        work resumes with zero re-prefill chunks."""
        if self.governor is None:
            return
        batch = sorted(
            ((r.admit_seq, r.rid) for r in self.slots
             if r is not None and r.state == DECODE
             and r.slo_class == SLO_BATCH),
            reverse=True)                       # youngest (newest) first
        rid = self.governor.step(self.metrics, self.sched,
                                 [b[1] for b in batch])
        if rid is not None:
            self.preempt(rid)
        self.metrics.set_counter("governor_sheds", self.governor.sheds)
        self.metrics.set_counter("governor_cap_raises",
                                 self.governor.cap_raises)

    def run_to_completion(self, max_steps: int = 10_000) -> None:
        """Step until queue and slots drain (or ``max_steps`` elapses)."""
        for _ in range(max_steps):
            if not self.pending():
                return
            self.step()

    def describe_backends(self) -> str:
        """One-line per-family kernel-backend summary (serve logging).

        Each family is tagged ``!nocontract`` when it registers no
        static-analysis contract hook — the same condition
        ``scripts/analyze.py --strict`` fails on (``contract.missing``),
        surfaced here so a serving log shows unaudited kernels at a glance.
        """
        if self.hx is None:
            return "ref (no HelixConfig)"
        from repro.kernels import registry
        parts = [f"{family}={getattr(self.hx, field)}"
                 + ("" if registry.FAMILIES[family].contract is not None
                    else "!nocontract")
                 for field, family in registry.FAMILY_FIELDS.items()]
        parts.append(f"fuse_append={self.hx.fuse_append}")
        parts.append(f"prune_blocks={self.hx.prune_blocks}")
        if self.paged:
            parts.append(f"paged_kv=True pool_blocks={self.pool_blocks} "
                         f"block_s={self.block_s}")
        if self.hx.lm_head_w8:
            parts.append("lm_head_w8=True")
        if self.chunk_tokens:
            parts.append(f"chunk_tokens={self.chunk_tokens}")
        return " ".join(parts)

    # -------------------------------------------------------------- phases
    def _admit(self) -> list[Request]:
        retired = []
        # one-shot prefills defer their first-token device value so ALL
        # admissions this step share ONE batched device->host transfer
        # (instead of one blocking int(np.asarray(...)) per prefill)
        deferred: list[tuple[Request, int, Any]] = []
        for req, slot in self.sched.admit():
            self.metrics.on_admit(req.rid)
            self.slots[slot] = req
            if self._try_restore(req, slot):
                continue
            toks = req.resume_tokens()
            if self.chunk_tokens and self.chunk_step is not None:
                from repro.models.model_zoo import init_prefill_buffers
                req.prefill_tokens = toks
                req.prefill_pos = 0
                req.buffers = init_prefill_buffers(
                    self.cfg, 1, len(toks), tp_width=self.tp_width,
                    dtype=self.buf_dtype, sharding=self.buf_sharding)
                if self.prefix_index is not None:
                    self._prefix_admits += 1
                    if req.shared_len and req.shared_kv is not None:
                        self._prefix_hits += 1
                        self._restore_prefix(req)
            else:
                retired += self._oneshot_prefill(req, slot, defer=deferred)
        if deferred:
            with span("serve.readback"):
                vals = np.asarray(jnp.stack([d for _, _, d in deferred]))
            for (req, slot, _), v in zip(deferred, vals):
                retired += self._commit_first_token(req, slot, int(v))
        # cache-pressure rejections retire without ever holding a slot
        while self.sched.rejected:
            req = self.sched.rejected.pop()
            self.metrics.on_finish(req.rid, "rejected")
            retired.append(req)
        return retired

    def _restore_candidate(self, req: Request) -> tuple[str | None, int]:
        """Which host-store entry (if any) can resume ``req`` without
        re-prefilling, and how many committed tokens it covers.

        Preempt-spill entries win (exact pages of this very request);
        otherwise a session entry whose stored tokens are a prefix of the
        new prompt covers the conversation history.  Either way the
        restored span must leave at least one token to decode (the engine
        re-enters DECODE with ``cur = resume[m]`` and teacher-forces the
        rest), and a prefix-share match longer than the restorable span
        wins instead."""
        resume = req.resume_tokens()
        if req.spill_key is not None:
            toks = self.store.tokens(req.spill_key)
            m = 0 if toks is None else len(toks)
            if 0 < m < len(resume) and tuple(resume[:m]) == toks:
                return req.spill_key, m
        if self.session_kv and req.session_id is not None:
            key = f"session:{req.session_id}"
            toks = self.store.tokens(key)
            if toks:
                m = min(len(toks), len(resume) - 1)
                if (m > 0 and tuple(resume[:len(toks)])[:m] == toks[:m]
                        and tuple(resume[:m]) == toks[:m]
                        and m > req.shared_len):
                    return key, m
        return None, 0

    def _try_restore(self, req: Request, slot: int) -> bool:
        """Attempt the zero-re-prefill resume path at admission.

        On a store hit the request enters RESTORING and a restore job is
        queued: pages scatter back H2D and decode continues exactly where
        it left off — committed the same step when the tier is healthy, or
        after the injected ``delay`` steps (other slots keep decoding
        meanwhile, so a slow host tier degrades this request's TTFT, never
        in-flight TTL).  Any failure (missing/evicted entry, injected
        restore_fail, checksum/generation mismatch) returns False and the
        caller falls back to the old re-prefill path — counted, never
        divergent."""
        if self.store is None:
            return False
        key, committed = self._restore_candidate(req)
        if key is None:
            return False
        planes, delay, why = self.store.restore(key)
        self._sync_store_counters()
        if planes is None:
            if why != "missing":
                self.metrics.bump("restores_failed")
            req.resume_fallback = True   # this admission re-prefills
            if req.spill_key == key:
                req.spill_key = None     # don't retry a dead entry
                req.spill_len = 0
            return False
        req.state = RESTORING
        req.prefill_tokens = None
        req.buffers = None
        self._restores[slot] = {"req": req, "planes": planes,
                                "remaining": delay, "committed": committed,
                                "t0": self.metrics.clock()}
        if delay == 0:
            self._commit_restore(slot)
        return True

    def _advance_restores(self) -> None:
        """Tick the in-flight (fault-delayed) restore jobs by one engine
        step, committing those whose delay expired.  Runs before
        admission, so ``delay=d`` holds the slot idle for exactly ``d``
        steps while every other slot prefills/decodes normally."""
        for slot in list(self._restores):
            job = self._restores[slot]
            job["remaining"] -= 1
            if job["remaining"] <= 0:
                self._commit_restore(slot)

    def _commit_restore(self, slot: int) -> None:
        """Land a restore job: H2D-scatter the spilled pages into the
        pages granted at re-admission (skipping prefix-shared leading
        pages, which already hold byte-identical rows), rebuild the
        device block-table row, reinstall the committed length, and
        re-enter DECODE with the catch-up token queue — zero prefill
        chunks."""
        job = self._restores.pop(slot)
        req: Request = job["req"]
        committed: int = job["committed"]
        n = self.pool.pages_for(committed)
        phys = self.pool.pages(req.rid)[:n]
        s0 = min(req.shared_pages, n)
        if s0 < n:
            self.state = scatter_pool_pages(
                self.state, phys[s0:n],
                {k: v[:, s0:n] for k, v in job["planes"].items()})
        self._mirror_table(slot)
        self.state["total_len"] = \
            self.state["total_len"].at[slot].set(committed)
        self.sched.slot_len[slot] = committed
        resume = req.resume_tokens()
        self.cur_tokens = self.cur_tokens.at[slot].set(int(resume[committed]))
        # tokens beyond the restored span that are already known (the
        # resumed request's last sample / the session's new turn) are
        # teacher-forced through the decode path one step each — they
        # attend over the restored pages, so no prefill chunk ever runs
        req.forced_tokens = list(resume[committed + 1:])
        req.shared_kv = None
        req.state = DECODE
        self._install_sampling(req, slot)
        if req.spill_key is not None:
            # one-shot: the entry is stale the moment decode continues
            self.store.drop(req.spill_key)
            req.spill_key = None
            req.spill_len = 0
        self.metrics.bump("restores")
        self.metrics.on_restore(req.rid, self.metrics.clock() - job["t0"])
        self._sync_store_counters()

    def _sync_store_counters(self) -> None:
        """Mirror the store's monotonic fault counters into the metrics
        summary (idempotent absolute sets)."""
        self.metrics.set_counter("checksum_mismatches",
                                 self.store.checksum_mismatches
                                 + self.store.stale_generations)
        self.metrics.set_counter("store_evictions", self.store.evictions)

    def _restore_prefix(self, req: Request) -> None:
        """Install the prefix index's host-fp K/V for the matched prefix
        into ``req``'s fresh carry buffers and fast-forward the prefill to
        the suffix.

        The stored K/V is the registrant's own prefill output for those
        positions — bit-identical to what re-prefilling the same tokens
        would write (chunked prefill is causal with absolute rope
        positions), so skipping ``[0, shared_len)`` changes nothing
        downstream: TTFT becomes suffix-only."""
        m = req.shared_len
        k_np, v_np = req.shared_kv
        req.shared_kv = None
        for key, host in (("kcache", k_np), ("vcache", v_np)):
            req.buffers[key] = req.buffers[key].at[:, 0, :m].set(
                jnp.asarray(host[:, :m],
                            req.buffers[key].dtype))
        req.prefill_pos = m

    def _register_prefix(self, req: Request, t: int) -> None:
        """Publish a finished prefill to the prefix index: its token
        prefix, its (now committed) page list, and a host fp copy of its
        carry-buffer K/V.

        Captured *before* any quantization: a later hit restores fp rows
        into the sharer's buffers, keeping the suffix prefill bit-exact
        even on kv8 engines (whose pool pages quantize per row, so the
        shared physical pages are also byte-identical to what the sharer
        would have written)."""
        with span("serve.readback"):
            kv = (np.asarray(req.buffers["kcache"][:, 0, :t]),
                  np.asarray(req.buffers["vcache"][:, 0, :t]))
        self.prefix_index.register(list(req.prefill_tokens),
                                   list(self.pool.pages(req.rid)), kv)

    def _prefill_chunk(self) -> list[Request]:
        """Advance ONE packed group of prefills by one chunk.

        Ragged packing: requests at *different* (offset, length) prefill
        progress pack into one chunk call — flash_prefill takes per-row
        ``q_offset`` and each request writes its chunk at its own buffer
        offset, so the packed call is bit-exact with per-request calls
        (batch rows are independent; carry buffers are zero-padded to the
        group's longest prompt, and those pad rows sit at positions every
        causal query masks).  The only shared dimension is the chunk width
        ``c`` (the token array must be rectangular), so the group is
        "every prefilling request with the same remaining-clamped chunk
        width as the oldest one"; the group containing the oldest
        prefilling request goes first."""
        pre = [(slot, r) for slot, r in enumerate(self.slots)
               if r is not None and r.state == PREFILL
               and r.prefill_tokens is not None]
        if not pre:
            return []

        def width(r: Request) -> int:
            return min(self.chunk_tokens,
                       len(r.prefill_tokens) - r.prefill_pos)

        # oldest admission first (admit_seq), NOT lowest slot index — a
        # freed low slot must not let fresh admissions starve an in-flight
        # prefill parked in a higher slot
        first = min(pre, key=lambda sr: sr[1].admit_seq)[1]
        c = width(first)
        group = [(s, r) for s, r in pre if width(r) == c]
        with span("serve.prefill", rid=first.rid,
                  tokens=c * len(group)):
            self._tick(prefill_tokens=c * len(group))
            for _, r in group:
                if self._is_resume(r):
                    # a prefill chunk that reruns known context — zero on
                    # the host-tier happy path, counted on every fallback
                    self.metrics.bump("resume_reprefill_chunks")
            tokens = jnp.asarray(
                np.stack([r.prefill_tokens[r.prefill_pos:r.prefill_pos + c]
                          for _, r in group]), jnp.int32)
            tmax = max(len(r.prefill_tokens) for _, r in group)

            def padbuf(a):
                pad = tmax - a.shape[2]
                if pad == 0:
                    return a
                width_ = [(0, 0)] * a.ndim
                width_[2] = (0, pad)
                return jnp.pad(a, width_)

            bufs = jax.tree.map(
                lambda *leaves: jnp.concatenate([padbuf(a) for a in leaves],
                                                axis=1),
                *[r.buffers for _, r in group])
            offs = jnp.asarray([r.prefill_pos for _, r in group], jnp.int32)
            if self.sampling is not None:
                # sampling engines build their chunk step with
                # return_last_logits=True: the done rows' final-position
                # logits feed the on-device first-token sampler
                next_toks, last_logits, bufs = self.chunk_step(
                    self.params, tokens, bufs, offs)
            else:
                next_toks, bufs = self.chunk_step(self.params, tokens, bufs,
                                                  offs)
            finished = []
            done = [r.prefill_pos + c >= len(r.prefill_tokens)
                    for _, r in group]
            # one batched transfer for every request finishing this chunk
            first_np = None
            if any(done):
                di = [i for i, d in enumerate(done) if d]
                if self.sampling is not None:
                    dev = self._first_token_dev(
                        last_logits[jnp.asarray(di)],
                        [group[i][1] for i in di])
                else:
                    dev = next_toks[jnp.asarray(di), c - 1]
                with span("serve.readback"):
                    first_np = {i: v for i, v in zip(di, np.asarray(dev))}
            for i, (slot, req) in enumerate(group):
                t_i = len(req.prefill_tokens)
                req.buffers = jax.tree.map(lambda a: a[:, i:i + 1, :t_i],
                                           bufs)
                req.prefill_pos += c
                if done[i]:
                    finished += self._finish_prefill(req, slot,
                                                     int(first_np[i]))
            return finished

    def _finish_prefill(self, req: Request, slot: int,
                        first_token: int) -> list[Request]:
        """Chunked prefill complete: hand the carry buffers off to the
        decode slot and commit the first generated token."""
        from repro.models.model_zoo import finalize_chunked_prefill
        t = len(req.prefill_tokens)
        # a paged fp pool's handoff program finalizes the buffers itself,
        # on the devices that hold them (model_zoo.prefill_to_pool)
        carry = self.paged and not self.kv8
        pstate = req.buffers
        if not carry:
            hx = self.hx if self.hx is not None else _default_hx(self.rr)
            pstate = finalize_chunked_prefill(self.cfg, hx, pstate, t,
                                              kvp=self.kvp)
        if self.prefix_index is not None:
            self._register_prefix(req, t)
        req.buffers = None
        req.prefill_tokens = None
        self._scatter_state(pstate, slot, t, req, carry=carry)
        return self._commit_first_token(req, slot, first_token)

    def _is_resume(self, req: Request) -> bool:
        """Whether this request's prefill work recomputes context the host
        tier could have restored: it was preempted before, or a restore
        attempt for it failed this admission."""
        m = self.metrics.requests.get(req.rid)
        return bool((m is not None and m.n_preempts > 0)
                    or req.resume_fallback)

    def _oneshot_prefill(self, req: Request, slot: int,
                         defer: list | None = None) -> list[Request]:
        toks_list = req.resume_tokens()
        if self._is_resume(req):
            # the whole one-shot prefill is one "chunk" of redone work
            self.metrics.bump("resume_reprefill_chunks")
        toks = jnp.asarray(toks_list, jnp.int32)[None, :]
        last_logits, pstate = self.prefill_step(self.params, {"tokens": toks})
        self._scatter_state(pstate, slot, len(toks_list), req)
        # device-side first-token decision (argmax, or the sampler when
        # the engine samples — prefill logits come out of ``forward``
        # already softcapped + vocab-masked, so they feed it directly)
        nxt_dev = self._first_token_dev(last_logits, [req])[0]
        if defer is not None:
            # scheduled admission: _admit batches every prefill's token
            # into ONE host transfer per engine step
            defer.append((req, slot, nxt_dev))
            return []
        nxt = int(np.asarray(nxt_dev))
        return self._commit_first_token(req, slot, nxt)

    def _first_token_dev(self, last_logits, reqs: list[Request]):
        """Device-side first-token decision for freshly prefilled rows:
        ``last_logits`` [G, padded_vocab] (vocab-masked by ``forward``),
        one row per request.  Greedy engines take the plain argmax;
        sampling engines run the serving/sampling.py sampler at
        ``sample_idx = 0`` — the first point of each request's PRNG
        stream, so prefill-time sampling and a decode-step sample of the
        same position agree bit-exactly."""
        if self.sampling is None:
            return jnp.argmax(last_logits[:, :self.cfg.vocab],
                              axis=-1).astype(jnp.int32)
        from repro.serving.sampling import request_seed, sample_tokens
        pols = [(r.sampling or self.sampling) for r in reqs]
        rows = [p.row() for p in pols]
        return sample_tokens(
            last_logits,
            jnp.asarray([v[0] for v in rows], jnp.float32),
            jnp.asarray([v[1] for v in rows], jnp.int32),
            jnp.asarray([v[2] for v in rows], jnp.float32),
            jnp.asarray([request_seed(p.seed, r.rid)
                         for p, r in zip(pols, reqs)], jnp.uint32),
            jnp.zeros((len(reqs),), jnp.int32))

    def _install_sampling(self, req: Request, slot: int) -> None:
        """Install ``req``'s sampling policy into ``slot``'s per-row state
        leaves.  ``sample_idx`` resumes at ``len(out_tokens)`` — the count
        of tokens already sampled — so a restored/preempted request
        continues the exact PRNG stream it left (forced catch-up tokens
        do not advance it, on either decode path)."""
        if self.sampling is None:
            return
        from repro.serving.sampling import request_seed
        sp = req.sampling or self.sampling
        t, k, p = sp.row()
        st = self.state
        st["sample_temp"] = st["sample_temp"].at[slot].set(t)
        st["sample_topk"] = st["sample_topk"].at[slot].set(k)
        st["sample_topp"] = st["sample_topp"].at[slot].set(p)
        st["sample_seed"] = st["sample_seed"].at[slot].set(
            request_seed(sp.seed, req.rid))
        st["sample_idx"] = st["sample_idx"].at[slot].set(len(req.out_tokens))

    def _commit_first_token(self, req: Request, slot: int,
                            token: int) -> list[Request]:
        req.out_tokens.append(token)
        self.cur_tokens = self.cur_tokens.at[slot].set(token)
        req.state = DECODE
        self._install_sampling(req, slot)
        self.metrics.on_token(req.rid)
        self.sched.record_served(slot)
        # the prefill token itself may already retire the request
        if (req.eos_id is not None and token == req.eos_id):
            return [self._retire(req, slot, "eos")]
        if len(req.out_tokens) >= req.max_new_tokens:
            return [self._retire(req, slot, "max_tokens")]
        r = self._grow_or_retire(req, slot)
        return [r] if r is not None else []

    def _grow_or_retire(self, req: Request, slot: int) -> Request | None:
        """Reserve what the next decode token needs through the capacity
        oracle (``Scheduler.grow_for_next_token``): fixed layout — nothing,
        until ``cap``; paged — the next page when a boundary is crossed,
        mirrored into the device block table.  Returns the retired request
        when growth is impossible (``finish_reason="capacity"``)."""
        grown = self.sched.grow_for_next_token(slot)
        if grown is None:
            return self._retire(req, slot, "capacity")
        if grown:
            self._mirror_table(slot)
        return None

    def _mirror_table(self, slot: int) -> None:
        """Write ``slot``'s page list into the device block-table row
        (unused tail entries point at the sink page 0)."""
        phys = self.pool.pages(self.slots[slot].rid)
        row = np.zeros((self.max_pages,), np.int32)
        row[:len(phys)] = phys
        self._write_table_row(slot, row)

    def _write_table_row(self, slot: int, row) -> None:
        """One block-table row written by one dispatch of a program
        compiled once, its output in the table's sharding: an eager
        ``.at[slot].set`` of a host row costs three (transfer, reshard,
        scatter) on the decode step that crosses a page boundary."""
        self.state["block_tables"] = self._set_table_row(
            self.state["block_tables"], np.int32(slot), row)

    def _scatter_state(self, pstate: dict[str, Any], slot: int,
                       t: int, req: Request, carry: bool = False) -> None:
        """Scatter a single-request prefill state into ``slot`` (copying
        the common round-robin prefix of every rank's local slots; int8
        engines quantize the fp prefill cache per slot row —
        ``quantize_decode_state`` — matching the decode append formula).
        Paged engines instead split the round-robin cache into pages
        (``cache_to_pages``) and write them at the physical pool planes the
        allocator granted at admission, then install the block-table row.
        ``carry``: ``pstate`` holds a chunked prefill's carry buffers,
        which the paged handoff finalizes itself."""
        if self.paged and "kcache" in pstate:
            self._scatter_paged(pstate, slot, t, req, carry)
        elif self.kv8 and "kcache" in pstate:
            fp_slot = {}
            for key in ("kcache", "vcache"):
                dst = jnp.zeros(
                    self.state[key].shape[:1] + (1,)
                    + self.state[key].shape[2:], jnp.float32)
                src = pstate[key][:, 0].astype(jnp.float32)
                fp_slot[key] = dst.at[:, 0].set(
                    _copy_rr(src, dst[:, 0], self.kvp))
            q = quantize_decode_state(fp_slot)
            for key in ("kcache", "vcache", "kscale", "vscale"):
                self.state[key] = self.state[key].at[:, slot].set(q[key][:, 0])
        else:
            for key in ("kcache", "vcache"):
                if key in self.state and key in pstate:
                    # prefill cache capacity may differ; copy the common
                    # prefix of every rank's local slots (layouts match:
                    # same kvp/rr)
                    src = pstate[key][:, 0]
                    dst = self.state[key][:, slot]
                    self.state[key] = self.state[key].at[:, slot].set(
                        _copy_rr(src, dst, self.kvp))
        for key in ("ssm_conv", "ssm_state", "xk", "xv"):
            if key in self.state and key in pstate:
                self.state[key] = self.state[key].at[:, slot].set(
                    pstate[key][:, 0])
        self.state["total_len"] = self.state["total_len"].at[slot].set(t)

    def _scatter_paged(self, pstate: dict[str, Any], slot: int,
                       t: int, req: Request, carry: bool = False) -> None:
        """Paged half of ``_scatter_state``: prefill cache -> pool pages.

        The request's round-robin cache splits into page stacks
        (``cache_to_pages`` — pages hold ``block_s`` consecutive positions)
        written at the physical planes granted at admission.  Pages granted
        beyond the prefill extent stay untouched: any stale rows they hold
        sit at positions >= t, which every backend masks.  int8 engines
        quantize pagewise with the decode-append formula, exactly like the
        fixed path."""
        phys = self.pool.pages(req.rid)
        cap = (cache_capacity(t, self.kvp, self.rr) if carry
               else pstate["kcache"].shape[3])
        n = min(-(-cap // self.block_s), len(phys))
        # shared leading pages already hold the registrant's rows —
        # byte-identical to what this request would write for the same
        # token prefix (per-row quantization on kv8), and possibly still
        # mapped by other requests; only the unshared tail is scattered.
        s0 = min(getattr(req, "shared_pages", 0), n)
        if s0 < n:
            idx = jnp.asarray(phys[s0:n], jnp.int32)
            if self.kv8:
                qpages = quantize_decode_state(
                    {key: cache_to_pages(pstate[key][:, 0], self.kvp,
                                         self.block_s)[:, s0:n]
                     .astype(jnp.float32) for key in ("kcache", "vcache")})
                for key in ("kcache", "vcache", "kscale", "vscale"):
                    self.state[key] = \
                        self.state[key].at[:, idx].set(qpages[key])
            else:
                self.state.update(self._to_pool(
                    {key: self.state[key] for key in ("kcache", "vcache")},
                    pstate, idx, t=t if carry else None, s0=s0))
        self._mirror_table(slot)
        # (_scatter_state's shared tail installs total_len and ssm leaves)

    def _cow_guard(self, active: list[int]) -> None:
        """Make every slot's append-target page exclusive before the decode
        step writes it (copy-on-write).

        The admission path already CoWs a shared partial page eagerly, so a
        shared append target here means a request decoded *through* a page
        boundary into a still-shared page — possible only when a request's
        committed length ends exactly on the shared-prefix boundary.  The
        allocator hands back a fresh page; the device copy of the old
        page's committed rows happens here, before the kernel's append."""
        for i in active:
            req = self.slots[i]
            li = self.sched.slot_len[i] // self.block_s
            phys = self.pool.pages(req.rid)
            if li >= len(phys) or self.pool.refcount(phys[li]) == 1:
                continue
            res = self.pool.cow(req.rid, li)
            assert res is not None, \
                "CoW with an empty free list: admission must pre-charge " \
                "the divergent page (scheduler._reserve)"
            old, new = res
            keys = ("kcache", "vcache") + \
                (("kscale", "vscale") if self.kv8 else ())
            for key in keys:
                self.state[key] = \
                    self.state[key].at[:, new].set(self.state[key][:, old])
            self._mirror_table(i)

    def _set_groups(self, active: list[int]) -> None:
        """Refresh the grouped-decode ``group_id``/``group_np`` leaves.

        Slots whose tables start on the same physical page form a group;
        ``group_np`` is the longest run of *identical* leading pages common
        to every member, capped at each member's full committed pages so
        the fused append (block ``slot_len // block_s``) always lands in
        the per-request suffix.  Every member gets the same ``group_np`` —
        the prefix pass has no per-member block mask, so an unequal start
        would double-count the blocks between the smallest and largest.
        Singletons and idle rows stay their own group with ``group_np=0``,
        which the kernel decodes exactly as ungrouped."""
        gid = np.arange(self.max_batch, dtype=np.int32)
        gnp = np.zeros(self.max_batch, dtype=np.int32)
        buckets: dict[int, list[int]] = {}
        for i in active:
            pages = self.pool.pages(self.slots[i].rid)
            if pages and pages[0] != 0:
                buckets.setdefault(pages[0], []).append(i)
        for members in buckets.values():
            if len(members) < 2:
                continue
            lists = [self.pool.pages(self.slots[i].rid) for i in members]
            depth = min(min(len(pl) for pl in lists),
                        min(self.sched.slot_len[i] // self.block_s
                            for i in members))
            lcp = 0
            while lcp < depth and all(pl[lcp] == lists[0][lcp]
                                      for pl in lists):
                lcp += 1
            if lcp == 0:
                continue
            g = min(members)
            for i in members:
                gid[i] = g
                gnp[i] = lcp
        self.state["group_id"] = jnp.asarray(gid)
        self.state["group_np"] = jnp.asarray(gnp)

    def _decode_step(self) -> list[Request]:
        """One decode step for every DECODE slot; returns retirements."""
        active = [i for i, r in enumerate(self.slots)
                  if r is not None and r.state == DECODE]
        if not active:
            return []
        with span("serve.dispatch"):
            self._tick(decode_slots=len(active))
            if self.paged and self.prefix_index is not None:
                self._cow_guard(active)
            if self.grouped:
                self._set_groups(active)
            next_tokens, self.state = self.serve_step(
                self.params, self.state, self.cur_tokens)
            self.cur_tokens = next_tokens
            # serve_step advances total_len for every row; pin non-decoding
            # slots back to 0.  (Not the prefilling request's committed
            # length: its K/V still lives in the carry buffers, so a
            # non-zero length would make every decode step stream that many
            # garbage cache blocks for the slot.  Length 0 keeps the dead
            # row O(1) and the finalize scatter installs the real
            # total_len.)
            idle = [i for i in range(self.max_batch) if i not in active]
            if idle:
                self.state["total_len"] = \
                    self.state["total_len"].at[jnp.asarray(idle)].set(0)
            self._grow_ahead(active)
        # one batched device->host transfer per step (per-slot int() calls
        # would each block on the device queue — B syncs instead of 1)
        with span("serve.readback"):
            toks_np = np.asarray(next_tokens)
        self.decode_syncs += 1
        with span("serve.replay"):
            return self._replay_step(active, toks_np)

    def _grow_ahead(self, active: list[int]) -> None:
        """Reserve, while the device runs the step just dispatched, the
        page each row's *next* token needs, and write its block-table row.

        Growth depends on lengths alone, so it need not wait for the
        sampled tokens; done here, the table write's dispatch (~1.7 ms of
        host time on a TPU v5e 2x2 host) overlaps the step instead of
        delaying the next one.  A row this step retires on max tokens is
        skipped; one it retires on EOS frees the page with its others in
        the replay; one the pool cannot serve now grows, or retires on
        capacity, in the replay's ``_grow_or_retire`` as before."""
        if not self.paged:
            return
        for i in active:
            req = self.slots[i]
            if (not req.forced_tokens
                    and len(req.out_tokens) + 1 >= req.max_new_tokens):
                continue
            if self.sched.grow_ahead(i):
                self._mirror_table(i)

    def _replay_step(self, active: list[int], toks_np) -> list[Request]:
        """A decode step's tokens into their requests: forced catch-up,
        emissions, retirements and growth, then the pool sample."""
        finished = []
        forced: list[tuple[int, int]] = []
        for i in active:
            req = self.slots[i]
            if req.forced_tokens:
                # teacher-forced catch-up after a restore: this step
                # appended the KV row for the current *known* token, so
                # the sampled token is overridden by the next known one.
                # Nothing is emitted (these are prompt/history tokens,
                # not samples): no out_tokens append, no TTFT/TTL event —
                # only the committed length advances.
                forced.append((i, req.forced_tokens.pop(0)))
                self.sched.on_token(i)
                r = self._grow_or_retire(req, i)
                if r is not None:
                    finished.append(r)
                continue
            tok = int(toks_np[i])
            req.out_tokens.append(tok)
            self.sched.on_token(i)
            self.sched.record_served(i)
            self.metrics.on_token(req.rid)
            self.decoded_tokens += 1
            if req.eos_id is not None and tok == req.eos_id:
                finished.append(self._retire(req, i, "eos"))
            elif len(req.out_tokens) >= req.max_new_tokens:
                finished.append(self._retire(req, i, "max_tokens"))
            else:
                r = self._grow_or_retire(req, i)
                if r is not None:
                    finished.append(r)
        if forced:
            idx = jnp.asarray([i for i, _ in forced], jnp.int32)
            val = jnp.asarray([t for _, t in forced], jnp.int32)
            self.cur_tokens = self.cur_tokens.at[idx].set(val)
            if self.sampling is not None:
                # forced catch-up consumed no sample: rewind the PRNG
                # counter serve_step advanced for those rows, so the
                # post-catch-up stream re-joins the original exactly
                self.state["sample_idx"] = \
                    self.state["sample_idx"].at[idx].add(-1)
        if self.paged:
            self._sample_pool()
        return finished

    def _decode_window(self) -> list[Request]:
        """N decode steps for every DECODE slot in ONE device dispatch.

        The windowed inner loop (``--decode-window N`` > 1): the scheduler
        pre-reserves each slot's page/capacity budget for the whole window
        (``grow_for_window`` — one atomic extend, so nothing allocates
        mid-window), ``serve_multistep`` runs N sample->append->step
        iterations entirely on device with per-row EOS/budget/forced masks,
        and the host blocks exactly once on the ``[B, N]`` token block —
        syncs per decoded token drop from 1 to 1/N.  The transfer is
        started async (``copy_to_host_async``) and the window's host-side
        bookkeeping overlaps the copy; the donated state means the next
        window's dispatch can be enqueued as soon as the replay finishes,
        overlapping host scheduling of window k+1 with device compute
        still in flight.

        The replay is j-major (in-window step order) so scheduler token
        accounting, retirement order and VirtualClock TTL attribution all
        match the single-step engine event for event; rows that freeze
        mid-window (EOS, max-tokens, capacity-limited budget) retire at
        the boundary, which keeps windowed streams bit-identical to
        window=1 (tests/serving/test_decode_window.py)."""
        n = self.decode_window
        active = [i for i, r in enumerate(self.slots)
                  if r is not None and r.state == DECODE]
        if not active:
            return []
        with span("serve.dispatch"):
            finished = []
            budgets = np.zeros((self.max_batch,), np.int32)
            wants = np.zeros((self.max_batch,), np.int32)
            eos = np.full((self.max_batch,), -1, np.int32)
            forced = np.zeros((self.max_batch, n), np.int32)
            nforced = np.zeros((self.max_batch,), np.int32)
            stepping = []
            for i in active:
                req = self.slots[i]
                nf = min(len(req.forced_tokens or ()), n)
                emit_max = max(req.max_new_tokens - len(req.out_tokens), 0)
                want = min(n, nf + emit_max)
                grant = self.sched.grow_for_window(i, want)
                if self.paged and grant:
                    self._mirror_table(i)
                if grant == 0:
                    # can't take a single step: the capacity retire the
                    # single-step engine's grow_for_next_token would have hit
                    finished.append(self._retire(req, i, "capacity"))
                    continue
                budgets[i], wants[i] = grant, want
                if req.eos_id is not None:
                    eos[i] = req.eos_id
                if nf:
                    forced[i, :nf] = req.forced_tokens[:nf]
                    nforced[i] = nf
                stepping.append(i)
            if not stepping:
                return finished
            if self.paged and self.prefix_index is not None:
                self._cow_guard(stepping)
            t0 = time.monotonic()
            out_block, cur, self.state = self.serve_multistep(
                self.params, self.state, self.cur_tokens,
                jnp.asarray(budgets), jnp.asarray(eos),
                jnp.asarray(forced), jnp.asarray(nforced))
            self.cur_tokens = cur
            # kick off the D2H copy, overlap host bookkeeping with it, then
            # block ONCE on the whole window's token block
            if hasattr(out_block, "copy_to_host_async"):
                out_block.copy_to_host_async()
            if self.paged:
                self._sample_pool()
        with span("serve.readback"):
            toks_np = np.asarray(out_block)
        self.decode_syncs += 1
        t1 = time.monotonic()
        with span("serve.replay"):
            return self._replay_window(stepping, budgets, wants, toks_np,
                                       t0, t1, finished)

    def _replay_window(self, stepping: list[int], budgets, wants, toks_np,
                       t0: float, t1: float,
                       finished: list[Request]) -> list[Request]:
        """A window's ``[B, N]`` token block into its requests, step by
        step (see ``_decode_window``); appends retirements to ``finished``
        and returns it."""
        # j-major replay: the same scheduler/metrics/retirement events the
        # single-step engine would emit, in the same order.  TTL samples
        # get in-window timestamps — VirtualClock ticks per replayed step,
        # wall clocks interpolate the measured window time over N.
        virtual = isinstance(self.metrics.clock, VirtualClock)
        nsteps = int(max(budgets[i] for i in stepping))
        retired: set[int] = set()
        for j in range(nsteps):
            rows = [i for i in stepping
                    if i not in retired and budgets[i] > j]
            if not rows:
                break
            at = None
            if virtual:
                self._tick(decode_slots=len(rows))
            else:
                at = t0 + (t1 - t0) * (j + 1) / nsteps
            for i in rows:
                req = self.slots[i]
                if req.forced_tokens:
                    # device fed the forced token in place of its sample
                    # (emitting pad); only the committed length advances
                    req.forced_tokens.pop(0)
                    self.sched.on_token(i)
                    continue
                tok = int(toks_np[i, j])
                req.out_tokens.append(tok)
                self.sched.on_token(i)
                self.sched.record_served(i)
                self.metrics.on_token(req.rid, at=at)
                self.decoded_tokens += 1
                if req.eos_id is not None and tok == req.eos_id:
                    finished.append(self._retire(req, i, "eos"))
                    retired.add(i)
                elif len(req.out_tokens) >= req.max_new_tokens:
                    finished.append(self._retire(req, i, "max_tokens"))
                    retired.add(i)
        # a capacity-limited grant the in-window EOS/max replay didn't
        # consume means the pool/cap wall sits exactly where the
        # single-step engine would retire with "capacity"
        for i in stepping:
            if i not in retired and budgets[i] < wants[i]:
                finished.append(self._retire(self.slots[i], i, "capacity"))
        return finished

    def sync_stats(self) -> dict[str, Any]:
        """Host-sync accounting for the decode loop: how many blocking
        device->host transfers the engine performed per decoded token.
        ``syncs_per_token`` is 1.0 for the single-step engine and 1/N
        under ``--decode-window N`` — the headline number of this
        optimization, asserted by scripts/decode_window_smoke.py and
        surfaced as a bench_serving column."""
        return {"decode_window": self.decode_window,
                "decode_syncs": self.decode_syncs,
                "decoded_tokens": self.decoded_tokens,
                "syncs_per_token":
                    self.decode_syncs / max(self.decoded_tokens, 1)}

    def _sample_pool(self) -> None:
        """Record one pool-health sample (occupancy / internal
        fragmentation of allocated pages) for ``pool_stats``."""
        used = self.pool.used_count
        if used == 0:
            return
        committed = sum(self.sched.slot_len)
        self._frag_sum += 1.0 - committed / (used * self.block_s)
        self._frag_n += 1

    def pool_stats(self) -> dict[str, float]:
        """Paged-pool health for the serving bench: peak occupancy (peak
        pages in use / allocatable pages), mean internal fragmentation of
        allocated pages (1 - committed/allocated slots, sampled each decode
        step), the retirement count with ``finish_reason="capacity"``, and
        the prefix-sharing pair: ``prefix_hit_rate`` (share of chunked
        admissions that matched a cached prefix) and ``pages_shared_peak``
        (peak pages mapped by more than one request).  Fixed-cap engines
        report zeros for the pool fields; ``capacity_retired`` is the real
        count on both layouts."""
        cap_retired = sum(
            1 for m in self.metrics.requests.values()
            if getattr(m, "finish_reason", None) == "capacity")
        if not self.paged:
            return {"paged_kv": False, "pool_occupancy_peak": 0.0,
                    "pool_frag_mean": 0.0, "capacity_retired": cap_retired,
                    "prefix_hit_rate": 0.0, "pages_shared_peak": 0,
                    "store_evictions": 0}
        frag = self._frag_sum / self._frag_n if self._frag_n else 0.0
        return {"paged_kv": True,
                "pool_occupancy_peak":
                    self.pool.peak_in_use / max(self.pool.capacity, 1),
                "pool_frag_mean": frag,
                "capacity_retired": cap_retired,
                "prefix_hit_rate":
                    self._prefix_hits / max(self._prefix_admits, 1),
                "pages_shared_peak": self.pool.pages_shared_peak,
                "store_evictions":
                    self.store.evictions if self.store is not None else 0}

    def tier_stats(self) -> dict:
        """Host KV tier health for the serving bench: store occupancy and
        the save/restore/fault counters (``HostPageStore.stats``).  Engines
        without a host store report all-zero counters so downstream schema
        consumers never key-error."""
        if self.store is None:
            return {k: 0 for k in (
                "host_pages_capacity", "host_pages_used", "host_entries",
                "host_saves", "host_restores", "restores_failed",
                "checksum_mismatches", "stale_generations",
                "store_evictions", "store_full")}
        return self.store.stats()

    def _retire(self, req: Request, slot: int, reason: str) -> Request:
        req.done = True
        req.state = DONE
        req.finish_reason = reason
        # session KV: persist the retired request's committed pages keyed
        # by session id — BEFORE the pool reclaims them — so the next turn
        # restores the conversation history instead of re-prefilling it
        if (self.session_kv and req.session_id is not None
                and self.store is not None
                and reason in ("eos", "max_tokens")):
            self._save_session(req, slot)
        if req.spill_key is not None:
            # a retired request never resumes; free its spill entry
            self.store.drop(req.spill_key)
            req.spill_key = None
            req.spill_len = 0
        self.slots[slot] = None
        self.sched.release(slot)
        self.state["total_len"] = self.state["total_len"].at[slot].set(0)
        if self.paged:
            # park the freed row on the sink page (all-zero table row)
            self._write_table_row(slot,
                                  np.zeros((self.max_pages,), np.int32))
        self.metrics.on_finish(req.rid, reason)
        return req

    def _save_session(self, req: Request, slot: int) -> None:
        """Spill a retiring request's committed pages under its session
        key (same exact-bytes gather + one batched D2H as ``_spill``).
        The stored token prefix is ``prompt + out[:-1]`` — always a proper
        prefix of turn N+1's prompt (history + new text), which is what
        makes the restore applicability check a plain prefix match."""
        committed = self.sched.slot_len[slot]
        phys = self.pool.pages(req.rid)[:self.pool.pages_for(committed)]
        if committed <= 0 or not phys:
            return
        planes = gather_pool_pages(self.state, phys)
        with span("serve.readback"):
            host = jax.device_get(planes)
        if self.store.put(f"session:{req.session_id}", host,
                          tokens=req.resume_tokens()[:committed]):
            self.metrics.bump("spills")
        self._sync_store_counters()


def _set_row(table, slot, row):
    return table.at[slot].set(row)


def _default_hx(rr_block: int) -> HelixConfig:
    return HelixConfig(kvp_axes=(), tpa_axis=None, rr_block=rr_block)


def _copy_rr(src, dst, kvp: int):
    """Copy a round-robin cache [L?, Kh, S_src, hsz] into capacity S_dst.

    Both layouts are (rank-major, local-slot) with the same kvp/rr, so rank
    r's local slots [0, S_src/kvp) map to dst-local slots [0, S_src/kvp).
    """
    s_src = src.shape[-2]
    s_dst = dst.shape[-2]
    if s_src == s_dst:
        return src
    ls, ld = s_src // kvp, s_dst // kvp
    n = min(ls, ld)
    srcr = src.reshape(*src.shape[:-2], kvp, ls, src.shape[-1])
    dstr = dst.reshape(*dst.shape[:-2], kvp, ld, dst.shape[-1])
    out = dstr.at[..., :, :n, :].set(srcr[..., :, :n, :])
    return out.reshape(dst.shape)
