"""Serving driver: batched decode with the Helix engine.

  PYTHONPATH=src python -m repro.launch.serve --arch granite-3-2b --reduced \
      --requests 8 --prompt-len 32 --max-new 16 --chunk-tokens 8

Kernel backends (kernels/registry.py) are selectable per family:
``--attn-backend`` routes the decode attention (flash_decode),
``--prefill-backend`` the full-sequence prefill attention (flash_prefill),
``--ssd-backend`` the Mamba2 SSD scan core (ssd_prefill),
``--matmul-backend`` the w8a16 int8-weight matmul (with ``--lm-head-w8``
quantizing the lm_head onto it); ``--no-fuse-append`` opts out of the fused
KV-append kernel epilogue and ``--no-prune-blocks`` of the length/causality-
aware K/V block pruning (both bit-exact).  ``--list-backends`` prints the
per-family availability matrix and exits (CI smoke target).

Serving scheduler (docs/serving.md): ``--chunk-tokens N`` prefills prompts
in N-token slices interleaved with decode steps (0 = monolithic one-shot
prefill), ``--sched-policy`` picks the admission order (fcfs | sjf), and
``--traffic poisson --arrival-rate R`` replays a synthetic Poisson arrival
process (R requests per engine step on average) instead of submitting
everything up front; ``--metrics`` prints the TTFT/TTL/queue-wait summary.
``--paged-kv`` switches to the shared-pool paged KV cache (``--pool-blocks``
sizes the pool): one global page pool + per-request block tables instead of
worst-case per-slot reservations, so admission gates on the global free-page
count — token streams stay bit-exact vs the fixed layout.

Host KV tier (docs/serving.md): ``--host-pages N`` spills preempted
requests' live pages to a host store so resume runs zero re-prefill
chunks, ``--session-kv`` persists retired requests' pages per session so
``--turns T`` multi-turn conversations restore their history, and
``--fault-plan 'k=v,...'`` deterministically injects the tier's failure
modes (every one degrades to re-prefill, never to divergent tokens —
scripts/chaos_smoke.py asserts this in CI).

Multi-tenant SLO front end (docs/serving.md): every run is driven by a
serving/workload.py **trace** — ``--trace FILE`` replays a saved JSONL
trace, otherwise one is generated from ``--traffic batch|poisson|bursty``
(``--arrival-rate``, ``--burst``) and the ``--tenants
"name[:weight[:slo[:share]]],..."`` mix.  ``--tenants`` arms
deficit-weighted-fair admission across tenants; ``--slo-ttl-ms`` arms the
TTL governor, which sheds batch-class slots through the spill path when
the interactive TTL p95 drifts past target; ``--virtual-clock`` swaps the
metrics clock for the deterministic cost model so two replays of the same
trace produce identical latency summaries (scripts/trace_smoke.py asserts
this in CI).

On-device sampling + multi-step decode (docs/serving.md): ``--sampling
greedy|temperature|top_k|top_p`` (with ``--temperature``, ``--top-k``,
``--top-p``, ``--seed``) moves token selection onto the device as a fused
epilogue over the lm_head logits, and ``--decode-window N`` runs N decode
steps per device dispatch via a ``lax.scan`` so the host blocks on ONE
[batch, N] token-block transfer per window instead of one sync per token
— token streams stay bit-identical to ``--decode-window 1``
(scripts/decode_window_smoke.py asserts streams and the 1/N sync rate in
CI); the summary gains ``engine.sync_stats()``'s ``syncs_per_token``.
"""
from __future__ import annotations

import argparse
import dataclasses
import functools
import json
import time

import numpy as np
import jax
import jax.numpy as jnp

from repro.configs import get_config
from repro.core.sharding import (HelixConfig, default_helix_config,
                                 helix_param_specs, to_shardings)
from repro.kernels.registry import BACKENDS, backend_table
from repro.models.model_zoo import (build_serve_multistep, build_serve_step,
                                    chunked_prefill_supported,
                                    make_chunk_prefill_step, make_prefill_step)
from repro.models.transformer import init_params
from repro.serving import DecodeEngine, Request
from repro.serving.sampling import SAMPLING_KINDS, SamplingParams
from repro.serving.metrics import VirtualClock
from repro.serving.scheduler import POLICIES
# poisson_arrival_steps moved to (and is re-exported from) the workload
# module so serve and bench replay the exact same arrival processes
from repro.serving.workload import (TenantSpec, generate_trace, load_trace,
                                    parse_tenants, poisson_arrival_steps,
                                    requests_from_trace, trace_id)
from repro.utils import enable_compile_cache, make_mesh


def init_serving_params(cfg, seed: int, mesh=None, hx=None):
    """Random weights for ``cfg`` from ``seed``, built by one compiled
    program: the generator writes each weight in place instead of holding
    eager temporaries (a full-width f32 model then peaks at its own size).
    With ``mesh`` (and its ``hx``) each weight is generated directly into
    its ``helix_param_specs`` sharding, so no device ever holds the whole
    model.  Deterministic in (cfg, seed) — ``chip_smoke.py`` rebuilds the
    served weights with it for its reference forward."""
    key = jax.random.PRNGKey(seed)
    out = None
    if mesh is not None:
        shapes = jax.eval_shape(functools.partial(init_params, cfg), key)
        out = to_shardings(mesh, helix_param_specs(cfg, shapes, hx, mesh))
    return jax.jit(init_params, static_argnums=0, out_shardings=out)(cfg, key)


def serve_demo(arch: str, *, reduced: bool, n_requests: int, prompt_len: int,
               max_new: int, max_batch: int = 8, mesh=None, hx=None,
               attn_backend: str | None = None,
               prefill_backend: str | None = None,
               ssd_backend: str | None = None,
               matmul_backend: str | None = None,
               fuse_append: bool | None = None,
               prune_blocks: bool | None = None,
               lm_head_w8: bool | None = None,
               paged_kv: bool | None = None,
               pool_blocks: int | None = None,
               prefix_share: bool = False,
               grouped_decode: bool | None = None,
               shared_prefix_len: int = 0,
               host_pages: int = 0, session_kv: bool = False,
               fault_plan=None, turns: int = 1,
               chunk_tokens: int = 0, sched_policy: str = "fcfs",
               traffic: str = "batch", arrival_rate: float = 0.5,
               burst: int = 4, trace=None, tenants=None,
               slo_ttl_ms: float = 0.0, virtual_clock=False,
               decode_window: int = 1, sampling: str | None = None,
               temperature: float = 1.0, top_k: int = 0, top_p: float = 1.0,
               seed: int = 0, log=print):
    """Run ``n_requests`` synthetic prompts through the continuous-batching
    engine and report throughput.  Returns (finished ``Request`` list,
    metrics summary dict — with the engine's ``pool_stats()`` merged in).

    The ``*_backend`` arguments override the corresponding ``hx`` fields
    (``None`` keeps the ``HelixConfig`` defaults); see kernels/registry.py.
    ``chunk_tokens`` > 0 enables chunked prefill (scheduler path);
    ``traffic="poisson"`` staggers submissions over engine steps with
    ``arrival_rate`` requests/step on average.  ``paged_kv`` switches the
    KV cache to the shared-pool paged layout (``pool_blocks`` pages of
    ``kvp * rr_block`` positions; default = the fixed layout's HBM), making
    cache pressure a global admission signal — bit-exact token streams
    either way (scripts/paged_smoke.py asserts this in CI).

    ``shared_prefix_len`` makes every synthetic prompt start with the same
    ``shared_prefix_len`` tokens (distinct random suffixes fill the rest);
    ``prefix_share`` turns on the engine's prefix index + refcounted
    copy-on-write page sharing over it (needs ``paged_kv`` + chunked
    prefill), and ``grouped_decode`` additionally decodes each shared
    prefix once per *group* of requests instead of once per request
    (``HelixConfig.grouped_decode``) — all bit-exact vs the unshared run
    (scripts/prefix_smoke.py asserts this in CI).

    Host KV tier (docs/serving.md): ``host_pages`` sizes the
    ``HostPageStore`` so preemptions spill live pages and resume with zero
    re-prefill chunks; ``session_kv`` persists retired requests' pages per
    session id; ``fault_plan`` (a ``serving/faults.FaultPlan`` or its
    ``"k=v,..."`` spec string) deterministically injects the tier's
    failure modes.  ``turns`` > 1 runs a multi-turn conversation workload:
    each request is a session whose turn t+1 prompt is its full turn-t
    context plus ``prompt_len`` fresh tokens, submitted the step turn t
    finishes — the summary's ``turn2_ttft_s`` isolates what the session
    restore buys (with ``session_kv`` it tracks the *new* turn length, not
    the ever-growing history).

    Workload/tenancy (serving/workload.py, docs/serving.md): the run is
    always trace-driven — ``trace`` (a path or a ``TraceRow`` list)
    replays a saved workload, otherwise one is generated from ``traffic``
    ("batch" | "poisson" | "bursty"), ``arrival_rate``/``burst`` and the
    ``tenants`` mix (a ``parse_tenants`` spec string or ``TenantSpec``s);
    the summary's ``trace_id`` names the exact workload either way.
    ``tenants`` also arms weighted-fair admission, ``slo_ttl_ms`` > 0
    arms the TTL governor (shed batch-to-spill when the interactive TTL
    p95 exceeds the target), and ``virtual_clock`` (True or a
    ``VirtualClock``) makes every latency in the summary deterministic.

    ``sampling`` (a ``SAMPLING_KINDS`` name) arms the engine's on-device
    sampler — token selection happens on device with per-request PRNG
    streams (``serving/sampling.py``; ``temperature``/``top_k``/``top_p``
    parameterize it, ``seed`` keys the streams) — and ``decode_window``
    > 1 runs that many decode steps per device dispatch
    (``build_serve_multistep``), syncing one [batch, N] token block per
    window; streams are bit-identical to ``decode_window=1`` and the
    summary reports ``syncs_per_token``.
    """
    cfg = get_config(arch)
    if reduced:
        cfg = cfg.reduced()
    if hx is None:
        # the paper's split of the mesh (KVP over every non-'model' axis);
        # on the default 1x1 mesh that is KVP=1 over a size-1 'data' axis
        hx = (HelixConfig(kvp_axes=("data",), tpa_axis=None) if mesh is None
              else default_helix_config(cfg, mesh))
    overrides = {k: v for k, v in [("attn_backend", attn_backend),
                                   ("prefill_backend", prefill_backend),
                                   ("ssd_backend", ssd_backend),
                                   ("matmul_backend", matmul_backend),
                                   ("fuse_append", fuse_append),
                                   ("prune_blocks", prune_blocks),
                                   ("lm_head_w8", lm_head_w8),
                                   ("paged_kv", paged_kv),
                                   ("grouped_decode", grouped_decode)]
                 if v is not None}
    if overrides:
        hx = dataclasses.replace(hx, **overrides)
    kvp = hx.kvp(mesh) if mesh else 1

    if mesh is None:
        # single-device: 1x1 trivial mesh keeps one code path
        mesh = make_mesh((1, 1), ("data", "model"))
    params = init_serving_params(cfg, seed, mesh, hx)
    sp = None
    if sampling is not None:
        sp = SamplingParams(kind=sampling, temperature=temperature,
                            top_k=top_k, top_p=top_p, seed=seed)
    serve_step = build_serve_step(cfg, mesh, hx)
    multistep = (build_serve_multistep(cfg, mesh, hx, window=decode_window)
                 if decode_window > 1 else None)
    prefill_step = make_prefill_step(cfg, mesh, hx)
    chunked = chunk_tokens > 0 and chunked_prefill_supported(cfg)
    chunk_step = (make_chunk_prefill_step(
        cfg, mesh, hx, return_last_logits=sp is not None)
        if chunked else None)
    if chunk_tokens > 0 and not chunked:
        log(f"[serve] {cfg.name}: chunked prefill unsupported for this "
            "family; falling back to one-shot prefill")

    if isinstance(fault_plan, str):
        from repro.serving.faults import FaultPlan
        fault_plan = FaultPlan.parse(fault_plan)
    if isinstance(tenants, str):
        tenants = parse_tenants(tenants)
    if trace is not None:
        rows = load_trace(trace) if isinstance(trace, str) else list(trace)
    else:
        rows = generate_trace(n_requests, arrival=traffic, rate=arrival_rate,
                              burst=burst,
                              tenants=tuple(tenants) if tenants
                              else (TenantSpec("default"),),
                              prompt_len=prompt_len, max_tokens=max_new,
                              seed=seed)
    rows = sorted(rows, key=lambda r: (r.arrival_step, r.rid))
    p_max = max((r.prompt_len for r in rows), default=prompt_len)
    m_max = max((r.max_tokens for r in rows), default=max_new)
    max_seq = p_max + m_max + 1
    # a multi-turn workload without history reuse still grows context per
    # turn (each later turn adds ``prompt_len`` fresh tokens + its reply);
    # max_seq must cover the final turn's full conversation
    turn_seq = (p_max + m_max) + (turns - 1) * (prompt_len + m_max) + 1
    if virtual_clock is True:
        virtual_clock = VirtualClock()
    engine = DecodeEngine(cfg, params, serve_step, prefill_step,
                          max_batch=max_batch,
                          max_seq=max(max_seq, turn_seq), kvp=kvp,
                          hx=hx, chunk_tokens=chunk_tokens if chunked else None,
                          chunk_prefill_step=chunk_step,
                          tp_width=mesh.shape["model"],
                          sched_policy=sched_policy,
                          pool_blocks=pool_blocks,
                          prefix_share=prefix_share,
                          host_pages=host_pages, session_kv=session_kv,
                          fault_plan=fault_plan,
                          tenants=({t.name: t.tenant_config()
                                    for t in tenants} if tenants else None),
                          slo_ttl_s=(slo_ttl_ms / 1e3) if slo_ttl_ms else None,
                          clock=virtual_clock or time.monotonic,
                          sampling=sp, decode_window=decode_window,
                          serve_multistep=multistep, mesh=mesh)
    log(f"[serve] backends: {engine.describe_backends()}")
    rng = np.random.default_rng(seed)
    shared = rng.integers(0, cfg.vocab, shared_prefix_len).tolist()
    pending = requests_from_trace(rows, cfg.vocab, shared_prefix=shared)
    if turns > 1:
        for r in pending:
            if r.session_id is None:
                r.session_id = f"s{r.rid}"
    arrivals = [r.arrival_step for r in rows]
    turn_of = {r.rid: 1 for r in pending}
    next_rid = max((r.rid for r in pending), default=-1) + 1
    finished: list[Request] = []
    t0 = time.time()
    steps = 0
    while pending or engine.pending():
        while pending and arrivals[0] <= steps:
            engine.submit(pending.pop(0))
            arrivals.pop(0)
        for r in engine.step():
            finished.append(r)
            t = turn_of[r.rid]
            if (turns > 1 and t < turns and r.session_id is not None
                    and r.finish_reason in ("eos", "max_tokens")):
                # next turn: full conversation so far + fresh "user" text;
                # with session_kv the engine restores the history pages
                # and only the fresh tokens ever prefill
                nxt = Request(
                    rid=next_rid,
                    prompt=(list(r.prompt) + list(r.out_tokens)
                            + rng.integers(0, cfg.vocab, prompt_len).tolist()),
                    max_new_tokens=max_new, session_id=r.session_id,
                    tenant=r.tenant, slo_class=r.slo_class)
                turn_of[next_rid] = t + 1
                next_rid += 1
                engine.submit(nxt)
        steps += 1
    dt = time.time() - t0
    toks = sum(len(r.out_tokens) for r in finished)
    summary = engine.metrics.summary()
    summary.update(engine.pool_stats())
    summary.update(engine.tier_stats())
    summary.update(engine.sync_stats())
    summary["trace_id"] = trace_id(rows)
    late = [engine.metrics.requests[r.rid].ttft for r in finished
            if turn_of.get(r.rid, 1) >= 2
            and engine.metrics.requests[r.rid].ttft is not None]
    summary["turn2_ttft_s"] = float(np.mean(late)) if late else 0.0
    log(f"[serve] {len(finished)} requests, {toks} tokens in {dt:.2f}s "
        f"({toks / max(dt, 1e-9):.1f} tok/s, {steps} engine steps)")
    return finished, summary


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch")
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--max-new", type=int, default=16)
    ap.add_argument("--max-batch", type=int, default=4)
    ap.add_argument("--chunk-tokens", type=int, default=0,
                    help="prefill prompts in this many tokens per engine "
                         "step, interleaved with decode (0 = one-shot "
                         "prefill; bit-exact either way)")
    ap.add_argument("--sched-policy", default="fcfs", choices=POLICIES,
                    help="admission order: fcfs (arrival) or sjf (shortest "
                         "remaining prefill first)")
    ap.add_argument("--traffic", default="batch",
                    choices=("batch", "poisson", "bursty"),
                    help="batch: submit all requests up front; poisson: "
                         "synthetic arrival process over engine steps; "
                         "bursty: closed flash-crowd bursts with poisson "
                         "gaps (serving/workload.py)")
    ap.add_argument("--arrival-rate", type=float, default=0.5,
                    help="poisson/bursty traffic: mean requests per engine "
                         "step")
    ap.add_argument("--burst", type=int, default=4,
                    help="bursty traffic: simultaneous arrivals per burst")
    ap.add_argument("--trace", default=None,
                    help="replay a saved serving/workload.py JSONL trace "
                         "instead of generating one from --traffic (the "
                         "summary's trace_id names the workload either way)")
    ap.add_argument("--tenants", default=None,
                    help="tenant mix 'name[:weight[:slo[:share]]],...' "
                         "(e.g. 'chat:3:interactive,jobs:1:batch'); arms "
                         "deficit-weighted-fair admission across tenants")
    ap.add_argument("--slo-ttl-ms", type=float, default=0.0,
                    help="interactive TTL p95 target in ms; > 0 arms the "
                         "TTL governor, which sheds batch-class slots "
                         "through the host-tier spill path when the target "
                         "is exceeded (serving/governor.py)")
    ap.add_argument("--virtual-clock", action="store_true",
                    help="use the deterministic cost-model metrics clock "
                         "(VirtualClock) so replaying the same trace "
                         "reproduces the latency summary bit-for-bit")
    ap.add_argument("--metrics", action="store_true",
                    help="print the TTFT/TTL/queue-wait summary JSON")
    ap.add_argument("--attn-backend", default=None, choices=BACKENDS,
                    help="flash_decode backend for decode attention "
                         "(default: the platform's — compiled 'pallas' on a "
                         "TPU, 'ref' elsewhere; 'pallas' needs a TPU)")
    ap.add_argument("--prefill-backend", default=None, choices=BACKENDS,
                    help="flash_prefill backend for prompt prefill")
    ap.add_argument("--ssd-backend", default=None, choices=BACKENDS,
                    help="ssd_prefill backend for the Mamba2 SSD scan core")
    ap.add_argument("--matmul-backend", default=None, choices=BACKENDS,
                    help="w8a16_matmul backend for the quantized lm_head "
                         "matmul (only used with --lm-head-w8)")
    ap.add_argument("--lm-head-w8", action="store_true",
                    help="int8-quantize the lm_head weights and route the "
                         "logits matmul through the w8a16_matmul family")
    ap.add_argument("--no-fuse-append", action="store_true",
                    help="disable the fused KV-append kernel epilogue "
                         "(pallas backends append via a separate cache pass)")
    ap.add_argument("--no-prune-blocks", action="store_true",
                    help="disable length/causality-aware K/V block pruning "
                         "in the Pallas attention kernels (dense masked "
                         "sweep; bit-exact either way)")
    ap.add_argument("--paged-kv", action="store_true",
                    help="shared-pool paged KV cache: K/V in pool pages "
                         "with per-request block tables; cache pressure "
                         "becomes a global free-page admission signal "
                         "(bit-exact vs the fixed per-slot layout)")
    ap.add_argument("--pool-blocks", type=int, default=None,
                    help="paged mode: total pool pages incl. the sink page "
                         "(default: the same HBM the fixed layout reserves)")
    ap.add_argument("--prefix-share", action="store_true",
                    help="prefix index + refcounted copy-on-write page "
                         "sharing: prompts matching a cached prefix map the "
                         "shared pages and prefill only their suffix (needs "
                         "--paged-kv and --chunk-tokens; bit-exact)")
    ap.add_argument("--grouped-decode", action="store_true",
                    help="grouped shared-prefix decode: requests whose "
                         "tables share leading pages read them once per "
                         "group per step instead of once per request "
                         "(needs --paged-kv; bit-exact)")
    ap.add_argument("--shared-prefix-len", type=int, default=0,
                    help="synthetic workload: every prompt starts with the "
                         "same this-many tokens (exercises --prefix-share)")
    ap.add_argument("--host-pages", type=int, default=0,
                    help="host KV tier capacity in pool pages: preempted "
                         "requests spill their live pages and resume with "
                         "zero re-prefill chunks (needs --paged-kv; 0 = no "
                         "spill tier)")
    ap.add_argument("--session-kv", action="store_true",
                    help="persist retired requests' KV pages in the host "
                         "tier keyed by session id, so the next turn of a "
                         "multi-turn conversation restores its history "
                         "instead of re-prefilling it (needs --paged-kv)")
    ap.add_argument("--fault-plan", default=None,
                    help="inject host-tier faults, 'k=v,...' over seed/"
                         "restore_fail/corrupt/store_full/delay/delay_steps "
                         "(e.g. 'seed=1,restore_fail=0.5,delay=0.2'); every "
                         "injected fault degrades to re-prefill, never to "
                         "divergent tokens")
    ap.add_argument("--turns", type=int, default=1,
                    help="multi-turn workload: each request is a session "
                         "whose turn t+1 resubmits its full context plus "
                         "fresh tokens (pairs with --session-kv; the "
                         "summary's turn2_ttft_s isolates the benefit)")
    ap.add_argument("--decode-window", type=int, default=1,
                    help="decode steps per device dispatch: the lax.scan "
                         "multi-step path syncs ONE [batch, N] token block "
                         "per window instead of one transfer per token "
                         "(streams bit-identical to N=1; "
                         "scripts/decode_window_smoke.py)")
    ap.add_argument("--sampling", default=None, choices=SAMPLING_KINDS,
                    help="on-device token sampling kind (default: host-free "
                         "greedy argmax on device, same as 'greedy'); "
                         "temperature/top_k/top_p read the flags below; "
                         "per-request PRNG streams are keyed by --seed + "
                         "request id (serving/sampling.py)")
    ap.add_argument("--temperature", type=float, default=1.0,
                    help="softmax temperature for --sampling temperature/"
                         "top_k/top_p (> 0; <= 0 would mean greedy)")
    ap.add_argument("--top-k", type=int, default=0,
                    help="keep the k highest logits before sampling "
                         "(--sampling top_k; 0 = no truncation)")
    ap.add_argument("--top-p", type=float, default=1.0,
                    help="nucleus sampling mass for --sampling top_p "
                         "(in (0, 1]; 1.0 = no truncation)")
    ap.add_argument("--seed", type=int, default=0,
                    help="base PRNG seed: model init and the per-request "
                         "sampling streams (request rid folds in, so "
                         "streams are independent and replayable)")
    ap.add_argument("--list-backends", action="store_true",
                    help="print the kernel registry's per-family backend "
                         "availability matrix and exit")
    args = ap.parse_args()
    if args.list_backends:
        print(backend_table())
        return
    if not args.arch:
        ap.error("--arch is required (or use --list-backends)")
    enable_compile_cache()
    _, summary = serve_demo(
        args.arch, reduced=args.reduced, n_requests=args.requests,
        prompt_len=args.prompt_len, max_new=args.max_new,
        max_batch=args.max_batch, attn_backend=args.attn_backend,
        prefill_backend=args.prefill_backend,
        ssd_backend=args.ssd_backend,
        matmul_backend=args.matmul_backend,
        fuse_append=False if args.no_fuse_append else None,
        prune_blocks=False if args.no_prune_blocks else None,
        lm_head_w8=True if args.lm_head_w8 else None,
        paged_kv=True if args.paged_kv else None,
        pool_blocks=args.pool_blocks,
        prefix_share=args.prefix_share,
        grouped_decode=True if args.grouped_decode else None,
        shared_prefix_len=args.shared_prefix_len,
        host_pages=args.host_pages, session_kv=args.session_kv,
        fault_plan=args.fault_plan, turns=args.turns,
        chunk_tokens=args.chunk_tokens, sched_policy=args.sched_policy,
        traffic=args.traffic, arrival_rate=args.arrival_rate,
        burst=args.burst, trace=args.trace, tenants=args.tenants,
        slo_ttl_ms=args.slo_ttl_ms, virtual_clock=args.virtual_clock,
        decode_window=args.decode_window, sampling=args.sampling,
        temperature=args.temperature, top_k=args.top_k, top_p=args.top_p,
        seed=args.seed)
    if args.metrics:
        print(json.dumps(summary, indent=2, default=float))


if __name__ == "__main__":
    main()
