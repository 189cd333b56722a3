"""Shared-pool paged KV cache: engine/scheduler-level behavior.

Covers the PR's acceptance criteria that live above the kernels:

  * paged vs fixed-cap token streams are bit-identical through the real
    engine across {ref, pallas-interpret} x {fp, kv8} x {one-shot,
    chunked} (the kernel-level prune/window lattice lives in
    tests/kernels/test_flash_decode_paged.py, the serve_step windowed
    lattice below);
  * a mixed short/long workload the fixed per-slot cap REJECTS is admitted
    and completed under the global pool (the whole point of paging);
  * both admission paths (scheduled submit() and legacy add_request())
    share one capacity oracle — the oversized-prompt rejection regression;
  * pool-pressure queueing: a request that fits the pool but not *now*
    waits instead of being rejected, and runs after pages free;
  * paged preemption resumes with identical tokens (pages released
    copy-free, re-prefill on resume).
"""
import dataclasses

import numpy as np
import pytest
import jax
import jax.numpy as jnp

from repro.configs import get_config
from repro.core.kvcache import cache_capacity, page_positions, state_to_paged
from repro.core.sharding import HelixConfig
from repro.models.model_zoo import (build_serve_step, make_chunk_prefill_step,
                                    make_prefill_step)
from repro.models.transformer import init_params
from repro.serving import DecodeEngine, Request
from repro.utils import make_mesh, set_mesh

CFG = get_config("granite-3-2b").reduced()
PARAMS = init_params(CFG, jax.random.PRNGKey(0))
MESH = make_mesh((1, 1), ("data", "model"))


def _hx(backend="ref", paged=False, kv8=False):
    return HelixConfig(kvp_axes=(), tpa_axis=None, attn_block_s=16,
                       attn_backend=backend, prefill_backend=backend,
                       paged_kv=paged, kv_cache_bits=8 if kv8 else 16)


def _engine(hx, *, max_batch=3, max_seq=48, chunk=0, pool_blocks=None,
            policy="fcfs"):
    with set_mesh(MESH):
        serve = build_serve_step(CFG, MESH, hx)
        prefill = make_prefill_step(CFG, MESH, hx)
        cs = make_chunk_prefill_step(CFG, MESH, hx) if chunk else None
        return DecodeEngine(CFG, PARAMS, serve, prefill, max_batch=max_batch,
                            max_seq=max_seq, hx=hx, chunk_tokens=chunk or None,
                            chunk_prefill_step=cs, tp_width=1,
                            sched_policy=policy, pool_blocks=pool_blocks)


def _prompts(lengths, seed=7):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, CFG.vocab, n).tolist() for n in lengths]


def _run(hx, *, chunk=0, lengths=(8, 11, 14, 17), max_new=5, **kw):
    eng = _engine(hx, chunk=chunk, **kw)
    reqs = [Request(rid=i, prompt=p, max_new_tokens=max_new)
            for i, p in enumerate(_prompts(lengths))]
    with set_mesh(MESH):
        for r in reqs:
            eng.submit(r)
        eng.run_to_completion()
    assert all(r.done for r in reqs)
    return [tuple(r.out_tokens) for r in reqs], eng


# ------------------------------------------------------- bit-exact lattice
@pytest.mark.parametrize("backend", ["ref", "pallas-interpret"])
@pytest.mark.parametrize("kv8", [False, True])
@pytest.mark.parametrize("chunk", [0, 5])
def test_paged_engine_stream_parity(backend, kv8, chunk):
    fixed, _ = _run(_hx(backend, paged=False, kv8=kv8), chunk=chunk)
    paged, eng = _run(_hx(backend, paged=True, kv8=kv8), chunk=chunk)
    assert fixed == paged
    stats = eng.pool_stats()
    assert stats["paged_kv"] and 0 < stats["pool_occupancy_peak"] <= 1
    assert eng.pool.free_count == eng.pool.capacity   # fully drained


@pytest.mark.parametrize("prune", [True, False])
def test_paged_serve_step_windowed_lattice(prune):
    """serve_step-level paged == fixed for a sliding-window arch (gemma3
    local:global) — the windowed half of the acceptance lattice, with
    pruning toggled, on the kernel backend."""
    cfg = get_config("gemma3-12b").reduced()
    params = init_params(cfg, jax.random.PRNGKey(1))
    hx_f = dataclasses.replace(_hx("pallas-interpret"), prune_blocks=prune)
    hx_p = dataclasses.replace(hx_f, paged_kv=True)
    B, T = 2, 12
    kvp, rr = 1, hx_f.rr_block
    cap = cache_capacity(32, kvp, rr)
    bs = page_positions(kvp, rr)
    mp = cap // bs
    with set_mesh(MESH):
        prefill = jax.jit(make_prefill_step(cfg, MESH, hx_f, s_cap=cap))
        toks = jax.random.randint(jax.random.PRNGKey(2), (B, T), 0, cfg.vocab)
        last_logits, st = prefill(params, {"tokens": toks})
        st = dict(st)
        st["total_len"] = jnp.full((B,), T, jnp.int32)
        n_pool = 1 + B * mp
        tables = np.zeros((B, n_pool), np.int32)
        nxt = 1
        for b in range(B):
            for p in range(mp):
                tables[b, p] = nxt
                nxt += 1
        stp = state_to_paged(st, tables, n_pool, kvp, bs)
        serve_f = jax.jit(build_serve_step(cfg, MESH, hx_f))
        serve_p = jax.jit(build_serve_step(cfg, MESH, hx_p))
        cur = jnp.argmax(last_logits[:, :cfg.vocab], axis=-1).astype(jnp.int32)
        cf = cp = cur
        sf, sp = dict(st), dict(stp)
        for _ in range(4):
            cf, sf = serve_f(params, sf, cf)
            cp, sp = serve_p(params, sp, cp)
            np.testing.assert_array_equal(np.asarray(cf), np.asarray(cp))


# ------------------------------------------------- global-pool admission
def test_pool_admits_what_per_slot_cap_rejects():
    """Mixed short/long workload: the long prompt exceeds the per-slot cap
    (fixed layout rejects it up front) but fits the global pool because the
    short requests leave pages free — it is admitted AND completes."""
    lengths = (60, 8, 8)        # per-slot cap for max_seq=24: 32 slots
    hx_f = _hx("ref", paged=False)
    eng_f = _engine(hx_f, max_batch=3, max_seq=24)
    reqs_f = [Request(rid=i, prompt=p, max_new_tokens=3)
              for i, p in enumerate(_prompts(lengths))]
    with set_mesh(MESH):
        for r in reqs_f:
            eng_f.submit(r)
        eng_f.run_to_completion()
    assert reqs_f[0].finish_reason == "rejected"       # fixed cap: never fits
    assert all(r.finish_reason == "max_tokens" for r in reqs_f[1:])

    hx_p = _hx("ref", paged=True)
    # same total HBM as the fixed engine (3 slots x 32 slots = 6 pages + sink)
    eng_p = _engine(hx_p, max_batch=3, max_seq=24)
    assert eng_p.pool.capacity * eng_p.block_s >= 64
    reqs_p = [Request(rid=i, prompt=p, max_new_tokens=3)
              for i, p in enumerate(_prompts(lengths))]
    with set_mesh(MESH):
        for r in reqs_p:
            eng_p.submit(r)
        eng_p.run_to_completion()
    assert reqs_p[0].finish_reason == "max_tokens"     # pool: admitted + done
    assert len(reqs_p[0].out_tokens) == 3


def test_oversized_prompt_rejected_on_both_admission_paths():
    """Regression (capacity-oracle unification): a prompt that can never
    fit is rejected with finish_reason='rejected' by BOTH submit() and the
    legacy add_request() — fixed and paged engines alike."""
    for paged in (False, True):
        hx = _hx("ref", paged=paged)
        too_big = _prompts((500,))[0]
        # scheduled path
        eng = _engine(hx, max_batch=2, max_seq=24)
        r1 = Request(rid=0, prompt=list(too_big), max_new_tokens=2)
        with set_mesh(MESH):
            eng.submit(r1)
            eng.step()
        assert r1.done and r1.finish_reason == "rejected", paged
        # legacy direct path
        eng2 = _engine(hx, max_batch=2, max_seq=24)
        r2 = Request(rid=1, prompt=list(too_big), max_new_tokens=2)
        with set_mesh(MESH):
            assert eng2.add_request(r2)     # accepted-but-retired contract
            out = eng2.step()
        assert r2 in out and r2.finish_reason == "rejected", paged
        if paged:
            assert eng.pool.free_count == eng.pool.capacity


def test_max_pages_caps_one_request():
    """``max_pages`` bounds a single request's table width even when the
    pool itself is larger: a prompt needing more pages is rejected."""
    hx = _hx("ref", paged=True)
    with set_mesh(MESH):
        serve = build_serve_step(CFG, MESH, hx)
        prefill = make_prefill_step(CFG, MESH, hx)
        eng = DecodeEngine(CFG, PARAMS, serve, prefill, max_batch=2,
                           max_seq=48, hx=hx, tp_width=1, pool_blocks=9,
                           max_pages=2)
        assert eng.max_pages == 2
        big = Request(rid=0, prompt=_prompts((40,))[0], max_new_tokens=2)
        small = Request(rid=1, prompt=_prompts((20,), seed=8)[0],
                        max_new_tokens=2)
        eng.submit(big)                     # pages_for(41) = 3 > max_pages
        eng.submit(small)                   # pages_for(21) = 2 fits
        eng.run_to_completion()
    assert big.finish_reason == "rejected"
    assert small.finish_reason == "max_tokens"


def test_pool_pressure_queues_instead_of_rejecting():
    """A request that fits the pool but not *right now* stays queued and
    runs once a retiring request frees its pages (global admission gate)."""
    hx = _hx("ref", paged=True)
    # tiny pool: 4 allocatable pages of 16 positions
    eng = _engine(hx, max_batch=2, max_seq=24, pool_blocks=5)
    a = Request(rid=0, prompt=_prompts((30,))[0], max_new_tokens=6)  # 2 pages
    b = Request(rid=1, prompt=_prompts((40,), seed=9)[0],
                max_new_tokens=2)                                    # 3 pages
    with set_mesh(MESH):
        eng.submit(a)
        eng.step()
        assert a.state == "decode"
        eng.submit(b)
        eng.step()
        # 2 of 4 pages busy -> b's 3 pages don't fit yet: queued, not rejected
        assert not b.done and b.state == "queued"
        eng.run_to_completion()
    assert a.finish_reason == "max_tokens"
    assert b.finish_reason == "max_tokens"
    assert eng.pool.free_count == eng.pool.capacity


def test_paged_preempt_resume_identical_tokens():
    """Preemption under the pool releases pages copy-free; the resumed
    request re-prefills and produces exactly the uninterrupted stream."""
    prompts = _prompts((11, 8), seed=3)

    def run(preempt):
        hx = _hx("ref", paged=True)
        eng = _engine(hx, max_batch=1, max_seq=48, chunk=4)
        a = Request(rid=0, prompt=list(prompts[0]), max_new_tokens=6)
        b = Request(rid=1, prompt=list(prompts[1]), max_new_tokens=3)
        with set_mesh(MESH):
            eng.submit(a)
            if preempt:
                while not (a.state == "decode" and len(a.out_tokens) >= 2):
                    eng.step()
                free_before = eng.pool.free_count
                assert eng.preempt(0)
                assert eng.pool.free_count > free_before   # pages returned
            eng.submit(b)
            eng.run_to_completion()
        return tuple(a.out_tokens), tuple(b.out_tokens)

    plain = run(False)
    resumed = run(True)
    assert plain == resumed


# ---------------------------------------------- growth ahead of the readback
def _phases_of_table_writes(eng):
    """Record, for each block-table write, the decode phase it ran in."""
    seen, phase = [], ["other"]

    def wrap(name):
        fn = getattr(eng, name)

        def run(*a, **kw):
            phase[0] = name
            try:
                return fn(*a, **kw)
            finally:
                phase[0] = "other"
        setattr(eng, name, run)

    wrap("_grow_ahead")
    wrap("_replay_step")
    mirror = eng._mirror_table

    def record(slot):
        seen.append(phase[0])
        return mirror(slot)
    eng._mirror_table = record
    return seen


@pytest.mark.parametrize("chunk", [0, 5])
def test_decode_growth_is_written_while_the_step_runs(chunk):
    """A paged decode step reserves the page of each row's next token and
    writes its table row right after dispatch, before the readback, so no
    table write lies between two steps; the streams equal the fixed
    layout's and the pool drains."""
    # 16-position pages: positions up to L + 38 need 3, 2 and 3 pages more
    # than the L + 1 that admission grants
    lengths, max_new = (14, 9, 30), 40
    fixed, _ = _run(_hx("ref"), chunk=chunk, lengths=lengths,
                    max_new=max_new, max_seq=80)
    eng = _engine(_hx("ref", paged=True), chunk=chunk, max_seq=80)
    writes = _phases_of_table_writes(eng)
    reqs = [Request(rid=i, prompt=p, max_new_tokens=max_new)
            for i, p in enumerate(_prompts(lengths))]
    with set_mesh(MESH):
        for r in reqs:
            eng.submit(r)
        eng.run_to_completion()
    assert [tuple(r.out_tokens) for r in reqs] == fixed
    assert writes.count("_grow_ahead") == 8
    assert "_replay_step" not in writes
    assert eng.pool.free_count == eng.pool.capacity


def test_eos_row_frees_the_page_grown_ahead():
    """A row that stops on EOS at the step whose next token would open a
    page reserved that page while the step ran, and gives it back with
    its others."""
    hx = _hx("ref", paged=True)
    prompt = _prompts((13,))[0]          # the prompt _run serves
    stream, eng = _run(hx, lengths=(13,), max_new=12, max_seq=48)
    stream = list(stream[0])
    # decode step j writes position 13 + j - 1 and emits out token j; the
    # step emitting token k = 16 - 13 writes position 15, the last of the
    # first page, so it grows the second page ahead
    k = eng.block_s - len(prompt)
    assert k == 3 and stream[k] not in stream[:k]
    eng = _engine(hx, max_seq=48)
    writes = _phases_of_table_writes(eng)
    r = Request(rid=0, prompt=list(prompt), max_new_tokens=12,
                eos_id=stream[k])
    with set_mesh(MESH):
        eng.submit(r)
        eng.run_to_completion()
    assert r.finish_reason == "eos" and r.out_tokens == stream[:k + 1]
    assert writes.count("_grow_ahead") == 1
    assert eng.pool.peak_in_use == 2
    assert eng.pool.free_count == eng.pool.capacity
