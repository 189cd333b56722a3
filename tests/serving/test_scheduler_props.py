"""Scheduler property suite: random arrival/length/eos streams through a
simulated engine loop must never double-assign a slot, lose or duplicate a
request, break token conservation, or violate the capacity invariant.

Hypothesis-driven when available (repro.testing.optional_hypothesis —
skips, never collection-errors, without it); the deterministic siblings
at the bottom always run."""
import math

from repro.serving.scheduler import (DECODE, DONE, PREFILL, QUEUED,
                                     Request, Scheduler)
from repro.testing import optional_hypothesis

given, settings, st = optional_hypothesis()


# ---------------------------------------------------------------- simulator
def predicted_tokens(prompt_len, max_new, eos_at, cap):
    """Tokens an uninterrupted request emits under engine retirement rules:
    first eos (index in its stream), then max_new, then capacity
    (cap - prompt_len tokens fit before the slot fills)."""
    n = min(max_new, cap - prompt_len)
    if eos_at is not None:
        n = min(n, eos_at + 1)
    return max(n, 0)


def simulate(specs, *, max_batch, cap, policy, chunk, preempt_at=(),
             max_steps=10_000):
    """Drive a ``Scheduler`` exactly the way ``DecodeEngine.step`` does —
    admission, one chunk of prefill progress for one same-progress group,
    one decode token per decoding slot — with token emission replaced by
    counters.  ``specs`` = [(arrival_step, prompt_len, max_new, eos_at)].
    ``preempt_at`` = {(rid, token_count)} -> preempt rid when it has that
    many tokens.  Returns (requests, scheduler, steps_run)."""
    sched = Scheduler(max_batch=max_batch, cap=cap, policy=policy)
    reqs = [Request(rid=i, prompt=list(range(p)), max_new_tokens=m,
                    eos_id=None) for i, (_, p, m, _) in enumerate(specs)]
    eos_at = {i: e for i, (_, _, _, e) in enumerate(specs)}
    arrivals = sorted(range(len(specs)), key=lambda i: specs[i][0])
    slot_req: dict[int, Request] = {}
    prefill_left: dict[int, int] = {}       # slot -> chunks remaining
    preempts = set(preempt_at)

    def emit(req, slot):
        """One generated token for req: append, retire per engine rules."""
        req.out_tokens.append(0)
        n = len(req.out_tokens)
        if eos_at[req.rid] is not None and n == eos_at[req.rid] + 1:
            reason = "eos"
        elif n >= req.max_new_tokens:
            reason = "max_tokens"
        elif sched.at_capacity(slot):
            reason = "capacity"
        else:
            return
        req.done, req.state, req.finish_reason = True, DONE, reason
        sched.release(slot)
        del slot_req[slot]

    step = 0
    while step < max_steps:
        # 1) arrivals
        while arrivals and specs[arrivals[0]][0] <= step:
            sched.submit(reqs[arrivals.pop(0)])
        # 2) admission
        for req, slot in sched.admit():
            slot_req[slot] = req
            n_toks = len(req.resume_tokens())
            prefill_left[slot] = max(math.ceil(n_toks / chunk), 1)
        # 3) one prefill chunk for the first prefilling group
        pre = sorted(s for s, r in slot_req.items() if r.state == PREFILL)
        if pre:
            lead = prefill_left[pre[0]]
            group = [s for s in pre if prefill_left[s] == lead]
            for s in group:
                prefill_left[s] -= 1
                if prefill_left[s] == 0:
                    slot_req[s].state = DECODE
                    emit(slot_req[s], s)        # first (prefill) token
        # 4) decode step
        for s in sorted(slot_req):
            if slot_req[s].state == DECODE:
                sched.on_token(s)
                emit(slot_req[s], s)
        # 5) injected preemptions
        for s, r in list(slot_req.items()):
            if (r.rid, len(r.out_tokens)) in preempts:
                preempts.discard((r.rid, len(r.out_tokens)))
                del slot_req[s]
                prefill_left.pop(s, None)
                sched.preempt(s, r)
        sched.check_invariants()
        _assert_partition(reqs, sched, slot_req)
        step += 1
        if not sched.queue and not slot_req and not arrivals:
            break
    return reqs, sched, step


def _assert_partition(reqs, sched, slot_req):
    """No lost or duplicated requests: queued / placed / done / rejected
    partition the submitted set."""
    queued = {r.rid for r in sched.queue}
    placed = {r.rid for r in slot_req.values()}
    done = {r.rid for r in reqs if r.done}
    assert not queued & placed and not queued & done and not placed & done
    # every bucketed rid is a real request (nothing invented or duplicated)
    all_rids = {r.rid for r in reqs}
    assert (queued | placed | done) <= all_rids
    # a request not in any bucket must simply not have arrived yet
    for r in reqs:
        if r.rid not in queued | placed | done:
            assert r.state == QUEUED and not r.out_tokens or r.rid in queued


# ------------------------------------------------------------- properties
SPEC = st.tuples(st.integers(0, 20),          # arrival step
                 st.integers(1, 30),          # prompt len
                 st.integers(1, 10),          # max_new
                 st.one_of(st.none(), st.integers(0, 9)))   # eos index


@given(st.lists(SPEC, min_size=1, max_size=20),
       st.sampled_from(["fcfs", "sjf"]),
       st.integers(1, 4),                     # max_batch
       st.integers(1, 8))                     # chunk
@settings(max_examples=60, deadline=None)
def test_random_streams_conserve_requests_and_tokens(specs, policy,
                                                     max_batch, chunk):
    cap = 32
    reqs, sched, steps = simulate(list(specs), max_batch=max_batch, cap=cap,
                                  policy=policy, chunk=chunk)
    # everything drained
    assert not sched.queue and all(r is None for r in sched.slot_rids)
    assert all(r.done for r in reqs)
    # conservation: emitted tokens match the retirement rules exactly
    for i, (_, p, m, e) in enumerate(specs):
        if p + 1 > cap:
            assert reqs[i].finish_reason == "rejected"
            assert reqs[i].out_tokens == []
        else:
            assert len(reqs[i].out_tokens) == predicted_tokens(p, m, e, cap)


@given(st.lists(SPEC, min_size=1, max_size=12),
       st.lists(st.tuples(st.integers(0, 11), st.integers(1, 5)),
                max_size=4),
       st.integers(1, 3))
@settings(max_examples=40, deadline=None)
def test_preemptions_never_lose_requests_or_tokens(specs, preempts,
                                                   max_batch):
    cap = 64                                  # roomy: resumes always fit
    specs = [(a, min(p, 20), m, e) for a, p, m, e in specs]
    reqs, sched, _ = simulate(specs, max_batch=max_batch, cap=cap,
                              policy="fcfs", chunk=4,
                              preempt_at=set(preempts))
    assert all(r.done for r in reqs)
    for i, (_, p, m, e) in enumerate(specs):
        assert len(reqs[i].out_tokens) == predicted_tokens(p, m, e, cap)


# ------------------------------------------------------- deterministic twins
def test_fcfs_order_and_slot_accounting():
    specs = [(0, 8, 4, None), (0, 6, 2, None), (1, 5, 3, 1), (3, 40, 2, None)]
    reqs, sched, _ = simulate(specs, max_batch=2, cap=32, policy="fcfs",
                              chunk=4)
    assert [len(r.out_tokens) for r in reqs] == [4, 2, 2, 0]
    assert reqs[2].finish_reason == "eos"
    assert reqs[3].finish_reason == "rejected"


def test_sjf_prefers_short_prefills():
    """With one slot busy and two queued, sjf admits the shorter prompt
    first even though it arrived later."""
    sched = Scheduler(max_batch=1, cap=64, policy="sjf")
    long_r = Request(rid=0, prompt=list(range(30)))
    short_r = Request(rid=1, prompt=list(range(5)))
    sched.submit(long_r)
    sched.submit(short_r)
    placed = sched.admit()
    assert [r.rid for r, _ in placed] == [1]


def test_capacity_invariant_holds_under_pressure():
    specs = [(0, 30, 10, None)] * 3           # each nearly fills cap=32
    reqs, sched, _ = simulate(specs, max_batch=2, cap=32, policy="fcfs",
                              chunk=8)
    assert all(r.finish_reason == "capacity" for r in reqs)
    assert all(len(r.out_tokens) == 2 for r in reqs)


def test_sjf_resumes_preempted_before_shorter_arrivals():
    """A preempted request resumes before fresh shorter prompts under sjf
    too — its spent prefill/decode work must not be stranded."""
    sched = Scheduler(max_batch=1, cap=64, policy="sjf")
    big = Request(rid=0, prompt=list(range(30)))
    sched.submit(big)
    [(_, slot)] = sched.admit()
    big.out_tokens.extend([7, 7])              # mid-decode
    sched.preempt(slot, big)
    sched.submit(Request(rid=1, prompt=list(range(3))))
    [(resumed, _)] = sched.admit()
    assert resumed is big and not big.preempted


def test_preempt_requeues_at_front():
    sched = Scheduler(max_batch=1, cap=32, policy="fcfs")
    a = Request(rid=0, prompt=[1, 2, 3])
    b = Request(rid=1, prompt=[4, 5])
    sched.submit(a)
    [(got, slot)] = sched.admit()
    assert got is a
    sched.submit(b)
    sched.preempt(slot, a)
    assert [r.rid for r in sched.queue] == [0, 1]
    [(resumed, _)] = sched.admit()
    assert resumed is a


def test_grow_ahead_reserves_the_page_after_next_and_never_refuses():
    """``grow_ahead`` grants the page that the token after the one in
    flight needs, only at a page boundary and only from free pages; it
    returns [] where ``grow_for_next_token`` would refuse."""
    from repro.serving.pool import BlockAllocator
    pool = BlockAllocator(n_blocks=3, block_s=4)          # 2 allocatable
    s = Scheduler(max_batch=1, cap=64, pool=pool, max_pages=8)
    s.submit(Request(rid=0, prompt=[1, 2], max_new_tokens=20))
    [(req, slot)] = s.admit()
    assert len(pool.pages(0)) == 1 and s.slot_len[slot] == 2
    assert s.grow_ahead(slot) == []                       # pos 3: page 1
    s.on_token(slot)
    got = s.grow_ahead(slot)                              # pos 4: page 2
    assert len(got) == 1 and pool.free_count == 0
    s.on_token(slot)
    assert s.grow_for_next_token(slot) == []              # already there
    s.on_token(slot)
    s.on_token(slot)
    s.on_token(slot)                                      # slot_len 7
    assert s.grow_ahead(slot) == []                       # pos 8: no page
    s.on_token(slot)
    assert s.grow_for_next_token(slot) is None            # refusal is here
    assert Scheduler(max_batch=1, cap=8).grow_ahead(0) == []
