"""DWFQ tenancy property suite (serving/scheduler.py): backlogged
tenants' served-token shares converge to their weight shares, idle time
banks no catch-up credit, interactive admission is never head-of-line
blocked behind over-cap batch work, and the whole layer is deterministic.

Drives the ``Scheduler`` through an engine-shaped loop (admit -> one
served token per running slot per step -> release at max_new), with
hypothesis when available (repro.testing.optional_hypothesis); the
deterministic siblings always run."""
from repro.serving.scheduler import (SLO_BATCH, SLO_INTERACTIVE, Request,
                                     Scheduler, TenantConfig)
from repro.testing import optional_hypothesis

given, settings, st = optional_hypothesis()


# ---------------------------------------------------------------- simulator
class TenantSim:
    """Engine-shaped driver: per step, admit; then every running slot
    serves one charged token; requests retire at ``max_new`` — the same
    decision sequence ``DecodeEngine.step``/``_decode_step`` feeds the
    scheduler, with device work replaced by counters."""

    def __init__(self, tenants, *, max_batch=4, cap=4096, policy="fcfs",
                 slo_aware=None):
        self.sched = Scheduler(max_batch, cap, policy=policy,
                               tenants=tenants, slo_aware=slo_aware)
        self.live = {}                        # slot -> [request, remaining]
        self.next_rid = 0
        self.admit_order = []                 # rids in admission order

    def submit(self, tenant, slo=SLO_INTERACTIVE, max_new=4, prompt_len=2):
        req = Request(rid=self.next_rid, prompt=[1] * prompt_len,
                      max_new_tokens=max_new, tenant=tenant, slo_class=slo)
        self.next_rid += 1
        self.sched.submit(req)
        return req

    def queued(self, tenant):
        return sum(1 for r in self.sched.queue if r.tenant == tenant)

    def step(self):
        self.admit()
        self.serve()

    def admit(self):
        for req, slot in self.sched.admit():
            self.live[slot] = [req, req.max_new_tokens]
            self.admit_order.append(req.rid)

    def serve(self):
        for slot in list(self.live):
            self.sched.record_served(slot)
            self.sched.on_token(slot)
            self.live[slot][1] -= 1
            if self.live[slot][1] == 0:
                self.sched.release(slot)
                del self.live[slot]
        self.sched.check_invariants()


def run_backlogged(weights, *, steps, policy="fcfs", backlog=3, max_new=4):
    """Keep every tenant ``backlog`` deep in the queue for ``steps`` steps;
    returns (sim, served_tokens dict)."""
    tenants = {n: TenantConfig(n, weight=w) for n, w in weights.items()}
    sim = TenantSim(tenants, policy=policy)
    for _ in range(steps):
        for name in weights:
            while sim.queued(name) < backlog:
                sim.submit(name, max_new=max_new)
        sim.step()
    return sim, dict(sim.sched.served_tokens)


# ---------------------------------------------------- fairness properties
@given(wa=st.sampled_from([1.0, 2.0, 3.0, 4.0]),
       wb=st.sampled_from([1.0, 2.0, 3.0, 4.0]),
       policy=st.sampled_from(["fcfs", "sjf"]))
@settings(max_examples=25, deadline=None)
def test_backlogged_share_converges_to_weight_share(wa, wb, policy):
    """DWFQ contract: two always-backlogged tenants split served tokens
    in proportion to their weights (within one request's granularity)."""
    _, served = run_backlogged({"a": wa, "b": wb}, steps=300, policy=policy)
    total = sum(served.values())
    assert total > 0
    share = served["a"] / total
    want = wa / (wa + wb)
    assert abs(share - want) < 0.1, (served, want)


@given(weights=st.lists(st.sampled_from([1.0, 2.0, 5.0]), min_size=3,
                        max_size=3))
@settings(max_examples=15, deadline=None)
def test_three_way_share(weights):
    names = ["t0", "t1", "t2"]
    _, served = run_backlogged(dict(zip(names, weights)), steps=300)
    total = sum(served.values())
    for n, w in zip(names, weights):
        assert abs(served.get(n, 0) / total - w / sum(weights)) < 0.12, \
            (served, weights)


def test_weight_share_deterministic_twin():
    """3:1 weights -> 75/25 served split, bit-stable across twin runs."""
    sim1, served1 = run_backlogged({"a": 3.0, "b": 1.0}, steps=400)
    sim2, served2 = run_backlogged({"a": 3.0, "b": 1.0}, steps=400)
    assert served1 == served2
    assert sim1.admit_order == sim2.admit_order
    total = sum(served1.values())
    assert abs(served1["a"] / total - 0.75) < 0.05, served1


# ------------------------------------------------------------ idle credit
def test_idle_tenant_banks_no_catchup_credit():
    """A tenant idle while others are served re-enters at the service
    frontier: its normalized service is floored to the least-served
    active tenant's, and over the next window it gets its *fair* share,
    not an unbounded catch-up burst."""
    tenants = {n: TenantConfig(n, weight=1.0) for n in ("a", "b", "idle")}
    sim = TenantSim(tenants)
    for _ in range(200):                  # idle tenant absent the whole time
        for name in ("a", "b"):
            while sim.queued(name) < 3:
                sim.submit(name, max_new=4)
        sim.step()
    frontier = min(sim.sched._service[t] for t in ("a", "b"))
    sim.submit("idle", max_new=4)
    # bounded credit: floored to the least-served active tenant, not 0
    assert sim.sched._service["idle"] >= frontier
    before = dict(sim.sched.served_tokens)
    for _ in range(120):
        for name in ("a", "b", "idle"):
            while sim.queued(name) < 3:
                sim.submit(name, max_new=4)
        sim.step()
    gained = {t: sim.sched.served_tokens[t] - before.get(t, 0)
              for t in tenants}
    window = sum(gained.values())
    # equal weights -> the returning tenant's slice of the window stays
    # near 1/3 (one in-flight request of slack), never a monopoly
    assert gained["idle"] <= window / 3 + 8, gained
    assert gained["idle"] >= window / 3 - 8, gained


@given(idle_steps=st.integers(min_value=10, max_value=300))
@settings(max_examples=15, deadline=None)
def test_idle_credit_floor_is_idle_duration_independent(idle_steps):
    """However long the tenant idled, its re-entry service equals the
    active frontier — credit cannot grow with idle time."""
    tenants = {n: TenantConfig(n, weight=1.0) for n in ("a", "idle")}
    sim = TenantSim(tenants)
    for _ in range(idle_steps):
        while sim.queued("a") < 2:
            sim.submit("a", max_new=4)
        sim.step()
    sim.submit("idle")
    assert sim.sched._service["idle"] == sim.sched._service["a"]


# ------------------------------------------------- class priority / quotas
def test_interactive_never_blocked_behind_over_cap_batch():
    """batch_cap exhausted + batch work at the head of the queue: an
    interactive request behind it still admits into the free slot."""
    sim = TenantSim({"j": TenantConfig("j"), "c": TenantConfig("c")},
                    max_batch=2)
    sim.sched.batch_cap = 0
    for _ in range(3):
        sim.submit("j", slo=SLO_BATCH)
    chat = sim.submit("c", slo=SLO_INTERACTIVE)
    sim.step()
    assert chat.rid in sim.admit_order, "interactive blocked behind batch"
    assert sim.sched._running(slo_class=SLO_BATCH) == 0


def test_tenant_slot_quota_enforced_without_blocking_others():
    """max_slots=1 caps one tenant's concurrency; the other tenant fills
    the remaining slots instead of queueing behind the quota."""
    sim = TenantSim({"q": TenantConfig("q", max_slots=1),
                     "f": TenantConfig("f")}, max_batch=3)
    for _ in range(5):
        sim.submit("q", max_new=6)
    for _ in range(5):
        sim.submit("f", max_new=6)
    for _ in range(20):
        sim.step()
        assert sim.sched._running(tenant="q") <= 1
    assert sim.sched.served_tokens["f"] > sim.sched.served_tokens["q"]


@given(seed=st.integers(min_value=0, max_value=2**31 - 1),
       cap=st.integers(min_value=0, max_value=3))
@settings(max_examples=30, deadline=None)
def test_no_free_slot_while_eligible_work_queued(seed, cap):
    """After every admit(): either the batch is full or nothing queued is
    eligible — the DWFQ filter skips, it never stalls the admission loop
    on admissible work."""
    import numpy as np
    rng = np.random.default_rng(seed)
    sim = TenantSim({"x": TenantConfig("x", max_slots=2),
                     "y": TenantConfig("y")}, max_batch=3)
    sim.sched.batch_cap = cap
    for _ in range(40):
        if rng.random() < 0.6:
            sim.submit(("x", "y")[int(rng.integers(2))],
                       slo=(SLO_INTERACTIVE, SLO_BATCH)[int(rng.integers(2))],
                       max_new=int(rng.integers(1, 5)))
        # checked right after admission: the serve half of a step may
        # retire requests and free slots that the next admit() fills
        sim.admit()
        if sim.sched.free_slot() is not None:
            assert not any(sim.sched._eligible(r) for r in sim.sched.queue)
        sim.serve()


# ------------------------------------------------------------ determinism
def test_legacy_path_untouched_without_tenancy():
    """slo_aware off: tenancy state stays inert (no service accounting)
    and admission is plain FCFS."""
    sched = Scheduler(max_batch=2, cap=64)
    assert not sched.slo_aware
    for i in range(4):
        sched.submit(Request(rid=i, prompt=[1, 2], tenant=f"t{i}",
                             slo_class=SLO_BATCH if i % 2 else
                             SLO_INTERACTIVE))
    placed = sched.admit()
    assert [r.rid for r, _ in placed] == [0, 1]     # arrival order, no DWFQ
    sched.record_served(0)
    sched.record_served(1)
    assert sched.served_tokens == {"t0": 1, "t1": 1}  # accounting only
    sched.check_invariants()
