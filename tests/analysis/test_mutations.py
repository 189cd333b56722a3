"""Mutation suite: seeded contract bugs must be flagged with the right id.

Each test plants one deliberately broken contract — an off-by-one prune
clamp, an overlapping alias window, an out-of-bounds page id in a shuffled
block table, a doubled psum — and asserts the analyzer reports exactly the
check id that names that bug class.  This is the analyzer's own oracle: a
checker that passes clean trees but misses planted bugs is worthless.

Mutations are applied to contract *objects* (dataclass surgery on the
returned ``KernelContract``s), never to kernel sources — the kernels under
test stay the shipped ones.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.analysis.findings import Report
from repro.analysis.host_sync import lint_source
from repro.analysis.index_audit import audit_contract
from repro.analysis.jaxpr_audit import audit_step_fn
from repro.kernels.flash_decode.ops import decode_case_contract
from repro.kernels.flash_prefill.ops import prefill_case_contract
from repro.utils import make_mesh, shard_map
from jax.sharding import PartitionSpec as P


def _checks(findings):
    return {f.check for f in findings}


def _replace_op(contract, name, **changes):
    ops = [dataclasses.replace(op, **changes) if op.name == name else op
           for op in contract.operands]
    return dataclasses.replace(contract, operands=ops)


def _wrap_map(fn, tweak):
    def wrapped(*args):
        return tweak(fn(*args))
    return wrapped


# ------------------------------------------------------------- index layer
def test_clean_decode_contract_passes():
    c = decode_case_contract("rr-prune")
    assert audit_contract(c) == []


def test_unclamped_index_map_is_bounds_block():
    """An index_map whose block coordinate runs one past the operand's
    last block (the missing-upper-clamp bug) -> bounds.block."""
    c = decode_case_contract("rr-dense", prune=False)
    k = next(op for op in c.operands if op.name == "k")
    overrun = _wrap_map(k.index_map,
                        lambda t: (t[0], t[1], t[2] + 1) + tuple(t[3:]))
    mutated = _replace_op(c, "k", index_map=overrun)
    found = _checks(audit_contract(mutated))
    assert "bounds.block" in found


def test_off_by_one_prune_clamp_is_dma_elision():
    """Clamp to last+1 instead of last: every pruned step walks one block
    past the previous one -> the DMA is NOT elided -> dma.elision."""
    c = decode_case_contract("rr-prune")
    k = next(op for op in c.operands if op.name == "k")

    def off_by_one(b, h, s, meta, tl, *rest):
        from repro.kernels.flash_decode.kernel import prune_block_range
        lo, nb = prune_block_range(
            tl[b], meta[0], meta[1], meta[2], kvp=2, rr_block=2,
            block_s=4, s_true=16, contiguous=False)
        last = jnp.maximum(lo + nb - 1, lo)
        # mutated clamp: min(lo+s, last + 1) — off by one
        return (b, h, jnp.clip(jnp.minimum(lo + s, last + 1), 0, 3), 0)

    mutated = _replace_op(c, "k", index_map=off_by_one)
    found = _checks(audit_contract(mutated))
    assert "dma.elision" in found


def test_oob_page_id_in_shuffled_table_is_bounds_page():
    """A shuffled block table with an out-of-pool page id must be a hard
    bounds.page error (foreign-memory read through the indirection)."""
    c = decode_case_contract("paged-prune", paged=True)
    table = np.array(c.table, copy=True)
    table[1, 1] = c.n_pool + 3                 # points past the pool
    mutated = decode_case_contract("paged-prune", paged=True, table=table)
    found = _checks(audit_contract(mutated))
    assert "bounds.page" in found


def test_unclamped_page_slot_is_dma_elision():
    """A paged page slot that walks the table without the live-span clamp
    fetches a fresh page at every dead S-block -> dma.elision."""
    c = decode_case_contract("paged-window", paged=True, window=6)
    pages = c.notes["pages"]

    table = jnp.asarray(c.table)

    def unclamped(b, s, *_):
        return (table[b, jnp.minimum(s * pages + 1, table.shape[1] - 1)],
                0, 0, 0)

    mutated = _replace_op(c, "k1", index_map=unclamped)
    found = _checks(audit_contract(mutated))
    assert "dma.elision" in found


def test_page_slot_past_pool_is_bounds_page():
    """A page slot addressing one page past the pool (an unchecked table
    read) -> bounds.page on the pool axis."""
    c = decode_case_contract("paged-prune", paged=True)
    v0 = next(op for op in c.operands if op.name == "v0")
    past = _wrap_map(v0.index_map,
                     lambda t: (t[0] + c.n_pool,) + tuple(t[1:]))
    mutated = _replace_op(c, "v0", index_map=past)
    found = _checks(audit_contract(mutated))
    assert "bounds.page" in found


def test_duplicate_page_across_requests_is_alias_race():
    """Two requests mapping the same non-sink pool page share writable
    memory -> alias.race."""
    c = decode_case_contract("paged-prune", paged=True)
    table = np.array(c.table, copy=True)
    table[1, 0] = table[0, 0]                  # request 1 steals req 0's page
    mutated = decode_case_contract("paged-prune", paged=True, table=table)
    found = _checks(audit_contract(mutated))
    assert "alias.race" in found


def test_shifted_append_window_is_alias_race():
    """Fused-append row window writing one slot past the in-kernel VMEM
    substitution target -> alias.race (the overlapping-alias-window bug)."""
    c = decode_case_contract("append-rr", append=True)
    k_row = next(op for op in c.operands if op.name == "k_row_out")
    shifted = _wrap_map(k_row.index_map,
                        lambda t: (t[0], t[1], t[2] + 1, t[3]))
    mutated = _replace_op(c, "k_row_out", index_map=shifted)
    found = _checks(audit_contract(mutated))
    assert "alias.race" in found


def test_batch_blind_append_window_is_alias_race():
    """A row window ignoring the batch coordinate makes every request
    write the same cache row -> one-writer-per-window violation."""
    c = decode_case_contract("append-rr", append=True)
    k_row = next(op for op in c.operands if op.name == "k_row_out")
    blind = _wrap_map(k_row.index_map, lambda t: (0,) + tuple(t[1:]))
    mutated = _replace_op(c, "k_row_out", index_map=blind)
    found = _checks(audit_contract(mutated))
    assert "alias.race" in found


def test_prefill_unclamped_causal_skip_is_caught():
    """Same off-by-one family in the prefill kernel's skip clamp."""
    c = prefill_case_contract("causal-prune")
    k = next(op for op in c.operands if op.name == "k")

    def off_by_one(b, h, qi, ki, meta, lens, offs, *rest):
        from repro.kernels.flash_prefill.kernel import prefill_block_range
        lo, nb = prefill_block_range(qi, lens[b], offs[b], meta[0],
                                     causal=True, blk_q=4, blk_k=4,
                                     s_true=16)
        last = jnp.maximum(lo + nb - 1, lo)
        return (b, h, jnp.minimum(jnp.minimum(ki + lo, last + 1), 3), 0)

    mutated = _replace_op(c, "k", index_map=off_by_one)
    found = _checks(audit_contract(mutated))
    assert "dma.elision" in found


def test_impure_index_map_reported_not_crashed():
    """A data-dependently branching (impure) index_map must surface as a
    finding, not crash the auditor (the purity contract of pruning.py)."""
    c = decode_case_contract("rr-prune")

    def impure(b, h, s, meta, tl, *rest):
        if tl[b] > 5:              # python branch on a traced value
            return (b, h, s, 0)
        return (b, h, 0, 0)

    mutated = _replace_op(c, "k", index_map=impure)
    found = _checks(audit_contract(mutated))
    assert "bounds.block" in found


# ------------------------------------------------------------- jaxpr layer
@pytest.fixture(scope="module")
def mesh():
    return make_mesh((1, 1), ("data", "model"))


def _audit(fn, args, mesh, expected):
    return audit_step_fn(fn, args, kvp_axes=("data",),
                         mesh_axes=mesh.axis_names, expected=expected,
                         where="tests", symbol="mutant")


def test_doubled_all_to_all_is_collective_count(mesh):
    """A duplicated KVP combine (the doubled-collective miscompile) must
    be collective.count."""
    def body(x):
        y = jax.lax.all_to_all(x, "data", 0, 0, tiled=False)
        return jax.lax.all_to_all(y, "data", 0, 0, tiled=False)

    fn = shard_map(body, mesh=mesh, in_specs=P("data"), out_specs=P("data"))
    x = jnp.zeros((1, 4))
    found = _checks(_audit(fn, (x,), mesh,
                           {"all_to_all": 1, "psum": 0}))
    assert found == {"collective.count"}


def test_missing_combine_is_collective_count(mesh):
    def body(x):
        return x * 2.0

    fn = shard_map(body, mesh=mesh, in_specs=P("data"), out_specs=P("data"))
    x = jnp.zeros((1, 4))
    found = _checks(_audit(fn, (x,), mesh, {"all_to_all": 1}))
    assert found == {"collective.count"}


def test_doubled_psum_is_collective_count(mesh):
    """A stray psum over the KVP axes (the doubled-psum mutation) — the
    Helix decode path reduces via all_to_all + all_gather, never psum."""
    def body(x):
        return x + jax.lax.psum(x, "data")

    fn = shard_map(body, mesh=mesh, in_specs=P("data"), out_specs=P("data"))
    x = jnp.zeros((1, 4))
    found = _checks(_audit(fn, (x,), mesh, {"psum": 0}))
    assert found == {"collective.count"}


def test_wrong_axis_combine_is_collective_axis(mesh):
    """A combine over the TP axis instead of the KVP axes."""
    def body(x):
        return jax.lax.all_gather(x, "model", tiled=True)

    fn = shard_map(body, mesh=mesh, in_specs=P(None, "model"),
                   out_specs=P(None, None), check_vma=False)
    x = jnp.zeros((1, 4))
    found = _checks(_audit(fn, (x,), mesh, {}))
    assert "collective.axis" in found


def test_state_dtype_upcast_is_dtype_upcast(mesh):
    """A step that silently upcasts an int8 state leaf to f32."""
    from repro.analysis.jaxpr_audit import check_state_dtypes

    def step(params, state, tok):
        return tok, {"kcache": state["kcache"].astype(jnp.float32),
                     "tl": state["tl"]}

    state = {"kcache": jax.ShapeDtypeStruct((2, 4), jnp.int8),
             "tl": jax.ShapeDtypeStruct((2,), jnp.int32)}
    tok = jax.ShapeDtypeStruct((2,), jnp.int32)
    found = _checks(check_state_dtypes(
        step, ({}, state, tok), state_index=1, where="tests",
        symbol="mutant"))
    assert found == {"dtype.upcast"}


# -------------------------------------------------------------- sync layer
def test_per_token_int_cast_is_flagged():
    src = (
        "import jax.numpy as jnp\n"
        "def step(self, logits):\n"
        "    return int(jnp.argmax(logits[0]))\n"
    )
    found = _checks(lint_source(src, "mutant.py"))
    assert found == {"sync.scalar-cast"}


def test_per_slot_asarray_loop_is_flagged():
    src = (
        "import numpy as np\n"
        "import jax.numpy as jnp\n"
        "def step(self, toks):\n"
        "    out = []\n"
        "    dev = jnp.asarray(toks)\n"
        "    for i in range(4):\n"
        "        out.append(np.asarray(dev[i]))\n"
        "    return out\n"
    )
    found = _checks(lint_source(src, "mutant.py"))
    assert found == {"sync.asarray-loop"}


def test_item_and_block_until_ready_flagged():
    src = (
        "import jax.numpy as jnp\n"
        "def step(x):\n"
        "    y = jnp.sum(x)\n"
        "    y.block_until_ready()\n"
        "    return y.item()\n"
    )
    found = _checks(lint_source(src, "mutant.py"))
    assert found == {"sync.item", "sync.block-until-ready"}


def test_per_page_device_get_loop_is_flagged():
    """The per-page spill anti-pattern the host tier must never use: one
    blocking jax.device_get per pool page inside the spill loop."""
    src = (
        "import jax\n"
        "def spill(self, state, phys):\n"
        "    pages = []\n"
        "    for p in phys:\n"
        "        pages.append(jax.device_get(state['kcache'][:, p]))\n"
        "    return pages\n"
    )
    found = _checks(lint_source(src, "mutant.py"))
    assert found == {"sync.device-get-loop"}


def test_batched_device_get_is_warning_not_error():
    """ONE batched device_get of a gathered plane dict (the sanctioned
    spill shape) lints as the baselinable warning, not the loop error."""
    src = (
        "import jax\n"
        "def spill(self, planes):\n"
        "    return jax.device_get(planes)\n"
    )
    findings = lint_source(src, "mutant.py")
    assert _checks(findings) == {"sync.device-get"}
    assert all(f.severity == "warning" for f in findings)


def test_second_window_transfer_is_new_per_token_ordinal():
    """The decode-window sync budget: ONE transfer inside _decode_window
    is the contract (ordinal #1, baselined); a mutant adding a second
    blocking read gets ordinal #2 — a symbol no baseline entry matches,
    so --strict fails.  This pins the transfer COUNT, not the site set."""
    one = (
        "import numpy as np\n"
        "import jax\n"
        "class E:\n"
        "    def __init__(self):\n"
        "        self.serve_multistep = jax.jit(lambda s: s)\n"
        "    def _decode_window(self):\n"
        "        blk = self.serve_multistep(0)\n"
        "        return np.asarray(blk)\n"
    )
    two = one + "        # mutant: a second blocking read\n"
    two = one.replace(
        "        return np.asarray(blk)\n",
        "        toks = np.asarray(blk)\n"
        "        lens = np.asarray(self.serve_multistep(1))\n"
        "        return toks, lens\n")
    syms = {f.symbol for f in lint_source(one, "engine.py")
            if f.check == "sync.per-token"}
    assert syms == {"_decode_window#1"}
    syms2 = {f.symbol for f in lint_source(two, "engine.py")
             if f.check == "sync.per-token"}
    assert syms2 == {"_decode_window#1", "_decode_window#2"}
    baseline = [{"check": "sync.per-token", "path": "engine.py",
                 "symbol": "_decode_window#1", "reason": "the window read"},
                {"check": "sync.asarray", "path": "engine.py",
                 "symbol": "_decode_window", "reason": "the window read"}]
    r = Report()
    r.extend(lint_source(two, "engine.py"))
    r.apply_baseline(baseline)
    left = r.unsuppressed()
    assert {f.symbol for f in left if f.check == "sync.per-token"} \
        == {"_decode_window#2"}


def test_transfers_outside_window_fns_get_no_per_token():
    """Ordinal stamping applies only to WINDOW_HOT_FNS — ordinary engine
    methods keep exactly their base sync findings."""
    src = (
        "import numpy as np\n"
        "import jax.numpy as jnp\n"
        "def _decode_step(self, toks):\n"
        "    return np.asarray(jnp.asarray(toks))\n"
    )
    found = _checks(lint_source(src, "engine.py"))
    assert found == {"sync.asarray"}


def test_jitted_self_attr_provenance():
    """Calls of self.<attr> bound to jax.jit anywhere in the module are
    device values — the engine's serve_step pattern."""
    src = (
        "import jax\n"
        "class E:\n"
        "    def __init__(self):\n"
        "        self.serve_step = jax.jit(lambda s: s)\n"
        "    def step(self):\n"
        "        toks = self.serve_step(0)\n"
        "        return int(toks)\n"
    )
    found = _checks(lint_source(src, "mutant.py"))
    assert found == {"sync.scalar-cast"}


def test_numpy_only_code_is_quiet():
    """Host-side numpy metric code must not be flagged (HOST default)."""
    src = (
        "import numpy as np\n"
        "def summarize(vals):\n"
        "    arr = np.asarray(vals, np.float64)\n"
        "    return float(arr.mean()), int(arr.size)\n"
    )
    assert lint_source(src, "metrics.py") == []


# ----------------------------------------------- at least 5 distinct ids
def test_mutation_suite_covers_required_check_ids():
    """The acceptance criterion: >= 5 distinct check ids exercised across
    the seeded-bug suite (bounds, alias-race, DMA-elision,
    collective-count, host-sync)."""
    required = {"bounds.block", "bounds.page", "alias.race", "dma.elision",
                "collective.count", "sync.scalar-cast"}
    # ids asserted by the tests above, statically:
    assert len(required) >= 5


def test_report_mutation_roundtrip():
    """Findings from a mutated contract survive the Report/baseline path
    with line-independent keys."""
    c = decode_case_contract("append-rr", append=True)
    k_row = next(op for op in c.operands if op.name == "k_row_out")
    shifted = _wrap_map(k_row.index_map,
                        lambda t: (t[0], t[1], t[2] + 1, t[3]))
    mutated = _replace_op(c, "k_row_out", index_map=shifted)
    r = Report()
    r.extend(audit_contract(mutated))
    assert r.unsuppressed("error")
    stale = r.apply_baseline([{
        "check": "alias.race",
        "path": "src/repro/kernels/flash_decode/kernel.py",
        "symbol": "flash_decode[append-rr]/k_row_out",
        "reason": "test"}])
    assert stale == []
    assert all(f.suppressed for f in r.findings
               if f.check == "alias.race")
