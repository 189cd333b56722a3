"""Block-accounting layer: visited-block counts match the analytic formula.

The accounting functions replay the same ``prune_block_range`` /
``prefill_block_range`` the kernels' index_maps clamp with; these tests pin
them against *independent* brute-force oracles (enumerating valid slots via
``shard_positions`` / the mask definition) and against the ISSUE's bounds:
decode visits <= ceil(local_valid_len / block_s) + 1 blocks per (b, h),
causal prefill visits the lower triangle of the (T/blk_q, S/blk_k) grid.
"""
import jax.numpy as jnp
import numpy as np
import pytest

import jax

from repro.kernels import registry
from repro.kernels.flash_decode import (flash_decode_accounting,
                                        local_valid_len, shard_positions)
from repro.kernels.flash_prefill import flash_prefill_accounting
from repro.utils import cdiv

B, QH, KH, HSZ = 2, 8, 2, 64
S_CAP, KVP, RR = 64, 4, 16


def _mk(s=S_CAP):
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    return (jax.random.normal(ks[0], (B, QH, HSZ)),
            jax.random.normal(ks[1], (B, KH, s, HSZ)),
            jax.random.normal(ks[2], (B, KH, s, HSZ)))


def _decode_oracle_blocks(tl_b, rank, *, window, block_s, s_cap,
                          slot_offset=0):
    """Brute force: blocks containing at least one unmasked slot (>= 1 — a
    fully-pruned request still fetches one clamped block)."""
    total = 0
    for tl in tl_b:
        pos = np.asarray(shard_positions(s_cap, rank, KVP, RR, slot_offset))
        valid = pos < tl
        if window > 0:
            valid &= pos >= tl - window
        blocks = {j // block_s for j in np.nonzero(valid)[0]}
        total += max(len(blocks), 1)
    return total * KH


@pytest.mark.parametrize("window", [0, 48], ids=["full", "windowed"])
@pytest.mark.parametrize("tl", [7, 100, S_CAP * KVP - 7,
                                np.asarray([200, 33], np.int32)],
                         ids=["tiny", "short", "full", "perreq"])
@pytest.mark.parametrize("block_s", [16, 32])
def test_decode_accounting_matches_bruteforce(window, tl, block_s):
    q, k, v = _mk()
    for rank in range(KVP):
        acc = flash_decode_accounting(q, k, v, tl, rank, kvp=KVP,
                                      rr_block=RR, window=window,
                                      block_s=block_s, prune=True)
        tl_b = np.broadcast_to(np.asarray(tl, np.int32).reshape(-1), (B,))
        expect = _decode_oracle_blocks(tl_b, rank, window=window,
                                       block_s=block_s, s_cap=S_CAP)
        assert acc["blocks_visited"] == expect, (rank, acc, expect)
        # the ISSUE bound: <= ceil(local_valid_len / block_s) + 1 per (b, h)
        for b in range(B):
            valid = int(local_valid_len(jnp.asarray(int(tl_b[b])), rank,
                                        KVP, RR))
            assert cdiv(min(valid, S_CAP), block_s) + 1 >= \
                _decode_oracle_blocks([tl_b[b]], rank, window=window,
                                      block_s=block_s, s_cap=S_CAP) // KH
        dense = flash_decode_accounting(q, k, v, tl, rank, kvp=KVP,
                                        rr_block=RR, window=window,
                                        block_s=block_s, prune=False)
        assert dense["blocks_visited"] == dense["blocks_total"]
        assert acc["blocks_visited"] <= dense["blocks_total"]
        assert acc["bytes_read"] == acc["blocks_visited"] * \
            2 * acc["block_s"] * HSZ * 4


def test_decode_accounting_window_caps_blocks():
    """Sliding window: visited blocks stay O(window / block_s) however long
    the sequence grows (the paper's sliding-window read bound)."""
    q, k, v = _mk()
    window, block_s = 32, 16
    w_blocks_max = cdiv(window // KVP, block_s) + 2      # span + 2 edges
    for tl in (64, 128, 240):
        acc = flash_decode_accounting(q, k, v, tl, 0, kvp=KVP, rr_block=RR,
                                      window=window, block_s=block_s)
        assert acc["blocks_visited"] <= B * KH * w_blocks_max, (tl, acc)


def test_decode_accounting_contiguous_and_slot_offset():
    q, k, v = _mk()
    acc = flash_decode_accounting(q, k, v, 80, 1, kvp=1, contiguous=True,
                                  block_s=16, prune=True)
    # rank 1 holds positions 64..127 -> 80 valid = 16 slots = 1 block
    assert acc["blocks_visited"] == B * KH * 1
    acc0 = flash_decode_accounting(q, k, v, 40, 0, kvp=1, contiguous=True,
                                   block_s=16, prune=True)
    assert acc0["blocks_visited"] == B * KH * cdiv(40, 16)
    # slot_offset shifts the span like the kernel's positions do
    accs = flash_decode_accounting(q, k, v, 200, 1, kvp=KVP, rr_block=RR,
                                   window=48, slot_offset=16, block_s=16)
    assert accs["blocks_visited"] <= B * KH * 3


def _prefill_oracle_blocks(t, s, lens, *, causal, window, q_offset, blk_q,
                           blk_k):
    """Brute force from the mask definition over the padded grid."""
    from repro.utils import round_up
    n_q = round_up(t, blk_q) // blk_q
    n_k = round_up(s, blk_k) // blk_k
    total = 0
    for kv_len in lens:
        for qi in range(n_q):
            qpos = q_offset + qi * blk_q + np.arange(blk_q)
            blocks = set()
            for ki in range(n_k):
                kpos = ki * blk_k + np.arange(blk_k)
                m = (kpos[None, :] < min(s, kv_len)) & np.ones(
                    (blk_q, 1), bool)
                if causal:
                    m &= kpos[None, :] <= qpos[:, None]
                if window > 0:
                    m &= kpos[None, :] > qpos[:, None] - window
                if m.any():
                    blocks.add(ki)
            total += max(len(blocks), 1)
    return total * KH


@pytest.mark.parametrize("causal", [True, False], ids=["causal", "cross"])
@pytest.mark.parametrize("window", [0, 20], ids=["full", "windowed"])
@pytest.mark.parametrize("q_offset", [0, 13], ids=["off0", "off13"])
@pytest.mark.parametrize("lens", [None, np.asarray([48, 19], np.int32),
                                  np.asarray([0, 48], np.int32)],
                         ids=["uniform", "perreq", "empty-row"])
def test_prefill_accounting_matches_bruteforce(causal, window, q_offset,
                                               lens):
    t = s = 48
    blk = 16
    ks = jax.random.split(jax.random.PRNGKey(1), 3)
    q = jax.random.normal(ks[0], (B, t, QH, HSZ))
    k = jax.random.normal(ks[1], (B, s, KH, HSZ))
    v = jax.random.normal(ks[2], (B, s, KH, HSZ))
    acc = flash_prefill_accounting(q, k, v, causal=causal, window=window,
                                   q_offset=q_offset, seq_lens=lens,
                                   blk_q=blk, blk_k=blk, prune=True)
    lens_b = np.broadcast_to(
        np.full((B,), s, np.int32) if lens is None
        else np.asarray(lens).reshape(-1), (B,))
    expect = _prefill_oracle_blocks(t, s, lens_b, causal=causal,
                                    window=window, q_offset=q_offset,
                                    blk_q=blk, blk_k=blk)
    assert acc["blocks_visited"] == expect, (acc, expect)
    dense = flash_prefill_accounting(q, k, v, causal=causal, window=window,
                                     q_offset=q_offset, seq_lens=lens,
                                     blk_q=blk, blk_k=blk, prune=False)
    assert dense["blocks_visited"] == dense["blocks_total"]


def test_prefill_causal_triangle_formula():
    """Causal T=S, uniform lens: visited == n(n+1)/2 kv blocks per (b, h)
    q-row sweep — the lower triangle, ~55% of the rectangle for deep
    grids."""
    t = s = 160
    blk = 16
    ks = jax.random.split(jax.random.PRNGKey(2), 3)
    q = jax.random.normal(ks[0], (1, t, 4, 32))
    k = jax.random.normal(ks[1], (1, s, 2, 32))
    v = jax.random.normal(ks[2], (1, s, 2, 32))
    acc = flash_prefill_accounting(q, k, v, causal=True, blk_q=blk,
                                   blk_k=blk, prune=True)
    n = acc["n_qblocks"]
    assert acc["blocks_visited"] == 2 * n * (n + 1) // 2   # kh=2
    frac = acc["blocks_visited"] / acc["blocks_total"]
    assert frac == pytest.approx((n + 1) / (2 * n))
    assert frac <= 0.56


def _sds(shape, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(shape, dtype)


def _slot_dmas_oracle(tables, spans, pages):
    """Brute force of the page-slot pipeline: slot ``p`` holds logical page
    ``s * pages + p`` while it lies in the row's ``[lo, hi)`` span, else
    the nearest of its own in-span pages (with none, its first page at or
    past ``lo``, within the table); a row with an empty span keeps the
    page the slot holds.  A DMA is issued at the first grid step and
    whenever the held pool page changes."""
    mp = tables.shape[1]
    n_sb = -(-mp // pages)
    dmas = 0
    for p in range(pages):
        held = None
        for r, (lo, hi) in enumerate(spans):
            if hi <= lo:
                continue
            own = [lg for lg in range(lo, hi) if lg % pages == p]
            for s in range(n_sb):
                lg = s * pages + p
                if not own:
                    lg = min(lo + (p - lo) % pages, mp - 1)
                elif lg not in own:
                    lg = min(own) if lg < min(own) else max(own)
                page = int(tables[r, lg])
                dmas += page != held
                held = page
        dmas += held is None          # no row fetches: the first step does
    return dmas


@pytest.mark.parametrize("pages", [1, 2], ids=["1page", "2pages"])
def test_grouped_accounting_prefix_bound_and_bruteforce(pages):
    """Grouped shared-prefix decode: the accounting's two-pass split is
    pinned against brute-force enumeration, and the prefix read volume
    scales with the number of *groups*, not the number of requests — the
    ~1/group_size bytes-read reduction the CoDec-style pass exists for.
    S-blocks hold ``pages`` whole pages; a shared span rounds down to
    whole blocks."""
    b, kh, hsz = 6, 2, 32
    bs, mp = 16, 5
    n_pool = 16
    pp = 2                                    # shared prefix pages per group
    # two groups of three: rows 0-2 share pages [1, 2], rows 3-5 share
    # [6, 7]; each row owns one or two suffix pages after the prefix
    tables = np.zeros((b, mp), np.int32)
    tables[0] = [1, 2, 3, 0, 0]
    tables[1] = [1, 2, 4, 5, 0]
    tables[2] = [1, 2, 8, 0, 0]
    tables[3] = [6, 7, 9, 0, 0]
    tables[4] = [6, 7, 10, 11, 0]
    tables[5] = [6, 7, 12, 0, 0]
    tl = np.array([37, 52, 35, 44, 61, 33], np.int32)
    gid = np.array([0, 0, 0, 3, 3, 3], np.int32)
    gnp = np.full((b,), pp, np.int32)
    kv = _sds((n_pool, kh, bs, hsz))
    common = dict(kvp=1, rr_block=bs, block_s=pages * bs,
                  block_tables=tables)
    acc = flash_decode_accounting(_sds((b, 8, hsz)), kv, kv, tl, 0,
                                  groups=(gid, gnp), **common)
    n_groups = len({int(g) for g in gid})

    # brute force, prefix pass: each group row computes its whole shared
    # S-blocks; memberless rows compute none
    shared_blocks = pp // pages
    prefix_oracle = n_groups * shared_blocks
    # brute force, suffix pass: blocks holding a valid slot at or past the
    # shared blocks
    suffix_oracle = 0
    for r in range(b):
        pos = np.asarray(shard_positions(mp * bs, 0, 1, bs))
        blocks = {j // (pages * bs) for j in np.nonzero(pos < tl[r])[0]}
        suffix_oracle += len({k for k in blocks if k >= shared_blocks})
    assert acc["prefix_blocks"] == prefix_oracle
    assert acc["suffix_blocks"] == suffix_oracle
    assert acc["blocks_visited"] == prefix_oracle + suffix_oracle
    assert acc["n_blocks"] == -(-mp // pages)

    # a group's shared pages are fetched once per group, not once per
    # member, and memberless group rows fetch nothing
    page_bytes = 2 * kh * bs * hsz * 4
    shared_pages = n_groups * shared_blocks * pages
    prefix_dmas = acc["prefix_bytes"] // page_bytes
    assert prefix_dmas == shared_pages
    assert shared_pages * 3 == shared_blocks * pages * b
    gspans = [(0, shared_blocks * pages if r in gid else 0)
              for r in range(b)]
    gtab = np.zeros_like(tables)
    gtab[gid] = tables
    assert prefix_dmas == _slot_dmas_oracle(gtab, gspans, pages)

    # bytes split is consistent and the ungrouped call reports no prefix
    assert acc["bytes_read"] == acc["page_dmas"] * page_bytes
    assert acc["bytes_read"] == acc["prefix_bytes"] + acc["suffix_bytes"]
    un = flash_decode_accounting(_sds((b, 8, hsz)), kv, kv, tl, 0,
                                 **common)
    assert un["prefix_blocks"] == un["prefix_bytes"] == 0
    assert un["suffix_blocks"] == un["blocks_visited"]
    # ungrouped, every live page is one DMA, less those a slot already
    # holds from the previous row (rows of a group share their prefix)
    live = [(0, -(-int(t) // bs)) for t in tl]
    assert un["page_dmas"] == _slot_dmas_oracle(tables, live, pages)
    assert un["page_dmas"] <= sum(hi for _, hi in live)
    sfx = [(shared_blocks * pages, hi) for _, hi in live]
    assert acc["suffix_bytes"] // page_bytes == _slot_dmas_oracle(
        tables, sfx, pages)
    # grouping strictly reduces total reads on this shared workload
    assert acc["bytes_read"] < un["bytes_read"]

    # dense grouped: the suffix walks every block above the shared ones,
    # the prefix is unchanged
    dense = flash_decode_accounting(_sds((b, 8, hsz)), kv, kv, tl, 0,
                                    groups=(gid, gnp), prune=False, **common)
    assert dense["suffix_blocks"] == b * (acc["n_blocks"] - shared_blocks)
    assert dense["prefix_blocks"] == prefix_oracle


@pytest.mark.parametrize("pages", [1, 2], ids=["1page", "2pages"])
def test_paged_idle_rows_fetch_nothing(pages):
    """Idle batch rows (length 0) hold the pages their slots already have:
    a batch with idle rows issues exactly the page DMAs of its live rows
    run back to back."""
    kh, hsz, bs, mp = 2, 32, 16, 5
    tables = np.array([[0, 0, 0, 0, 0], [1, 2, 3, 0, 0], [0, 0, 0, 0, 0],
                       [4, 5, 6, 7, 0], [0, 0, 0, 0, 0]], np.int32)
    tl = np.array([0, 40, 0, 61, 0], np.int32)
    kv = _sds((8, kh, bs, hsz))
    common = dict(kvp=1, rr_block=bs, block_s=pages * bs)
    acc = flash_decode_accounting(_sds((5, 8, hsz)), kv, kv, tl, 0,
                                  block_tables=tables, **common)
    live = flash_decode_accounting(_sds((2, 8, hsz)), kv, kv, tl[[1, 3]], 0,
                                   block_tables=tables[[1, 3]], **common)
    spans = [(0, -(-int(t) // bs)) for t in tl]
    assert acc["page_dmas"] == live["page_dmas"]
    assert acc["page_dmas"] == _slot_dmas_oracle(tables, spans, pages)
    assert acc["blocks_visited"] == live["blocks_visited"]


def test_registry_accounting_surface():
    """registry.accounting resolves the attention families and rejects the
    families without an accounting layer."""
    assert registry.accounting("flash_decode") is flash_decode_accounting
    assert registry.accounting("flash_prefill") is flash_prefill_accounting
    with pytest.raises(ValueError):
        registry.accounting("ssd_prefill")
    with pytest.raises(ValueError):
        registry.accounting("nope")
