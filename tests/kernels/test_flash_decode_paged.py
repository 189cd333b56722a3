"""Paged (shared-pool + block-table) flash_decode == fixed-cap layout,
bit-exactly, across the decode mode lattice.

The paged pool is a page-granularity permutation of the fixed layout
(core/kvcache.py).  The paged kernel gathers ``pages`` whole pages per
S-block; with the fixed kernel's S-block set to the same ``pages * PS``
slots, both layouts run identical tiles in identical order, so outputs
must be *bit*-identical — prune on/off, windowed, per-request lengths,
int8, fused append, sink entries, and through the ref (gather) backend
too.  Pages per block cover one page, two, a block that does not divide
the 4-page table, and the whole table."""
import numpy as np
import pytest
import jax
import jax.numpy as jnp

from repro.core.kvcache import gather_pages
from repro.kernels.flash_decode.ops import (flash_decode,
                                            flash_decode_accounting)
from repro.models.attention import decode_attention

KVP, RR = 4, 16
PS = RR                     # per-rank page rows == rr_block
MP = 4                      # logical pages per request
S_LOC = MP * PS             # fixed local capacity
B, QH, KH, HSZ = 3, 8, 2, 64

# pages per S-block: one, two, one that leaves a ragged last block, all
PAGES = pytest.mark.parametrize("pages", [1, 2, 3, MP],
                                ids=["1page", "2pages", "3pages", "table"])


def make_case(seed=0):
    """Fixed local shard + its paged twin under a shuffled page table."""
    rng = np.random.default_rng(seed)
    k = jnp.asarray(rng.standard_normal((B, KH, S_LOC, HSZ), np.float32))
    v = jnp.asarray(rng.standard_normal((B, KH, S_LOC, HSZ), np.float32))
    q = jnp.asarray(rng.standard_normal((B, QH, HSZ), np.float32))
    n_pool = 1 + B * MP
    tables = np.zeros((B, MP), np.int32)
    perm = rng.permutation(np.arange(1, n_pool))
    pool_k = jnp.zeros((n_pool, KH, PS, HSZ), jnp.float32)
    pool_v = jnp.zeros((n_pool, KH, PS, HSZ), jnp.float32)
    i = 0
    for b in range(B):
        for p in range(MP):
            phys = int(perm[i]); i += 1
            tables[b, p] = phys
            pool_k = pool_k.at[phys].set(k[b, :, p * PS:(p + 1) * PS])
            pool_v = pool_v.at[phys].set(v[b, :, p * PS:(p + 1) * PS])
    return q, k, v, pool_k, pool_v, jnp.asarray(tables)


def quant(c):
    scale = jnp.maximum(jnp.max(jnp.abs(c), axis=-1) / 127.0, 1e-30)
    payload = jnp.clip(jnp.round(c / scale[..., None]),
                       -127, 127).astype(jnp.int8)
    return payload, scale


# per-request lengths, one uniform length, and idle rows (length 0) around
# a live one, whose page slots hold the live row's pages
TLS = [jnp.asarray([200, 37, 150], jnp.int32), 150,
       jnp.asarray([0, 37, 0], jnp.int32)]


@PAGES
@pytest.mark.parametrize("prune", [True, False])
@pytest.mark.parametrize("window", [0, 48])
@pytest.mark.parametrize("tl_i", [0, 1, 2])
def test_paged_equals_fixed(pages, prune, window, tl_i):
    q, k, v, pk, pv, tables = make_case()
    tl = TLS[tl_i]
    bs = pages * PS
    of, lf = flash_decode(q, k, v, tl, 1, kvp=KVP, rr_block=RR,
                          window=window, block_s=bs, prune=prune,
                          interpret=True)
    op, lp = flash_decode(q, pk, pv, tl, 1, kvp=KVP, rr_block=RR,
                          window=window, block_s=bs, prune=prune,
                          block_tables=tables, interpret=True)
    np.testing.assert_array_equal(np.asarray(of), np.asarray(op))
    np.testing.assert_array_equal(np.asarray(lf), np.asarray(lp))


@PAGES
@pytest.mark.parametrize("prune", [True, False])
def test_paged_quant_equals_fixed(pages, prune):
    q, k, v, pk, pv, tables = make_case(1)
    k8, ks = quant(k); v8, vs = quant(v)
    pk8, pks = quant(pk); pv8, pvs = quant(pv)
    tl = TLS[0]
    bs = pages * PS
    of, _ = flash_decode(q, k8, v8, tl, 1, kvp=KVP, rr_block=RR, block_s=bs,
                         kscale=ks, vscale=vs, prune=prune,
                         interpret=True)
    op, _ = flash_decode(q, pk8, pv8, tl, 1, kvp=KVP, rr_block=RR,
                         block_s=bs, kscale=pks, vscale=pvs, prune=prune,
                         block_tables=tables,
                         interpret=True)
    np.testing.assert_array_equal(np.asarray(of), np.asarray(op))


@PAGES
@pytest.mark.parametrize("quantized", [False, True])
def test_paged_fused_append_equals_fixed(pages, quantized):
    q, k, v, pk, pv, tables = make_case(2)
    rng = np.random.default_rng(3)
    kn = jnp.asarray(rng.standard_normal((B, KH, HSZ), np.float32))
    vn = jnp.asarray(rng.standard_normal((B, KH, HSZ), np.float32))
    tl = jnp.asarray([201, 38, 151], jnp.int32)   # counts the appended token
    bs = pages * PS
    if quantized:
        k8, ks = quant(k); v8, vs = quant(v)
        pk8, pks = quant(pk); pv8, pvs = quant(pv)
        rf = flash_decode(q, k8, v8, tl, 1, kvp=KVP, rr_block=RR, block_s=bs,
                          kscale=ks, vscale=vs, k_new=kn, v_new=vn,
                          interpret=True)
        rp = flash_decode(q, pk8, pv8, tl, 1, kvp=KVP, rr_block=RR,
                          block_s=bs, kscale=pks, vscale=pvs, k_new=kn,
                          v_new=vn, block_tables=tables,
                          interpret=True)
    else:
        rf = flash_decode(q, k, v, tl, 1, kvp=KVP, rr_block=RR, block_s=bs,
                          k_new=kn, v_new=vn,
                          interpret=True)
        rp = flash_decode(q, pk, pv, tl, 1, kvp=KVP, rr_block=RR,
                          block_s=bs, k_new=kn, v_new=vn,
                          block_tables=tables, interpret=True)
    np.testing.assert_array_equal(np.asarray(rf[0]), np.asarray(rp[0]))
    # appended pool planes reassemble into the appended fixed caches
    for fixed, pool in zip(rf[2:], rp[2:]):
        np.testing.assert_array_equal(
            np.asarray(gather_pages(pool, tables)), np.asarray(fixed))


def test_ref_backend_gather_path():
    """decode_attention's ref backend gathers pages into the dense cache."""
    q, k, v, pk, pv, tables = make_case(4)
    tl = TLS[0]
    of, lf = decode_attention(q, k, v, tl, backend="ref", kvp=KVP,
                              rr_block=RR, rank=1)
    op, lp = decode_attention(q, pk, pv, tl, backend="ref", kvp=KVP,
                              rr_block=RR, rank=1, block_tables=tables)
    np.testing.assert_array_equal(np.asarray(of), np.asarray(op))
    np.testing.assert_array_equal(np.asarray(lf), np.asarray(lp))


@PAGES
def test_paged_accounting_matches_fixed_bound(pages):
    """Paged accounting replays the same logical ranges: a live S-block of
    all heads per (b, h) block the fixed layout visits at the same block
    size, and the prune_smoke bound (<= ceil(valid_len/block_s) + 1 blocks
    per request) still holds; every live page is fetched exactly once."""
    from repro.kernels.flash_decode.ref import local_valid_len
    q, k, v, pk, pv, tables = make_case(5)
    tl = TLS[0]
    bs = pages * PS
    fixed = flash_decode_accounting(q, k, v, tl, 1, kvp=KVP, rr_block=RR,
                                    block_s=bs, prune=True)
    paged = flash_decode_accounting(q, pk, pv, tl, 1, kvp=KVP, rr_block=RR,
                                    block_s=bs, prune=True,
                                    block_tables=tables)
    assert paged["blocks_visited"] * KH == fixed["blocks_visited"]
    assert paged["block_s"] == bs and paged["pages_per_block"] == pages
    assert paged["n_blocks"] == -(-MP // pages)
    assert paged["grid_steps"] == B * paged["n_blocks"]
    live_pages = 0
    for b in range(B):
        valid = int(local_valid_len(jnp.asarray(tl)[b], 1, KVP, RR))
        live_pages += -(-valid // PS)
        per_b = flash_decode_accounting(
            q[b:b + 1], pk, pv, jnp.asarray(tl)[b:b + 1], 1, kvp=KVP,
            rr_block=RR, block_s=bs, prune=True,
            block_tables=tables[b:b + 1])
        assert per_b["blocks_visited"] <= -(-valid // bs) + 1
        # a page slot with no live page fetches its clamped page once
        assert per_b["page_dmas"] == max(-(-valid // PS), pages)
    assert paged["page_dmas"] >= live_pages
    assert paged["bytes_read"] == paged["page_dmas"] * 2 * KH * PS * HSZ * 4


@PAGES
def test_sink_entries_are_harmless(pages):
    """Table entries past a request's extent point at the sink page 0;
    the masked sweep over them must not change the output (dense prune=False
    sweep reads them, masks them)."""
    q, k, v, pk, pv, tables = make_case(6)
    short = jnp.asarray([40, 40, 40], jnp.int32)   # < 1 page of positions
    trimmed = np.asarray(tables).copy()
    trimmed[:, 1:] = 0                             # only page 0 allocated
    bs = pages * PS
    for prune in (False, True):
        of, _ = flash_decode(q, k, v, short, 1, kvp=KVP, rr_block=RR,
                             block_s=bs, prune=prune,
                             interpret=True)
        op, _ = flash_decode(q, pk, pv, short, 1, kvp=KVP, rr_block=RR,
                             block_s=bs, prune=prune,
                             block_tables=jnp.asarray(trimmed),
                             interpret=True)
        np.testing.assert_array_equal(np.asarray(of), np.asarray(op))


# grouped decode: the prefix pass multiplies a group's stacked query rows in
# one matmul, and XLA:CPU picks its dot kernel by row count, so outputs
# agree with the ungrouped sweep to f32 rounding; appended planes bit for bit
GROUPED_TOL = dict(rtol=1e-6, atol=1e-6)


@pytest.mark.parametrize("pages,shared", [(1, 3), (2, 3), (3, 2), (2, 1)],
                         ids=["1page-3shared", "2pages-3shared",
                              "3pages-2shared", "2pages-1shared"])
@pytest.mark.parametrize("mode", ["plain", "window", "append"])
def test_grouped_equals_ungrouped(pages, shared, mode):
    """Rows 0 and 1 share their first ``shared`` pool pages, a span that is
    not a whole number of S-blocks: grouping rounds it down to whole
    blocks (none at all when it is shorter than one) and must not change
    the result."""
    q, _, _, pk, pv, tables = make_case(7)
    tbl = np.asarray(tables).copy()
    tbl[1, :shared] = tbl[0, :shared]
    tbl = jnp.asarray(tbl)
    gid = jnp.asarray([0, 0, 2], jnp.int32)
    gnp = jnp.asarray([shared, shared, 0], jnp.int32)
    tl = jnp.asarray([220, 200, 150], jnp.int32)
    kw = dict(kvp=KVP, rr_block=RR, block_s=pages * PS, block_tables=tbl,
              interpret=True)
    if mode == "window":
        kw["window"] = 100
    if mode == "append":
        rng = np.random.default_rng(8)
        kw["k_new"] = jnp.asarray(rng.standard_normal((B, KH, HSZ),
                                                      np.float32))
        kw["v_new"] = jnp.asarray(rng.standard_normal((B, KH, HSZ),
                                                      np.float32))
    ru = flash_decode(q, pk, pv, tl, 1, **kw)
    rg = flash_decode(q, pk, pv, tl, 1, groups=(gid, gnp), **kw)
    for u, g in zip(ru[:2], rg[:2]):
        np.testing.assert_allclose(np.asarray(g), np.asarray(u),
                                   **GROUPED_TOL)
    for u, g in zip(ru[2:], rg[2:]):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(u))
    acc = flash_decode_accounting(q, pk, pv, tl, 1, groups=(gid, gnp), **kw)
    assert acc["prefix_blocks"] == shared // pages
