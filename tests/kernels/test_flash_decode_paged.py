"""Paged (shared-pool + block-table) flash_decode == fixed-cap layout,
bit-exactly, across the decode mode lattice.

The paged pool is a page-granularity permutation of the fixed layout
(core/kvcache.py): with the fixed kernel's S-block size pinned to the page
size, both layouts stream identical tiles in identical order, so outputs
must be *bit*-identical — prune on/off, windowed, per-request lengths,
int8, fused append, and through the ref (gather) backend too."""
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


TLS = [jnp.asarray([200, 37, 150], jnp.int32), 150]


@pytest.mark.parametrize("prune", [True, False])
@pytest.mark.parametrize("window", [0, 48])
@pytest.mark.parametrize("tl_i", [0, 1])
def test_paged_equals_fixed(prune, window, tl_i):
    q, k, v, pk, pv, tables = make_case()
    tl = TLS[tl_i]
    of, lf = flash_decode(q, k, v, tl, 1, kvp=KVP, rr_block=RR,
                          window=window, block_s=PS, prune=prune,
                          interpret=True)
    op, lp = flash_decode(q, pk, pv, tl, 1, kvp=KVP, rr_block=RR,
                          window=window, prune=prune, block_tables=tables,
                          interpret=True)
    np.testing.assert_array_equal(np.asarray(of), np.asarray(op))
    np.testing.assert_array_equal(np.asarray(lf), np.asarray(lp))


@pytest.mark.parametrize("prune", [True, False])
def test_paged_quant_equals_fixed(prune):
    q, k, v, pk, pv, tables = make_case(1)
    k8, ks = quant(k); v8, vs = quant(v)
    pk8, pks = quant(pk); pv8, pvs = quant(pv)
    tl = TLS[0]
    of, _ = flash_decode(q, k8, v8, tl, 1, kvp=KVP, rr_block=RR, block_s=PS,
                         kscale=ks, vscale=vs, prune=prune,
                         interpret=True)
    op, _ = flash_decode(q, pk8, pv8, tl, 1, kvp=KVP, rr_block=RR,
                         kscale=pks, vscale=pvs, prune=prune,
                         block_tables=tables,
                         interpret=True)
    np.testing.assert_array_equal(np.asarray(of), np.asarray(op))


@pytest.mark.parametrize("quantized", [False, True])
def test_paged_fused_append_equals_fixed(quantized):
    q, k, v, pk, pv, tables = make_case(2)
    rng = np.random.default_rng(3)
    kn = jnp.asarray(rng.standard_normal((B, KH, HSZ), np.float32))
    vn = jnp.asarray(rng.standard_normal((B, KH, HSZ), np.float32))
    tl = jnp.asarray([201, 38, 151], jnp.int32)   # counts the appended token
    if quantized:
        k8, ks = quant(k); v8, vs = quant(v)
        pk8, pks = quant(pk); pv8, pvs = quant(pv)
        rf = flash_decode(q, k8, v8, tl, 1, kvp=KVP, rr_block=RR, block_s=PS,
                          kscale=ks, vscale=vs, k_new=kn, v_new=vn,
                          interpret=True)
        rp = flash_decode(q, pk8, pv8, tl, 1, kvp=KVP, rr_block=RR,
                          kscale=pks, vscale=pvs, k_new=kn, v_new=vn,
                          block_tables=tables,
                          interpret=True)
    else:
        rf = flash_decode(q, k, v, tl, 1, kvp=KVP, rr_block=RR, block_s=PS,
                          k_new=kn, v_new=vn,
                          interpret=True)
        rp = flash_decode(q, pk, pv, tl, 1, kvp=KVP, rr_block=RR,
                          k_new=kn, v_new=vn, block_tables=tables,
                          interpret=True)
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


def test_paged_accounting_matches_fixed_bound():
    """Paged accounting replays the same logical ranges: identical visited
    counts at the same block size, and the prune_smoke bound
    (<= ceil(valid_len/block_s) + 1 per (b, h)) still holds."""
    from repro.kernels.flash_decode.ref import local_valid_len
    q, k, v, pk, pv, tables = make_case(5)
    tl = TLS[0]
    fixed = flash_decode_accounting(q, k, v, tl, 1, kvp=KVP, rr_block=RR,
                                    block_s=PS, prune=True)
    paged = flash_decode_accounting(q, pk, pv, tl, 1, kvp=KVP, rr_block=RR,
                                    prune=True, block_tables=tables)
    assert paged["blocks_visited"] == fixed["blocks_visited"]
    assert paged["block_s"] == PS and paged["n_blocks"] == MP
    for b in range(B):
        valid = int(local_valid_len(jnp.asarray(tl)[b], 1, KVP, RR))
        bound = -(-valid // PS) + 1
        per_bh = flash_decode_accounting(
            q[b:b + 1], pk, pv, jnp.asarray(tl)[b:b + 1], 1, kvp=KVP,
            rr_block=RR, prune=True,
            block_tables=tables[b:b + 1])["blocks_visited"] / KH
        assert per_bh <= bound


def test_sink_entries_are_harmless():
    """Table entries past a request's extent point at the sink page 0;
    the masked sweep over them must not change the output (dense prune=False
    sweep reads them, masks them)."""
    q, k, v, pk, pv, tables = make_case(6)
    short = jnp.asarray([40, 40, 40], jnp.int32)   # < 1 page of positions
    trimmed = np.asarray(tables).copy()
    trimmed[:, 1:] = 0                             # only page 0 allocated
    of, _ = flash_decode(q, k, v, short, 1, kvp=KVP, rr_block=RR,
                         block_s=PS, prune=False,
                         interpret=True)
    op, _ = flash_decode(q, pk, pv, short, 1, kvp=KVP, rr_block=RR,
                         prune=False, block_tables=jnp.asarray(trimmed),
                         interpret=True)
    np.testing.assert_array_equal(np.asarray(of), np.asarray(op))
