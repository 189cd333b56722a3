"""flash_prefill ragged q_offset + paged-KV contracts.

Per-row ``q_offset`` is the ragged chunk-packing contract: a packed call
whose row ``i`` carries ``q_offset[i]`` must be bit-identical, row for
row, to solo calls at scalar ``q_offset[i]`` — on the kernel backend AND
the ref backend (``chunked_attention``); the paged mode streams the KV
operand through a block table and must match the fixed layout exactly."""
import numpy as np
import pytest
import jax
import jax.numpy as jnp

from repro.kernels.flash_prefill.ops import (flash_prefill,
                                             flash_prefill_accounting)
from repro.models.attention import chunked_attention

B, T, QH, KH, HSZ = 3, 16, 4, 2, 32
S = 64


def make_case(seed=0):
    rng = np.random.default_rng(seed)
    q = jnp.asarray(rng.standard_normal((B, T, QH, HSZ), np.float32))
    k = jnp.asarray(rng.standard_normal((B, S, KH, HSZ), np.float32))
    v = jnp.asarray(rng.standard_normal((B, S, KH, HSZ), np.float32))
    return q, k, v


OFFS = np.asarray([0, 12, 29], np.int32)


@pytest.mark.parametrize("backend", ["ref", "pallas-interpret"])
@pytest.mark.parametrize("window", [0, 24])
def test_per_row_q_offset_matches_solo(backend, window):
    q, k, v = make_case()
    lens = jnp.asarray([48, 64, 33], jnp.int32)

    def attend(qi, ki, vi, off, lens_i):
        if backend == "ref":
            return chunked_attention(qi, ki, vi, causal=True, window=window,
                                     q_offset=off, seq_lens=lens_i,
                                     chunk_q=8)
        return flash_prefill(qi, ki, vi, causal=True, window=window,
                             q_offset=off, seq_lens=lens_i,
                             blk_q=8, blk_k=16,
                             interpret=True)

    packed = attend(q, k, v, jnp.asarray(OFFS), lens)
    for i, off in enumerate(OFFS):
        solo = attend(q[i:i + 1], k[i:i + 1], v[i:i + 1], int(off),
                      lens[i:i + 1])
        np.testing.assert_array_equal(np.asarray(packed[i]),
                                      np.asarray(solo[0]))


def test_ragged_ref_matches_kernel():
    q, k, v = make_case(1)
    lens = jnp.asarray([40, 64, 20], jnp.int32)
    a = chunked_attention(q, k, v, causal=True, q_offset=jnp.asarray(OFFS),
                          seq_lens=lens, chunk_q=8)
    b = flash_prefill(q, k, v, causal=True, q_offset=jnp.asarray(OFFS),
                      seq_lens=lens, blk_q=8, blk_k=16,
                      interpret=True)
    np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                               rtol=2e-6, atol=2e-6)


@pytest.mark.parametrize("prune", [True, False])
def test_paged_prefill_equals_fixed(prune):
    """KV streamed through a shuffled block table == the dense layout."""
    rng = np.random.default_rng(2)
    page = 16
    mp = S // page
    q, k, v = make_case(3)
    n_pool = 1 + B * mp
    tables = np.zeros((B, mp), np.int32)
    perm = rng.permutation(np.arange(1, n_pool))
    pool_k = jnp.zeros((n_pool, KH, page, HSZ), jnp.float32)
    pool_v = jnp.zeros((n_pool, KH, page, HSZ), jnp.float32)
    i = 0
    for b in range(B):
        for p in range(mp):
            phys = int(perm[i]); i += 1
            tables[b, p] = phys
            pool_k = pool_k.at[phys].set(
                k[b, p * page:(p + 1) * page].transpose(1, 0, 2))
            pool_v = pool_v.at[phys].set(
                v[b, p * page:(p + 1) * page].transpose(1, 0, 2))
    lens = jnp.asarray([48, 64, 33], jnp.int32)
    fixed = flash_prefill(q, k, v, causal=True, q_offset=jnp.asarray(OFFS),
                          seq_lens=lens, blk_q=8, blk_k=page, prune=prune,
                          interpret=True)
    paged = flash_prefill(q, pool_k, pool_v, causal=True,
                          q_offset=jnp.asarray(OFFS), seq_lens=lens,
                          blk_q=8, prune=prune,
                          block_tables=jnp.asarray(tables),
                          interpret=True)
    np.testing.assert_array_equal(np.asarray(fixed), np.asarray(paged))
    # accounting: indirection does not change the visited-block count
    af = flash_prefill_accounting(q, k, v, causal=True,
                                  q_offset=jnp.asarray(OFFS), seq_lens=lens,
                                  blk_q=8, blk_k=page, prune=prune)
    ap = flash_prefill_accounting(q, pool_k, pool_v, causal=True,
                                  q_offset=jnp.asarray(OFFS), seq_lens=lens,
                                  blk_q=8, prune=prune,
                                  block_tables=jnp.asarray(tables))
    assert af["blocks_visited"] == ap["blocks_visited"]
    assert ap["blk_k"] == page and ap["n_kblocks"] == mp


def test_scalar_offset_unchanged():
    """Scalar q_offset keeps the pre-ragged semantics bit-exactly (the
    broadcast [B] prefetch is the same value per row)."""
    q, k, v = make_case(4)
    a = flash_prefill(q, k, v, causal=True, q_offset=7, blk_q=8, blk_k=16,
                      interpret=True)
    b = flash_prefill(q, k, v, causal=True,
                      q_offset=jnp.full((B,), 7, jnp.int32),
                      blk_q=8, blk_k=16, interpret=True)
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
