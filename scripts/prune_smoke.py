#!/usr/bin/env python
"""CI smoke for length/causality-aware block pruning (scripts/ci.sh).

Asserts the PR's acceptance criteria cheaply (small shapes, seconds):

  1. flash_decode with pruning visits <= ceil(local_valid_len / block_s) + 1
     K/V blocks per (b, h) (per row in the paged layout) at short lengths —
     not S_cap / block_s — and the windowed case caps at O(window / block_s);
  2. causal flash_prefill visits ~the lower triangle (~55% for deep grids)
     of the (T/blk_q) x (S/blk_k) rectangle;
  3. pruned and unpruned kernel outputs are bit-exact in both families.

Run directly:  PYTHONPATH=src python scripts/prune_smoke.py
"""
from __future__ import annotations

import pathlib
import sys

ROOT = pathlib.Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT / "src"))

import jax                                                     # noqa: E402
import jax.numpy as jnp                                        # noqa: E402
import numpy as np                                             # noqa: E402

from repro.kernels import registry                             # noqa: E402
from repro.kernels.flash_decode import (flash_decode,          # noqa: E402
                                        local_valid_len)
from repro.kernels.flash_prefill import flash_prefill          # noqa: E402
from repro.utils import cdiv                                   # noqa: E402


def main() -> int:
    # ---- decode: short request in a large-capacity shard ----
    b, qh, kh, hsz, s_cap = 2, 8, 2, 64, 256
    kvp, rr, block_s, rank = 4, 16, 32, 1
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(ks[0], (b, qh, hsz))
    k = jax.random.normal(ks[1], (b, kh, s_cap, hsz))
    v = jax.random.normal(ks[2], (b, kh, s_cap, hsz))
    account = registry.accounting("flash_decode")

    total_len = 100                      # ~25 valid local slots of 256
    for window in (0, 48):
        acc = account(q, k, v, total_len, rank, kvp=kvp, rr_block=rr,
                      window=window, block_s=block_s, prune=True)
        dense = account(q, k, v, total_len, rank, kvp=kvp, rr_block=rr,
                        window=window, block_s=block_s, prune=False)
        valid = int(local_valid_len(jnp.asarray(total_len), rank, kvp, rr))
        bound = cdiv(valid, block_s) + 1
        per_bh = acc["blocks_visited"] / (b * kh)
        assert per_bh <= bound, (per_bh, bound)
        assert acc["blocks_visited"] < dense["blocks_total"], acc
        out_p, lse_p = flash_decode(q, k, v, total_len, rank, kvp=kvp,
                                    rr_block=rr, window=window,
                                    block_s=block_s, prune=True,
                                    interpret=True)
        out_d, lse_d = flash_decode(q, k, v, total_len, rank, kvp=kvp,
                                    rr_block=rr, window=window,
                                    block_s=block_s, prune=False,
                                    interpret=True)
        np.testing.assert_array_equal(np.asarray(out_p), np.asarray(out_d))
        np.testing.assert_array_equal(np.asarray(lse_p), np.asarray(lse_d))
        print(f"[prune_smoke] decode window={window}: "
              f"{acc['blocks_visited']}/{dense['blocks_total']} blocks "
              f"(<= {bound}/ (b,h)), outputs bit-exact")

    # ---- paged decode: table indirection keeps the same bound ----
    ps = block_s                              # page rows == kernel block
    mp = s_cap // ps
    tables = np.zeros((b, mp), np.int32)
    perm = np.random.default_rng(0).permutation(np.arange(1, 1 + b * mp))
    pool_k = jnp.zeros((1 + b * mp, kh, ps, 64), jnp.float32)
    pool_v = jnp.zeros((1 + b * mp, kh, ps, 64), jnp.float32)
    i = 0
    for bb in range(b):
        for p in range(mp):
            phys = int(perm[i]); i += 1
            tables[bb, p] = phys
            pool_k = pool_k.at[phys].set(k[bb, :, p * ps:(p + 1) * ps])
            pool_v = pool_v.at[phys].set(v[bb, :, p * ps:(p + 1) * ps])
    # A paged grid step covers every KV head of one row; the fixed layout
    # counts one block per (b, h), so at equal block_s they differ by Kh.
    accp = account(q, pool_k, pool_v, total_len, rank, kvp=kvp, rr_block=rr,
                   block_s=ps, prune=True, block_tables=tables)
    accf = account(q, k, v, total_len, rank, kvp=kvp, rr_block=rr,
                   block_s=ps, prune=True)
    assert accp["blocks_visited"] * kh == accf["blocks_visited"], (accp, accf)
    valid = int(local_valid_len(jnp.asarray(total_len), rank, kvp, rr))
    assert accp["blocks_visited"] / b <= cdiv(valid, ps) + 1
    out_f, _ = flash_decode(q, k, v, total_len, rank, kvp=kvp, rr_block=rr,
                            block_s=ps, prune=True, interpret=True)
    out_g, _ = flash_decode(q, pool_k, pool_v, total_len, rank, kvp=kvp,
                            rr_block=rr, block_s=ps, prune=True,
                            block_tables=jnp.asarray(tables), interpret=True)
    np.testing.assert_array_equal(np.asarray(out_f), np.asarray(out_g))
    print(f"[prune_smoke] paged decode: {accp['blocks_visited']} blocks "
          f"through the block table (x Kh == fixed), outputs bit-exact")

    # ---- prefill: causal triangle ----
    t = s = 320
    blk = 32
    ks = jax.random.split(jax.random.PRNGKey(1), 3)
    qp = jax.random.normal(ks[0], (1, t, 4, 32))
    kp = jax.random.normal(ks[1], (1, s, 2, 32))
    vp = jax.random.normal(ks[2], (1, s, 2, 32))
    paccount = registry.accounting("flash_prefill")
    acc = paccount(qp, kp, vp, causal=True, blk_q=blk, blk_k=blk, prune=True)
    frac = acc["blocks_visited"] / acc["blocks_total"]
    n = acc["n_qblocks"]
    assert abs(frac - (n + 1) / (2 * n)) < 1e-9, (frac, n)
    assert frac <= 0.56, frac
    out_p = flash_prefill(qp, kp, vp, causal=True, blk_q=blk, blk_k=blk,
                          prune=True, interpret=True)
    out_d = flash_prefill(qp, kp, vp, causal=True, blk_q=blk, blk_k=blk,
                          prune=False, interpret=True)
    np.testing.assert_array_equal(np.asarray(out_p), np.asarray(out_d))
    print(f"[prune_smoke] prefill causal: {frac * 100:.0f}% of the "
          f"rectangle visited, outputs bit-exact")
    print("[prune_smoke] OK")
    return 0


if __name__ == "__main__":
    sys.exit(main())
