"""Pallas TPU flash-prefill kernel (full-sequence attention).

TPU mapping
-----------
  grid = (B, Kh, T/blk_q, S/blk_k)   — kv blocks innermost; online-softmax
                                       state (m, l, acc) lives in VMEM scratch
                                       and persists across the kv loop.
  q block   (G, blk_q, hsz) resident per (b, h, qi); the G query heads of
                            one kv head stack along rows (G*blk_q, hsz)
  k/v block (blk_k, hsz)    streamed HBM->VMEM
  out       written at the last kv step (full row normalized)

Masking semantics (shared with ref.py) are computed in-kernel from prefetched
scalars only — no per-position mask array is read from HBM:

  meta [1] int32 : (window,) — window <= 0 disables the sliding-window mask
                   and is a *runtime* scalar, so traced per-layer windows
                   (gemma3 local/global scan) work.
  lens [B] int32 : per-request valid KV lengths (continuous-batching prefill
                   over right-padded prompts); kv positions >= lens[b] are
                   masked.  Uniform batches prefetch a broadcast scalar.
  offs [B] int32 : *per-request* q_offset — the global position of query
                   row 0 (prefill continuation).  Per-row offsets are what
                   let the serving engine pack requests at different
                   (offset, length) prefill progress into ONE ragged chunk
                   call (docs/serving.md); uniform batches prefetch a
                   broadcast scalar.

``causal`` is a static kernel parameter: True for decoder self-attention
(key <= query), False for encoder-decoder cross attention (whisper), where
T != S and only the lens/capacity masks apply.  Slots >= the true (unpadded)
S are masked unconditionally, so S padding is exact even without causality.
Fully-masked rows (lens[b] == 0) emit zeros, not NaNs.

Causal/window block skipping (``prune=True``, the default)
----------------------------------------------------------
For one query block the contributing kv positions form a contiguous span:
``kpos < min(s_true, lens[b])`` and, causally, ``kpos <= qpos_max``; with a
sliding window additionally ``kpos > qpos_min - window``.  The kernel clamps
the K/V ``index_map`` to that span — grid step ``ki`` streams physical block
``min(lo + ki, hi - 1)``, so every skipped step re-references the previous
block and Pallas TPU elides the HBM->VMEM DMA — and skips the compute body
with ``pl.when``.  For causal T = S this drops the visited rectangle to its
lower triangle (~(n+1)/2n of the full sweep); a window caps it at
O(window/blk_k) blocks per query row.  Bit-exact vs the masked sweep (a
fully-masked block contributes the identity online-softmax update).
``prefill_block_range`` is the single source of truth; the accounting layer
(ops.py) replays it to report visited blocks/bytes.  MXU contraction dims
are hsz / blk_k (multiples of 128 for aligned configs).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.utils import NEG_INF
from repro.kernels.pruning import phys_block as _phys_block


def prefill_block_range(qi, kv_len, q_offset, window, *, causal: bool,
                        blk_q: int, blk_k: int, s_true: int):
    """(first_kv_block, n_valid_kv_blocks) for query block ``qi``.

    The single source of truth for prefill block skipping: the kernel's K/V
    ``index_map``s clamp to this range and its body skips compute outside
    it; ``ops.flash_prefill_accounting`` replays it to count streamed
    blocks.  ``qi``/``kv_len``/``q_offset``/``window`` may be traced scalars
    (grid index + scalar-prefetch values).
    """
    kv_len = jnp.asarray(kv_len, jnp.int32)
    window = jnp.asarray(window, jnp.int32)
    hi_slot = jnp.minimum(s_true, kv_len)
    if causal:
        # a kv slot is causally reachable iff kpos <= the block's last qpos
        hi_slot = jnp.minimum(hi_slot, q_offset + (qi + 1) * blk_q)
    lo_slot = jnp.where(
        window > 0,
        jnp.clip(q_offset + qi * blk_q - window + 1, 0, s_true), 0)
    lo = lo_slot // blk_k
    hi = (hi_slot + blk_k - 1) // blk_k
    return lo, jnp.maximum(hi - lo, 0)


def prefill_index_maps(*, causal: bool, blk_q: int, blk_k: int, s_true: int,
                       n_kblocks: int, prune: bool, paged: bool):
    """Named index_map callables for one prefill-kernel configuration.

    The single source of truth for the kernel's DMA addressing:
    ``flash_prefill_kernel`` passes exactly these callables to
    ``pallas_call``, and ``ops.flash_prefill_contract`` exposes the same
    callables to the static index-space auditor (``repro.analysis``).

    Every map takes ``(b, h, qi, ki, meta_ref, len_ref, off_ref,
    [tables_ref])`` and is a pure jnp function of its arguments (no
    data-dependent python branches; see ``kernels/pruning.py``).  Keys:

      kv  streamed K/V blocks (1, 1, blk_k, hsz); skip-clamped, and
          table-indirected in paged mode
      q   resident query / output blocks (1, 1, G, blk_q, hsz) (constant
          along the kv axis)
    """

    def kv_idx(b, h, qi, ki, meta_ref, len_ref, off_ref, *rest):
        if prune:
            lo, nb = prefill_block_range(
                qi, len_ref[b], off_ref[b], meta_ref[0], causal=causal,
                blk_q=blk_q, blk_k=blk_k, s_true=s_true)
            lg = _phys_block(ki, lo, nb, n_kblocks)
        else:
            lg = ki
        if paged:
            return (rest[0][b, lg], h, 0, 0)
        return (b, h, lg, 0)

    def q_idx(b, h, qi, ki, *_):
        return (b, h, 0, qi, 0)

    return {"kv": kv_idx, "q": q_idx}


def _prefill_kernel(meta_ref, len_ref, off_ref, *refs, scale: float,
                    causal: bool, blk_q: int, blk_k: int, g: int, hsz: int,
                    s_true: int, prune: bool, paged: bool):
    if paged:
        _tbl_ref, q_ref, k_ref, v_ref, o_ref, acc_ref, m_ref, l_ref = refs
    else:
        q_ref, k_ref, v_ref, o_ref, acc_ref, m_ref, l_ref = refs
    bi = pl.program_id(0)
    qi = pl.program_id(2)
    ki = pl.program_id(3)
    n_kblocks = pl.num_programs(3)
    q_offset = off_ref[bi]
    window = meta_ref[0]
    kv_len = len_ref[bi]

    @pl.when(ki == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    if prune:
        lo_blk, nb = prefill_block_range(qi, kv_len, q_offset, window,
                                         causal=causal, blk_q=blk_q,
                                         blk_k=blk_k, s_true=s_true)
        phys = _phys_block(ki, lo_blk, nb, n_kblocks)
        active = ki < nb
    else:
        phys, active = ki, None

    def _compute():
        q = q_ref[0, 0].astype(jnp.float32) * scale      # [G, blq, hsz]
        k = k_ref[0, 0].astype(jnp.float32)              # [blk, hsz]
        v = v_ref[0, 0].astype(jnp.float32)              # [blk, hsz]

        s = jax.lax.dot_general(q.reshape(g * blk_q, hsz), k,
                                (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        s = s.reshape(g, blk_q, blk_k)

        qpos = q_offset + qi * blk_q \
            + jax.lax.broadcasted_iota(jnp.int32, (1, blk_q, 1), 1)
        kpos = phys * blk_k \
            + jax.lax.broadcasted_iota(jnp.int32, (1, 1, blk_k), 2)
        # true-capacity + per-request-length masks apply in every mode; the
        # causal / sliding-window masks only relate q and kv positions.
        mask = jnp.logical_and(kpos < s_true, kpos < kv_len)
        if causal:
            mask = jnp.logical_and(mask, kpos <= qpos)
        mask = jnp.logical_and(
            mask, jnp.logical_or(window <= 0, kpos > qpos - window))
        s = jnp.where(mask, s, NEG_INF)

        s2 = s.reshape(g * blk_q, blk_k)
        mask2 = jnp.broadcast_to(mask, (g, blk_q, blk_k)).reshape(
            g * blk_q, blk_k)
        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, jnp.max(s2, axis=1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        # masked lanes must not contribute when a whole row is masked
        # (m_new == NEG_INF => exp(0) == 1 would pollute l), so gate p.
        p = jnp.where(mask2, jnp.exp(s2 - m_new), 0.0)
        l_ref[...] = alpha * l_ref[...] + jnp.sum(p, axis=1, keepdims=True)
        acc_ref[...] = alpha * acc_ref[...] + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
        m_ref[...] = m_new

    if prune:
        pl.when(active)(_compute)
    else:
        _compute()

    @pl.when(ki == n_kblocks - 1)
    def _finalize():
        l = l_ref[...]
        denom = jnp.maximum(l, 1e-37)
        out = jnp.where(l > 0, acc_ref[...] / denom, 0.0)
        o_ref[0, 0] = out.reshape(g, blk_q, hsz).astype(o_ref.dtype)


def flash_prefill_kernel(q, k, v, meta, lens, offs, *, scale: float,
                         causal: bool, blk_q: int, blk_k: int, s_true: int,
                         prune: bool = True, block_tables=None,
                         interpret: bool):
    """Raw pallas_call.  Shapes must already be padded/blocked (see ops.py).

    q [B, Kh, G, T_pad, hsz]; k, v [B, Kh, S_pad, hsz]; meta [1] int32
    (window,); lens [B] int32 per-request valid KV lengths; offs [B] int32
    per-request q_offset (ragged chunk packing);
    s_true: unpadded S (slots >= s_true are masked); prune: skip (don't
    mask) kv blocks that are causally/window/length-dead (bit-exact).

    Paged mode (``block_tables`` [B, max_pages] int32, scalar-prefetched):
    k/v are shared pool planes ``[n_pool, Kh, blk_k, hsz]``; grid step
    ``ki`` streams physical page ``block_tables[b, logical]`` where
    ``logical`` is the fixed layout's (possibly skip-clamped) kv-block id.
    All masking runs on logical positions, so paged == fixed bit-exactly.

    Returns out [B, Kh, G, T_pad, hsz] in q.dtype.
    """
    b, kh, g, t, hsz = q.shape
    paged = block_tables is not None
    if paged:
        assert k.shape[2] == blk_k, (k.shape, blk_k)
        n_kblocks = block_tables.shape[1]
        s = n_kblocks * blk_k
    else:
        s = k.shape[2]
        assert s % blk_k == 0
        n_kblocks = s // blk_k
    assert t % blk_q == 0

    grid = (b, kh, t // blk_q, n_kblocks)
    kernel = functools.partial(_prefill_kernel, scale=scale, causal=causal,
                               blk_q=blk_q, blk_k=blk_k, g=g, hsz=hsz,
                               s_true=s_true, prune=prune, paged=paged)

    idx = prefill_index_maps(causal=causal, blk_q=blk_q, blk_k=blk_k,
                             s_true=s_true, n_kblocks=n_kblocks, prune=prune,
                             paged=paged)
    kv_idx, q_idx = idx["kv"], idx["q"]

    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4 if paged else 3,
            grid=grid,
            in_specs=[
                pl.BlockSpec((1, 1, g, blk_q, hsz), q_idx),
                pl.BlockSpec((1, 1, blk_k, hsz), kv_idx),
                pl.BlockSpec((1, 1, blk_k, hsz), kv_idx),
            ],
            out_specs=pl.BlockSpec((1, 1, g, blk_q, hsz), q_idx),
            scratch_shapes=[
                pltpu.VMEM((blk_q * g, hsz), jnp.float32),
                pltpu.VMEM((blk_q * g, 1), jnp.float32),
                pltpu.VMEM((blk_q * g, 1), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((b, kh, g, t, hsz), q.dtype),
        interpret=interpret,
        name="flash_prefill",
    )(*((meta, lens, offs) + ((block_tables,) if paged else ())
        + (q, k, v)))
