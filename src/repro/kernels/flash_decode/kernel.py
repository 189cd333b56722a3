"""Pallas TPU flash-decode kernel (Helix attention phase hotspot).

Decode-shape attention: one new query token per sequence against a (possibly
round-robin-sharded) KV cache shard.  Emits the partial output *and* the
log-sum-exp — the Helix combine (core/combine.py) needs both.

TPU mapping
-----------
  grid = (B, Kh, S_cap / block_s)   — S blocks iterated innermost so the
                                      online-softmax state lives in VMEM scratch
  q block   (1, 1, Qp, hsz)  : the Qp = padded Q-per-KV-head group, resident
  k/v block (1, 1, bs, hsz)  : streamed HBM->VMEM, bs a multiple of 128 (MXU)
  scale blk (1, 1, bs)       : int8-cache dequant scales (quant mode only)
  scratch   acc f32 (Qp,hsz), m/l f32 (Qp,1)

The two matmuls per block — (Qp,hsz)@(hsz,bs) and (Qp,bs)@(bs,hsz) — keep the
MXU contraction dims at hsz/bs multiples of 128 (hsz=64 archs pad lanes
internally).  VMEM footprint per step: 2*bs*hsz*2B (K,V) + Qp*hsz*4B + O(Qp),
e.g. bs=512, hsz=128: ~288 KiB — far under the ~16 MiB/core VMEM budget, so the
grid pipeline can double-buffer the K/V streams.

Masking semantics match ref.py and are computed in-kernel from prefetched
scalars only — no per-slot position array is read from HBM:

  meta [3] int32 : (rank, slot_offset, window) — slot_offset shifts the local
                   slot index (the sliding-window cache-slice fast path);
                   window <= 0 disables the sliding-window mask, and is a
                   *runtime* scalar so traced per-layer windows work.
  tl   [B] int32 : per-request global sequence lengths (continuous batching);
                   uniform batches prefetch a broadcast scalar.

Layouts: round-robin (§2.3) pos = ((j//rr)*kvp + rank)*rr + j%rr, or
contiguous (whisper cross-attention KV split) pos = rank*S_true + j.  Slots
j >= S_true (the unpadded local capacity) are masked unconditionally, so S
padding is exact in both layouts.

Block pruning (``prune=True``, the default)
-------------------------------------------
Positions are strictly increasing in the local slot index in *both* layouts,
so the valid slots of a request form one contiguous span ``[jj_lo, jj_hi)``
(``jj_lo > 0`` only with a sliding window).  Instead of sweeping the full
padded capacity and masking dead blocks, the kernel

  1. clamps the K/V (and scale) ``index_map`` to that span — grid step ``s``
     streams physical block ``min(lo + s, hi - 1)``, so every pruned step
     references the block of the previous step and Pallas TPU elides the
     HBM->VMEM DMA entirely;
  2. skips the compute body of pruned steps with ``pl.when``.

Per-step HBM traffic drops from O(S_cap) to O(valid_len) per request —
O(window) for sliding-window layers, which subsumes the caller-side
dynamic-slice fast path (``slot_offset``) and composes with every other mode
(per-request lengths, contiguous layout, quant, fused append).  Pruned and
unpruned results are bit-identical: a fully-masked block contributes the
identity online-softmax update.  ``prune_block_range`` is the single source
of truth for the span; the block-accounting layer (ops.py) replays it to
report blocks/bytes actually streamed.

Quant mode (§Perf kv8): K/V arrive int8 with per-(B, Kh, slot) f32 scales and
are dequantized block-by-block in VMEM — the f32 copy of the shard never
exists in HBM.

Fused KV-append epilogue (append mode)
--------------------------------------
The rr-slot ``append_kv`` update is fused into the kernel: the caller passes
the *pre-append* cache plus the new token's K/V row, and the kernel

  1. substitutes the new row into the streamed K/V tile in VMEM for the
     attention compute (the HBM block containing the target slot is stale),
  2. writes the row back to the cache through a (1, 1, rw, hsz) output
     window (``append_rows``: the sublane tile holding the row) whose
     index_map derives the target slot from the prefetched per-request
     lengths — ``input_output_aliases`` makes these outputs *the same HBM
     buffers* as the K/V inputs, so the rest of the cache is untouched and
     the separate append pass (one full-cache HBM round-trip per layer per
     decode step) disappears.

The row window is re-written (idempotently) at every S-block step, so the
kernel is correct under both write-back policies Pallas implementations use
(every visit, or last visit only).  The window's other rows, and the whole
window on non-owner ranks (round-robin: the new position lives on exactly
one KVP rank), are written back unmodified from a matching (1, 1, rw, hsz)
*input* window.  Append mode composes with
per-request [B] lengths (each row appends at its own slot) but excludes the
contiguous layout (static cross-attention KV is never appended) and the
``slot_offset`` cache-slice path — the Helix caller falls back to the
unfused ``append_kv`` there (core/helix.py).

int8 append (append + quant): the new token's row arrives *unquantized*
(f32); the kernel quantizes it in VMEM with the same per-(B, Kh) symmetric
formula as ``core/helix.quantize_kv_token`` (scale = max|x|/127, round,
clip) and persists payload + scale through aliased (1, 1, rw, hsz) /
(1, 1, rw) windows, so the fused path is bit-exact with ``append_kv_quant`` followed
by the attention pass.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.utils import NEG_INF
from repro.kernels.flash_decode.ref import local_valid_len
from repro.kernels.pruning import phys_block as _phys_block
from repro.kernels.pruning import table_block as _table_block


def append_rows(block_s: int) -> int:
    """Rows of the fused-append write window: the widest sublane tile
    (8 rows at 32 bits, 16 at 16, 32 at 8) that divides the S block.  A
    one-row window is refused by Mosaic (a block's second-minor dim must be
    a multiple of 8 or the whole array dim); a paged page of rr_block rows
    is written whole."""
    import math
    return math.gcd(block_s, 32)


def _append_slot(total_len, kvp: int, rr_block: int, s_max: int):
    """Local rr slot of the appended token (position total_len - 1), clamped
    to the padded capacity.  Rank-independent (same formula on every rank);
    ownership is a separate check."""
    pos = total_len - 1
    blk = pos // rr_block
    j = (blk // kvp) * rr_block + pos % rr_block
    return jnp.clip(j, 0, s_max - 1)


def _quantize_row(x):
    """In-kernel mirror of ``core/helix.quantize_kv_token`` for one [hsz]
    f32 row: (int8-valued f32 payload, f32 scale).  Must stay formula-exact
    with the host-side version so fused int8 append is bit-identical."""
    scale = jnp.maximum(jnp.max(jnp.abs(x)) / 127.0, 1e-30)
    q = jnp.clip(jnp.round(x / scale), -127, 127)
    return q, scale


def valid_slot_span(total_len, rank, slot_offset, window, *, kvp: int,
                    rr_block: int, s_true: int, contiguous: bool):
    """``[jj_lo, jj_hi)`` — the physical-slot span that can hold unmasked
    slots for one request.

    Positions are strictly increasing in the local slot index in both
    layouts, so ``pos < total_len`` bounds a prefix and (with a window)
    ``pos >= total_len - window`` bounds a suffix; their intersection is one
    contiguous span.  All arguments may be traced scalars (this runs inside
    Pallas ``index_map``s against prefetched scalars).
    """
    total_len = jnp.maximum(jnp.asarray(total_len, jnp.int32), 0)
    window = jnp.asarray(window, jnp.int32)
    if contiguous:
        j_hi = total_len - rank * s_true
        j_lo = total_len - window - rank * s_true
    else:
        j_hi = local_valid_len(total_len, rank, kvp, rr_block)
        j_lo = local_valid_len(jnp.maximum(total_len - window, 0), rank, kvp,
                               rr_block)
    jj_hi = jnp.clip(j_hi - slot_offset, 0, s_true)
    jj_lo = jnp.where(window > 0, jnp.clip(j_lo - slot_offset, 0, s_true), 0)
    return jj_lo, jj_hi


def prune_block_range(total_len, rank, slot_offset, window, *, kvp: int,
                      rr_block: int, block_s: int, s_true: int,
                      contiguous: bool = False):
    """(first_block, n_valid_blocks) of the S-block span a request can touch.

    The single source of truth for decode block pruning: the kernel's K/V
    ``index_map``s clamp to this range (so pruned grid steps re-reference the
    previous block and the DMA is elided), the kernel body skips compute
    outside it, and ``ops.flash_decode_accounting`` replays it to count the
    blocks/bytes actually streamed.
    """
    jj_lo, jj_hi = valid_slot_span(total_len, rank, slot_offset, window,
                                   kvp=kvp, rr_block=rr_block, s_true=s_true,
                                   contiguous=contiguous)
    lo = jj_lo // block_s
    hi = (jj_hi + block_s - 1) // block_s
    return lo, jnp.maximum(hi - lo, 0)


def decode_index_maps(*, kvp: int, rr_block: int, block_s: int, s_true: int,
                      n_blocks: int, contiguous: bool, prune: bool,
                      paged: bool, grouped: bool = False):
    """Named index_map callables for one decode-kernel configuration.

    The single source of truth for the kernel's DMA addressing:
    ``flash_decode_kernel`` passes exactly these callables to
    ``pallas_call``, and ``ops.flash_decode_contract`` exposes the same
    callables to the static index-space auditor (``repro.analysis``), so
    what the auditor proves is what the kernel runs.

    Every map takes ``(b, h, s, meta_ref, tl_ref, [tables_ref])`` — the
    grid coordinates then the scalar-prefetch operands — and is a pure jnp
    function of them (no data-dependent python branches; see
    ``kernels/pruning.py``).  Keys:

      kv     streamed K/V blocks (1, 1, block_s, hsz); prune-clamped, and
             table-indirected in paged mode
      scale  streamed dequant-scale blocks (1, 1, block_s); same clamp
      row    fused-append (1, 1, rw, hsz) window holding the new token's
             row (``rw = append_rows(block_s)``; block index in rw units)
      srow   fused-append (1, 1, rw) scale-row window
      q      resident query block (constant along the S axis)
      new    the new token's (1, 1, 1, hsz) K/V row (resident)
      lse    the [B, Kh, Qp, 1] log-sum-exp output (a column, so the block's
             minor dims equal the array's — Mosaic's tiling rule)

    ``grouped`` (suffix pass of the shared-prefix grouped decode — paged
    only): a fourth prefetch operand ``start [B]`` gives each request's
    first *unshared* logical page; the pruned span's lower bound is lifted
    to it, so the shared prefix pages — already streamed once per group by
    the prefix pass (``grouped_prefix_index_maps``) — are never re-read
    per request.  Maps then take ``(b, h, s, meta, tl, tables, start)``.
    """
    s_pad = n_blocks * block_s
    rw = append_rows(block_s)
    assert not grouped or paged, "grouped suffix maps require paged mode"

    def logical_block(s, meta_ref, tl_ref, b, *rest):
        # pruned steps re-reference the previous step's block: the DMA is
        # elided, so HBM reads scale with the valid length, not capacity
        if not prune:
            return s
        lo, nb = prune_block_range(
            tl_ref[b], meta_ref[0], meta_ref[1], meta_ref[2], kvp=kvp,
            rr_block=rr_block, block_s=block_s, s_true=s_true,
            contiguous=contiguous)
        if grouped:
            # suffix pass: blocks below the request's shared-prefix extent
            # were streamed by the prefix pass — lift the span above them
            lo2 = jnp.maximum(lo, rest[1][b])
            nb = jnp.maximum(lo + nb - lo2, 0)
            lo = lo2
        return _phys_block(s, lo, nb, n_blocks)

    def kv_idx(b, h, s, meta_ref, tl_ref, *rest):
        # paged: the physical pool page comes from the prefetched table at
        # the (clamped) logical id — same id as the fixed layout, so the
        # DMA-elision property survives the indirection (pruning.table_block)
        lg = logical_block(s, meta_ref, tl_ref, b, *rest)
        if paged:
            return (rest[0][b, lg], h, 0, 0)
        return (b, h, lg, 0)

    def scale_idx(b, h, s, meta_ref, tl_ref, *rest):
        return kv_idx(b, h, s, meta_ref, tl_ref, *rest)[:3]

    def row_idx(b, h, s, meta_ref, tl_ref, *rest):
        # window holding the appended token's row; depends on the prefetched
        # per-request length only (rank-independent slot formula)
        j_new = _append_slot(tl_ref[b], kvp, rr_block, s_pad)
        if paged:
            return (rest[0][b, j_new // block_s], h,
                    (j_new % block_s) // rw, 0)
        return (b, h, j_new // rw, 0)

    def srow_idx(b, h, s, meta_ref, tl_ref, *rest):
        return row_idx(b, h, s, meta_ref, tl_ref, *rest)[:3]

    def q_idx(b, h, s, *_):
        return (b, h, 0, 0)

    def new_idx(b, h, s, *_):
        return (b, h, 0, 0)

    def lse_idx(b, h, s, *_):
        return (b, h, 0, 0)

    return {"kv": kv_idx, "scale": scale_idx, "row": row_idx,
            "srow": srow_idx, "q": q_idx, "new": new_idx, "lse": lse_idx}


def _decode_kernel(meta_ref, tl_ref, *refs, scale: float,
                   kvp: int, rr_block: int, block_s: int, s_true: int,
                   contiguous: bool, quant: bool, append: bool, prune: bool,
                   paged: bool, grouped: bool = False):
    if paged:
        tbl_ref, *refs = refs
    if grouped:
        # suffix pass of the grouped shared-prefix decode: one more prefetch
        # operand (per-request first unshared page) plus the prefix pass's
        # raw online-softmax state, resumed instead of a cold init.
        start_ref, *refs = refs
        acc0_ref, m0_ref, l0_ref, *refs = refs
    q_ref, k_ref, v_ref, *rest = refs
    if append and quant:
        (kscale_ref, vscale_ref, knew_ref, vnew_ref,
         krow_in_ref, vrow_in_ref, ksrow_in_ref, vsrow_in_ref,
         o_ref, lse_ref, krow_out_ref, vrow_out_ref,
         ksrow_out_ref, vsrow_out_ref, acc_ref, m_ref, l_ref) = rest
    elif append:
        (knew_ref, vnew_ref, krow_in_ref, vrow_in_ref, o_ref, lse_ref,
         krow_out_ref, vrow_out_ref, acc_ref, m_ref, l_ref) = rest
    elif quant:
        kscale_ref, vscale_ref, o_ref, lse_ref, acc_ref, m_ref, l_ref = rest
    else:
        o_ref, lse_ref, acc_ref, m_ref, l_ref = rest
    bi = pl.program_id(0)
    si = pl.program_id(2)
    n_blocks = pl.num_programs(2)
    rank = meta_ref[0]
    slot_offset = meta_ref[1]
    window = meta_ref[2]
    total_len = tl_ref[bi]

    @pl.when(si == 0)
    def _init():
        if grouped:
            # resume the prefix pass's raw state: blocks < start were
            # already accumulated once per group, in the same block order
            # the ungrouped kernel would have used, so continuing the
            # online softmax from here is bit-exact.
            acc_ref[...] = acc0_ref[0, 0]
            m_ref[...] = m0_ref[0, 0]
            l_ref[...] = l0_ref[0, 0]
        else:
            acc_ref[...] = jnp.zeros_like(acc_ref)
            m_ref[...] = jnp.full_like(m_ref, NEG_INF)
            l_ref[...] = jnp.zeros_like(l_ref)

    if prune:
        lo_blk, nb = prune_block_range(
            total_len, rank, slot_offset, window, kvp=kvp, rr_block=rr_block,
            block_s=block_s, s_true=s_true, contiguous=contiguous)
        if grouped:
            # shared-prefix blocks were streamed by the prefix pass; lift
            # the span above them (mirrors decode_index_maps grouped clamp)
            lo2 = jnp.maximum(lo_blk, start_ref[bi])
            nb = jnp.maximum(lo_blk + nb - lo2, 0)
            lo_blk = lo2
        phys = _phys_block(si, lo_blk, nb, n_blocks)
        active = si < nb
    elif grouped:
        phys, active = si, si >= start_ref[bi]
    else:
        phys, active = si, None

    if append:
        # epilogue: derive the new token's slot/ownership, quantize in quant
        # mode, and persist the row through the aliased (1,1,rw,hsz) output
        # windows (idempotent re-write each S step — correct under both
        # write-back policies; the window's other rows, and the whole
        # window on non-owner ranks, are restored from the input window).
        j_new = _append_slot(total_len, kvp, rr_block, n_blocks * block_s)
        owner = (((total_len - 1) // rr_block) % kvp) == rank
        rw = krow_in_ref.shape[2]
        kn = knew_ref[0, 0]                              # [1, hsz]
        vn = vnew_ref[0, 0]
        if quant:
            kn, ks_new = _quantize_row(kn)               # int8-valued f32
            vn, vs_new = _quantize_row(vn)
            lane = jax.lax.broadcasted_iota(jnp.int32, (rw,), 0)
            srow_hit = jnp.logical_and(owner, lane == j_new % rw)
            ksrow_out_ref[0, 0] = jnp.where(srow_hit, ks_new,
                                            ksrow_in_ref[0, 0])
            vsrow_out_ref[0, 0] = jnp.where(srow_hit, vs_new,
                                            vsrow_in_ref[0, 0])
        wrows = jax.lax.broadcasted_iota(jnp.int32, (rw, 1), 0)
        whit = jnp.logical_and(owner, wrows == j_new % rw)
        krow_out_ref[0, 0] = jnp.where(
            whit, kn.astype(krow_out_ref.dtype), krow_in_ref[0, 0])
        vrow_out_ref[0, 0] = jnp.where(
            whit, vn.astype(vrow_out_ref.dtype), vrow_in_ref[0, 0])

    def _compute():
        kraw = k_ref[0, 0]                               # [bs, hsz] cache dt
        vraw = v_ref[0, 0]
        if quant:
            kscale = kscale_ref[0, 0]                    # [bs] f32
            vscale = vscale_ref[0, 0]
        if append:
            # substitute the new token's row into the VMEM tile (the
            # streamed HBM block is pre-append); in quant mode the
            # quantized payload + scale are substituted so fusion stays
            # bit-exact with append-then-attend.
            local = j_new - phys * block_s
            rows = jax.lax.broadcasted_iota(jnp.int32, (block_s, 1), 0)
            hit = jnp.logical_and(owner, rows == local)
            kraw = jnp.where(hit, kn.astype(kraw.dtype), kraw)
            vraw = jnp.where(hit, vn.astype(vraw.dtype), vraw)
            if quant:
                kscale = jnp.where(hit[:, 0], ks_new, kscale)
                vscale = jnp.where(hit[:, 0], vs_new, vscale)

        q = q_ref[0, 0].astype(jnp.float32) * scale      # [Qp, hsz]
        k = kraw.astype(jnp.float32)                     # [bs, hsz]
        v = vraw.astype(jnp.float32)
        if quant:
            k = k * kscale[:, None]
            v = v * vscale[:, None]

        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)  # [Qp,bs]

        # Global positions of this block's slots (computed, not read).  jj is
        # the physical (possibly padded) slot index; j the logical one after
        # the sliding-window slice offset.
        jj = phys * block_s + jax.lax.broadcasted_iota(
            jnp.int32, (1, block_s), 1)
        j = jj + slot_offset
        if contiguous:
            pos = rank * s_true + j
        else:
            pos = ((j // rr_block) * kvp + rank) * rr_block + (j % rr_block)
        mask = jnp.logical_and(jj < s_true, pos < total_len)
        mask = jnp.logical_and(
            mask, jnp.logical_or(window <= 0, pos >= total_len - window))

        s = jnp.where(mask, s, NEG_INF)

        m_prev = m_ref[...]                               # [Qp, 1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        # exp(NEG_INF - NEG_INF)=1 is harmless (l, acc still 0); but masked
        # lanes must not contribute when m_new == NEG_INF, so gate p.
        p = jnp.where(mask, jnp.exp(s - m_new), 0.0)      # [Qp, bs]
        l_ref[...] = alpha * l_ref[...] + jnp.sum(p, axis=1, keepdims=True)
        acc_ref[...] = alpha * acc_ref[...] + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
        m_ref[...] = m_new

    if active is not None:
        pl.when(active)(_compute)
    else:
        _compute()

    @pl.when(si == n_blocks - 1)
    def _finalize():
        l = l_ref[...]
        denom = jnp.maximum(l, 1e-37)
        o_ref[0, 0] = jnp.where(l > 0, acc_ref[...] / denom, 0.0).astype(o_ref.dtype)
        lse = jnp.where(l > 0, m_ref[...] + jnp.log(denom), NEG_INF)  # [Qp, 1]
        lse_ref[0, 0] = lse.astype(jnp.float32)


def flash_decode_kernel(q, k, v, meta, tl, *, scale: float, kvp: int,
                        rr_block: int, block_s: int, s_true: int,
                        contiguous: bool = False, kscale=None, vscale=None,
                        k_new=None, v_new=None, prune: bool = True,
                        block_tables=None, sfx_start=None, init_state=None,
                        interpret: bool):
    """Raw pallas_call.  Shapes must already be padded/blocked (see ops.py).

    q: [B, Kh, Qp, hsz]; k, v: [B, Kh, S_pad, hsz]; meta: [3] int32
    (rank, slot_offset, window); tl: [B] int32 per-request lengths;
    kscale/vscale: [B, Kh, S_pad] f32 (int8-cache mode — k/v are int8);
    k_new/v_new: [B, Kh, hsz] — fused-append mode (excludes contiguous; tl
    must already include the appended token).  fp caches take k_new in the
    cache dtype; int8 caches take the *unquantized* f32 row and quantize it
    in-kernel (payload + per-(B,Kh) scale written through aliased windows).
    s_true: unpadded local capacity (slots >= s_true are masked).
    prune: skip fully-invalid S blocks (index_map clamp + pl.when) instead
    of masking them — bit-exact either way.

    Paged mode (``block_tables`` [B, max_pages] int32, scalar-prefetched):
    k/v are shared *pool* planes ``[n_pool, Kh, block_s, hsz]`` (scales
    ``[n_pool, Kh, block_s]``) instead of per-request rows; grid step ``s``
    streams physical page ``block_tables[b, logical]`` where ``logical`` is
    exactly the fixed layout's (possibly prune-clamped) block id
    (kernels/pruning.table_block).  All masking/position math runs on the
    logical ids, so paged vs fixed is bit-exact; pruning composes (the
    valid-span clamp walks table entries, keeping DMA elision).  The fused
    append writes its row windows through the table too; outputs alias the
    pool planes.  Excludes the contiguous layout and ``slot_offset``.

    Grouped suffix mode (``sfx_start`` [B] int32 + ``init_state`` — paged
    only): this call becomes the *suffix* pass of the grouped shared-prefix
    decode.  ``init_state = (acc0 [B,Kh,Qp,hsz], m0 [B,Kh,Qp], l0
    [B,Kh,Qp])`` f32 is the per-request unstacked raw state from
    ``prefix_pass_kernel`` and seeds the online softmax at the first grid
    step; blocks below ``sfx_start[b]`` are skipped (prune mode lifts the
    span clamp, so the prefix pages' DMAs stay elided).  Because the prefix
    pass visits blocks ``0..start-1`` in the same order and with the same
    masks as the ungrouped kernel, resuming here is bit-exact with a plain
    ungrouped call.

    returns out [B, Kh, Qp, hsz] (q.dtype), lse [B, Kh, Qp] (f32), plus the
    appended caches (aliased with k, v — pool planes in paged mode) and, in
    int8 append mode, the updated kscale, vscale.
    """
    b, kh, qp, hsz = q.shape
    paged = block_tables is not None
    quant = kscale is not None
    assert quant == (vscale is not None)
    append = k_new is not None
    assert append == (v_new is not None)
    assert not (append and contiguous), \
        "fused append excludes the contiguous layout"
    grouped = sfx_start is not None
    assert grouped == (init_state is not None)
    assert not grouped or paged, "grouped suffix mode requires paged mode"
    if paged:
        assert not contiguous, "paged mode excludes the contiguous layout"
        assert k.shape[2] == block_s, (k.shape, block_s)
        n_blocks = block_tables.shape[1]          # logical pages per request
        s_pad = n_blocks * block_s                # logical local capacity
    else:
        s_pad = k.shape[2]
        assert s_pad % block_s == 0
        n_blocks = s_pad // block_s
    assert qp % 8 == 0
    rw = append_rows(block_s)

    grid = (b, kh, n_blocks)
    kernel = functools.partial(
        _decode_kernel, scale=scale, kvp=kvp, rr_block=rr_block,
        block_s=block_s, s_true=s_true, contiguous=contiguous, quant=quant,
        append=append, prune=prune, paged=paged, grouped=grouped)

    idx = decode_index_maps(
        kvp=kvp, rr_block=rr_block, block_s=block_s, s_true=s_true,
        n_blocks=n_blocks, contiguous=contiguous, prune=prune, paged=paged,
        grouped=grouped)
    q_idx, kv_idx, scale_idx = idx["q"], idx["kv"], idx["scale"]
    row_idx, srow_idx = idx["row"], idx["srow"]

    in_specs = []
    args = (meta, tl) + ((block_tables,) if paged else ())
    if grouped:
        # the prefix pass's raw state rides in *before* q so the q/k/v
        # positions (and the append aliases below) shift by exactly three
        acc0, m0, l0 = init_state
        args += (sfx_start,)
        in_specs += [
            pl.BlockSpec((1, 1, qp, hsz), q_idx),
            pl.BlockSpec((1, 1, qp, 1), idx["lse"]),
            pl.BlockSpec((1, 1, qp, 1), idx["lse"]),
        ]
    in_specs += [
        pl.BlockSpec((1, 1, qp, hsz), q_idx),
        pl.BlockSpec((1, 1, block_s, hsz), kv_idx),
        pl.BlockSpec((1, 1, block_s, hsz), kv_idx),
    ]
    if grouped:
        args += (acc0.astype(jnp.float32),
                 m0.astype(jnp.float32).reshape(b, kh, qp, 1),
                 l0.astype(jnp.float32).reshape(b, kh, qp, 1), q, k, v)
    else:
        args += (q, k, v)
    out_specs = [
        pl.BlockSpec((1, 1, qp, hsz), q_idx),
        pl.BlockSpec((1, 1, qp, 1), idx["lse"]),
    ]
    out_shape = [
        jax.ShapeDtypeStruct((b, kh, qp, hsz), q.dtype),
        jax.ShapeDtypeStruct((b, kh, qp, 1), jnp.float32),
    ]
    aliases = {}
    # inputs are numbered including the scalar-prefetch args; paged mode
    # prefetches the block table too, and grouped suffix mode the per-row
    # start page, shifting everything after them
    npre = (3 if paged else 2) + (1 if grouped else 0)
    # the k/v inputs sit right after q, which follows the three init-state
    # arrays in grouped mode
    qoff = npre + (3 if grouped else 0)
    if quant:
        in_specs += [
            pl.BlockSpec((1, 1, block_s), scale_idx),
            pl.BlockSpec((1, 1, block_s), scale_idx),
        ]
        args += (kscale.astype(jnp.float32), vscale.astype(jnp.float32))
    if append:
        in_specs += [
            pl.BlockSpec((1, 1, 1, hsz), idx["new"]),
            pl.BlockSpec((1, 1, 1, hsz), idx["new"]),
            pl.BlockSpec((1, 1, rw, hsz), row_idx),
            pl.BlockSpec((1, 1, rw, hsz), row_idx),
        ]
        args += (k_new.reshape(b, kh, 1, hsz), v_new.reshape(b, kh, 1, hsz),
                 k, v)
        out_specs += [
            pl.BlockSpec((1, 1, rw, hsz), row_idx),
            pl.BlockSpec((1, 1, rw, hsz), row_idx),
        ]
        out_shape += [
            jax.ShapeDtypeStruct(k.shape, k.dtype),
            jax.ShapeDtypeStruct(v.shape, v.dtype),
        ]
        # e.g. unpaged: meta=0, tl=1, q=2, k=3, v=4 -> outputs 2/3 are the
        # appended caches (aliased with the K/V inputs)
        aliases = {qoff + 1: 2, qoff + 2: 3}
        if quant:
            in_specs += [
                pl.BlockSpec((1, 1, rw), srow_idx),
                pl.BlockSpec((1, 1, rw), srow_idx),
            ]
            args += (kscale.astype(jnp.float32), vscale.astype(jnp.float32))
            out_specs += [
                pl.BlockSpec((1, 1, rw), srow_idx),
                pl.BlockSpec((1, 1, rw), srow_idx),
            ]
            out_shape += [
                jax.ShapeDtypeStruct(kscale.shape, jnp.float32),
                jax.ShapeDtypeStruct(vscale.shape, jnp.float32),
            ]
            # the scale outputs (4/5) alias the full scale inputs, the
            # cache outputs (2/3) the full K/V inputs
            aliases = {qoff + 1: 2, qoff + 2: 3,
                       qoff + 3: 4, qoff + 4: 5}

    res = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=npre,
            grid=grid,
            in_specs=in_specs,
            out_specs=out_specs,
            scratch_shapes=[
                pltpu.VMEM((qp, hsz), jnp.float32),
                pltpu.VMEM((qp, 1), jnp.float32),
                pltpu.VMEM((qp, 1), jnp.float32),
            ],
        ),
        out_shape=out_shape,
        input_output_aliases=aliases,
        interpret=interpret,
        name="flash_decode",
    )(*args)
    return (res[0], res[1].reshape(b, kh, qp)) + tuple(res[2:])


def grouped_prefix_index_maps(*, n_blocks: int):
    """Index maps for the grouped shared-prefix pass (CoDec-style, arXiv
    2505.17694).

    Grid is ``(G, Kh, n_blocks)``; each group ``g`` streams its shared
    prefix pages once — span-clamped to ``[0, gnp[g])`` so pruned steps
    re-reference the previous page and the DMA is elided (same property as
    the decode maps).  Prefetch operands are ``(meta [3], gnp [G],
    gtl [G, Gm], gtab [G, max_pages])``; every map is a pure jnp function
    of the grid coordinates and prefetched scalars.
    """

    def kv_idx(g, h, s, meta_ref, gnp_ref, gtl_ref, gtab_ref):
        lg = _phys_block(s, 0, gnp_ref[g], n_blocks)
        return (gtab_ref[g, lg], h, 0, 0)

    def scale_idx(g, h, s, *refs):
        return kv_idx(g, h, s, *refs)[:3]

    def q_idx(g, h, s, *_):
        return (g, h, 0, 0)

    def ml_idx(g, h, s, *_):
        return (g, h, 0, 0)

    return {"kv": kv_idx, "scale": scale_idx, "q": q_idx, "acc": q_idx,
            "ml": ml_idx}


def _prefix_kernel(meta_ref, gnp_ref, gtl_ref, gtab_ref, *refs, scale: float,
                   kvp: int, rr_block: int, block_s: int, s_true: int,
                   quant: bool, gm: int, qp: int):
    if quant:
        (q_ref, k_ref, v_ref, kscale_ref, vscale_ref,
         acc_out, m_out, l_out, acc_ref, m_ref, l_ref) = refs
    else:
        (q_ref, k_ref, v_ref,
         acc_out, m_out, l_out, acc_ref, m_ref, l_ref) = refs
    gi = pl.program_id(0)
    si = pl.program_id(2)
    n_blocks = pl.num_programs(2)
    rank = meta_ref[0]
    window = meta_ref[2]
    np_g = gnp_ref[gi]
    # per-member lengths, broadcast to the stacked Q rows: member m owns
    # rows [m*qp, (m+1)*qp).  gm is static, so this unrolls to SMEM loads.
    tl_g = jnp.stack([gtl_ref[gi, mi] for mi in range(gm)])        # [gm]
    tl_rows = jnp.broadcast_to(tl_g[:, None], (gm, qp)).reshape(gm * qp)

    @pl.when(si == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    lg = _phys_block(si, 0, np_g, n_blocks)
    active = si < np_g

    @pl.when(active)
    def _compute():
        kraw = k_ref[0, 0]                               # [bs, hsz] cache dt
        vraw = v_ref[0, 0]
        q = q_ref[0, 0].astype(jnp.float32) * scale      # [gm*qp, hsz]
        k = kraw.astype(jnp.float32)
        v = vraw.astype(jnp.float32)
        if quant:
            k = k * kscale_ref[0, 0][:, None]
            v = v * vscale_ref[0, 0][:, None]

        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)

        # position math on the *logical* block id — shared prefix pages sit
        # at the same leading logical indices in every member's table, so
        # one block serves all gm members; only the length/window masks
        # differ per member row.
        jj = lg * block_s + jax.lax.broadcasted_iota(
            jnp.int32, (1, block_s), 1)
        pos = ((jj // rr_block) * kvp + rank) * rr_block + (jj % rr_block)
        tl_col = tl_rows[:, None]                        # [gm*qp, 1]
        mask = jnp.logical_and(jj < s_true, pos < tl_col)
        mask = jnp.logical_and(
            mask, jnp.logical_or(window <= 0, pos >= tl_col - window))

        s = jnp.where(mask, s, NEG_INF)

        m_prev = m_ref[...]                              # [gm*qp, 1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.where(mask, jnp.exp(s - m_new), 0.0)
        l_ref[...] = alpha * l_ref[...] + jnp.sum(p, axis=1, keepdims=True)
        acc_ref[...] = alpha * acc_ref[...] + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
        m_ref[...] = m_new

    @pl.when(si == n_blocks - 1)
    def _emit():
        # RAW online-softmax state — no normalization; the suffix pass
        # resumes from exactly these (acc, m, l) per member row.
        acc_out[0, 0] = acc_ref[...]
        m_out[0, 0] = m_ref[...]
        l_out[0, 0] = l_ref[...]


def prefix_pass_kernel(q_stacked, k, v, meta, gnp, gtl, gtab, *, scale: float,
                       kvp: int, rr_block: int, block_s: int, s_true: int,
                       kscale=None, vscale=None, interpret: bool):
    """Raw pallas_call: shared-prefix pass of the grouped decode.

    q_stacked: [G, Kh, Gm*Qp, hsz] — requests sharing a prefix have their
    query blocks stacked along one row axis (member m at rows [m*Qp,
    (m+1)*Qp)); padding member rows must carry gtl == 0 so they mask to the
    identity update.  k/v: shared pool planes [n_pool, Kh, block_s, hsz]
    (int8 + [n_pool, Kh, block_s] f32 scales in quant mode).  meta: [3]
    int32 (rank, 0, window); gnp: [G] shared prefix pages per group; gtl:
    [G, Gm] per-member total lengths; gtab: [G, max_pages] the group's
    (identical leading) page table.

    Each shared page is streamed from HBM **once per group** instead of
    once per member — the ~1/group_size prefix bytes-read reduction the
    accounting layer proves.  Returns the raw f32 online-softmax state
    (acc [G, Kh, Gm*Qp, hsz], m [G, Kh, Gm*Qp], l [G, Kh, Gm*Qp]) for the
    suffix pass (``flash_decode_kernel(sfx_start=..., init_state=...)``).
    Groups with ``gnp == 0`` (singletons/idle rows) emit the cold state
    (acc = 0, m = -inf, l = 0), so the suffix pass degenerates to the
    ungrouped kernel for them.
    """
    g, kh, rows, hsz = q_stacked.shape
    gm_max = gtl.shape[1]
    assert rows % gm_max == 0, (rows, gm_max)
    qp = rows // gm_max
    quant = kscale is not None
    assert quant == (vscale is not None)
    assert k.shape[2] == block_s, (k.shape, block_s)
    n_blocks = gtab.shape[1]

    idx = grouped_prefix_index_maps(n_blocks=n_blocks)
    kernel = functools.partial(
        _prefix_kernel, scale=scale, kvp=kvp, rr_block=rr_block,
        block_s=block_s, s_true=s_true, quant=quant, gm=gm_max, qp=qp)

    in_specs = [
        pl.BlockSpec((1, 1, rows, hsz), idx["q"]),
        pl.BlockSpec((1, 1, block_s, hsz), idx["kv"]),
        pl.BlockSpec((1, 1, block_s, hsz), idx["kv"]),
    ]
    args = (meta, gnp, gtl, gtab, q_stacked, k, v)
    if quant:
        in_specs += [
            pl.BlockSpec((1, 1, block_s), idx["scale"]),
            pl.BlockSpec((1, 1, block_s), idx["scale"]),
        ]
        args += (kscale.astype(jnp.float32), vscale.astype(jnp.float32))

    acc, m, l = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            grid=(g, kh, n_blocks),
            in_specs=in_specs,
            out_specs=[
                pl.BlockSpec((1, 1, rows, hsz), idx["acc"]),
                pl.BlockSpec((1, 1, rows, 1), idx["ml"]),
                pl.BlockSpec((1, 1, rows, 1), idx["ml"]),
            ],
            scratch_shapes=[
                pltpu.VMEM((rows, hsz), jnp.float32),
                pltpu.VMEM((rows, 1), jnp.float32),
                pltpu.VMEM((rows, 1), jnp.float32),
            ],
        ),
        out_shape=[
            jax.ShapeDtypeStruct((g, kh, rows, hsz), jnp.float32),
            jax.ShapeDtypeStruct((g, kh, rows, 1), jnp.float32),
            jax.ShapeDtypeStruct((g, kh, rows, 1), jnp.float32),
        ],
        interpret=interpret,
        name="flash_decode_prefix",
    )(*args)
    return acc, m.reshape(g, kh, rows), l.reshape(g, kh, rows)
