"""Pallas TPU flash-decode kernel (Helix attention phase hotspot).

Decode-shape attention: one new query token per sequence against a (possibly
round-robin-sharded) KV cache shard.  Emits the partial output *and* the
log-sum-exp — the Helix combine (core/combine.py) needs both.

TPU mapping
-----------
Fixed layout (``flash_decode_kernel``, per-request cache rows):

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

Paged layout (``paged_decode_kernel``, shared pool planes
``[n_pool, Kh, R, hsz]`` of R-row pages plus a ``[B, max_pages]`` table):

  grid = (B, ceil(max_pages / P))   — one S-block of P whole pages a step,
                                      all Kh heads of the row at once
  q block    (1, Kh, Qp, hsz) : resident
  page slots (1, Kh, R, hsz)  : each pool plane passed once per slot p;
                                slot p's index map reads the call's
                                prefetched ``page_schedule`` (``page_dma``
                                through the table, worked out once a call),
                                so the grid pipeline double-buffers a whole
                                block of pages (all heads, Kh*R*hsz
                                contiguous) per step
  scale slots (1, Kh, R)      : int8-pool dequant scales (quant mode)
  scratch    acc f32 (Kh,Qp,hsz), m/l f32 (Kh,Qp,1)

``P = block_pages(block_s, R, max_pages)`` — as many whole pages as fit the
S-block size, capped at the table width — so one online-softmax update per
head covers the same ``P*R`` slots, concatenated page by page, as the fixed
layout at ``block_s = P*R``: paged stays bit-exact with fixed at that block
size.  A dead block, or a dead page of a live block, re-addresses the page
its slot already holds, so Pallas elides the DMA, and ``pl.when`` skips a
dead block's compute; slots of unfetched pages are masked and their V rows
zeroed.  (A manual ``make_async_copy`` per page from a ``memory_space=ANY``
pool would be the direct route, but Mosaic refuses to slice an HBM array
whose minor dim is under 128 lanes, as granite's 64-wide heads are.)

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
padded capacity and masking dead blocks, the fixed-layout kernel

  1. clamps the K/V (and scale) ``index_map`` to that span — grid step ``s``
     streams physical block ``min(lo + s, hi - 1)``, so every pruned step
     references the block of the previous step and Pallas TPU elides the
     HBM->VMEM DMA entirely;
  2. skips the compute body of pruned steps with ``pl.when``.

(The paged kernel's page slots hold their pages through a dead block;
``paged_spans``, ``page_dma``.)
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
     window (``append_rows``: the sublane tile holding the row; all heads,
     (1, Kh, rw, hsz), in the paged kernel) whose index_map derives the
     target slot from the prefetched per-request lengths —
     ``input_output_aliases`` makes these outputs *the same HBM buffers* as
     the K/V inputs, so the rest of the cache is untouched and the separate
     append pass (one full-cache HBM round-trip per layer per decode step)
     disappears.

The row window is re-written (idempotently) at every S-block step, so the
kernel is correct under both write-back policies Pallas implementations use
(every visit, or last visit only).  The window's other rows, and the whole
window on non-owner ranks (round-robin: the new position lives on exactly
one KVP rank), are written back unmodified from a matching *input* window.
Append mode composes with per-request [B] lengths (each row appends at its
own slot) but excludes the contiguous layout (static cross-attention KV is
never appended) and the ``slot_offset`` cache-slice path — the Helix caller
falls back to the unfused ``append_kv`` there (core/helix.py).

int8 append (append + quant): the new token's row arrives *unquantized*
(f32); the kernel quantizes it in VMEM with the same per-(B, Kh) symmetric
formula as ``core/helix.quantize_kv_token`` (scale = max|x|/127, round,
clip) and persists payload + scale through aliased row / scale-row windows,
so the fused path is bit-exact with ``append_kv_quant`` followed by the
attention pass.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.utils import NEG_INF
from repro.kernels.flash_decode.ref import local_valid_len
from repro.kernels.pruning import phys_block as _phys_block


def append_rows(block_s: int) -> int:
    """Rows of the fused-append write window: the widest sublane tile
    (8 rows at 32 bits, 16 at 16, 32 at 8) that divides the S block.  A
    one-row window is refused by Mosaic (a block's second-minor dim must be
    a multiple of 8 or the whole array dim); a paged page of rr_block rows
    is written whole."""
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
    """In-kernel mirror of ``core/helix.quantize_kv_token`` for [..., hsz]
    f32 rows: (int8-valued f32 payload, f32 scale [..., 1]).  Must stay
    formula-exact with the host-side version so fused int8 append is
    bit-identical."""
    scale = jnp.maximum(jnp.max(jnp.abs(x), axis=-1, keepdims=True) / 127.0,
                        1e-30)
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
                      n_blocks: int, contiguous: bool, prune: bool):
    """Named index_map callables for one fixed-layout decode configuration.

    The single source of truth for the kernel's DMA addressing:
    ``flash_decode_kernel`` passes exactly these callables to
    ``pallas_call``, and ``ops.flash_decode_contract`` exposes the same
    callables to the static index-space auditor (``repro.analysis``), so
    what the auditor proves is what the kernel runs.

    Every map takes ``(b, h, s, meta_ref, tl_ref)`` — the grid coordinates
    then the scalar-prefetch operands — and is a pure jnp function of them
    (no data-dependent python branches; see ``kernels/pruning.py``).  Keys:

      kv     streamed K/V blocks (1, 1, block_s, hsz); prune-clamped
      scale  streamed dequant-scale blocks (1, 1, block_s); same clamp
      row    fused-append (1, 1, rw, hsz) window holding the new token's
             row (``rw = append_rows(block_s)``; block index in rw units)
      srow   fused-append (1, 1, rw) scale-row window
      q      resident query block (constant along the S axis)
      new    the new token's (1, 1, 1, hsz) K/V row (resident)
      lse    the [B, Kh, Qp, 1] log-sum-exp output (a column, so the block's
             minor dims equal the array's — Mosaic's tiling rule)
    """
    s_pad = n_blocks * block_s
    rw = append_rows(block_s)

    def kv_idx(b, h, s, meta_ref, tl_ref):
        # pruned steps re-reference the previous step's block: the DMA is
        # elided, so HBM reads scale with the valid length, not capacity
        if not prune:
            return (b, h, s, 0)
        lo, nb = prune_block_range(
            tl_ref[b], meta_ref[0], meta_ref[1], meta_ref[2], kvp=kvp,
            rr_block=rr_block, block_s=block_s, s_true=s_true,
            contiguous=contiguous)
        return (b, h, _phys_block(s, lo, nb, n_blocks), 0)

    def scale_idx(b, h, s, meta_ref, tl_ref):
        return kv_idx(b, h, s, meta_ref, tl_ref)[:3]

    def row_idx(b, h, s, meta_ref, tl_ref):
        # window holding the appended token's row; depends on the prefetched
        # per-request length only (rank-independent slot formula)
        j_new = _append_slot(tl_ref[b], kvp, rr_block, s_pad)
        return (b, h, j_new // rw, 0)

    def srow_idx(b, h, s, meta_ref, tl_ref):
        return row_idx(b, h, s, meta_ref, tl_ref)[:3]

    def q_idx(b, h, s, *_):
        return (b, h, 0, 0)

    def new_idx(b, h, s, *_):
        return (b, h, 0, 0)

    def lse_idx(b, h, s, *_):
        return (b, h, 0, 0)

    return {"kv": kv_idx, "scale": scale_idx, "row": row_idx,
            "srow": srow_idx, "q": q_idx, "new": new_idx, "lse": lse_idx}


def _slot_mask(jj, total_len, rank, slot_offset, window, *, kvp: int,
               rr_block: int, s_true: int, contiguous: bool):
    """Validity of physical slots ``jj`` (any shape) for one request."""
    j = jj + slot_offset
    if contiguous:
        pos = rank * s_true + j
    else:
        pos = ((j // rr_block) * kvp + rank) * rr_block + (j % rr_block)
    mask = jnp.logical_and(jj < s_true, pos < total_len)
    return jnp.logical_and(
        mask, jnp.logical_or(window <= 0, pos >= total_len - window))


def _online_update(s, mask, v, m_ref, l_ref, acc_ref):
    """One online-softmax step over a block: scores ``s`` [rows, bs] (masked
    by ``mask``) against values ``v`` [bs, hsz], into the running state."""
    s = jnp.where(mask, s, NEG_INF)
    m_prev = m_ref[...]                                   # [rows, 1]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
    alpha = jnp.exp(m_prev - m_new)
    # exp(NEG_INF - NEG_INF)=1 is harmless (l, acc still 0); but masked
    # lanes must not contribute when m_new == NEG_INF, so gate p.
    p = jnp.where(mask, jnp.exp(s - m_new), 0.0)          # [rows, bs]
    l_ref[...] = alpha * l_ref[...] + jnp.sum(p, axis=1, keepdims=True)
    acc_ref[...] = alpha * acc_ref[...] + jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
    m_ref[...] = m_new


def _normalized(acc, m, l):
    """(out f32, lse) from the raw online-softmax state."""
    denom = jnp.maximum(l, 1e-37)
    out = jnp.where(l > 0, acc / denom, 0.0)
    lse = jnp.where(l > 0, m + jnp.log(denom), NEG_INF)
    return out, lse.astype(jnp.float32)


def _decode_kernel(meta_ref, tl_ref, *refs, scale: float,
                   kvp: int, rr_block: int, block_s: int, s_true: int,
                   contiguous: bool, quant: bool, append: bool, prune: bool):
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
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    if prune:
        lo_blk, nb = prune_block_range(
            total_len, rank, slot_offset, window, kvp=kvp, rr_block=rr_block,
            block_s=block_s, s_true=s_true, contiguous=contiguous)
        phys = _phys_block(si, lo_blk, nb, n_blocks)
        active = si < nb
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
            ks_new, vs_new = ks_new[0, 0], vs_new[0, 0]
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
        # the physical (possibly padded) slot index.
        jj = phys * block_s + jax.lax.broadcasted_iota(
            jnp.int32, (1, block_s), 1)
        mask = _slot_mask(jj, total_len, rank, slot_offset, window, kvp=kvp,
                          rr_block=rr_block, s_true=s_true,
                          contiguous=contiguous)
        _online_update(s, mask, v, m_ref, l_ref, acc_ref)

    if active is not None:
        pl.when(active)(_compute)
    else:
        _compute()

    @pl.when(si == n_blocks - 1)
    def _finalize():
        out, lse = _normalized(acc_ref[...], m_ref[...], l_ref[...])
        o_ref[0, 0] = out.astype(o_ref.dtype)
        lse_ref[0, 0] = lse


def flash_decode_kernel(q, k, v, meta, tl, *, scale: float, kvp: int,
                        rr_block: int, block_s: int, s_true: int,
                        contiguous: bool = False, kscale=None, vscale=None,
                        k_new=None, v_new=None, prune: bool = True,
                        interpret: bool):
    """Raw fixed-layout pallas_call.  Shapes must already be padded/blocked
    (see ops.py).

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

    returns out [B, Kh, Qp, hsz] (q.dtype), lse [B, Kh, Qp] (f32), plus the
    appended caches (aliased with k, v) and, in int8 append mode, the
    updated kscale, vscale.
    """
    b, kh, qp, hsz = q.shape
    quant = kscale is not None
    assert quant == (vscale is not None)
    append = k_new is not None
    assert append == (v_new is not None)
    assert not (append and contiguous), \
        "fused append excludes the contiguous layout"
    s_pad = k.shape[2]
    assert s_pad % block_s == 0
    n_blocks = s_pad // block_s
    assert qp % 8 == 0
    rw = append_rows(block_s)

    grid = (b, kh, n_blocks)
    kernel = functools.partial(
        _decode_kernel, scale=scale, kvp=kvp, rr_block=rr_block,
        block_s=block_s, s_true=s_true, contiguous=contiguous, quant=quant,
        append=append, prune=prune)

    idx = decode_index_maps(
        kvp=kvp, rr_block=rr_block, block_s=block_s, s_true=s_true,
        n_blocks=n_blocks, contiguous=contiguous, prune=prune)
    q_idx, kv_idx, scale_idx = idx["q"], idx["kv"], idx["scale"]
    row_idx, srow_idx = idx["row"], idx["srow"]

    in_specs = [
        pl.BlockSpec((1, 1, qp, hsz), q_idx),
        pl.BlockSpec((1, 1, block_s, hsz), kv_idx),
        pl.BlockSpec((1, 1, block_s, hsz), kv_idx),
    ]
    args = (meta, tl, q, k, v)
    out_specs = [
        pl.BlockSpec((1, 1, qp, hsz), q_idx),
        pl.BlockSpec((1, 1, qp, 1), idx["lse"]),
    ]
    out_shape = [
        jax.ShapeDtypeStruct((b, kh, qp, hsz), q.dtype),
        jax.ShapeDtypeStruct((b, kh, qp, 1), jnp.float32),
    ]
    aliases = {}
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
        # inputs are numbered including the two scalar-prefetch args:
        # meta=0, tl=1, q=2, k=3, v=4 -> outputs 2/3 are the appended
        # caches (aliased with the K/V inputs)
        aliases = {3: 2, 4: 3}
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
            # the scale outputs (4/5) alias the full scale inputs
            aliases = {3: 2, 4: 3, 5: 4, 6: 5}

    res = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
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


# --- paged layout: S-blocks of whole pages, gathered through the table ----

def block_pages(block_s: int, page_rows: int, max_pages: int) -> int:
    """P: whole pool pages per S-block of the paged kernel — as many
    ``page_rows``-row pages as fit in ``block_s`` slots (at least one),
    capped at the table width.  Chosen from shapes alone."""
    return max(1, min(block_s // page_rows, max_pages))


def paged_spans(total_len, rank, window, start=None, *, kvp: int,
                rr_block: int, page_rows: int, pages: int, max_pages: int,
                prune: bool):
    """``(pg_lo, pg_hi, blk_lo, blk_hi)``: the logical pages ``[pg_lo,
    pg_hi)`` the paged kernel fetches for one request, and the live
    S-blocks ``[blk_lo, blk_hi)`` of ``pages`` pages that hold them.

    Pruned: the valid-slot span (``valid_slot_span``) rounded out to whole
    pages — the same blocks ``prune_block_range`` gives the fixed layout at
    ``block_s = pages * page_rows``.  Dense: every page of the table.
    ``start`` (grouped suffix pass, in S-blocks) lifts the span above the
    shared prefix the prefix pass already streamed.  Pure jnp of traced
    scalars: the kernel, its index maps and the accounting all call it.
    """
    if prune:
        jj_lo, jj_hi = valid_slot_span(
            total_len, rank, 0, window, kvp=kvp, rr_block=rr_block,
            s_true=max_pages * page_rows, contiguous=False)
        pg_lo = jj_lo // page_rows
        pg_hi = (jj_hi + page_rows - 1) // page_rows
    else:
        pg_lo = jnp.zeros((), jnp.int32)
        pg_hi = jnp.full((), max_pages, jnp.int32)
    if start is not None:
        pg_lo = jnp.maximum(pg_lo, start * pages)
    blk_lo = pg_lo // pages
    blk_hi = jnp.where(pg_hi > pg_lo, (pg_hi + pages - 1) // pages, blk_lo)
    return pg_lo, pg_hi, blk_lo, blk_hi


def page_dma(s, p, pg_lo, pg_hi, *, pages: int, max_pages: int):
    """The logical page (table entry) page slot ``p`` addresses at S-block
    ``s``, for a row fetching pages ``[pg_lo, pg_hi)`` — the one
    definition of the paged kernels' page DMAs (through ``page_schedule``
    the kernel's index maps, the accounting and the auditor all read
    it).

    Slot ``p`` holds logical page ``s * pages + p`` while that page lies in
    the span; outside it the slot stays on its nearest page in the span
    (with none, its first page at or past ``pg_lo``), so a dead block, or a
    dead page of a live block, re-addresses the page the slot already
    holds and Pallas elides the DMA.  Always inside the table.
    """
    s_first = (pg_lo - p + pages - 1) // pages        # first fetch step
    s_last = (pg_hi - 1 - p) // pages                 # last fetch step
    s = jnp.clip(s, s_first, jnp.maximum(s_last, s_first))
    return jnp.clip(s * pages + p, 0, max_pages - 1)


def page_schedule(tables, pg_lo, pg_hi, *, pages: int, max_pages: int):
    """``[rows, ceil(max_pages / pages) * pages]`` pool pages: entry ``s *
    pages + p`` is the page slot ``p`` holds at S-block ``s`` — ``page_dma``
    through each row's table for rows fetching pages ``[pg_lo, pg_hi)``
    ([rows] or scalars).  Worked out once a call, outside the kernel, so a
    page slot's index map is one scalar read a grid step.

    A row with no page to fetch (an idle batch row, a memberless group
    row) holds every slot on the page it already has — the last block of
    the nearest fetching row above, else the first block of the nearest
    below — so it issues no DMA at all."""
    tables = jnp.asarray(tables)
    rows = tables.shape[0]
    n_sb = -(-max_pages // pages)
    pg_lo = jnp.broadcast_to(pg_lo, (rows,))
    pg_hi = jnp.broadcast_to(pg_hi, (rows,))
    lg = page_dma(jnp.arange(n_sb)[None, :, None],
                  jnp.arange(pages)[None, None, :],
                  pg_lo[:, None, None], pg_hi[:, None, None],
                  pages=pages, max_pages=max_pages)
    sched = jnp.take_along_axis(tables, lg.reshape(rows, -1), axis=1)
    live = pg_hi > pg_lo
    r = jnp.arange(rows)
    above = jax.lax.cummax(jnp.where(live, r, -1))
    below = jax.lax.cummin(jnp.where(live, r, rows), reverse=True)
    held = jnp.where((above >= 0)[:, None],
                     sched[jnp.maximum(above, 0), -pages:],
                     sched[jnp.minimum(below, rows - 1), :pages])
    return jnp.where(live[:, None], sched, jnp.tile(held, (1, n_sb)))


def paged_index_maps(*, kvp: int, rr_block: int, page_rows: int, pages: int,
                     max_pages: int):
    """Named index_map callables of the paged decode kernel, grid ``(B,
    ceil(max_pages / pages))``; maps take ``(b, s, meta_ref, tl_ref,
    sched_ref, rowpg_ref[, start_ref])`` — ``sched`` the call's
    ``page_schedule``, ``rowpg`` [B] the pool page of each row's appended
    token.  Shared with ``ops.flash_decode_contract`` so the static
    auditor proves the addressing the kernel runs.  Keys:

      pages  one map per page slot ``p``: the (1, Kh, R, hsz) pool page
             the schedule gives it
      scales the slots' (1, Kh, R) scale blocks
      row    fused-append (1, Kh, rw, hsz) window holding the new row
      srow   fused-append (1, Kh, rw) scale-row window
      res    resident per-row blocks (q, out, lse, the resumed state)
    """
    s_true = max_pages * page_rows
    rw = append_rows(page_rows)

    def row_idx(b, s, meta_ref, tl_ref, sched_ref, rowpg_ref, *_):
        # the new token's page window; that page is in the live span, so
        # it is the page the VMEM substitution targets
        j_new = _append_slot(tl_ref[b], kvp, rr_block, s_true)
        return (rowpg_ref[b], 0, (j_new % page_rows) // rw, 0)

    def srow_idx(*a):
        return row_idx(*a)[:3]

    return dict(_slot_maps(pages, 2), row=row_idx, srow=srow_idx)


def prefix_index_maps(*, pages: int):
    """Index maps of the grouped shared-prefix pass, grid ``(G,
    ceil(max_pages / pages))``: group row ``g``'s page slots follow its
    ``page_schedule`` over the pages of its ``gnb[g]`` whole shared
    S-blocks.  Maps take ``(g, s, meta_ref, gnb_ref, gtl_ref,
    gsched_ref)``."""
    return _slot_maps(pages, 3)


def _slot_maps(pages: int, sched_at: int):
    """Page-slot, scale-slot and resident maps over a schedule that is the
    ``sched_at``-th scalar-prefetch operand."""

    def slot_idx(p):
        def idx(b, s, *pre):
            return (pre[sched_at][b, s * pages + p], 0, 0, 0)
        return idx

    def scale_idx(p):
        def idx(b, s, *pre):
            return (pre[sched_at][b, s * pages + p], 0, 0)
        return idx

    def res_idx(b, s, *_):
        return (b, 0, 0, 0)

    return {"pages": [slot_idx(p) for p in range(pages)],
            "scales": [scale_idx(p) for p in range(pages)], "res": res_idx}


def _block_tiles(refs, h):
    """Head ``h``'s rows of an S-block's page slots as one f32 [P*R, hsz]
    tile in the fixed layout's slot order (pages cast before they join)."""
    return jnp.concatenate([r[0, h].astype(jnp.float32) for r in refs], 0)


def _scale_cols(refs):
    """Dequant scales of an S-block's page slots ([1, Kh, R] each, slots
    along lanes) as per-slot columns [P*R, Kh]."""
    return jnp.concatenate([r[0].T for r in refs], 0)


def _block_update(q_ref, kp, vp, kcols, vcols, mask, m_ref, l_ref, acc_ref,
                  *, scale: float, vmask=None, subst=None):
    """One online-softmax update per head over an S-block of pages.

    ``subst(h, k, v, ks, vs)`` substitutes the fused-append row; ``vmask``
    [bs, 1] zeroes V rows of slots no page fetched this block.
    """
    for h in range(q_ref.shape[1]):
        k = _block_tiles(kp, h)                          # [bs, hsz] f32
        v = _block_tiles(vp, h)
        ks = vs = None
        if kcols is not None:
            ks, vs = kcols[:, h:h + 1], vcols[:, h:h + 1]
        if subst is not None:
            k, v, ks, vs = subst(h, k, v, ks, vs)
        if ks is not None:
            k = k * ks
            v = v * vs
        if vmask is not None:
            v = jnp.where(vmask, v, 0.0)
        q = q_ref[0, h].astype(jnp.float32) * scale     # [rows, hsz]
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        _online_update(s, mask, v, m_ref.at[h], l_ref.at[h], acc_ref.at[h])


def _paged_kernel(meta_ref, tl_ref, sched_ref, rowpg_ref, *refs,
                  scale: float, kvp: int,
                  rr_block: int, page_rows: int, pages: int, max_pages: int,
                  quant: bool, append: bool, prune: bool, grouped: bool):
    if grouped:
        # suffix pass of the grouped shared-prefix decode: one more prefetch
        # operand (per-request first unshared S-block) plus the prefix
        # pass's raw online-softmax state, resumed instead of a cold init.
        start_ref, acc0_ref, m0_ref, l0_ref, *refs = refs
    q_ref, *refs = refs
    kp, vp, refs = refs[:pages], refs[pages:2 * pages], refs[2 * pages:]
    ksp = vsp = None
    if quant:
        ksp, vsp = refs[:pages], refs[pages:2 * pages]
        refs = refs[2 * pages:]
    if append:
        knew_ref, vnew_ref, krow_in, vrow_in, *refs = refs
        if quant:
            ksrow_in, vsrow_in, *refs = refs
    o_ref, lse_ref, *refs = refs
    if append:
        krow_out, vrow_out, *refs = refs
        if quant:
            ksrow_out, vsrow_out, *refs = refs
    acc_ref, m_ref, l_ref = refs

    bi = pl.program_id(0)
    si = pl.program_id(1)
    n_sb = pl.num_programs(1)
    rank, window = meta_ref[0], meta_ref[2]
    total_len = tl_ref[bi]
    bs = pages * page_rows
    s_true = max_pages * page_rows
    pg_lo, pg_hi, blk_lo, blk_hi = paged_spans(
        total_len, rank, window, start_ref[bi] if grouped else None,
        kvp=kvp, rr_block=rr_block, page_rows=page_rows, pages=pages,
        max_pages=max_pages, prune=prune)

    @pl.when(si == 0)
    def _init():
        if grouped:
            # resume the prefix pass's raw state: blocks < start were
            # accumulated once per group, in the same block order the
            # ungrouped kernel uses, so continuing here is bit-exact.
            acc_ref[...] = acc0_ref[0]
            m_ref[...] = m0_ref[0]
            l_ref[...] = l0_ref[0]
        else:
            acc_ref[...] = jnp.zeros_like(acc_ref)
            m_ref[...] = jnp.full_like(m_ref, NEG_INF)
            l_ref[...] = jnp.zeros_like(l_ref)

    if append:
        # the new row, quantized in quant mode, persisted through the
        # aliased (1, Kh, rw, hsz) windows (idempotent re-write each step;
        # other rows, and the whole window off the owner rank, restored
        # from the input window)
        j_new = _append_slot(total_len, kvp, rr_block, s_true)
        owner = (((total_len - 1) // rr_block) % kvp) == rank
        rw = krow_in.shape[2]
        kn = knew_ref[0]                                 # [Kh, 1, hsz]
        vn = vnew_ref[0]
        if quant:
            kn, ks_new = _quantize_row(kn)               # [Kh, 1, 1] scales
            vn, vs_new = _quantize_row(vn)
            lane = jax.lax.broadcasted_iota(jnp.int32, (1, rw), 1)
            shit = jnp.logical_and(owner, lane == j_new % rw)
            ksrow_out[0] = jnp.where(shit, ks_new[:, 0], ksrow_in[0])
            vsrow_out[0] = jnp.where(shit, vs_new[:, 0], vsrow_in[0])
        wrows = jax.lax.broadcasted_iota(jnp.int32, (1, rw, 1), 1)
        whit = jnp.logical_and(owner, wrows == j_new % rw)
        krow_out[0] = jnp.where(whit, kn.astype(krow_out.dtype), krow_in[0])
        vrow_out[0] = jnp.where(whit, vn.astype(vrow_out.dtype), vrow_in[0])

    @pl.when(jnp.logical_and(si >= blk_lo, si < blk_hi))
    def _compute():
        jj = si * bs + jax.lax.broadcasted_iota(jnp.int32, (1, bs), 1)
        mask = _slot_mask(jj, total_len, rank, 0, window, kvp=kvp,
                          rr_block=rr_block, s_true=s_true, contiguous=False)
        # a slot whose page lies outside the fetched span still holds the
        # last page it fetched: its scores are masked and its V rows zeroed
        jc = si * bs + jax.lax.broadcasted_iota(jnp.int32, (bs, 1), 0)
        vmask = _slot_mask(jc, total_len, rank, 0, window, kvp=kvp,
                           rr_block=rr_block, s_true=s_true,
                           contiguous=False)
        subst = None
        if append:
            hit = jnp.logical_and(owner, jc == j_new)    # [bs, 1]

            def subst(h, k, v, ks, vs):
                # the block's pages are pre-append: substitute the new row
                # (the quantized payload and its scale in quant mode)
                k = jnp.where(hit, kn[h].astype(kp[0].dtype)
                              .astype(jnp.float32), k)
                v = jnp.where(hit, vn[h].astype(vp[0].dtype)
                              .astype(jnp.float32), v)
                if ks is not None:
                    ks = jnp.where(hit, ks_new[h], ks)
                    vs = jnp.where(hit, vs_new[h], vs)
                return k, v, ks, vs

        _block_update(q_ref, kp, vp,
                      _scale_cols(ksp) if quant else None,
                      _scale_cols(vsp) if quant else None,
                      mask, m_ref, l_ref, acc_ref, scale=scale, vmask=vmask,
                      subst=subst)

    @pl.when(si == n_sb - 1)
    def _finalize():
        out, lse = _normalized(acc_ref[...], m_ref[...], l_ref[...])
        o_ref[0] = out.astype(o_ref.dtype)
        lse_ref[0] = lse


def paged_decode_kernel(q, k, v, meta, tl, tables, *, scale: float,
                        kvp: int, rr_block: int, pages: int,
                        kscale=None, vscale=None, k_new=None, v_new=None,
                        prune: bool = True, sfx_start=None, init_state=None,
                        interpret: bool):
    """Raw paged pallas_call (same kernel name, ``flash_decode``).

    q: [B, Kh, Qp, hsz]; k, v: shared pool planes ``[n_pool, Kh, R, hsz]``
    (scales ``[n_pool, Kh, R]`` f32 with int8 planes); tables: [B,
    max_pages] int32, request ``b``'s logical local slots ``[p*R,
    (p+1)*R)`` in pool page ``tables[b, p]``; meta: [3] int32 (rank, 0,
    window); tl: [B].  ``pages`` whole pages form one S-block
    (``block_pages``): each pool plane is passed once per page slot, and
    slot ``p``'s (1, Kh, R, hsz) block is the page the call's
    ``page_schedule`` gives it, so the grid pipeline double-buffers a
    whole S-block of pages per step.  Fused append (``k_new``/``v_new`` [B, Kh, hsz], f32 rows
    for int8 pools) writes the new row's page window through the table;
    the outputs alias the pool planes.

    Grouped suffix mode (``sfx_start`` [B] int32, in S-blocks, +
    ``init_state = (acc0 [B,Kh,Qp,hsz], m0 [B,Kh,Qp], l0 [B,Kh,Qp])`` f32
    from ``prefix_pass_kernel``): the online softmax resumes that raw state
    and blocks below ``sfx_start[b]`` are neither fetched nor computed.
    Because the prefix pass visits blocks ``0..start-1`` with the same
    masks and block partition, resuming is bit-exact with an ungrouped
    call.

    returns out [B, Kh, Qp, hsz] (q.dtype), lse [B, Kh, Qp] (f32), plus the
    appended pool planes and, with int8 pools, the updated scale planes.
    """
    b, kh, qp, hsz = q.shape
    page_rows = k.shape[2]
    max_pages = tables.shape[1]
    quant = kscale is not None
    append = k_new is not None
    grouped = sfx_start is not None
    assert grouped == (init_state is not None)
    assert qp % 8 == 0
    rw = append_rows(page_rows)
    idx = paged_index_maps(kvp=kvp, rr_block=rr_block, page_rows=page_rows,
                           pages=pages, max_pages=max_pages)
    res = idx["res"]

    pg_lo, pg_hi, _, _ = paged_spans(
        tl, meta[0], meta[2], sfx_start, kvp=kvp, rr_block=rr_block,
        page_rows=page_rows, pages=pages, max_pages=max_pages, prune=prune)
    j_new = _append_slot(tl, kvp, rr_block, max_pages * page_rows)
    rowpg = jnp.take_along_axis(tables, (j_new // page_rows)[:, None], 1)
    prefetch = (meta, tl, page_schedule(tables, pg_lo, pg_hi, pages=pages,
                                        max_pages=max_pages), rowpg[:, 0])
    in_specs, args = [], ()
    if grouped:
        acc0, m0, l0 = init_state
        prefetch += (sfx_start,)
        in_specs += [pl.BlockSpec((1, kh, qp, hsz), res),
                     pl.BlockSpec((1, kh, qp, 1), res),
                     pl.BlockSpec((1, kh, qp, 1), res)]
        args += (acc0.astype(jnp.float32),
                 m0.astype(jnp.float32).reshape(b, kh, qp, 1),
                 l0.astype(jnp.float32).reshape(b, kh, qp, 1))
    kv_pos = len(prefetch) + len(args) + 1        # k's first input number
    page_specs = [pl.BlockSpec((1, kh, page_rows, hsz), m)
                  for m in idx["pages"]]
    in_specs += [pl.BlockSpec((1, kh, qp, hsz), res)] + page_specs * 2
    args += (q,) + (k,) * pages + (v,) * pages
    out_specs = [pl.BlockSpec((1, kh, qp, hsz), res),
                 pl.BlockSpec((1, kh, qp, 1), res)]
    out_shape = [jax.ShapeDtypeStruct((b, kh, qp, hsz), q.dtype),
                 jax.ShapeDtypeStruct((b, kh, qp, 1), jnp.float32)]
    aliases = {}
    if quant:
        kscale = kscale.astype(jnp.float32)
        vscale = vscale.astype(jnp.float32)
        in_specs += [pl.BlockSpec((1, kh, page_rows), m)
                     for m in idx["scales"]] * 2
        args += (kscale,) * pages + (vscale,) * pages
    if append:
        row = pl.BlockSpec((1, kh, rw, hsz), idx["row"])
        in_specs += [pl.BlockSpec((1, kh, 1, hsz), res)] * 2 + [row] * 2
        args += (k_new.reshape(b, kh, 1, hsz), v_new.reshape(b, kh, 1, hsz),
                 k, v)
        out_specs += [row] * 2
        out_shape += [jax.ShapeDtypeStruct(k.shape, k.dtype),
                      jax.ShapeDtypeStruct(v.shape, v.dtype)]
        # outputs 2/3 are the appended pool planes (aliased with k, v)
        aliases = {kv_pos: 2, kv_pos + pages: 3}
        if quant:
            srow = pl.BlockSpec((1, kh, rw), idx["srow"])
            in_specs += [srow] * 2
            args += (kscale, vscale)
            out_specs += [srow] * 2
            out_shape += [jax.ShapeDtypeStruct(kscale.shape, jnp.float32)] * 2
            aliases.update({kv_pos + 2 * pages: 4, kv_pos + 3 * pages: 5})

    kernel = functools.partial(
        _paged_kernel, scale=scale, kvp=kvp, rr_block=rr_block,
        page_rows=page_rows, pages=pages, max_pages=max_pages, quant=quant,
        append=append, prune=prune, grouped=grouped)
    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=len(prefetch),
            grid=(b, -(-max_pages // pages)),
            in_specs=in_specs,
            out_specs=out_specs,
            scratch_shapes=[
                pltpu.VMEM((kh, qp, hsz), jnp.float32),
                pltpu.VMEM((kh, qp, 1), jnp.float32),
                pltpu.VMEM((kh, qp, 1), jnp.float32),
            ],
        ),
        out_shape=out_shape,
        input_output_aliases=aliases,
        interpret=interpret,
        name="flash_decode",
    )(*prefetch, *args)
    return (out[0], out[1].reshape(b, kh, qp)) + tuple(out[2:])


def _prefix_kernel(meta_ref, gnb_ref, gtl_ref, gsched_ref, q_ref, *refs,
                   scale: float, kvp: int, rr_block: int, page_rows: int,
                   pages: int, max_pages: int, quant: bool, gm: int,
                   qp: int):
    kp, vp, refs = refs[:pages], refs[pages:2 * pages], refs[2 * pages:]
    ksp = vsp = None
    if quant:
        ksp, vsp = refs[:pages], refs[pages:2 * pages]
        refs = refs[2 * pages:]
    acc_out, m_out, l_out, acc_ref, m_ref, l_ref = refs
    gi = pl.program_id(0)
    si = pl.program_id(1)
    n_sb = pl.num_programs(1)
    rank, window = meta_ref[0], meta_ref[2]
    bs = pages * page_rows

    @pl.when(si == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    @pl.when(si < gnb_ref[gi])
    def _compute():
        # per-member lengths as a row column: member m owns Q rows
        # [m*qp, (m+1)*qp).  gm is static, so this unrolls to SMEM loads.
        rows = jax.lax.broadcasted_iota(jnp.int32, (gm * qp, 1), 0) // qp
        tl_col = jnp.zeros((gm * qp, 1), jnp.int32)
        for mi in range(gm):
            tl_col = jnp.where(rows == mi, gtl_ref[gi, mi], tl_col)
        # position math on the *logical* block — shared prefix pages sit at
        # the same leading logical indices in every member's table, so one
        # block serves all gm members; only the length/window masks differ.
        # Every page of a shared block is fetched (the shared span is whole
        # blocks), so no V row needs zeroing.
        jj = si * bs + jax.lax.broadcasted_iota(jnp.int32, (1, bs), 1)
        mask = _slot_mask(jj, tl_col, rank, 0, window, kvp=kvp,
                          rr_block=rr_block, s_true=max_pages * page_rows,
                          contiguous=False)
        _block_update(q_ref, kp, vp,
                      _scale_cols(ksp) if quant else None,
                      _scale_cols(vsp) if quant else None,
                      mask, m_ref, l_ref, acc_ref, scale=scale)

    @pl.when(si == n_sb - 1)
    def _emit():
        # RAW online-softmax state — no normalization; the suffix pass
        # resumes from exactly these (acc, m, l) per member row.
        acc_out[0] = acc_ref[...]
        m_out[0] = m_ref[...]
        l_out[0] = l_ref[...]


def prefix_pass_kernel(q_stacked, k, v, meta, gnb, gtl, gtab, *, scale: float,
                       kvp: int, rr_block: int, pages: int, kscale=None,
                       vscale=None, interpret: bool):
    """Raw pallas_call: shared-prefix pass of the grouped decode.

    q_stacked: [G, Kh, Gm*Qp, hsz] — requests sharing a prefix have their
    query blocks stacked along one row axis (member m at rows [m*Qp,
    (m+1)*Qp)); padding member rows must carry gtl == 0 so they mask to the
    identity update.  k/v: shared pool planes [n_pool, Kh, R, hsz] (int8 +
    [n_pool, Kh, R] f32 scales in quant mode).  meta: [3] int32 (rank, 0,
    window); gnb: [G] whole shared S-blocks (of ``pages`` pages) per group;
    gtl: [G, Gm] per-member total lengths; gtab: [G, max_pages] the group's
    (identical leading) page table.

    Grid ``(G, ceil(max_pages / pages))``, all heads a step, pages gathered
    by page slots (``prefix_index_maps``) like the decode kernel: each
    shared page is streamed from HBM **once per group** instead of once
    per member — the ~1/group_size prefix bytes-read reduction the
    accounting layer proves.  Returns the raw f32 online-softmax state
    (acc [G, Kh, Gm*Qp, hsz], m [G, Kh, Gm*Qp], l [G, Kh, Gm*Qp]) for the
    suffix pass (``paged_decode_kernel(sfx_start=..., init_state=...)``).
    Groups with ``gnb == 0`` (singletons/idle rows) fetch nothing (their
    slots hold the previous row's pages), compute nothing and emit the
    cold state (acc = 0, m = -inf, l = 0), so the suffix pass
    degenerates to the ungrouped kernel for them.
    """
    g, kh, rows, hsz = q_stacked.shape
    gm_max = gtl.shape[1]
    assert rows % gm_max == 0, (rows, gm_max)
    qp = rows // gm_max
    quant = kscale is not None
    assert quant == (vscale is not None)
    page_rows = k.shape[2]
    max_pages = gtab.shape[1]
    idx = prefix_index_maps(pages=pages)
    res = idx["res"]
    gsched = page_schedule(gtab, 0, jnp.minimum(gnb * pages, max_pages),
                           pages=pages, max_pages=max_pages)

    page_specs = [pl.BlockSpec((1, kh, page_rows, hsz), m)
                  for m in idx["pages"]]
    in_specs = [pl.BlockSpec((1, kh, rows, hsz), res)] + page_specs * 2
    args = (meta, gnb, gtl, gsched, q_stacked) + (k,) * pages + (v,) * pages
    if quant:
        in_specs += [pl.BlockSpec((1, kh, page_rows), m)
                     for m in idx["scales"]] * 2
        args += ((kscale.astype(jnp.float32),) * pages
                 + (vscale.astype(jnp.float32),) * pages)

    kernel = functools.partial(
        _prefix_kernel, scale=scale, kvp=kvp, rr_block=rr_block,
        page_rows=page_rows, pages=pages, max_pages=max_pages, quant=quant,
        gm=gm_max, qp=qp)
    acc, m, l = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            grid=(g, -(-max_pages // pages)),
            in_specs=in_specs,
            out_specs=[
                pl.BlockSpec((1, kh, rows, hsz), res),
                pl.BlockSpec((1, kh, rows, 1), res),
                pl.BlockSpec((1, kh, rows, 1), res),
            ],
            scratch_shapes=[
                pltpu.VMEM((kh, rows, hsz), jnp.float32),
                pltpu.VMEM((kh, rows, 1), jnp.float32),
                pltpu.VMEM((kh, rows, 1), jnp.float32),
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
