"""Helix attention (§2.1): KVP×TPA sharded decode attention as a shard_map
module, composable inside a jit/GSPMD step function.

Design (DESIGN.md §2): the *only* explicit-SPMD region is the paper's
contribution — per-rank flash-decode over the local KV shard, the single
all-to-all over the query-head axis, and the LSE rescale-sum combine.  The
surrounding projections / FFN / MoE run under GSPMD with phase-dependent
sharding constraints (core/sharding.py), which is how the same device pool
is "re-provisioned" between attention and FFN on TPU.

Round-robin cache layout (§2.3): global position p lives at

    owner rank r = (p // rr) % KVP
    local slot j = ((p // rr) // KVP) * rr + p % rr

i.e. global cache slot s = r * S_loc + j when the sequence dim is sharded
contiguously over the kvp axes.  ``rr_slot_of_position`` maps p -> s for the
GSPMD cache append; the in-shard mask inverts it (kernels/flash_decode/ref).
"""
from __future__ import annotations

import functools
from typing import Sequence

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from repro.core.combine import combine_fragments
from repro.core.sharding import HelixConfig
from repro.kernels.flash_decode.ops import flash_decode
from repro.kernels.flash_decode.ref import flash_decode_ref, local_valid_len
from repro.utils import round_up, shard_map


def helix_out_dim(q_dim: int, n_devices: int) -> int:
    """Flattened attention-output dim after the all-to-all (padded)."""
    return round_up(q_dim, n_devices)


def rr_slot_of_position(pos, kvp: int, s_loc: int, rr_block: int):
    """Global round-robin cache slot for sequence position ``pos``."""
    blk = pos // rr_block
    rank = blk % kvp
    local = (blk // kvp) * rr_block + pos % rr_block
    return rank * s_loc + local


def _window_slice(total_len, rank, s_loc, *, kvp, rr_block, window):
    """§Perf (beyond-paper): sliding-window layers only need the last
    ``window`` positions.  Positions are strictly increasing in the local
    slot index, so the live span is the W_loc slots ending at this rank's
    valid length — slice it out and read O(window/KVP) bytes instead of
    O(S/KVP).  Returns (j_lo, w_loc) or None when the slice doesn't apply
    (static window and scalar total_len required)."""
    if not (isinstance(window, int) and window > 0
            and jnp.ndim(total_len) == 0):
        return None
    w_loc = min((window // (kvp * rr_block) + 2) * rr_block, s_loc)
    if w_loc >= s_loc:
        return None
    j_hi = local_valid_len(total_len, rank, kvp, rr_block)
    j_lo = jnp.clip(j_hi - w_loc, 0, s_loc - w_loc)
    return j_lo, w_loc


def fuse_append_applicable(hx, kvp: int, window, total_len, s_cap: int, *,
                           quant: bool = False,
                           contiguous: bool = False,
                           paged: bool = False) -> bool:
    """Static check: can this decode step run the fused KV-append epilogue?

    The fused path (kernels/flash_decode append mode) writes the new token's
    K/V row inside the kernel — quantizing it in-kernel for int8 caches —
    eliminating the separate ``append_kv``/``append_kv_quant`` cache
    round-trip.  It requires a Pallas backend with ``hx.fuse_append`` on and
    a round-robin cache, and must not collide with the sliding-window
    cache-slice fast path (which attends over a *slice* of the shard — an
    in-kernel write there would miss the real cache).  With
    ``hx.prune_blocks`` (the default) that conflict cannot arise: in-kernel
    block pruning subsumes the slice fast path, so windowed layers fuse
    too.  All inputs are trace-time static, so the choice costs nothing at
    runtime.
    """
    if hx.attn_backend == "ref" or not hx.fuse_append:
        return False
    if contiguous:
        return False
    del quant  # int8 caches fuse too (in-kernel quantization)
    if paged:
        # the paged pool never takes the cache-slice fast path (pages are
        # indirected, not sliceable), so fusion always composes
        return True
    if hx.prune_blocks:
        return True
    s_loc = s_cap // kvp
    return _window_slice(total_len, 0, s_loc, kvp=kvp, rr_block=hx.rr_block,
                         window=window) is None


def _local_attend(q, k, v, total_len, rank, *, kvp, rr_block, window,
                  contiguous: bool, kscale=None, vscale=None,
                  backend: str = "ref", k_new=None, v_new=None,
                  prune: bool = True, block_tables=None,
                  block_s: int = 512, groups=None):
    """Per-rank partial attention + LSE over the local KV shard.

    contiguous=True: static split (whisper cross-attn KV) — every local slot
    s maps to global position rank*S_loc + s; otherwise round-robin (§2.3).
    kscale/vscale [B, Kh, S_loc]: int8-cache dequant scales (§Perf knob).
    backend: "ref" (pure jnp), "pallas-interpret" or "pallas" — the Pallas
    flash-decode kernel (kernels/flash_decode) in interpreted / compiled
    mode.  The kernel covers every mode natively (per-request [B] lengths,
    contiguous layout, sliding window, int8 dequant from scales), so all
    backends are drop-in exact up to fp summation order.
    prune: in-kernel block pruning (Pallas backends) — HBM reads scale with
    the valid length / window, not the slot capacity, which subsumes the
    caller-side cache-slice fast path below.
    k_new/v_new [B, Kh, hsz]: fused KV-append epilogue (Pallas backends
    only; see ``fuse_append_applicable``) — the kernel appends the new
    token's row to the local shard and returns
    ``(out, lse, kcache, vcache)`` (+ the updated scales for int8 caches)
    instead of ``(out, lse)``.
    block_tables [B, max_pages]: shared-pool paged mode — k/v (and scales)
    are this rank's pool-plane shards ``[n_pool, Kh, ps_loc, ...]``; the
    Pallas backends stream pages through the prefetched table, the ref
    backend gathers the pages into the equivalent dense local cache first
    (bit-exact — masked tail slots contribute exact zeros).
    block_s: kernel S-block size (``HelixConfig.attn_block_s``); the paged
    kernel gathers the whole pages that fit it.
    groups: (group_id [B], group_np [B]) — grouped shared-prefix decode
    (Pallas paged mode); the ref backend *ignores* the grouping, which is
    exactly the oracle semantics (grouping must not change results).
    """
    fused = k_new is not None
    paged = block_tables is not None
    assert not fused or backend != "ref", \
        "fused append requires a Pallas backend"
    assert not (paged and contiguous), \
        "paged mode excludes the contiguous (cross-attn) layout"
    assert groups is None or paged, \
        "grouped decode requires the paged pool"
    if paged and backend == "ref":
        from repro.core.kvcache import gather_pages
        k = gather_pages(k, block_tables)
        v = gather_pages(v, block_tables)
        if kscale is not None:
            kscale = gather_pages(kscale, block_tables)
            vscale = gather_pages(vscale, block_tables)
        paged, block_tables = False, None
    s_loc = k.shape[2]
    # Sliding-window cache-slice fast path: slice the live span out of the
    # shard and re-align positions via slot_offset.  Only worth it where the
    # kernel can't prune for itself — the ref backend, or a Pallas backend
    # with pruning disabled.  Incompatible with the fused append (the kernel
    # must write the real cache, not a slice) and with the paged pool (pages
    # are indirected, not sliceable) — fuse_append_applicable() excludes
    # the overlap.
    slot_offset = 0
    if (not contiguous and not fused and not paged
            and (backend == "ref" or not prune)):
        sl = _window_slice(total_len, rank, s_loc, kvp=kvp,
                           rr_block=rr_block, window=window)
        if sl is not None:
            j_lo, w_loc = sl
            k = jax.lax.dynamic_slice_in_dim(k, j_lo, w_loc, axis=2)
            v = jax.lax.dynamic_slice_in_dim(v, j_lo, w_loc, axis=2)
            if kscale is not None:
                kscale = jax.lax.dynamic_slice_in_dim(
                    kscale, j_lo, w_loc, axis=2)
                vscale = jax.lax.dynamic_slice_in_dim(
                    vscale, j_lo, w_loc, axis=2)
            slot_offset = j_lo
    if backend != "ref":
        return flash_decode(q, k, v, total_len, rank, kvp=kvp,
                            rr_block=rr_block, window=window,
                            contiguous=contiguous, slot_offset=slot_offset,
                            kscale=kscale, vscale=vscale,
                            k_new=k_new, v_new=v_new, prune=prune,
                            block_tables=block_tables, block_s=block_s,
                            groups=groups, interpret=backend != "pallas")
    # ---- pure-JAX reference path ----
    if contiguous:
        # positions rank*s_loc + j: with kvp=1 the round-robin formula
        # degenerates to pos = slot_offset + j, so the contiguous layout is
        # the ref with a rank-sized slot offset (window stays honoured).
        return flash_decode_ref(q, k, v, total_len, 0, kvp=1,
                                rr_block=rr_block, window=window,
                                slot_offset=rank * s_loc,
                                kscale=kscale, vscale=vscale)
    return flash_decode_ref(q, k, v, total_len, rank, kvp=kvp,
                            rr_block=rr_block, window=window,
                            slot_offset=slot_offset,
                            kscale=kscale, vscale=vscale)


def helix_attention(mesh: Mesh, hx: HelixConfig, q, kcache, vcache, total_len,
                    *, window: int | jax.Array = 0, contiguous: bool = False,
                    hopb_chunks: int = 1, kscale=None, vscale=None,
                    k_new=None, v_new=None, block_tables=None, groups=None):
    """Exact sharded decode attention.

    Args:
      q:            [B, Qh, hsz] global (replicated over kvp, heads over tpa).
      kcache/vcache:[B, Kh, S_cap, hsz] global; S_cap sharded over kvp axes,
                    heads over tpa axis (round-robin slot layout).
      total_len:    scalar or [B] int32 — global sequence length(s).
      window:       sliding window (0 = full); may be traced (gemma3 scan).
      hopb_chunks:  HOP-B (§2.1.3): split the batch into this many
                    independent chunks so XLA's latency-hiding scheduler can
                    overlap chunk i's all-to-all with chunk i+1's attention
                    compute (TPU-idiomatic equivalent of stream overlap).
      k_new/v_new:  [B, Kh, hsz] — fused KV-append epilogue: the new token's
                    K/V row is written into the cache *inside* the decode
                    kernel (its owner rank's shard), replacing the separate
                    ``append_kv`` pass.  With an int8 cache (kscale/vscale
                    given) the kernel quantizes the row in-kernel and also
                    returns the updated scales.  Pass the pre-append caches
                    and a ``total_len`` that already counts the new token;
                    the caller must have checked ``fuse_append_applicable``.
      block_tables: [B, max_pages] int32 — shared-pool *paged* mode:
                    kcache/vcache are pool planes ``[n_blocks, Kh, block_s,
                    hsz]`` (scales ``[n_blocks, Kh, block_s]``) whose
                    block_s axis shards over the kvp axes exactly like the
                    fixed layout's slot axis — each rank holds block_s/KVP
                    rows of every page, its round-robin local slots for
                    that page (core/kvcache.py paged layout).  The table is
                    replicated; per-rank attention streams pages through
                    it.  Fused append composes (the kernel writes the new
                    row's page through the table).
      groups:       (group_id [B], group_np [B]) int32 — grouped shared-
                    prefix decode (paged mode): requests whose tables share
                    their leading ``group_np`` pages stream each shared page
                    once per group (kernels/flash_decode ``groups``).  Both
                    arrays are replicated; the ref backend ignores them
                    (grouping is bit-exact, so the oracle doesn't need
                    them).  Forces ``hopb_chunks=1`` — groups span the
                    whole batch, chunking would split them.

    Returns: [B, Qh*hsz] attention output, sharded over (tpa, kvp) on dim 1 —
    exactly the TP layout the post-attention projection consumes (§2.2).
    In fused-append mode returns ``(out, kcache, vcache)`` with the appended
    caches (same global layout/sharding as the inputs — whole pool planes in
    paged mode), plus ``(kscale, vscale)`` for int8 caches.
    """
    import math
    b, qh, hsz = q.shape
    kvp_axes = hx.kvp_axes
    tpa = hx.tpa_axis
    kvp = math.prod(mesh.shape[a] for a in kvp_axes)
    qh_local = qh // (mesh.shape[tpa] if tpa else 1)
    fused = k_new is not None
    paged = block_tables is not None
    grouped = groups is not None
    assert not fused or not contiguous
    assert not (paged and contiguous)
    assert not grouped or paged, "grouped decode requires the paged pool"
    if grouped:
        hopb_chunks = 1        # groups span the batch; chunks would split them
    # The all-to-all splits the flattened (Qh_local*hsz) dim into KVP slices.
    # When it does not divide (e.g. hymba q_dim=1600, N=256) we zero-pad the
    # flat dim only — attention itself runs the canonical heads; pad elements
    # carry clamped head indices so combine weights hit zeros (exact).  The
    # caller pads the out-projection rows to match (helix_out_dim).
    d_flat = qh_local * hsz
    d_pad = round_up(d_flat, kvp)
    if d_pad != d_flat:
        assert tpa is None, "flat-dim padding only supported in pure-KVP mode"
    sl = d_pad // kvp
    flat_heads = jnp.minimum(jnp.arange(d_pad, dtype=jnp.int32) // hsz,
                             qh_local - 1)
    head_idx_table = flat_heads.reshape(kvp, sl)          # [KVP, sl]

    def local_fn(q_l, k_l, v_l, tl, *extras):
        rank = jax.lax.axis_index(kvp_axes)
        ks_l = vs_l = kn_l = vn_l = tbl_l = grp_l = None
        if kscale is not None:
            ks_l, vs_l, extras = extras[0], extras[1], extras[2:]
        if fused:
            kn_l, vn_l, extras = extras[0], extras[1], extras[2:]
        if paged:
            tbl_l, extras = extras[0], extras[1:]
        if grouped:
            grp_l = (extras[0], extras[1])
        res = _local_attend(q_l, k_l, v_l, tl, rank, kvp=kvp,
                            rr_block=hx.rr_block, window=window,
                            contiguous=contiguous,
                            kscale=ks_l, vscale=vs_l,
                            backend=hx.attn_backend,
                            k_new=kn_l, v_new=vn_l,
                            prune=hx.prune_blocks,
                            block_tables=tbl_l,
                            block_s=hx.attn_block_s,
                            groups=grp_l)
        out, lse = res[0], res[1]
        bl = out.shape[0]
        with jax.named_scope("combine"):
            # single all-to-all over the query-head axis (§2.1.2): volume
            # B×H/TPA, independent of S.
            flat = out.reshape(bl, d_flat)
            if d_pad != d_flat:
                flat = jnp.pad(flat, ((0, 0), (0, d_pad - d_flat)))
            frags = flat.reshape(bl, kvp, sl).transpose(1, 0, 2)  # [KVP,B,sl]
            if kvp_axes:
                frags = jax.lax.all_to_all(frags, kvp_axes, split_axis=0,
                                           concat_axis=0, tiled=False)
                lses = jax.lax.all_gather(lse, kvp_axes, axis=0, tiled=False)
            else:
                # KVP=1: nothing to exchange (a collective over no axes
                # drops the rank axis); the local fragment is the whole
                # combine input
                lses = lse[None]
            my_slice = jax.lax.dynamic_index_in_dim(
                head_idx_table, rank, axis=0, keepdims=False)
            combined = combine_fragments(frags, lses, my_slice)  # [B, sl]
        if fused:
            # + appended local KV shards (and updated scales for int8)
            return (combined,) + tuple(res[2:])
        return combined

    tl_spec = P() if jnp.ndim(total_len) == 0 else P(None)
    quant = kscale is not None
    # fixed layout: cache [B, Kh, S_cap, hsz], slot axis over kvp; paged:
    # pool [n_blocks, Kh, block_s, hsz], the page's block_s axis over kvp —
    # the *same* spec, by construction of the paged layout
    cache_spec = P(None, tpa, kvp_axes, None)
    in_specs = (P(None, tpa, None),                       # q: repl over kvp
                cache_spec,                               # kcache
                cache_spec,                               # vcache
                tl_spec)
    if quant:
        in_specs += (P(None, tpa, kvp_axes), P(None, tpa, kvp_axes))
    if fused:
        in_specs += (P(None, tpa, None), P(None, tpa, None))  # k_new, v_new
    if paged:
        in_specs += (P(None, None),)                      # tables: replicated
    if grouped:
        in_specs += (P(None), P(None))                    # group_id, group_np
    out_spec = P(None, ((tpa,) if tpa else ()) + kvp_axes)
    scale_spec = P(None, tpa, kvp_axes)
    if fused:
        out_specs = (out_spec, cache_spec, cache_spec)
        if quant:
            out_specs += (scale_spec, scale_spec)
    else:
        out_specs = out_spec
    shard_fn = shard_map(
        local_fn, mesh=mesh, in_specs=in_specs,
        out_specs=out_specs, check_vma=False)

    def call(qs, ks, vs, tl, kss, vss, kns, vns, tbl):
        args = (qs, ks, vs, tl)
        if quant:
            args += (kss, vss)
        if fused:
            args += (kns, vns)
        if paged:
            args += (tbl,)
        if grouped:
            args += (jnp.asarray(groups[0], jnp.int32),
                     jnp.asarray(groups[1], jnp.int32))
        return shard_fn(*args)

    if hopb_chunks <= 1:
        return call(q, kcache, vcache, total_len, kscale, vscale,
                    k_new, v_new, block_tables)

    # ---- HOP-B: batch-wise communication/computation overlap (§2.1.3) ----
    assert b % hopb_chunks == 0, (b, hopb_chunks)
    bc = b // hopb_chunks
    outs = []
    # paged pool planes carry no batch axis: every chunk sees the whole
    # pool (its table rows select its pages).  In fused mode the appended
    # pool must thread chunk-to-chunk — that serializes the cache writes,
    # but the attention/all-to-all overlap HOP-B exists for is unaffected.
    kc_cur, vc_cur, ks_cur, vs_cur = kcache, vcache, kscale, vscale
    for i in range(hopb_chunks):
        csl = slice(i * bc, (i + 1) * bc)
        tl_i = total_len if jnp.ndim(total_len) == 0 else total_len[csl]
        res = call(q[csl],
                   kc_cur if paged else kc_cur[csl],
                   vc_cur if paged else vc_cur[csl], tl_i,
                   (ks_cur if paged else ks_cur[csl]) if quant else None,
                   (vs_cur if paged else vs_cur[csl]) if quant else None,
                   k_new[csl] if fused else None,
                   v_new[csl] if fused else None,
                   block_tables[csl] if paged else None)
        if fused and paged:
            outs.append(res[0])
            kc_cur, vc_cur = res[1], res[2]
            if quant:
                ks_cur, vs_cur = res[3], res[4]
        else:
            outs.append(res)
    if fused and paged:
        out = jnp.concatenate(outs, axis=0)
        if quant:
            return out, kc_cur, vc_cur, ks_cur, vs_cur
        return out, kc_cur, vc_cur
    if fused:
        return tuple(jnp.concatenate([o[i] for o in outs], axis=0)
                     for i in range(len(outs[0])))
    return jnp.concatenate(outs, axis=0)


def paged_slot_of_position(pos, block_tables, *, kvp: int, rr_block: int,
                           block_s: int):
    """(physical page [B], in-page row [B]) holding global position ``pos``.

    The paged twin of ``rr_slot_of_position``: position ``pos`` lives on
    rank ``r = (pos//rr) % KVP`` at local slot ``j``, i.e. logical page
    ``j // ps_loc`` at in-page row ``r*ps_loc + j % ps_loc`` (``ps_loc =
    block_s/KVP`` — the page's block_s axis is rank-major).  Negative
    positions (idle engine rows) clamp to logical page 0, whose table entry
    is the reserved sink page."""
    pos = jnp.asarray(pos, jnp.int32)
    ps_loc = block_s // kvp
    blk = pos // rr_block
    rank = blk % kvp
    j = (blk // kvp) * rr_block + pos % rr_block
    page = jnp.clip(j // ps_loc, 0, block_tables.shape[1] - 1)
    row = rank * ps_loc + j % ps_loc
    b = block_tables.shape[0]
    phys = block_tables[jnp.arange(b), jnp.broadcast_to(page, (b,))]
    return phys, jnp.broadcast_to(row, (b,))


def append_kv(kcache, vcache, k_new, v_new, total_len, *, kvp: int,
              rr_block: int, block_tables=None):
    """Round-robin KV concatenation (§2.3), GSPMD-compatible.

    kcache [B, Kh, S_cap, hsz] (S_cap = KVP * S_loc, round-robin layout);
    k_new [B, Kh, hsz] for the token at position total_len - 1.  total_len
    may be scalar (uniform batch: dynamic-update-slice) or [B] (continuous
    batching: per-request scatter).

    Paged mode (``block_tables`` [B, max_pages]): kcache/vcache are pool
    planes ``[n_blocks, Kh, block_s, hsz]`` and the row scatters into the
    physical page the table names for the token's logical page
    (``paged_slot_of_position``); idle rows (total_len 0) land on the
    reserved sink page 0.
    """
    if block_tables is not None:
        phys, row = paged_slot_of_position(
            total_len - 1, block_tables, kvp=kvp, rr_block=rr_block,
            block_s=kcache.shape[2])
        kcache = kcache.at[phys, :, row, :].set(k_new.astype(kcache.dtype))
        vcache = vcache.at[phys, :, row, :].set(v_new.astype(vcache.dtype))
        return kcache, vcache
    s_cap = kcache.shape[2]
    s_loc = s_cap // kvp
    pos = total_len - 1
    slot = rr_slot_of_position(pos, kvp, s_loc, rr_block)
    if jnp.ndim(total_len) == 0:
        k_new = k_new[:, :, None, :].astype(kcache.dtype)
        v_new = v_new[:, :, None, :].astype(vcache.dtype)
        kcache = jax.lax.dynamic_update_slice(kcache, k_new, (0, 0, slot, 0))
        vcache = jax.lax.dynamic_update_slice(vcache, v_new, (0, 0, slot, 0))
        return kcache, vcache
    b = kcache.shape[0]
    rows = jnp.arange(b)
    kcache = kcache.at[rows, :, slot, :].set(k_new.astype(kcache.dtype))
    vcache = vcache.at[rows, :, slot, :].set(v_new.astype(vcache.dtype))
    return kcache, vcache


def quantize_kv_token(x):
    """[B, Kh, hsz] -> (int8 [B, Kh, hsz], scale f32 [B, Kh]) symmetric."""
    xf = x.astype(jnp.float32)
    scale = jnp.maximum(jnp.max(jnp.abs(xf), axis=-1) / 127.0, 1e-30)
    q = jnp.clip(jnp.round(xf / scale[..., None]), -127, 127).astype(jnp.int8)
    return q, scale


def append_kv_quant(kcache, vcache, kscale, vscale, k_new, v_new, total_len,
                    *, kvp: int, rr_block: int, block_tables=None):
    """int8 round-robin KV append: quantize the new token per (B, Kh) and
    write payload + scale at its round-robin slot (§2.3 + §Perf kv8).
    Paged mode (``block_tables``): the payload/scale scatter goes through
    the block table into the pool planes, like ``append_kv``."""
    kq, ks = quantize_kv_token(k_new)
    vq, vs = quantize_kv_token(v_new)
    kcache, vcache = append_kv(kcache, vcache, kq, vq, total_len, kvp=kvp,
                               rr_block=rr_block, block_tables=block_tables)
    if block_tables is not None:
        phys, row = paged_slot_of_position(
            total_len - 1, block_tables, kvp=kvp, rr_block=rr_block,
            block_s=kcache.shape[2])
        kscale = kscale.at[phys, :, row].set(ks.astype(kscale.dtype))
        vscale = vscale.at[phys, :, row].set(vs.astype(vscale.dtype))
        return kcache, vcache, kscale, vscale
    s_loc = kcache.shape[2] // kvp
    slot = rr_slot_of_position(total_len - 1, kvp, s_loc, rr_block)
    if jnp.ndim(total_len) == 0:
        kscale = jax.lax.dynamic_update_slice(
            kscale, ks[:, :, None].astype(kscale.dtype), (0, 0, slot))
        vscale = jax.lax.dynamic_update_slice(
            vscale, vs[:, :, None].astype(vscale.dtype), (0, 0, slot))
    else:
        rows = jnp.arange(kcache.shape[0])
        kscale = kscale.at[rows, :, slot].set(ks.astype(kscale.dtype))
        vscale = vscale.at[rows, :, slot].set(vs.astype(vscale.dtype))
    return kcache, vcache, kscale, vscale


def prefill_to_rr_layout(cache, kvp: int, rr_block: int):
    """[B, Kh, S, hsz] contiguous-position cache -> round-robin slot layout.

    S must be a multiple of kvp*rr_block.  Pure reshape/transpose: block b of
    rr_block positions goes to rank b % kvp, local block b // kvp.
    """
    b, kh, s, hsz = cache.shape
    nblk = s // rr_block
    assert nblk % kvp == 0, (s, kvp, rr_block)
    c = cache.reshape(b, kh, nblk // kvp, kvp, rr_block, hsz)
    c = c.transpose(0, 1, 3, 2, 4, 5)          # [B,Kh,KVP,nloc,rr,hsz]
    return c.reshape(b, kh, s, hsz)
