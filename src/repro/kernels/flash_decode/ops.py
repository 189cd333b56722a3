"""jit'd public wrapper for the flash_decode Pallas kernel.

Handles layout (Q-head grouping for GQA), padding (Q-group to sublane multiple,
S to block multiple) and un-padding, so callers use natural shapes:

    out, lse = flash_decode(q, k, v, total_len, rank, kvp=..., ...)

    q      [B, Qh, hsz]
    k, v   [B, Kh, S_cap, hsz]     (Qh % Kh == 0)
    out    [B, Qh, hsz]            lse [B, Qh] f32

Covers everything core/helix.py::_local_attend needs (the kernel is the real
Helix execution path when ``HelixConfig.attn_backend`` selects it):

  * ``total_len`` — scalar or per-request [B] int32 (continuous batching);
    prefetched as a length vector, one entry per batch row.
  * ``contiguous`` — non-round-robin shard layout (whisper cross-attention):
    local slot j holds global position rank*S_cap + j.
  * ``slot_offset`` — the sliding-window cache-slice fast path: positions are
    computed for slot j + slot_offset.
  * ``window`` — runtime sliding-window scalar (<= 0 disables); may be a
    traced per-layer value.
  * ``kscale``/``vscale`` [B, Kh, S_cap] — int8 K/V cache mode: dequant
    happens inside the kernel, block-by-block in VMEM.
  * ``k_new``/``v_new`` [B, Kh, hsz] — fused KV-append epilogue: the kernel
    writes the new token's row into the (aliased) cache and attends over it,
    so the separate ``append_kv`` cache round-trip disappears.  Requires the
    round-robin layout without slot_offset; ``total_len`` must already count
    the appended token.  Returns ``(out, lse, kcache, vcache)``; with an
    int8 cache (``kscale``/``vscale`` given) the raw rows are quantized
    in-kernel and ``(out, lse, kcache, vcache, kscale, vscale)`` comes back.
  * ``prune`` — block pruning (default on): fully-invalid S blocks are
    *skipped*, not masked — the K/V index_maps clamp to the valid span so
    Pallas elides the pruned blocks' DMAs, and ``pl.when`` skips their
    compute.  Bit-exact vs ``prune=False``; per-request HBM traffic becomes
    O(valid_len) instead of O(S_cap).  ``flash_decode_accounting`` reports
    the resulting blocks/bytes per call.

Padded S slots are masked in-kernel against the true capacity (prefetch-free:
it is a static kernel parameter), so any S_cap works in both layouts.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from repro.utils import round_up, pad_dim
from repro.kernels.contract import KernelContract, Operand
from repro.kernels.flash_decode.kernel import (_append_slot, append_rows,
                                               decode_index_maps,
                                               flash_decode_kernel,
                                               grouped_prefix_index_maps,
                                               prefix_pass_kernel,
                                               prune_block_range)


@functools.partial(
    jax.jit,
    static_argnames=("kvp", "rr_block", "scale", "block_s", "interpret",
                     "contiguous", "prune"))
def flash_decode(q, k, v, total_len, rank, *, kvp: int = 1, rr_block: int = 16,
                 window=0, scale: float | None = None, block_s: int = 512,
                 interpret: bool, contiguous: bool = False,
                 slot_offset=0, kscale=None, vscale=None,
                 k_new=None, v_new=None, prune: bool = True,
                 block_tables=None, groups=None):
    """Decode-shape attention over one KV shard via the Pallas kernel.

    This is the flash_decode *family* entry point the kernel-backend
    registry routes to (``HelixConfig.attn_backend``); see the module
    docstring for the full mode lattice and ``flash_decode_ref`` for the
    oracle that defines the semantics.

    Paged mode: with ``block_tables`` ([B, max_pages] int32) the K/V
    operands are shared pool planes ``[n_pool, Kh, page_s, hsz]``
    (``kscale``/``vscale`` become ``[n_pool, Kh, page_s]``): request ``b``'s
    logical local slots ``[p*page_s, (p+1)*page_s)`` live in physical pool
    page ``block_tables[b, p]``.  The kernel's S-block size is pinned to
    ``page_s`` and the index_maps stream through the prefetched table
    (bit-exact vs the fixed layout at the same block size; pruning, quant
    and the fused append all compose).  Unallocated table entries should
    point at the reserved sink page 0.

    Grouped shared-prefix decode (``groups`` — paged only): ``groups =
    (group_id [B], group_np [B])`` int32 marks requests whose block tables
    share their leading ``group_np`` physical pages (CoDec-style, arXiv
    2505.17694).  ``group_id`` is any stable representative (e.g. the
    lowest member's batch row); singletons use their own row with
    ``group_np == 0``.  The call splits into two passes: a *prefix* pass
    (``prefix_pass_kernel``) stacks each group's Q rows and streams every
    shared page **once per group**, emitting raw online-softmax state, and
    the *suffix* pass resumes that state while its span clamp skips blocks
    below ``group_np``.  Bit-exact with ``groups=None`` — same block order,
    same masks — while prefix HBM reads drop by ~1/group_size.

    Returns ``(out [B, Qh, hsz], lse [B, Qh] f32)``, plus the appended
    ``(kcache, vcache)`` when ``k_new``/``v_new`` engage the fused-append
    epilogue (and the updated ``(kscale, vscale)`` for int8 caches) — pool
    planes in paged mode.
    """
    b, qh, hsz = q.shape
    kh = k.shape[1]
    paged = block_tables is not None
    assert qh % kh == 0, (qh, kh)
    g = qh // kh
    if scale is None:
        scale = float(hsz) ** -0.5
    quant = kscale is not None
    append = k_new is not None
    if append:
        assert v_new is not None and not contiguous
        # slot_offset may reach here as a (weak) tracer under an outer jit;
        # only a concrete non-zero value can be rejected eagerly.  The Helix
        # caller guarantees the slice fast path and fusion never overlap
        # (core/helix.fuse_append_applicable).
        assert not (isinstance(slot_offset, int) and slot_offset != 0), \
            "fused append excludes the sliding-window cache-slice fast path"
    if paged:
        assert not contiguous, "paged mode excludes the contiguous layout"
        assert not (isinstance(slot_offset, int) and slot_offset != 0), \
            "paged mode excludes the cache-slice fast path"
        # page size is the kernel block; logical capacity spans the table
        block_s = k.shape[2]
        s_cap = block_tables.shape[1] * block_s
        kp, vp = k, v
        tables = jnp.asarray(block_tables, jnp.int32)
    else:
        s_cap = k.shape[2]
        block_s = min(block_s, round_up(s_cap, 128))
        with jax.named_scope("kv_pool"):
            kp = pad_dim(k, 2, block_s)
            vp = pad_dim(v, 2, block_s)
        tables = None
    qp = round_up(g, 8)

    qg = q.reshape(b, kh, g, hsz)
    qg = pad_dim(qg, 2, qp)
    if kscale is not None:
        with jax.named_scope("kv_pool"):
            kscale = kscale.astype(jnp.float32)
            vscale = vscale.astype(jnp.float32)
            if not paged:
                kscale = pad_dim(kscale, 2, block_s)
                vscale = pad_dim(vscale, 2, block_s)

    meta = jnp.stack([jnp.asarray(rank, jnp.int32),
                      jnp.asarray(slot_offset, jnp.int32),
                      jnp.asarray(window, jnp.int32)])
    tl = jnp.asarray(total_len, jnp.int32).reshape(-1)     # scalar -> [1]
    tl = jnp.broadcast_to(tl, (b,))

    kw = {}
    if append:
        if quant:
            # the kernel quantizes the raw rows itself (payload + scale)
            kw = dict(k_new=k_new.astype(jnp.float32),
                      v_new=v_new.astype(jnp.float32))
        else:
            # match the unfused append_kv dtype cast so fusion is bit-exact
            kw = dict(k_new=k_new.astype(k.dtype), v_new=v_new.astype(v.dtype))

    if groups is not None:
        assert paged, "grouped decode requires paged mode"
        gid = jnp.asarray(groups[0], jnp.int32)
        gnp_req = jnp.asarray(groups[1], jnp.int32)
        # static worst case: B group rows x B member slots (every request a
        # singleton, or one group holding the whole batch); unused rows
        # carry gnp == 0 / gtl == 0 and degenerate to the identity update
        gnp = jnp.zeros((b,), jnp.int32).at[gid].max(gnp_req)
        bidx = jnp.arange(b)
        same = gid[None, :] == gid[:, None]
        ms = jnp.sum(same & (bidx[None, :] < bidx[:, None]), axis=1)
        gtl = jnp.zeros((b, b), jnp.int32).at[gid, ms].set(tl)
        # duplicate-index winner is irrelevant: only the leading gnp[g]
        # entries are read, and members of a group share exactly those
        gtab = jnp.zeros((b, tables.shape[1]), jnp.int32).at[gid].set(tables)
        qs = jnp.zeros((b, kh, b, qp, hsz), qg.dtype).at[gid, :, ms].set(qg)
        acc_g, m_g, l_g = prefix_pass_kernel(
            qs.reshape(b, kh, b * qp, hsz), kp, vp, meta, gnp, gtl, gtab,
            scale=scale, kvp=kvp, rr_block=rr_block, block_s=block_s,
            s_true=s_cap, kscale=kscale, vscale=vscale, interpret=interpret)
        acc0 = acc_g.reshape(b, kh, b, qp, hsz)[gid, :, ms]
        m0 = m_g.reshape(b, kh, b, qp)[gid, :, ms]
        l0 = l_g.reshape(b, kh, b, qp)[gid, :, ms]
        kw.update(sfx_start=gnp_req, init_state=(acc0, m0, l0))

    res = flash_decode_kernel(
        qg, kp, vp, meta, tl, scale=scale, kvp=kvp, rr_block=rr_block,
        block_s=block_s, s_true=s_cap, contiguous=contiguous,
        kscale=kscale, vscale=vscale, prune=prune, block_tables=tables,
        interpret=interpret, **kw)

    out, lse = res[0], res[1]
    out = out[:, :, :g, :].reshape(b, qh, hsz)
    lse = lse[:, :, :g].reshape(b, qh)
    if append:
        if paged:
            return (out, lse) + tuple(res[2:])
        with jax.named_scope("kv_pool"):
            kc, vc = res[2][:, :, :s_cap], res[3][:, :, :s_cap]
            if quant:
                return (out, lse, kc, vc, res[4][:, :, :s_cap],
                        res[5][:, :, :s_cap])
            return out, lse, kc, vc
    return out, lse


def flash_decode_accounting(q, k, v, total_len, rank, *, kvp: int = 1,
                            rr_block: int = 16, window=0,
                            block_s: int = 512, contiguous: bool = False,
                            slot_offset=0, prune: bool = True,
                            kscale=None, vscale=None, block_tables=None,
                            groups=None, **_ignored):
    """Blocks/bytes the matching ``flash_decode`` call streams from HBM.

    Replays the kernel's pruning ``index_map`` (``prune_block_range`` — the
    same function the kernel clamps its K/V DMAs with) over the grid and
    counts *distinct* block fetches: consecutive grid steps that reference
    the same block are one DMA on TPU, which is exactly how pruning turns
    masked blocks into elided reads.  ``prune=False`` reproduces the dense
    sweep (every block of every (b, h) pair).

    Paged mode (``block_tables`` [B, max_pages]): ``k``/``v`` are pool
    planes ``[n_pool, Kh, page_s, hsz]``; the replay walks the same logical
    page ranges through the table — a request's pages are distinct physical
    planes, so the distinct-fetch count (and the prune bound
    ``<= ceil(valid_len/block_s) + 1`` per (b, h)) is unchanged by the
    indirection, only ``block_s`` is pinned to the page size.

    Grouped mode (``groups = (group_id [B], group_np [B])``): replays both
    passes.  The prefix pass streams ``max(group_np_g, 1)`` pages per
    *group* grid row (all B rows exist; memberless rows reference the
    clamped sink page once), the suffix pass per request lifts the pruned
    span's lower bound to ``group_np[b]`` — together they prove the
    ~1/group_size prefix bytes-read reduction.  The split is reported via
    ``prefix_blocks``/``suffix_blocks`` (and ``prefix_bytes``/
    ``suffix_bytes``); ungrouped calls report ``prefix_blocks == 0``.

    Pure host-side arithmetic — no kernel launch, any argument set accepted
    by ``flash_decode`` works (extra kwargs are ignored), and ``q``/``k``/
    ``v`` may be ``jax.ShapeDtypeStruct``s (only shapes/dtypes are read).
    Returns a dict:

      ``blocks_visited`` / ``blocks_total`` — distinct K/V block DMAs vs the
      dense sweep, summed over the (B, Kh, S-blocks) grid;
      ``bytes_read`` / ``bytes_total`` — the corresponding K+V HBM bytes
      (+ dequant-scale bytes in int8 mode);
      ``prefix_blocks``/``suffix_blocks``, ``prefix_bytes``/
      ``suffix_bytes`` — the grouped two-pass split of ``blocks_visited``;
      ``block_s``, ``n_blocks`` — resolved kernel blocking.
    """
    paged = block_tables is not None
    kh, hsz = k.shape[1], k.shape[3]
    b = q.shape[0]
    if paged:
        block_s = k.shape[2]                       # page size is the block
        n_blocks = np.shape(block_tables)[1]       # logical pages
        s_cap = n_blocks * block_s
    else:
        s_cap = k.shape[2]
        block_s = min(block_s, round_up(s_cap, 128))
        s_pad = round_up(s_cap, block_s)
        n_blocks = s_pad // block_s

    tl = np.broadcast_to(np.asarray(total_len, np.int32).reshape(-1), (b,))
    if prune:
        lo, nb = prune_block_range(
            jnp.asarray(tl), jnp.asarray(rank, jnp.int32),
            jnp.asarray(slot_offset, jnp.int32), jnp.asarray(window, jnp.int32),
            kvp=kvp, rr_block=rr_block, block_s=block_s, s_true=s_cap,
            contiguous=contiguous)
        lo, nb = np.asarray(lo), np.asarray(nb)
        if groups is not None:
            # suffix pass: the span's lower bound is lifted to the first
            # unshared page (mirrors decode_index_maps grouped clamp)
            start = np.broadcast_to(
                np.asarray(groups[1], np.int32).reshape(-1), (b,))
            lo2 = np.maximum(lo, start)
            nb = np.maximum(lo + nb - lo2, 0)
        # a fully-pruned request still references one (clamped) block: the
        # grid's first step fetches it before pl.when skips the compute
        per_req = np.maximum(nb, 1)
    else:
        per_req = np.full((b,), n_blocks)
    prefix_blocks = 0
    if groups is not None:
        # prefix pass grid is (B group rows, Kh, n_blocks): row g streams
        # its max(gnp, 1) span-clamped shared pages once per *group*
        gid = np.broadcast_to(np.asarray(groups[0], np.int32).reshape(-1),
                              (b,))
        gnp_req = np.broadcast_to(np.asarray(groups[1], np.int32).reshape(-1),
                                  (b,))
        gnp = np.zeros((b,), np.int32)
        np.maximum.at(gnp, gid, gnp_req)
        prefix_blocks = int(kh * np.maximum(gnp, 1).sum())
    suffix_blocks = int(kh * per_req.sum())
    blocks_visited = prefix_blocks + suffix_blocks
    blocks_total = b * kh * n_blocks
    el = jnp.dtype(k.dtype).itemsize
    blk_bytes = 2 * block_s * hsz * el                    # K + V payload
    if kscale is not None:
        blk_bytes += 2 * block_s * 4                      # f32 dequant scales
    return {
        "blocks_visited": blocks_visited,
        "blocks_total": blocks_total,
        "bytes_read": blocks_visited * blk_bytes,
        "bytes_total": blocks_total * blk_bytes,
        "prefix_blocks": prefix_blocks,
        "suffix_blocks": suffix_blocks,
        "prefix_bytes": prefix_blocks * blk_bytes,
        "suffix_bytes": suffix_blocks * blk_bytes,
        "block_s": block_s,
        "n_blocks": n_blocks,
    }

# --- static-analysis contract -------------------------------------------

# default audit lattice: prune x window x paged x kv8 x rr/contiguous x
# slot_offset x fused append, at interpreter-friendly toy shapes.  Mode
# exclusions mirror flash_decode's assertions (append/paged exclude the
# contiguous layout and the cache-slice fast path).
_CONTRACT_LATTICE = (
    dict(case="rr-prune"),
    dict(case="rr-dense", prune=False),
    dict(case="rr-window", window=6),
    dict(case="rr-window-slice", window=6, slot_offset=3),
    dict(case="rr-rank0", rank=0),
    dict(case="contig-prune", contiguous=True, kvp=1, rank=0),
    dict(case="contig-window", contiguous=True, kvp=1, rank=0, window=6),
    dict(case="contig-rank1", contiguous=True, rank=1, total_len=(20, 30)),
    dict(case="kv8-prune", quant=True),
    dict(case="append-rr", append=True),
    dict(case="append-kv8", append=True, quant=True),
    dict(case="append-window", append=True, window=6),
    dict(case="paged-prune", paged=True),
    dict(case="paged-dense", paged=True, prune=False),
    dict(case="paged-kv8", paged=True, quant=True),
    dict(case="paged-append-kv8", paged=True, quant=True, append=True),
    dict(case="paged-sink-tail", paged=True, sink_tail=True),
    dict(case="paged-grouped", paged=True, grouped=True),
    dict(case="paged-grouped-append", paged=True, grouped=True, append=True),
    dict(case="paged-shared-prefix", paged=True, grouped=True,
         shared_prefix=True, kvp=1, rank=0, total_len=(9, 13)),
    dict(case="paged-shared-append", paged=True, grouped=True,
         shared_prefix=True, append=True, kvp=1, rank=0, total_len=(9, 13)),
)


def decode_case_contract(case="rr-prune", *, b=2, qh=4, kh=2, hsz=8,
                         s_cap=16, kvp=2, rr_block=2, block_s=4, rank=1,
                         total_len=(5, 13), window=0, slot_offset=0,
                         contiguous=False, quant=False, append=False,
                         prune=True, paged=False, sink_tail=False,
                         grouped=False, shared_prefix=False, seed=0):
    """Build the ``KernelContract`` for one flash_decode configuration.

    Mirrors ``flash_decode``'s geometry resolution (padding, block sizing,
    prefetch layout) at the given shapes and binds the *same* index_map
    callables the kernel would pass to ``pallas_call``
    (``kernel.decode_index_maps``), so the static auditor proves properties
    of the real DMA addressing.  ``sink_tail`` leaves unallocated paged
    table entries on the reserved sink page 0.  ``grouped`` audits the
    grouped-suffix maps: a ``start [B]`` prefetch operand joins the table,
    the init-state operands precede q, and the pruned span is lifted to the
    start page.  ``shared_prefix`` makes the requests share their leading
    table page (request 1 maps request 0's first page) and sets the
    ``shared_ok`` note so the table audit allows the read-only duplicate —
    append targets must still be exclusive.  Returns one
    ``KernelContract``; ``flash_decode_contract`` assembles the lattice.
    """
    g = qh // kh
    qp = round_up(g, 8)
    if paged:
        n_blocks = s_cap // block_s
        s_pad = n_blocks * block_s
    else:
        block_s = min(block_s, round_up(s_cap, 128))
        s_pad = round_up(s_cap, block_s)
        n_blocks = s_pad // block_s
    s_true = s_cap

    meta = np.array([rank, slot_offset, window], np.int32)
    tl = np.broadcast_to(np.asarray(total_len, np.int32).reshape(-1), (b,))
    prefetch = (meta, tl)

    table = None
    n_pool = None
    start = None
    if paged:
        rng = np.random.RandomState(seed)
        n_pool = 1 + b * n_blocks            # page 0 is the reserved sink
        table = (1 + rng.permutation(b * n_blocks)
                 .reshape(b, n_blocks)).astype(np.int32)
        if sink_tail:
            # entries past the valid span are unallocated -> sink page 0
            need = (tl + block_s - 1) // block_s
            for i in range(b):
                table[i, max(int(need[i]), 1):] = 0
        if shared_prefix:
            # both requests map request 0's first page as their shared
            # (read-only, refcounted) leading prefix page
            table[1, 0] = table[0, 0]
        prefetch = prefetch + (table,)
    if grouped:
        assert paged, "grouped suffix maps require paged mode"
        # first unshared logical page per request: with shared_prefix both
        # requests resume past the one shared page; otherwise request 0 is
        # a singleton (start 0) and request 1 pretends one prefix page
        start = (np.full((b,), 1, np.int32) if shared_prefix
                 else np.arange(b, dtype=np.int32) % 2)
        prefetch = prefetch + (start,)

    idx = decode_index_maps(
        kvp=kvp, rr_block=rr_block, block_s=block_s, s_true=s_true,
        n_blocks=n_blocks, contiguous=contiguous, prune=prune, paged=paged,
        grouped=grouped)

    kv_shape = ((n_pool, kh, block_s, hsz) if paged
                else (b, kh, s_pad, hsz))
    sc_shape = ((n_pool, kh, block_s) if paged else (b, kh, s_pad))
    pax = 0 if paged else None
    rw = append_rows(block_s)

    operands = []
    if grouped:
        # the prefix pass's raw state precedes q (kernel arg order)
        operands += [
            Operand("acc0", (b, kh, qp, hsz), (1, 1, qp, hsz), idx["q"]),
            Operand("m0", (b, kh, qp, 1), (1, 1, qp, 1), idx["lse"]),
            Operand("l0", (b, kh, qp, 1), (1, 1, qp, 1), idx["lse"]),
        ]
    operands += [
        Operand("q", (b, kh, qp, hsz), (1, 1, qp, hsz), idx["q"]),
        Operand("k", kv_shape, (1, 1, block_s, hsz), idx["kv"],
                streamed=True, paged_axis=pax),
        Operand("v", kv_shape, (1, 1, block_s, hsz), idx["kv"],
                streamed=True, paged_axis=pax),
    ]
    if quant:
        operands += [
            Operand("kscale", sc_shape, (1, 1, block_s), idx["scale"],
                    streamed=True, paged_axis=pax),
            Operand("vscale", sc_shape, (1, 1, block_s), idx["scale"],
                    streamed=True, paged_axis=pax),
        ]
    if append:
        operands += [
            Operand("k_new", (b, kh, 1, hsz), (1, 1, 1, hsz), idx["new"]),
            Operand("v_new", (b, kh, 1, hsz), (1, 1, 1, hsz), idx["new"]),
            Operand("k_row_in", kv_shape, (1, 1, rw, hsz), idx["row"],
                    paged_axis=pax),
            Operand("v_row_in", kv_shape, (1, 1, rw, hsz), idx["row"],
                    paged_axis=pax),
        ]
        if quant:
            operands += [
                Operand("kscale_row_in", sc_shape, (1, 1, rw), idx["srow"],
                        paged_axis=pax),
                Operand("vscale_row_in", sc_shape, (1, 1, rw), idx["srow"],
                        paged_axis=pax),
            ]
    operands += [
        Operand("out", (b, kh, qp, hsz), (1, 1, qp, hsz), idx["q"],
                kind="out"),
        Operand("lse", (b, kh, qp, 1), (1, 1, qp, 1), idx["lse"],
                kind="out"),
    ]
    npre = (3 if paged else 2) + (1 if grouped else 0)
    qoff = npre + (3 if grouped else 0)
    aliases = {}
    if append:
        operands += [
            Operand("k_row_out", kv_shape, (1, 1, rw, hsz), idx["row"],
                    kind="out", alias_of="k", paged_axis=pax),
            Operand("v_row_out", kv_shape, (1, 1, rw, hsz), idx["row"],
                    kind="out", alias_of="v", paged_axis=pax),
        ]
        aliases = {qoff + 1: 2, qoff + 2: 3}
        if quant:
            operands += [
                Operand("kscale_row_out", sc_shape, (1, 1, rw), idx["srow"],
                        kind="out", alias_of="kscale", paged_axis=pax),
                Operand("vscale_row_out", sc_shape, (1, 1, rw), idx["srow"],
                        kind="out", alias_of="vscale", paged_axis=pax),
            ]
            aliases = {qoff + 1: 2, qoff + 2: 3, qoff + 3: 4, qoff + 4: 5}

    active = None
    if prune:
        lo_d, nb_d = prune_block_range(
            jnp.asarray(tl), jnp.asarray(rank, jnp.int32),
            jnp.asarray(slot_offset, jnp.int32),
            jnp.asarray(window, jnp.int32), kvp=kvp, rr_block=rr_block,
            block_s=block_s, s_true=s_true, contiguous=contiguous)
        lo_np, nb_np = np.asarray(lo_d), np.asarray(nb_d)
        if grouped:
            lo2 = np.maximum(lo_np, start)
            nb_np = np.maximum(lo_np + nb_np - lo2, 0)

        def active(bi, h, s, _nb=nb_np):
            return bool(s < _nb[bi])
    # dense grouped mode skips compute below start but still streams every
    # block (no index clamp), so no elision predicate applies there

    expected_row = None
    if append:
        j_new = np.asarray(_append_slot(jnp.asarray(tl), kvp, rr_block,
                                        s_pad))

        def expected_row(bi, h, _j=j_new, _tbl=table):
            # the (1, 1, rw, hsz) window block holding the appended row
            j = int(_j[bi])
            if _tbl is not None:
                return (int(_tbl[bi, j // block_s]), h,
                        (j % block_s) // rw, 0)
            return (bi, h, j // rw, 0)

    return KernelContract(
        family="flash_decode", case=case, grid=(b, kh, n_blocks),
        operands=operands, prefetch=prefetch, stream_axis=2,
        aliases=aliases, active=active, expected_row=expected_row,
        table=table, n_pool=n_pool,
        notes=dict(kvp=kvp, rr_block=rr_block, block_s=block_s,
                   s_true=s_true, prune=prune, paged=paged, quant=quant,
                   append=append, contiguous=contiguous, window=window,
                   slot_offset=slot_offset, grouped=grouped,
                   shared_ok=shared_prefix))


def prefix_case_contract(case="grouped-prefix", *, g=2, gm=2, kh=2, hsz=8,
                         qp=8, kvp=1, rr_block=2, block_s=4, n_blocks=4,
                         window=0, quant=False, seed=0):
    """``KernelContract`` for the grouped shared-prefix pass.

    Grid ``(G, Kh, n_blocks)`` over group rows; binds the *same*
    ``grouped_prefix_index_maps`` callables ``prefix_pass_kernel`` hands to
    ``pallas_call``.  Group 0 holds two members sharing a two-page prefix,
    group 1 is a memberless padding row (``gnp == 0``, all lengths 0) — the
    degenerate shape every batch position the engine leaves ungrouped
    takes, whose span clamp pins the stream to one page.
    """
    rows = gm * qp
    rng = np.random.RandomState(seed)
    n_pool = 1 + g * n_blocks
    gtab = (1 + rng.permutation(g * n_blocks)
            .reshape(g, n_blocks)).astype(np.int32)
    gnp = np.array([2] + [0] * (g - 1), np.int32)
    gtl = np.zeros((g, gm), np.int32)
    gtl[0] = [2 * block_s + 1, 3 * block_s + 1][:gm]
    meta = np.array([0, 0, window], np.int32)

    idx = grouped_prefix_index_maps(n_blocks=n_blocks)
    operands = [
        Operand("q", (g, kh, rows, hsz), (1, 1, rows, hsz), idx["q"]),
        Operand("k", (n_pool, kh, block_s, hsz), (1, 1, block_s, hsz),
                idx["kv"], streamed=True, paged_axis=0),
        Operand("v", (n_pool, kh, block_s, hsz), (1, 1, block_s, hsz),
                idx["kv"], streamed=True, paged_axis=0),
    ]
    if quant:
        operands += [
            Operand("kscale", (n_pool, kh, block_s), (1, 1, block_s),
                    idx["scale"], streamed=True, paged_axis=0),
            Operand("vscale", (n_pool, kh, block_s), (1, 1, block_s),
                    idx["scale"], streamed=True, paged_axis=0),
        ]
    operands += [
        Operand("acc", (g, kh, rows, hsz), (1, 1, rows, hsz), idx["acc"],
                kind="out"),
        Operand("m", (g, kh, rows, 1), (1, 1, rows, 1), idx["ml"],
                kind="out"),
        Operand("l", (g, kh, rows, 1), (1, 1, rows, 1), idx["ml"],
                kind="out"),
    ]

    def active(gi, h, s, _np=gnp):
        return bool(s < _np[gi])

    return KernelContract(
        family="flash_decode", case=case, grid=(g, kh, n_blocks),
        operands=operands, prefetch=(meta, gnp, gtl, gtab), stream_axis=2,
        active=active, table=gtab, n_pool=n_pool,
        notes=dict(kvp=kvp, rr_block=rr_block, block_s=block_s,
                   quant=quant, grouped_prefix=True))


def flash_decode_contract():
    """Contracts for the flash_decode audit lattice (``repro.analysis``).

    One ``KernelContract`` per configuration in the default lattice —
    prune x window x paged x kv8 x rr/contiguous x slot_offset x fused
    append x grouped/shared-prefix — each binding the kernel's real
    index_map callables at toy shapes the auditor can enumerate
    exhaustively, plus the grouped shared-prefix pass's own contracts.
    """
    suite = [decode_case_contract(**dict(c)) for c in _CONTRACT_LATTICE]
    suite.append(prefix_case_contract())
    suite.append(prefix_case_contract(case="grouped-prefix-kv8", quant=True))
    return suite
