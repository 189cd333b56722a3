"""jit'd public wrapper for flash_prefill: natural [B,T,Qh,hsz] layout,
padding to block multiples, GQA head grouping, scalar-prefetch packing —
plus the block-accounting layer that reports how many kv blocks the
causal/window skip (``prune``) actually streams."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels.flash_prefill.kernel import (flash_prefill_kernel,
                                                prefill_block_range)
from repro.utils import round_up


@functools.partial(jax.jit, static_argnames=("causal", "scale", "blk_q",
                                             "blk_k", "prune", "interpret"))
def flash_prefill(q, k, v, *, causal: bool = True, window=0, q_offset=0,
                  seq_lens=None, scale: float | None = None,
                  blk_q: int = 128, blk_k: int = 128, prune: bool = True,
                  block_tables=None, interpret: bool):
    """Full-sequence attention via the Pallas flash-prefill kernel.

    The kernel-backed sibling of ``models/attention.chunked_attention`` —
    this is the flash_prefill *family* entry point the kernel-backend
    registry routes to (``HelixConfig.prefill_backend``).

    Args:
      q: ``[B, T, Qh, hsz]`` queries; ``Qh % Kh == 0`` (GQA grouping).
      k, v: ``[B, S, Kh, hsz]`` keys/values.  ``S == T`` for causal
        self-attention; any ``S`` for cross attention (``causal=False``).
        In paged mode (``block_tables`` given) the K/V are shared pool
        planes ``[n_pool, Kh, page_k, hsz]`` instead — kernel layout, page
        size ``page_k`` pinned as ``blk_k``.
      causal: static — mask ``kpos > qpos`` (decoder self-attention).
      window: sliding window (``<= 0`` disables).  May be a *traced* scalar
        (per-layer local/global windows under ``lax.scan``).
      q_offset: global position of query row 0 (prefill continuation); may
        be traced, and may be a *per-request* ``[B]`` vector — the ragged
        chunk-packing contract that lets the serving engine pack prefills
        at different (offset, length) progress into one call.
      seq_lens: optional ``[B]`` int32 per-request valid KV lengths
        (continuous-batching prefill over right-padded prompts); kv positions
        ``>= seq_lens[b]`` are masked.  ``None`` means all ``S`` positions
        are live.  Rows with ``seq_lens[b] == 0`` emit zeros.
      scale: score scale; defaults to ``hsz ** -0.5``.
      blk_q, blk_k: kernel block sizes (static; see docs/kernels.md).
      prune: skip kv blocks that are causally/window/length-dead instead of
        masking them (index_map clamp + ``pl.when``; bit-exact either way).
        Causal T = S sweeps ~the lower triangle of the (T/blk_q, S/blk_k)
        rectangle; ``flash_prefill_accounting`` reports the exact counts.
      block_tables: optional ``[B, max_pages]`` int32 — paged KV: kv-block
        ``i`` of request ``b`` streams from pool plane
        ``block_tables[b, i]`` (scalar-prefetched indirection; composes
        with the causal/window skip, bit-exact vs the fixed layout).
        Requires ``seq_lens``: table entries beyond a request's allocation
        point at the shared sink page, whose contents are arbitrary — only
        the per-request length mask keeps them out of the softmax.
      interpret: run the kernel through the Pallas interpreter (any JAX
        backend) instead of compiling for TPU.

    Returns:
      ``[B, T, Qh, hsz]`` attention output in ``q.dtype``.
    """
    b, t, qh, hsz = q.shape
    paged = block_tables is not None
    kh = k.shape[1] if paged else k.shape[2]
    assert qh % kh == 0
    g = qh // kh
    if scale is None:
        scale = float(hsz) ** -0.5

    blk_q = min(blk_q, round_up(t, 8))
    t_pad = round_up(t, blk_q)

    # [B,T,Kh,G,hsz] -> [B,Kh,G,T,hsz]: the kernel stacks a block's G
    # query heads along rows (a row-major merge of whole sublane tiles)
    qg = q.reshape(b, t, kh, g, hsz).transpose(0, 2, 3, 1, 4)
    qg = jnp.pad(qg, ((0, 0), (0, 0), (0, 0), (0, t_pad - t), (0, 0)))
    if paged:
        # sink-page table entries hold arbitrary data; only the per-request
        # length mask keeps them out of the reduction
        assert seq_lens is not None, "paged flash_prefill requires seq_lens"
        blk_k = k.shape[2]                    # page size is the kv block
        s = np.shape(block_tables)[1] * blk_k
        kg, vg = k, v                         # pool planes, kernel layout
        tables = jnp.asarray(block_tables, jnp.int32)
    else:
        s = k.shape[1]
        blk_k = min(blk_k, round_up(s, 8))
        s_pad = round_up(s, blk_k)
        kg = k.transpose(0, 2, 1, 3)
        vg = v.transpose(0, 2, 1, 3)
        kg = jnp.pad(kg, ((0, 0), (0, 0), (0, s_pad - s), (0, 0)))
        vg = jnp.pad(vg, ((0, 0), (0, 0), (0, s_pad - s), (0, 0)))
        tables = None
    # kv rows beyond the true S are masked in-kernel (s_true); pad q rows
    # produce well-defined garbage and are sliced away below.

    meta = jnp.asarray(window, jnp.int32).reshape(1)
    offs = jnp.broadcast_to(
        jnp.asarray(q_offset, jnp.int32).reshape(-1), (b,))
    if seq_lens is None:
        lens = jnp.full((b,), s, jnp.int32)
    else:
        lens = jnp.broadcast_to(jnp.asarray(seq_lens, jnp.int32), (b,))

    out = flash_prefill_kernel(qg, kg, vg, meta, lens, offs, scale=scale,
                               causal=causal, blk_q=blk_q, blk_k=blk_k,
                               s_true=s, prune=prune, block_tables=tables,
                               interpret=interpret)
    out = out[:, :, :, :t].transpose(0, 3, 1, 2, 4)
    return out.reshape(b, t, qh, hsz)


def flash_prefill_accounting(q, k, v, *, causal: bool = True, window=0,
                             q_offset=0, seq_lens=None, blk_q: int = 128,
                             blk_k: int = 128, prune: bool = True,
                             block_tables=None, **_ignored):
    """KV blocks/bytes the matching ``flash_prefill`` call streams from HBM.

    Replays the kernel's skip range (``prefill_block_range`` — the same
    function its K/V ``index_map``s clamp with) over the (B, Kh, T-blocks,
    S-blocks) grid and counts distinct block fetches (consecutive steps on
    the same block are one DMA).  ``q_offset`` may be per-request ([B]) —
    the ragged-packing contract.  Paged mode (``block_tables``): ``k``/``v``
    are pool planes; the replay walks the same logical kv-block ranges
    through the table (distinct logical pages are distinct planes, so the
    count is unchanged; ``blk_k`` pins to the page size).  Pure host-side
    arithmetic; accepts any ``flash_prefill`` argument set (extra kwargs
    are ignored).

    Returns ``{"blocks_visited", "blocks_total", "bytes_read",
    "bytes_total", "blk_q", "blk_k", "n_qblocks", "n_kblocks"}``.
    """
    b, t, _, hsz = q.shape
    paged = block_tables is not None
    if paged:
        kh = k.shape[1]
        blk_k = k.shape[2]
        n_k = np.shape(block_tables)[1]
        s = n_k * blk_k
    else:
        s, kh = k.shape[1], k.shape[2]
        blk_k = min(blk_k, round_up(s, 8))
        n_k = round_up(s, blk_k) // blk_k
    blk_q = min(blk_q, round_up(t, 8))
    n_q = round_up(t, blk_q) // blk_q

    lens = np.broadcast_to(
        np.full((b,), s, np.int32) if seq_lens is None
        else np.asarray(seq_lens, np.int32).reshape(-1), (b,))
    offs = np.broadcast_to(
        np.asarray(q_offset, np.int32).reshape(-1), (b,))
    if prune:
        # prefill_block_range is elementwise jnp: one vectorized call over
        # the [b, n_q] grid instead of b*n_q eager dispatch loops
        _, nb = prefill_block_range(
            jnp.arange(n_q, dtype=jnp.int32)[None, :],
            jnp.asarray(lens)[:, None], jnp.asarray(offs)[:, None],
            jnp.asarray(window, jnp.int32), causal=causal,
            blk_q=blk_q, blk_k=blk_k, s_true=s)
        # a fully-skipped row still fetches one (clamped) block
        visited = int(np.maximum(np.asarray(nb), 1).sum())
    else:
        visited = b * n_q * n_k
    blocks_visited = kh * visited
    blocks_total = b * kh * n_q * n_k
    blk_bytes = 2 * blk_k * hsz * jnp.dtype(k.dtype).itemsize   # K + V
    return {
        "blocks_visited": blocks_visited,
        "blocks_total": blocks_total,
        "bytes_read": blocks_visited * blk_bytes,
        "bytes_total": blocks_total * blk_bytes,
        "blk_q": blk_q,
        "blk_k": blk_k,
        "n_qblocks": n_q,
        "n_kblocks": n_k,
    }

# --- static-analysis contract -------------------------------------------

from repro.kernels.contract import KernelContract, Operand  # noqa: E402
from repro.kernels.flash_prefill.kernel import prefill_index_maps  # noqa: E402

# default audit lattice: causal x window x prune x paged x ragged packing
_CONTRACT_LATTICE = (
    dict(case="causal-prune"),
    dict(case="causal-dense", prune=False),
    dict(case="causal-window", window=6),
    dict(case="causal-ragged", q_offset=(0, 3), seq_lens=(5, 16)),
    dict(case="cross-dense", causal=False, prune=False),
    dict(case="cross-lens", causal=False, seq_lens=(5, 16)),
    dict(case="paged-prune", paged=True, seq_lens=(5, 16)),
    dict(case="paged-window", paged=True, window=6, seq_lens=(5, 16)),
    dict(case="paged-sink-tail", paged=True, seq_lens=(5, 12),
         sink_tail=True),
)


def prefill_case_contract(case="causal-prune", *, b=2, kh=2, g=2, hsz=8,
                          t=8, s=16, blk_q=4, blk_k=4, causal=True,
                          window=0, q_offset=0, seq_lens=None, prune=True,
                          paged=False, sink_tail=False, seed=0):
    """Build the ``KernelContract`` for one flash_prefill configuration.

    Mirrors ``flash_prefill``'s geometry resolution (block sizing, padding,
    prefetch layout) and binds the *same* index_map callables the kernel
    passes to ``pallas_call`` (``kernel.prefill_index_maps``).  Returns one
    ``KernelContract``; ``flash_prefill_contract`` assembles the lattice.
    """
    blk_q = min(blk_q, round_up(t, 8))
    t_pad = round_up(t, blk_q)
    if paged:
        n_kblocks = s // blk_k
        s_pad = n_kblocks * blk_k
    else:
        blk_k = min(blk_k, round_up(s, 8))
        s_pad = round_up(s, blk_k)
        n_kblocks = s_pad // blk_k

    meta = np.array([window], np.int32)
    lens = (np.full((b,), s, np.int32) if seq_lens is None
            else np.broadcast_to(np.asarray(seq_lens, np.int32), (b,)))
    offs = np.broadcast_to(np.asarray(q_offset, np.int32).reshape(-1), (b,))
    prefetch = (meta, lens, offs)

    table = None
    n_pool = None
    if paged:
        rng = np.random.RandomState(seed)
        n_pool = 1 + b * n_kblocks           # page 0 is the reserved sink
        table = (1 + rng.permutation(b * n_kblocks)
                 .reshape(b, n_kblocks)).astype(np.int32)
        if sink_tail:
            need = (lens + blk_k - 1) // blk_k
            for i in range(b):
                table[i, max(int(need[i]), 1):] = 0
        prefetch = prefetch + (table,)

    idx = prefill_index_maps(causal=causal, blk_q=blk_q, blk_k=blk_k,
                             s_true=s, n_kblocks=n_kblocks, prune=prune,
                             paged=paged)

    kv_shape = ((n_pool, kh, blk_k, hsz) if paged
                else (b, kh, s_pad, hsz))
    pax = 0 if paged else None
    operands = [
        Operand("q", (b, kh, g, t_pad, hsz), (1, 1, g, blk_q, hsz),
                idx["q"]),
        Operand("k", kv_shape, (1, 1, blk_k, hsz), idx["kv"],
                streamed=True, paged_axis=pax),
        Operand("v", kv_shape, (1, 1, blk_k, hsz), idx["kv"],
                streamed=True, paged_axis=pax),
        Operand("out", (b, kh, g, t_pad, hsz), (1, 1, g, blk_q, hsz),
                idx["q"], kind="out"),
    ]

    active = None
    if prune:
        _, nb = prefill_block_range(
            jnp.arange(t_pad // blk_q, dtype=jnp.int32)[None, :],
            jnp.asarray(lens)[:, None], jnp.asarray(offs)[:, None],
            jnp.asarray(window, jnp.int32), causal=causal, blk_q=blk_q,
            blk_k=blk_k, s_true=s)
        nb_np = np.asarray(nb)

        def active(bi, h, qi, ki, _nb=nb_np):
            return bool(ki < _nb[bi, qi])

    return KernelContract(
        family="flash_prefill", case=case,
        grid=(b, kh, t_pad // blk_q, n_kblocks), operands=operands,
        prefetch=prefetch, stream_axis=3, active=active, table=table,
        n_pool=n_pool,
        notes=dict(causal=causal, window=window, prune=prune, paged=paged,
                   blk_q=blk_q, blk_k=blk_k, s_true=s))


def flash_prefill_contract():
    """Contracts for the flash_prefill audit lattice (``repro.analysis``).

    One ``KernelContract`` per configuration in the default lattice —
    causal x window x prune x paged x ragged chunk packing — each binding
    the kernel's real index_map callables at toy shapes.
    """
    return [prefill_case_contract(**dict(c)) for c in _CONTRACT_LATTICE]
