"""Attention: reference + memory-bounded chunked implementations (pure jnp).

Also: the static head-layout machinery that pads/permutes GQA heads so that
tensor-parallel sharding respects KV-group boundaries (DESIGN.md §5).

Conventions
-----------
  q        [B, T, Qh, hsz]
  k, v     [B, S, Kh, hsz]     with Qh % Kh == 0 (after layout)
  output   [B, T, Qh, hsz]

The train/prefill path uses ``chunked_attention`` (lax.scan over query
chunks — memory O(B·h·cq·S) instead of O(B·h·T·S)).  The decode path lives
in core/helix.py (sharded) and kernels/flash_decode (TPU hotspot).
"""
from __future__ import annotations

import dataclasses
import functools

import numpy as np
import jax
import jax.numpy as jnp

from repro.utils import NEG_INF, round_up, cdiv


# ------------------------------------------------------------- head layout
@dataclasses.dataclass(frozen=True)
class HeadLayout:
    """Static padded/permuted GQA head layout for width-W head sharding.

    q_src[i]  — original q head feeding padded slot i (== Qh ⇒ zero pad)
    kv_src[j] — original kv head replicated into padded slot j
    """
    q_heads: int
    kv_heads: int
    q_pad: int
    kv_pad: int
    q_src: tuple[int, ...]
    kv_src: tuple[int, ...]

    @property
    def group(self) -> int:
        return self.q_pad // self.kv_pad

    @property
    def is_identity(self) -> bool:
        return (self.q_pad == self.q_heads and self.kv_pad == self.kv_heads
                and self.q_src == tuple(range(self.q_heads)))


@functools.lru_cache(maxsize=None)
def head_layout(q_heads: int, kv_heads: int, width: int) -> HeadLayout:
    """Pad Kh to a multiple-or-divisor-aligned count and Qh to match, so a
    width-way shard of the padded q-head axis never crosses a kv group."""
    assert q_heads % kv_heads == 0, (q_heads, kv_heads)
    g0 = q_heads // kv_heads
    # Kh -> smallest Kp >= Kh that is a divisor or multiple of width (dummy
    # zero kv heads fill the gap); group g0 -> smallest gp with W | Kp*gp.
    # Together these guarantee a width-way shard of the padded q-head axis
    # never splits a kv group across ranks.  Dummy kv heads are attended only
    # by pad q slots whose out-projection rows are zero => numerically exact.
    kv_pad = kv_heads
    while not (width % kv_pad == 0 or kv_pad % width == 0):
        kv_pad += 1
    gp = g0
    while (kv_pad * gp) % width:
        gp += 1
    q_pad = kv_pad * gp
    q_src, kv_src = [], []
    for j in range(kv_pad):
        kv_src.append(j if j < kv_heads else kv_heads)       # dummy sentinel
        for t in range(gp):
            real = j < kv_heads and t < g0
            q_src.append(j * g0 + t if real else q_heads)    # pad sentinel
    return HeadLayout(q_heads, kv_heads, q_pad, kv_pad,
                      tuple(q_src), tuple(kv_src))


def apply_q_layout(wq: jax.Array, layout: HeadLayout, hsz: int) -> jax.Array:
    """[H, Qh*hsz] -> [H, Qp*hsz] padded/permuted view (zero pads)."""
    if layout.is_identity:
        return wq
    h = wq.shape[0]
    w = wq.reshape(h, layout.q_heads, hsz)
    w = jnp.concatenate([w, jnp.zeros((h, 1, hsz), wq.dtype)], axis=1)
    return w[:, np.array(layout.q_src)].reshape(h, layout.q_pad * hsz)


def apply_o_layout(wo: jax.Array, layout: HeadLayout, hsz: int) -> jax.Array:
    """[Qh*hsz, H] -> [Qp*hsz, H] (zero rows at pads — padding is exact)."""
    if layout.is_identity:
        return wo
    h = wo.shape[-1]
    w = wo.reshape(layout.q_heads, hsz, h)
    w = jnp.concatenate([w, jnp.zeros((1, hsz, h), wo.dtype)], axis=0)
    return w[np.array(layout.q_src)].reshape(layout.q_pad * hsz, h)


def apply_kv_layout(wkv: jax.Array, layout: HeadLayout, hsz: int) -> jax.Array:
    """[H, Kh*hsz] -> [H, Kp*hsz] padded view (dummy kv heads are zero)."""
    if layout.is_identity:
        return wkv
    h = wkv.shape[0]
    w = wkv.reshape(h, layout.kv_heads, hsz)
    w = jnp.concatenate([w, jnp.zeros((h, 1, hsz), wkv.dtype)], axis=1)
    return w[:, np.array(layout.kv_src)].reshape(h, layout.kv_pad * hsz)


# ------------------------------------------------------------- reference
def ref_attention(q, k, v, *, causal: bool = True, window: int = 0,
                  q_offset: int | jax.Array = 0):
    """Naive full-matrix attention (small tests only)."""
    b, t, qh, hsz = q.shape
    s, kh = k.shape[1], k.shape[2]
    g = qh // kh
    qf = q.astype(jnp.float32).reshape(b, t, kh, g, hsz) * (hsz ** -0.5)
    kf = k.astype(jnp.float32)
    scores = jnp.einsum("btkgd,bskd->bkgts", qf, kf)
    qpos = jnp.arange(t) + q_offset
    kpos = jnp.arange(s)
    mask = jnp.ones((t, s), bool)
    if causal:
        mask &= kpos[None, :] <= qpos[:, None]
    # window may be a traced per-layer scalar (gemma3 local/global scan);
    # 0 means "no window"
    weff = jnp.where(jnp.asarray(window) > 0, jnp.asarray(window), t + s + 10)
    mask &= kpos[None, :] > qpos[:, None] - weff
    scores = jnp.where(mask, scores, NEG_INF)
    p = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bkgts,bskd->btkgd", p, v.astype(jnp.float32))
    return out.reshape(b, t, qh, hsz).astype(q.dtype)


# ------------------------------------------------------------- chunked
def chunked_attention(q, k, v, *, causal: bool = True, window: int = 0,
                      chunk_q: int = 512, q_offset: int | jax.Array = 0,
                      unroll: bool = False, seq_lens=None):
    """Memory-bounded attention: lax.scan over query chunks.

    Each chunk computes its full score row (the row fits: cq × S), so no
    online-softmax state is needed.  Used by train_step / prefill_step; the
    TPU hotspot equivalent is kernels/flash_prefill.  ``unroll`` emits the
    chunk loop inline — required by the dry-run because cost_analysis counts
    a while-loop body once, not x trip-count.  ``seq_lens`` ([B] int32,
    optional) masks kv positions ``>= seq_lens[b]`` per request — the ref
    side of flash_prefill's ragged continuous-batching contract.  For causal
    self-attention over right-padded prompts the extra mask only affects pad
    *query* rows (valid rows never see later positions), so passing it keeps
    the valid rows bit-identical.  ``q_offset`` may be a per-request ``[B]``
    vector (ragged chunk packing: every row attends at its own prefill
    progress) — masking then runs per row, bit-identical per row to the
    scalar-offset call.
    """
    b, t, qh, hsz = q.shape
    s, kh = k.shape[1], k.shape[2]
    g = qh // kh
    cq = min(chunk_q, t)
    t_pad = round_up(t, cq)
    if t_pad != t:
        q = jnp.pad(q, ((0, 0), (0, t_pad - t), (0, 0), (0, 0)))
    nchunk = t_pad // cq

    qc = q.reshape(b, nchunk, cq, kh, g, hsz).transpose(1, 0, 3, 4, 2, 5)
    kf = k.astype(jnp.float32)
    vf = v.astype(jnp.float32)
    kpos = jnp.arange(s)

    off = jnp.asarray(q_offset, jnp.int32)
    ragged_off = off.ndim == 1                            # [B] per-request

    def one_chunk(ci, qi):
        qf = qi.astype(jnp.float32) * (hsz ** -0.5)       # [B,Kh,G,cq,hsz]
        scores = jnp.einsum("bkgtd,bskd->bkgts", qf, kf)  # [B,Kh,G,cq,S]
        weff = jnp.where(jnp.asarray(window) > 0, jnp.asarray(window),
                         t + s + 10)
        if ragged_off:
            qpos = ci * cq + jnp.arange(cq)[None, :] + off[:, None]  # [B,cq]
            mask = jnp.ones((b, cq, s), bool)
            if causal:
                mask &= kpos[None, None, :] <= qpos[..., None]
            mask &= kpos[None, None, :] > qpos[..., None] - weff
        else:
            qpos = ci * cq + jnp.arange(cq) + off
            mask = jnp.ones((cq, s), bool)
            if causal:
                mask &= kpos[None, :] <= qpos[:, None]
            mask &= kpos[None, :] > qpos[:, None] - weff
        per_row = ragged_off or seq_lens is not None
        if seq_lens is not None:
            lens = jnp.broadcast_to(jnp.asarray(seq_lens, jnp.int32), (b,))
            if not ragged_off:
                mask = jnp.broadcast_to(mask[None], (b, cq, s))
            mask = mask & (kpos[None, None, :] < lens[:, None, None])
        if per_row:
            mask = mask[:, None, None]                    # [B,1,1,cq,S]
        scores = jnp.where(mask, scores, NEG_INF)
        p = jax.nn.softmax(scores, axis=-1)
        # fully-masked rows (seq_lens[b] == 0) produce uniform p over -inf
        # scores; zero them so dead rows emit zeros, matching the kernel
        if per_row:
            p = jnp.where(jnp.any(mask, axis=-1, keepdims=True), p, 0.0)
        return jnp.einsum("bkgts,bskd->bkgtd", p, vf).astype(q.dtype)

    _, outs = jax.lax.scan(
        lambda _, args: (None, one_chunk(*args)),
        None, (jnp.arange(nchunk), qc),
        unroll=nchunk if unroll else 1)                   # [n,B,Kh,G,cq,hsz]
    out = outs.transpose(1, 0, 4, 2, 3, 5).reshape(b, t_pad, qh, hsz)
    return out[:, :t]


def cross_attention(q, k, v, *, chunk_q: int = 512):
    """Non-causal encoder-decoder cross attention (whisper)."""
    return chunked_attention(q, k, v, causal=False, window=0, chunk_q=chunk_q)


# --------------------------------------------------- kernel-backed prefill
@functools.lru_cache(maxsize=None)
def _kernel_prefill_fn(causal: bool, interpret: bool, chunk_q: int,
                       unroll: bool, prune: bool, ragged: bool):
    """flash_prefill with a custom VJP whose backward re-runs the jnp
    reference (``chunked_attention``) — Pallas kernels define no transpose
    rule, so this is what lets the pallas backends run under
    ``value_and_grad`` (train_step).  Forward values come from the kernel;
    gradients are the oracle's (identical up to fp summation order, since
    the forwards agree to that order).  ``ragged`` statically selects the
    per-request ``seq_lens`` variant (continuous-batching prefill)."""

    @jax.custom_vjp
    def f(q, k, v, window, q_offset, seq_lens):
        from repro.kernels.flash_prefill.ops import flash_prefill
        return flash_prefill(q, k, v, causal=causal, window=window,
                             q_offset=q_offset,
                             seq_lens=seq_lens if ragged else None,
                             prune=prune, interpret=interpret)

    def fwd(q, k, v, window, q_offset, seq_lens):
        return (f(q, k, v, window, q_offset, seq_lens),
                (q, k, v, window, q_offset, seq_lens))

    def bwd(res, g):
        q, k, v, window, q_offset, seq_lens = res
        _, vjp = jax.vjp(
            lambda q, k, v: chunked_attention(
                q, k, v, causal=causal, window=window, chunk_q=chunk_q,
                q_offset=q_offset, unroll=unroll,
                seq_lens=seq_lens if ragged else None), q, k, v)
        dq, dk, dv = vjp(g)
        zero = lambda x: np.zeros(np.shape(x), jax.dtypes.float0)
        return dq, dk, dv, zero(window), zero(q_offset), zero(seq_lens)

    f.defvjp(fwd, bwd)
    return f


def prefill_attention(q, k, v, *, causal: bool = True, window=0,
                      q_offset: int | jax.Array = 0, chunk_q: int = 512,
                      unroll: bool = False, backend: str = "ref",
                      prune: bool = True, seq_lens=None, policy=None):
    """Full-sequence attention with kernel-backend selection.

    The prefill/train sibling of ``decode_attention``: ``backend`` routes the
    flash_prefill family through the registry lattice — ``"ref"`` is the
    memory-bounded ``chunked_attention`` scan, ``"pallas-interpret"`` /
    ``"pallas"`` the flash-prefill kernel (interpreted / compiled) with a
    ref-VJP backward so training works.  ``window`` and ``q_offset`` may be
    traced (per-layer windows under ``lax.scan``; ``q_offset`` is also how a
    chunked-prefill slice attends to its already-cached prefix — see
    docs/serving.md).  ``prune`` (kernel backends): skip causally/window-dead
    kv blocks instead of masking them (bit-exact; see docs/kernels.md "Block
    pruning").  ``seq_lens`` ([B] int32, optional) masks kv positions
    ``>= seq_lens[b]`` per request (ragged continuous-batching prefill),
    uniformly across backends.  ``policy`` (the mesh sharding policy of a
    GSPMD forward, models/transformer): the kernel runs on each device's
    batch x head shard through ``policy.per_shard``.

      q [B, T, Qh, hsz]; k, v [B, S, Kh, hsz] -> out [B, T, Qh, hsz].
    """
    if backend == "ref":
        return chunked_attention(q, k, v, causal=causal, window=window,
                                 chunk_q=chunk_q, q_offset=q_offset,
                                 unroll=unroll, seq_lens=seq_lens)
    from repro.kernels import registry
    registry.validate("flash_prefill", backend)
    ragged = seq_lens is not None
    fn = _kernel_prefill_fn(causal, registry.interpret_flag(backend),
                            chunk_q, unroll, prune, ragged)
    lens = (jnp.asarray(seq_lens, jnp.int32) if ragged
            else jnp.zeros((), jnp.int32))
    args = (q, k, v, jnp.asarray(window, jnp.int32),
            jnp.asarray(q_offset, jnp.int32), lens)
    if policy is None:
        return fn(*args)
    heads = ("dp", None, "tp", None)
    rows = [("dp",) if a.ndim else () for a in args[3:]]
    return policy.per_shard(fn, args, (heads, heads, heads, *rows), heads)


# ------------------------------------------------------------- decode
def decode_attention(q, k, v, total_len, *, window=0, backend: str = "ref",
                     kvp: int = 1, rr_block: int = 16, rank=0,
                     kscale=None, vscale=None, block_s: int = 512,
                     prune: bool = True, block_tables=None):
    """Single-shard decode-shape attention with backend selection.

    The unsharded sibling of core/helix.py's per-rank local attend —
    benchmarks and single-device decode use it directly.  ``backend`` picks
    the implementation: "ref" (pure-jnp oracle), "pallas-interpret" (the
    flash-decode kernel through the Pallas interpreter — runs anywhere), or
    "pallas" (compiled TPU kernel).  All are exact up to fp summation order.

      q [B, Qh, hsz]; k, v [B, Kh, S, hsz]; total_len scalar or [B] int32.

    ``block_tables`` ([B, max_pages] int32) switches to the shared-pool
    paged layout: k/v are pool planes ``[n_pool, Kh, page_s, hsz]`` and the
    kernel streams each request's pages through the table (the ref backend
    gathers them into the dense equivalent first) — bit-exact vs the fixed
    layout at ``block_s == page_s``.

    Returns (out [B, Qh, hsz], lse [B, Qh] f32).
    """
    from repro.kernels.flash_decode.ops import flash_decode
    from repro.kernels.flash_decode.ref import flash_decode_ref
    if backend == "ref":
        if block_tables is not None:
            from repro.core.kvcache import gather_pages
            k = gather_pages(k, block_tables)
            v = gather_pages(v, block_tables)
            if kscale is not None:
                kscale = gather_pages(kscale, block_tables)
                vscale = gather_pages(vscale, block_tables)
        return flash_decode_ref(q, k, v, total_len, rank, kvp=kvp,
                                rr_block=rr_block, window=window,
                                kscale=kscale, vscale=vscale)
    return flash_decode(q, k, v, total_len, rank, kvp=kvp, rr_block=rr_block,
                        window=window, block_s=block_s,
                        kscale=kscale, vscale=vscale, prune=prune,
                        block_tables=block_tables,
                        interpret=backend != "pallas")
