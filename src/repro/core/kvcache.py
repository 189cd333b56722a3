"""Distributed decode state: round-robin KV caches (§2.3), SSM states,
whisper cross-attention KV — plus their PartitionSpecs and dry-run stand-ins.
"""
from __future__ import annotations

from typing import Any

import numpy as np
import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.configs.base import ArchConfig
from repro.core.sharding import HelixConfig
from repro.utils import round_up


def cache_capacity(cfg_seq_len: int, kvp: int, rr_block: int) -> int:
    """Smallest valid cache capacity >= seq_len (multiple of kvp*rr)."""
    return round_up(cfg_seq_len, kvp * rr_block)


# ----------------------------------------------------------- paged layout
#
# Shared-pool paged KV cache (serving/pool.py, docs/serving.md): instead of
# one fixed [B, Kh, S_cap, hsz] row per slot, K/V live in pool planes
#
#     [L, n_blocks, Kh, block_s, hsz]        (+ [L, n_blocks, Kh, block_s]
#                                             f32 scale planes for kv8)
#
# where one *page* holds ``block_s`` consecutive global positions
# [i*block_s, (i+1)*block_s) of whichever request owns it, and a per-request
# ``[B, max_pages]`` block table maps logical page i -> physical pool plane.
# Under KVP sharding the page's block_s axis splits over the kvp axes —
# rank r holds rows [r*ps_loc, (r+1)*ps_loc) with ps_loc = block_s/kvp,
# which are exactly its round-robin local slots [i*ps_loc, (i+1)*ps_loc)
# (requires ps_loc % rr_block == 0, i.e. block_s a multiple of
# kvp*rr_block).  The paged pool is therefore a page-granularity
# *permutation* of the fixed layout: with identity tables the two layouts
# are reshapes of each other, which is what makes paged-vs-fixed bit-exact
# parity provable (tests/kernels/test_flash_decode_paged.py).
# Page 0 is the reserved sink for idle-row appends (serving/pool.py).


def page_positions(kvp: int, rr_block: int) -> int:
    """Global positions per pool page: the smallest legal page (one
    round-robin cycle, ``kvp * rr_block``) — each KVP rank then holds
    ``rr_block`` rows of every page.  The paged decode kernel's S-block is
    the whole pages that fit ``HelixConfig.attn_block_s``; at a block size
    that is a multiple of ``rr_block`` the paged and fixed online-softmax
    block partitions align, making the two layouts bit-identical end to
    end."""
    return kvp * rr_block


def cache_to_pages(row, kvp: int, block_s: int):
    """One request's fixed-layout cache -> its page stack.

    ``row`` is ``[L, Kh, S_cap, ...]`` in the *global* rank-major
    round-robin layout (slot ``r*S_loc + j``); returns
    ``[L, P, Kh, block_s, ...]`` with ``P = S_cap_padded / block_s`` pages
    whose in-page row ``r*ps_loc + jj`` holds rank ``r``'s local slot
    ``i*ps_loc + jj`` — the paged pool layout documented above.  Works for
    K/V payloads (trailing hsz) and scale planes (no trailing axis)."""
    l, kh, s_cap = row.shape[:3]
    trail = row.shape[3:]
    ps_loc = block_s // kvp
    s_pad = round_up(s_cap, block_s)
    if s_pad != s_cap:
        pad = [(0, 0)] * row.ndim
        pad[2] = (0, s_pad - s_cap)
        row = jnp.pad(row, pad)
    p = s_pad // block_s
    r = row.reshape(l, kh, kvp, p, ps_loc, *trail)
    r = jnp.moveaxis(r, 3, 1)                       # [L, P, Kh, kvp, ps, ...]
    return r.reshape(l, p, kh, block_s, *trail)


def pages_to_cache(pages, kvp: int):
    """Inverse of ``cache_to_pages``: ``[L, P, Kh, block_s, ...]`` page
    stack -> ``[L, Kh, P*block_s, ...]`` fixed rank-major round-robin
    cache."""
    l, p, kh, block_s = pages.shape[:4]
    trail = pages.shape[4:]
    ps_loc = block_s // kvp
    r = pages.reshape(l, p, kh, kvp, ps_loc, *trail)
    r = jnp.moveaxis(r, 1, 3)                       # [L, Kh, kvp, P, ps, ...]
    return r.reshape(l, kh, p * block_s, *trail)


def gather_pages(pool, tables):
    """Dense per-request view of a pool plane (the ref-backend oracle path).

    ``pool`` ``[n_blocks, Kh, block_s, ...]`` + ``tables`` ``[B, max_pages]``
    -> ``[B, Kh, max_pages*block_s, ...]`` fixed-layout local caches (one
    gather; the Pallas kernels do this lazily through their index_maps
    instead)."""
    b, mp = tables.shape
    g = pool[tables]                                # [B, MP, Kh, bs, ...]
    g = jnp.moveaxis(g, 2, 1)                       # [B, Kh, MP, bs, ...]
    return g.reshape(b, pool.shape[1], mp * pool.shape[2], *pool.shape[3:])


def gather_pool_pages(state: dict[str, Any], phys) -> dict[str, jax.Array]:
    """Device-side page-stack gather for the host spill path.

    Selects the physical pages ``phys`` (logical-page order) out of every
    pool plane present in ``state`` — K/V payloads and, on kv8 engines,
    the f32 scale planes — as ``[L, P, ...]`` stacks.  One gather per
    plane; the caller performs the single batched device->host transfer
    (serving/engine.py's sanctioned spill site), so the exact pool bytes
    (int8 payloads + scales included) round-trip through the host tier."""
    idx = jnp.asarray(phys, jnp.int32)
    return {key: state[key][:, idx]
            for key in ("kcache", "vcache", "kscale", "vscale")
            if key in state}


def scatter_pool_pages(state: dict[str, Any], phys,
                       planes: dict[str, Any]) -> dict[str, Any]:
    """Inverse of ``gather_pool_pages``: H2D restore of spilled pages.

    Writes each plane's ``[L, P, ...]`` page stack back into the pool at
    the physical pages ``phys`` (freshly granted at re-admission — the
    original tenancy is gone).  Returns a copy of ``state`` with the pool
    planes updated; bytes land exactly as spilled, which is what makes a
    spill/restore resume bit-exact with never having been preempted."""
    out = dict(state)
    idx = jnp.asarray(phys, jnp.int32)
    for key, stack in planes.items():
        out[key] = state[key].at[:, idx].set(
            jnp.asarray(stack, state[key].dtype))
    return out


def state_to_paged(state: dict[str, Any], tables, n_blocks: int, kvp: int,
                   block_s: int) -> dict[str, Any]:
    """Fixed-cap decode state -> the equivalent paged state (test helper).

    Scatters every slot's cache rows into the pool planes at the physical
    pages named by ``tables`` ([B, max_pages] int32; entry 0 = sink) and
    adds ``block_tables`` to the state.  Pages beyond a row's table extent
    must be 0 in ``tables``; slot data beyond the table extent is dropped
    (it must be dead).  Non-attention leaves pass through."""
    out = dict(state)
    out["block_tables"] = jnp.asarray(tables, jnp.int32)
    for key in ("kcache", "vcache", "kscale", "vscale"):
        if key not in state:
            continue
        plane = state[key]                          # [L, B, Kh, S_cap, ...]
        l, b = plane.shape[:2]
        trail = plane.shape[4:] if plane.ndim > 4 else ()
        pool = jnp.zeros((l, n_blocks, plane.shape[2], block_s) + trail,
                         plane.dtype)
        for i in range(b):
            pages = cache_to_pages(plane[:, i], kvp, block_s)
            phys = np.asarray(tables[i])
            live = phys > 0
            idx = np.nonzero(live)[0]
            idx = idx[idx < pages.shape[1]]
            if idx.size:
                pool = pool.at[:, phys[idx]].set(pages[:, idx])
        out[key] = pool
    return out


def sampling_leaf_shapes(batch: int) -> dict[str, Any]:
    """ShapeDtypeStructs for the on-device sampling leaves (one value per
    batch row, carried in the decode state so the sampler epilogue stays a
    pure function of ``(params, state, tokens)``): ``sample_temp``/
    ``sample_topp`` f32, ``sample_topk`` i32, ``sample_seed`` u32 (the
    per-request PRNG seed) and ``sample_idx`` i32 (tokens sampled so far —
    the ``fold_in`` counter; see serving/sampling.py).  Presence of
    ``sample_seed`` in a state is what switches ``serve_step`` from the
    argmax epilogue to the sampler."""
    b = (batch,)
    return {"sample_temp": jax.ShapeDtypeStruct(b, jnp.float32),
            "sample_topk": jax.ShapeDtypeStruct(b, jnp.int32),
            "sample_topp": jax.ShapeDtypeStruct(b, jnp.float32),
            "sample_seed": jax.ShapeDtypeStruct(b, jnp.uint32),
            "sample_idx": jax.ShapeDtypeStruct(b, jnp.int32)}


def decode_state_shapes(cfg: ArchConfig, batch: int, seq_len: int,
                        kvp: int, rr_block: int = 16,
                        dtype=jnp.bfloat16, kv_bits: int = 16,
                        pool_blocks: int = 0,
                        max_pages: int = 0,
                        grouped: bool = False,
                        sampling: bool = False) -> dict[str, Any]:
    """ShapeDtypeStructs for every decode-state leaf (dry-run input_specs).

    ``pool_blocks > 0`` switches the attention K/V leaves to the shared-pool
    *paged* layout (see the paged-layout block above): pool planes
    ``[L, pool_blocks, Kh, block_s, hsz]`` with ``block_s =
    page_positions(kvp, rr_block)``, plus a ``block_tables``
    ``[batch, max_pages]`` int32 leaf (``max_pages`` defaults to
    ``pool_blocks`` — any request may take the whole pool).  ``grouped``
    (paged only) adds the grouped shared-prefix decode's ``group_id``/
    ``group_np`` ``[batch]`` int32 leaves (``HelixConfig.grouped_decode``;
    the serving engine recomputes them each step).  ``sampling`` adds the
    per-row on-device sampling leaves (``sampling_leaf_shapes``)."""
    s: dict[str, Any] = {"total_len": jax.ShapeDtypeStruct((), jnp.int32)}
    if sampling:
        s.update(sampling_leaf_shapes(batch))
    L = cfg.n_layers
    if cfg.has_attention:
        kv_dtype = jnp.int8 if kv_bits == 8 else dtype
        if pool_blocks > 0:
            bs = page_positions(kvp, rr_block)
            mp = max_pages or pool_blocks
            kv = jax.ShapeDtypeStruct(
                (L, pool_blocks, cfg.n_kv_heads, bs, cfg.hsz), kv_dtype)
            s["kcache"], s["vcache"] = kv, kv
            s["block_tables"] = jax.ShapeDtypeStruct((batch, mp), jnp.int32)
            if grouped:
                gi = jax.ShapeDtypeStruct((batch,), jnp.int32)
                s["group_id"], s["group_np"] = gi, gi
            if kv_bits == 8:
                sc = jax.ShapeDtypeStruct(
                    (L, pool_blocks, cfg.n_kv_heads, bs), jnp.float32)
                s["kscale"], s["vscale"] = sc, sc
        else:
            cap = cache_capacity(seq_len, kvp, rr_block)
            kv = jax.ShapeDtypeStruct(
                (L, batch, cfg.n_kv_heads, cap, cfg.hsz), kv_dtype)
            s["kcache"], s["vcache"] = kv, kv
            if kv_bits == 8:
                sc = jax.ShapeDtypeStruct((L, batch, cfg.n_kv_heads, cap),
                                          jnp.float32)
                s["kscale"], s["vscale"] = sc, sc
    if cfg.has_ssm:
        s["ssm_conv"] = jax.ShapeDtypeStruct(
            (L, batch, cfg.conv_dim, cfg.ssm_conv - 1), jnp.float32)
        s["ssm_state"] = jax.ShapeDtypeStruct(
            (L, batch, cfg.ssm_heads, cfg.ssm_headdim, cfg.ssm_state),
            jnp.float32)
    if cfg.is_encdec:
        s_enc = round_up(seq_len, kvp)
        xkv = jax.ShapeDtypeStruct(
            (L, batch, cfg.n_kv_heads, s_enc, cfg.hsz), dtype)
        s["xk"], s["xv"] = xkv, xkv
        s["enc_len"] = jax.ShapeDtypeStruct((), jnp.int32)
    return s


def decode_state_specs(cfg: ArchConfig, hx: HelixConfig,
                       batch: int | None = None,
                       mesh=None, sampling: bool = False) -> dict[str, Any]:
    """PartitionSpecs matching decode_state_shapes.

    The paged pool planes ``[L, n_blocks, Kh, block_s, hsz]`` reuse the
    fixed layout's spec: the sequence-ish axis (dim 3 — ``block_s`` for
    paged, ``S_cap`` for fixed) shards over the kvp axes, heads over tpa.
    ``block_tables`` is replicated (tiny int32), as are the ``sampling``
    leaves (per-row scalars)."""
    tpa, kvp = hx.tpa_axis, hx.kvp_axes
    s: dict[str, Any] = {"total_len": P()}
    if sampling:
        for key in sampling_leaf_shapes(1):
            s[key] = P(None)
    if cfg.has_attention:
        s["kcache"] = s["vcache"] = P(None, None, tpa, kvp, None)
        if hx.paged_kv:
            s["block_tables"] = P(None, None)
            if hx.grouped_decode:
                s["group_id"] = s["group_np"] = P(None)
        if hx.kv_cache_bits == 8:
            s["kscale"] = s["vscale"] = P(None, None, tpa, kvp)
    if cfg.has_ssm:
        # batch over 'data' (when divisible), ssm heads/channels over 'model'
        dsz = mesh.shape["data"] if mesh else 1
        msz = mesh.shape["model"] if mesh else 1
        bax = "data" if (batch is None or batch % dsz == 0) else None
        hax = "model" if cfg.ssm_heads % msz == 0 else None
        cax = "model" if cfg.conv_dim % msz == 0 else None
        s["ssm_conv"] = P(None, bax, cax, None)
        s["ssm_state"] = P(None, bax, hax, None, None)
    if cfg.is_encdec:
        s["xk"] = s["xv"] = P(None, None, tpa, kvp, None)
        s["enc_len"] = P()
    return s


def init_decode_state(cfg: ArchConfig, batch: int, seq_len: int, kvp: int,
                      rr_block: int = 16, dtype=jnp.bfloat16,
                      total_len: int | jax.Array = 0,
                      kv_bits: int = 16, pool_blocks: int = 0,
                      max_pages: int = 0,
                      grouped: bool = False,
                      sampling: bool = False) -> dict[str, Any]:
    """Zero-initialised decode state (concrete arrays, small/test use).

    ``kv_bits=8`` allocates int8 K/V payloads plus per-slot f32 scale
    planes (``kscale``/``vscale``).  ``pool_blocks > 0`` allocates the
    shared-pool *paged* layout instead (pool planes + zeroed
    ``block_tables`` — every row starts parked on the sink page 0).
    ``grouped`` adds zeroed ``group_id``/``group_np`` leaves (all rows
    singleton groups under group 0 with no shared prefix, which decodes
    identically to ungrouped).  ``sampling`` adds zeroed per-row sampling
    leaves (all rows greedy — temp 0 — until the engine installs a
    request's policy at commit/restore time)."""
    shapes = decode_state_shapes(cfg, batch, seq_len, kvp, rr_block, dtype,
                                 kv_bits=kv_bits, pool_blocks=pool_blocks,
                                 max_pages=max_pages, grouped=grouped,
                                 sampling=sampling)
    state = {k: jnp.zeros(v.shape, v.dtype) for k, v in shapes.items()}
    tl = jnp.asarray(total_len, jnp.int32)
    state["total_len"] = tl
    return state


def quantize_decode_state(state: dict[str, Any]) -> dict[str, Any]:
    """fp round-robin K/V caches -> int8 payloads + per-slot f32 scales.

    Per-(…, slot) symmetric quantization over the ``hsz`` axis with the
    same formula as ``core/helix.quantize_kv_token`` (the decode-step
    append), so a prefilled-then-quantized cache and a cache grown token by
    token agree on shared slots.  Layout-agnostic: the reduction runs over
    the trailing ``hsz`` axis, so fixed-cap ``[L, B, Kh, S, hsz]`` caches
    and paged pool planes ``[L, n_blocks, Kh, block_s, hsz]`` both work
    (scale planes come back one axis shorter).  Zero (unfilled) slots
    quantize to zero payloads with the epsilon scale.  Returns a copy of
    ``state`` with ``kcache``/``vcache`` replaced and ``kscale``/``vscale``
    added; other leaves pass through."""
    out = dict(state)
    for key, skey in (("kcache", "kscale"), ("vcache", "vscale")):
        c = state[key].astype(jnp.float32)
        scale = jnp.maximum(jnp.max(jnp.abs(c), axis=-1) / 127.0, 1e-30)
        out[key] = jnp.clip(jnp.round(c / scale[..., None]),
                            -127, 127).astype(jnp.int8)
        out[skey] = scale
    return out
