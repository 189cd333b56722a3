"""Public model API: step-function builders + dry-run input specs.

  make_train_step(cfg, mesh, optcfg)   -> train_step(params, opt, batch)
  make_prefill_step(cfg, mesh, hx)     -> prefill(params, batch) -> (logits,
                                          decode-state in round-robin layout)
  build_serve_step (re-export)         -> decode (models/decode_model.py)
  data_specs(cfg, shape)               -> ShapeDtypeStructs for batch inputs
"""
from __future__ import annotations

import math
from typing import Any

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from repro.configs.base import ArchConfig, ShapeCell
from repro.core.helix import prefill_to_rr_layout
from repro.core.kvcache import cache_capacity
from repro.core.sharding import HelixConfig, MeshPolicy, train_roles
from repro.models.decode_model import (  # noqa: F401 re-export
    build_serve_multistep, build_serve_step)
from repro.models.layers import full_precision
from repro.models.transformer import (NO_POLICY, chunked_prefill_supported,
                                      forward, init_params, lm_loss)
from repro.optim import AdamWConfig, adamw_init, adamw_update
from repro.utils import round_up

__all__ = ["make_train_step", "make_prefill_step", "build_serve_step",
           "build_serve_multistep",
           "make_chunk_prefill_step", "init_prefill_buffers",
           "finalize_chunked_prefill", "chunked_prefill_supported",
           "data_specs", "data_partition_specs", "init_params", "adamw_init"]


def _dp_size(mesh: Mesh | None) -> int:
    if mesh is None:
        return 1
    return math.prod(mesh.shape[a] for a in mesh.axis_names if a != "model")


def _forward_kwargs(cfg: ArchConfig, batch: dict[str, Any], mesh, policy,
                    moe_groups: int):
    kw: dict[str, Any] = dict(policy=policy, moe_groups=moe_groups,
                              tp_width=mesh.shape["model"] if mesh else 1)
    if cfg.vision_patches:
        kw["patch_embeds"] = batch["patch_embeds"]
    if cfg.is_encdec:
        kw["enc_frames"] = batch["enc_frames"]
    return kw


# ------------------------------------------------------------------ train
def make_train_step(cfg: ArchConfig, mesh: Mesh | None = None,
                    optcfg: AdamWConfig = AdamWConfig(), chunk_q: int = 512,
                    unroll: bool = False, prefill_backend: str = "ref",
                    ssd_backend: str = "ref", prune_blocks: bool = True):
    """Build ``train_step(params, opt_state, batch)`` for one architecture.

    ``prefill_backend`` / ``ssd_backend`` route the full-sequence attention
    and SSD-scan hotspots through the kernel registry (kernels/registry.py);
    the pallas backends carry a ref-VJP backward, so the same knob works
    under ``value_and_grad``.  ``prune_blocks`` is flash_prefill's
    causal/window block skip (kernel backends; bit-exact on/off).
    """
    policy = MeshPolicy(mesh, train_roles(mesh)) if mesh else NO_POLICY
    moe_groups = _dp_size(mesh) if cfg.moe else 1

    def loss_fn(params, batch):
        logits, extras = forward(
            cfg, params, batch["tokens"], chunk_q=chunk_q, unroll=unroll,
            prefill_backend=prefill_backend, ssd_backend=ssd_backend,
            prune_blocks=prune_blocks,
            **_forward_kwargs(cfg, batch, mesh, policy, moe_groups))
        loss = lm_loss(cfg, logits, batch["labels"])
        return loss + extras["aux_loss"], loss

    def train_step(params, opt_state, batch):
        (_, loss), grads = jax.value_and_grad(
            loss_fn, has_aux=True)(params, batch)
        params, opt_state, metrics = adamw_update(params, grads, opt_state,
                                                  optcfg)
        metrics["loss"] = loss
        return params, opt_state, metrics

    return train_step


# ---------------------------------------------------------------- prefill
def prefill_cache_to_rr(cfg: ArchConfig, hx: HelixConfig, kc_raw, vc_raw,
                        t: int, cap: int, kvp: int):
    """Prefill-layout K/V caches -> round-robin decode layout.

    ``kc_raw``/``vc_raw`` are ``[L, B, T', Kp, hsz]`` (``forward``'s
    ``return_cache`` extras — possibly padded query rows / padded GQA heads;
    only the first ``t`` rows and ``cfg.n_kv_heads`` heads are live).
    Returns ``(kcache, vcache)`` as ``[L, B, Kh, cap, hsz]`` in the
    round-robin slot layout (core/helix.prefill_to_rr_layout).  Shared by
    the one-shot ``make_prefill_step`` handoff and the chunked-prefill
    finalize so the two paths cannot drift."""
    kc = kc_raw[:, :, :t, :cfg.n_kv_heads].transpose(0, 1, 3, 2, 4)
    vc = vc_raw[:, :, :t, :cfg.n_kv_heads].transpose(0, 1, 3, 2, 4)
    pad = [(0, 0)] * 5
    pad[3] = (0, cap - t)
    kc, vc = jnp.pad(kc, pad), jnp.pad(vc, pad)
    kcache = jax.vmap(lambda c: prefill_to_rr_layout(c, kvp, hx.rr_block))(kc)
    vcache = jax.vmap(lambda c: prefill_to_rr_layout(c, kvp, hx.rr_block))(vc)
    return kcache, vcache


def make_prefill_step(cfg: ArchConfig, mesh: Mesh | None, hx: HelixConfig,
                      s_cap: int | None = None, chunk_q: int = 512,
                      unroll: bool = False):
    """Prefill + handoff: contiguous caches -> round-robin decode layout.

    Kernel backends come from ``hx``: ``hx.prefill_backend`` routes the
    full-sequence attention (flash_prefill family), ``hx.ssd_backend``
    the Mamba2 SSD scan core (ssd_prefill family) and ``hx.prune_blocks``
    flash_prefill's causal/window block skip.
    """
    policy = MeshPolicy(mesh, train_roles(mesh)) if mesh else NO_POLICY
    kvp = hx.kvp(mesh) if mesh else 1
    moe_groups = _dp_size(mesh) if cfg.moe else 1

    @full_precision
    def prefill_step(params, batch):
        tokens = batch["tokens"]
        b, t = tokens.shape
        cap = s_cap or cache_capacity(t, kvp, hx.rr_block)
        logits, extras = forward(
            cfg, params, tokens, return_cache=True, chunk_q=chunk_q,
            unroll=unroll, prefill_backend=hx.prefill_backend,
            ssd_backend=hx.ssd_backend, prune_blocks=hx.prune_blocks,
            **_forward_kwargs(cfg, batch, mesh, policy, moe_groups))
        state: dict[str, Any] = {"total_len": jnp.asarray(t, jnp.int32)}
        if cfg.has_attention:
            state["kcache"], state["vcache"] = prefill_cache_to_rr(
                cfg, hx, extras["kcache"], extras["vcache"], t, cap, kvp)
        if cfg.has_ssm:
            state["ssm_conv"] = extras["ssm_conv"]
            state["ssm_state"] = extras["ssm_state"]
        if cfg.is_encdec:
            from repro.models.encdec import cross_kv
            kx, vx = cross_kv(cfg, params["layers"], extras["enc_out"])
            s_enc = kx.shape[2]
            s_enc_pad = round_up(s_enc, kvp)
            padx = [(0, 0)] * 5
            padx[3] = (0, s_enc_pad - s_enc)
            state["xk"] = jnp.pad(kx.transpose(0, 1, 3, 2, 4), padx)
            state["xv"] = jnp.pad(vx.transpose(0, 1, 3, 2, 4), padx)
            state["enc_len"] = jnp.asarray(s_enc, jnp.int32)
        return logits[:, -1], state

    return prefill_step


# ------------------------------------------------------- chunked prefill
def init_prefill_buffers(cfg: ArchConfig, batch: int, t: int, *,
                         tp_width: int = 1,
                         dtype=jnp.float32) -> dict[str, Any]:
    """Zero K/V carry buffers for a chunked prefill of length ``t``.

    Returns {"kcache"/"vcache": [L, batch, t, Kp, hsz]} in ``forward``'s
    prefill cache layout (Kp = the GQA head layout's padded kv head count
    for ``tp_width``, the mesh's 'model' axis size).  ``t`` must equal the
    one-shot prefill length for the chunked run to be bit-exact
    (docs/serving.md)."""
    from repro.models.attention import head_layout
    kp = head_layout(cfg.n_heads, cfg.n_kv_heads, tp_width).kv_pad
    shape = (cfg.n_layers, batch, t, kp, cfg.hsz)
    return {"kcache": jnp.zeros(shape, dtype), "vcache": jnp.zeros(shape, dtype)}


def make_chunk_prefill_step(cfg: ArchConfig, mesh: Mesh | None,
                            hx: HelixConfig, chunk_q: int = 512,
                            unroll: bool = False,
                            return_last_logits: bool = False):
    """Build the prefix-aware chunked-prefill step (docs/serving.md).

    Returns ``chunk_step(params, tokens, buffers, q_offset) ->
    (next_tokens, new_buffers)``: ``tokens`` is the ``[B, C]`` chunk at
    global positions ``[q_offset, q_offset + C)``, ``buffers`` the carry
    dict from ``init_prefill_buffers`` with ``[0, q_offset)`` already
    filled, and ``next_tokens`` the ``[B, C]`` greedy next token after each
    chunk position (row ``t - 1 - q_offset`` of the final chunk is the
    request's first generated token, bit-identical to the one-shot
    ``prefill_step`` argmax).  ``q_offset`` may be a scalar or a *per-row*
    ``[B]`` vector — ragged chunk packing: each request's chunk lands at
    its own prefill progress (per-row rope positions, buffer writes and
    flash_prefill masking), so requests at different (offset, length) pack
    into one call bit-exactly.  Jit-able; ``q_offset`` may be traced so
    every chunk of a prefill shares one trace.  Only
    ``chunked_prefill_supported`` archs are accepted.

    ``return_last_logits`` makes the step return a 3-tuple
    ``(next_tokens, last_logits, new_buffers)`` where ``last_logits`` is
    the full ``[B, padded_vocab]`` logits row of each request's final chunk
    position (already softcapped + vocab-masked by ``forward``) — the
    serving engine's on-device first-token sampler consumes these instead
    of the greedy ``next_tokens``."""
    assert chunked_prefill_supported(cfg), \
        f"chunked prefill unsupported for {cfg.name} ({cfg.family})"
    policy = MeshPolicy(mesh, train_roles(mesh)) if mesh else NO_POLICY

    @full_precision
    def chunk_step(params, tokens, buffers, q_offset):
        logits, extras = forward(
            cfg, params, tokens, return_cache=True, chunk_q=chunk_q,
            unroll=unroll, prefill_backend=hx.prefill_backend,
            ssd_backend=hx.ssd_backend, prune_blocks=hx.prune_blocks,
            prefix_state=buffers, q_offset=q_offset, policy=policy,
            tp_width=mesh.shape["model"] if mesh else 1)
        next_tokens = jnp.argmax(logits[:, :, :cfg.vocab],
                                 axis=-1).astype(jnp.int32)
        new_buffers = {"kcache": extras["kcache"],
                       "vcache": extras["vcache"]}
        if return_last_logits:
            return next_tokens, logits[:, -1], new_buffers
        return next_tokens, new_buffers

    return chunk_step


def finalize_chunked_prefill(cfg: ArchConfig, hx: HelixConfig, buffers,
                             t: int, s_cap: int | None = None,
                             kvp: int = 1) -> dict[str, Any]:
    """Fully-filled chunked-prefill buffers -> round-robin decode state.

    The exact handoff ``make_prefill_step`` performs (shared
    ``prefill_cache_to_rr``), so a chunked prefill's final decode state is
    bit-identical to the one-shot path's."""
    cap = s_cap or cache_capacity(t, kvp, hx.rr_block)
    kcache, vcache = prefill_cache_to_rr(
        cfg, hx, buffers["kcache"], buffers["vcache"], t, cap, kvp)
    return {"total_len": jnp.asarray(t, jnp.int32),
            "kcache": kcache, "vcache": vcache}


# ------------------------------------------------------------- input data
def data_specs(cfg: ArchConfig, cell: ShapeCell) -> dict[str, Any]:
    """ShapeDtypeStructs for the *data* inputs of one (arch x shape) cell."""
    b, t = cell.global_batch, cell.seq_len
    if cell.kind == "decode":
        d: dict[str, Any] = {"tokens": jax.ShapeDtypeStruct((b,), jnp.int32)}
        return d
    d = {"tokens": jax.ShapeDtypeStruct((b, t), jnp.int32)}
    if cell.kind == "train":
        d["labels"] = jax.ShapeDtypeStruct((b, t), jnp.int32)
    if cfg.vision_patches:
        d["patch_embeds"] = jax.ShapeDtypeStruct(
            (b, cfg.vision_patches, cfg.d_model), jnp.bfloat16)
    if cfg.is_encdec:
        d["enc_frames"] = jax.ShapeDtypeStruct(
            (b, t * cfg.enc_seq_ratio, cfg.d_model), jnp.bfloat16)
    return d


def data_partition_specs(cfg: ArchConfig, cell: ShapeCell,
                         mesh: Mesh) -> dict[str, Any]:
    dp = tuple(n for n in mesh.axis_names if n != "model")
    if cell.kind == "decode":
        return {"tokens": P(None)}
    d = {"tokens": P(dp, None)}
    if cell.kind == "train":
        d["labels"] = P(dp, None)
    if cfg.vision_patches:
        d["patch_embeds"] = P(dp, None, None)
    if cfg.is_encdec:
        d["enc_frames"] = P(dp, None, None)
    return d
