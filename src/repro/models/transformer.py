"""Model zoo core: param init + full-sequence forward for every family.

One parameter tree / one forward covers: dense GQA (granite, starcoder2,
gemma3 local:global, phi-3-vision), pure SSM (mamba2), hybrid (hymba),
MoE (granite-moe, arctic incl. dense residual), and enc-dec (whisper,
via encdec.py driving the same decoder stack).

The forward here is the *reference / GSPMD* path used by train_step and
prefill_step (sharding injected through a ShardingPolicy); the explicit-SPMD
Helix decode path (core/helix.py + models/decode_model.py) consumes the same
parameter tree.

Simplifications vs. upstream checkpoints (documented in DESIGN.md §6): all
norms are RMSNorm, single RoPE theta per model, sinusoidal positions for
whisper.  These do not affect the paper's contribution (sharding strategy).
"""
from __future__ import annotations

import functools
from typing import Any, Callable

import numpy as np
import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig
from repro.models import ssm as ssm_lib
from repro.models.attention import (HeadLayout, apply_kv_layout, apply_o_layout,
                                    apply_q_layout, chunked_attention,
                                    head_layout, prefill_attention)
from repro.models.layers import (activation, apply_rope, dense_init, embed_init,
                                 rms_norm, sinusoidal_positions, softcap)
from repro.models.moe import MoEParams, init_moe, moe_ffn


class NoPolicy:
    """Sharding policy stub: identity constraints (single-device paths)."""

    def __call__(self, x, *axes):
        return x

    def per_shard(self, fn, args, axes, out_axes):
        return fn(*args)


NO_POLICY = NoPolicy()


# ===================================================================== init
def _init_attn(cfg: ArchConfig, key, dtype):
    ks = jax.random.split(key, 4)
    h = cfg.d_model
    return {
        "wq": dense_init(ks[0], (h, cfg.q_dim), dtype),
        "wk": dense_init(ks[1], (h, cfg.kv_dim), dtype),
        "wv": dense_init(ks[2], (h, cfg.kv_dim), dtype),
        "wo": dense_init(ks[3], (cfg.q_dim, h), dtype,
                         scale=(cfg.q_dim ** -0.5) / np.sqrt(2 * cfg.n_layers)),
    }


def _init_ffn(cfg: ArchConfig, key, dtype):
    ks = jax.random.split(key, 3)
    h, f = cfg.d_model, cfg.d_ff
    p = {"w1": dense_init(ks[0], (h, f), dtype),
         "w2": dense_init(ks[1], (f, h), dtype,
                          scale=(f ** -0.5) / np.sqrt(2 * cfg.n_layers))}
    if cfg.act != "gelu":  # gated variants carry w3
        p["w3"] = dense_init(ks[2], (h, f), dtype)
    return p


def _init_layer(cfg: ArchConfig, key, dtype, with_cross: bool):
    ks = jax.random.split(key, 8)
    p: dict[str, Any] = {"ln1": jnp.zeros((cfg.d_model,), dtype)}
    if cfg.has_attention:
        p["attn"] = _init_attn(cfg, ks[0], dtype)
    if cfg.has_ssm:
        p["ssm"] = ssm_lib.init_ssm(cfg, ks[1], dtype)._asdict()
    if with_cross:
        p["lnx"] = jnp.zeros((cfg.d_model,), dtype)
        p["xattn"] = _init_attn(cfg, ks[2], dtype)
    if cfg.d_ff or cfg.moe:
        p["ln2"] = jnp.zeros((cfg.d_model,), dtype)
    if cfg.d_ff:
        p["ffn"] = _init_ffn(cfg, ks[3], dtype)
    if cfg.moe:
        p["moe"] = init_moe(cfg.moe, cfg.d_model, ks[4], dtype)._asdict()
    return p


def init_params(cfg: ArchConfig, key, dtype=jnp.float32):
    """Full parameter tree; per-layer leaves stacked on axis 0 (scan-ready)."""
    ks = jax.random.split(key, 8)
    layer_keys = jax.random.split(ks[0], cfg.n_layers)
    layers = jax.vmap(
        lambda k: _init_layer(cfg, k, dtype, with_cross=cfg.is_encdec)
    )(layer_keys)
    params: dict[str, Any] = {
        "embed": embed_init(ks[1], (cfg.padded_vocab, cfg.d_model), dtype),
        "ln_f": jnp.zeros((cfg.d_model,), dtype),
        "layers": layers,
    }
    if not cfg.tie_embeddings:
        params["lm_head"] = dense_init(ks[2], (cfg.d_model, cfg.padded_vocab),
                                       dtype)
    if cfg.is_encdec:
        enc_keys = jax.random.split(ks[3], cfg.enc_layers)
        params["enc"] = {
            "layers": jax.vmap(
                lambda k: _init_layer(cfg, k, dtype, with_cross=False)
            )(enc_keys),
            "ln_f": jnp.zeros((cfg.d_model,), dtype),
        }
    return params


# =============================================================== layer fwd
def _attn_block(cfg: ArchConfig, ap, h, *, layout: HeadLayout, window,
                policy, causal=True, kv_override=None, q_offset=0,
                chunk_q=512, unroll=False, attn_backend="ref", prune=True,
                kv_buffer=None, seq_lens=None):
    """Projection + (optionally cross-) attention + out-proj.  h [B,T,H].

    ``attn_backend`` routes the attention core through the flash_prefill
    kernel family (models/attention.prefill_attention); ``prune`` is its
    causal/window block-skipping knob (kernel backends, bit-exact).

    ``kv_buffer`` (chunked prefill, docs/serving.md): a pair of
    ``[B, S_buf, Kp, hsz]`` carry buffers holding the K/V of the already-
    prefilled prefix ``[0, q_offset)``.  The chunk's freshly projected K/V
    rows are written at ``[q_offset, q_offset + T)`` and attention runs over
    the *whole* buffer — causal masking hides the yet-unfilled tail, so with
    ``S_buf`` equal to the one-shot sequence length the chunk is bit-exact
    with the one-shot prefill.  The updated buffers are returned as the
    cache pair.  ``seq_lens`` masks kv positions per request (ragged
    packing)."""
    b, t, _ = h.shape
    hsz = cfg.hsz
    wq = apply_q_layout(ap["wq"], layout, hsz)
    wo = apply_o_layout(ap["wo"], layout, hsz)
    q = policy(h @ wq, "dp", None, "tp").reshape(b, t, layout.q_pad, hsz)
    if kv_override is None:
        wk = apply_kv_layout(ap["wk"], layout, hsz)
        wv = apply_kv_layout(ap["wv"], layout, hsz)
        k = policy(h @ wk, "dp", None, "tp").reshape(b, t, layout.kv_pad, hsz)
        v = policy(h @ wv, "dp", None, "tp").reshape(b, t, layout.kv_pad, hsz)
        off = jnp.asarray(q_offset, jnp.int32)
        ragged = off.ndim == 1                 # [B] per-request offsets
        if cfg.use_rope:
            pos = (off[:, None] + jnp.arange(t)[None, :] if ragged
                   else (jnp.arange(t) + off)[None, :])
            q = apply_rope(q, pos, cfg.rope_theta)
            k = apply_rope(k, pos, cfg.rope_theta)
        if kv_buffer is not None:
            kbuf, vbuf = kv_buffer
            if ragged:
                # ragged chunk packing: every request writes its chunk rows
                # at its own prefill progress
                upd = jax.vmap(lambda bu, nu, o: jax.lax.dynamic_update_slice(
                    bu, nu, (o, 0, 0)))
                kbuf = upd(kbuf, k.astype(kbuf.dtype), off)
                vbuf = upd(vbuf, v.astype(vbuf.dtype), off)
            else:
                kbuf = jax.lax.dynamic_update_slice(
                    kbuf, k.astype(kbuf.dtype), (0, off, 0, 0))
                vbuf = jax.lax.dynamic_update_slice(
                    vbuf, v.astype(vbuf.dtype), (0, off, 0, 0))
            k, v = kbuf, vbuf
    else:
        k, v = kv_override                     # cross-attn: precomputed enc KV
    out = prefill_attention(q, k, v, causal=causal, window=window,
                            chunk_q=chunk_q, q_offset=q_offset,
                            unroll=unroll, backend=attn_backend, prune=prune,
                            seq_lens=seq_lens, policy=policy)
    out = out.reshape(b, t, layout.q_pad * hsz)
    proj = policy(out, "dp", None, "tp") @ wo
    return policy(proj, "dp", None, None), (k, v)


def _ffn_block(cfg: ArchConfig, fp, h, policy):
    act = activation(cfg.act)
    if "w3" in fp:
        y = act(h @ fp["w1"]) * (h @ fp["w3"])
    else:
        y = act(h @ fp["w1"])
    y = policy(y, "dp", None, "tp")
    return policy(y @ fp["w2"], "dp", None, None)


def decoder_layer(cfg: ArchConfig, lp, x, *, layout, window, policy,
                  enc_out=None, moe_groups=1, chunk_q=512, unroll=False,
                  attn_backend="ref", ssd_backend="ref", prune=True,
                  kv_buffer=None, q_offset=0, seq_lens=None):
    """One decoder layer.  Returns (x, (kcache, vcache, ssm_state, aux)).

    ``attn_backend`` / ``ssd_backend`` select the flash_prefill and
    ssd_prefill kernel backends (kernels/registry.py); ``prune`` the
    flash_prefill block-skipping knob.  ``kv_buffer`` / ``q_offset`` /
    ``seq_lens`` are the chunked-prefill carry contract (see
    ``_attn_block``): when given, the returned kcache/vcache are the
    *updated full-prefix buffers* instead of the chunk's own rows."""
    b, t, _ = x.shape
    h = rms_norm(x, lp["ln1"])
    cache_kv = (jnp.zeros((b, t, 0, cfg.hsz), x.dtype),) * 2
    ssm_state = None
    if cfg.has_attention and cfg.has_ssm:                       # hybrid
        a_out, cache_kv = _attn_block(cfg, lp["attn"], h, layout=layout,
                                      window=window, policy=policy,
                                      chunk_q=chunk_q, unroll=unroll,
                                      attn_backend=attn_backend, prune=prune)
        s_out, ssm_state = ssm_lib.ssd_chunked(
            ssm_lib.SSMParams(**lp["ssm"]), cfg, h, unroll=unroll,
            backend=ssd_backend)
        x = x + 0.5 * (a_out + s_out)
    elif cfg.has_attention:
        a_out, cache_kv = _attn_block(cfg, lp["attn"], h, layout=layout,
                                      window=window, policy=policy,
                                      chunk_q=chunk_q, unroll=unroll,
                                      attn_backend=attn_backend, prune=prune,
                                      kv_buffer=kv_buffer, q_offset=q_offset,
                                      seq_lens=seq_lens)
        x = x + a_out
    else:                                                        # pure ssm
        s_out, ssm_state = ssm_lib.ssd_chunked(
            ssm_lib.SSMParams(**lp["ssm"]), cfg, h, unroll=unroll,
            backend=ssd_backend)
        x = x + s_out

    if enc_out is not None:                                      # cross-attn
        hx = rms_norm(x, lp["lnx"])
        xl = head_layout(cfg.n_heads, cfg.n_kv_heads, 1)
        kx = (enc_out @ lp["xattn"]["wk"]).reshape(
            b, enc_out.shape[1], cfg.n_kv_heads, cfg.hsz)
        vx = (enc_out @ lp["xattn"]["wv"]).reshape(
            b, enc_out.shape[1], cfg.n_kv_heads, cfg.hsz)
        x_out, _ = _attn_block(cfg, lp["xattn"], hx, layout=xl, window=0,
                               policy=policy, causal=False,
                               kv_override=(kx, vx), chunk_q=chunk_q,
                               unroll=unroll, attn_backend=attn_backend,
                               prune=prune)
        x = x + x_out

    aux = jnp.zeros((), jnp.float32)
    if cfg.d_ff or cfg.moe:
        h2 = rms_norm(x, lp["ln2"])
        delta = 0.0
        if cfg.d_ff:
            delta = _ffn_block(cfg, lp["ffn"], h2, policy)
        if cfg.moe:
            y, aux = moe_ffn(
                MoEParams(**lp["moe"]), h2.reshape(b * t, -1),
                cfg.moe, activation("silu"), groups=moe_groups,
                c_disp=lambda v: policy(v, "dp", None, None, None),
                c_exp=lambda v: policy(v, "pod", "ep", None, None))
            delta = delta + policy(y.reshape(b, t, -1), "dp", None, None)
        x = x + delta
    return x, (cache_kv[0], cache_kv[1], ssm_state, aux)


def layer_windows(cfg: ArchConfig) -> np.ndarray:
    """Per-layer sliding-window sizes ([L] int32; 0 = global attention)."""
    w = np.zeros((cfg.n_layers,), np.int32)
    if cfg.local_window and cfg.local_ratio:
        period = cfg.local_ratio + 1
        for i in range(cfg.n_layers):
            if (i + 1) % period != 0:          # 5 local then 1 global
                w[i] = cfg.local_window
    return w


# =============================================================== full fwd
def chunked_prefill_supported(cfg: ArchConfig) -> bool:
    """Whether ``cfg`` can prefill in prefix-attending chunks *bit-exactly*.

    Requires every cross-position interaction to be causal attention: pure
    SSM / hybrid scans and MoE capacity routing mix information across the
    whole sequence in chunk-boundary-dependent fp orders, and enc-dec /
    vision prefixes need the full prompt up front.  The serving engine falls
    back to one-shot prefill for unsupported archs."""
    return (cfg.has_attention and not cfg.has_ssm and not cfg.is_encdec
            and cfg.moe is None and not cfg.vision_patches)


def forward(cfg: ArchConfig, params, tokens, *, policy=NO_POLICY,
            patch_embeds=None, enc_frames=None, return_cache: bool = False,
            moe_groups: int = 1, chunk_q: int = 512, tp_width: int = 1,
            remat: bool = True, unroll: bool = False,
            prefill_backend: str = "ref", ssd_backend: str = "ref",
            prune_blocks: bool = True, prefix_state=None, q_offset=0,
            seq_lens=None):
    """Full-sequence forward.  tokens [B, T] int32 -> (logits, extras).

    extras = {"aux_loss": scalar, "kcache"/"vcache": [L,B,T,Kh_p,hsz],
              "ssm_conv"/"ssm_state": [L,...]} (caches when return_cache).

    ``prefill_backend`` / ``ssd_backend`` route the attention and SSD-scan
    hotspots through the kernel registry (ref | pallas-interpret | pallas);
    the pallas backends use a ref-VJP backward, so gradients flow (train).
    ``prune_blocks`` is flash_prefill's causal/window block-skipping knob
    (kernel backends only; bit-exact on/off).

    Chunked prefill (``chunked_prefill_supported`` archs only, see
    docs/serving.md): ``prefix_state`` = {"kcache"/"vcache":
    [L, B, S_buf, Kp, hsz]} carry buffers whose rows ``[0, q_offset)`` hold
    the already-prefilled prefix; ``tokens`` is then the ``[B, T]`` chunk at
    global positions ``[q_offset, q_offset + T)``.  The chunk's K/V rows are
    written into the buffers and attention runs over the whole buffer
    (causal masking hides the unfilled tail), so extras' kcache/vcache are
    the *updated full buffers* — bit-exact with the one-shot prefill when
    ``S_buf`` equals the one-shot sequence length.  ``seq_lens`` masks kv
    positions per request (ragged packing).
    """
    b, t = tokens.shape
    if prefix_state is not None:
        assert chunked_prefill_supported(cfg), \
            f"chunked prefill unsupported for {cfg.name} ({cfg.family})"
        assert return_cache, "chunked prefill needs return_cache=True"
    x = params["embed"][tokens]                                 # [B,T,H]
    x = policy(x, "dp", None, None)
    if patch_embeds is not None:                                # vlm stub
        p = patch_embeds.shape[1]
        x = jnp.concatenate([patch_embeds.astype(x.dtype), x[:, p:]], axis=1)
    if not cfg.use_rope and not cfg.is_encdec:
        from repro.models.layers import sinusoidal_at
        off = jnp.asarray(q_offset, jnp.int32)
        if off.ndim == 1:                      # ragged per-request offsets
            pos = (off[:, None] + jnp.arange(t)[None, :]).astype(jnp.float32)
            x = x + sinusoidal_at(pos, cfg.d_model).astype(x.dtype)
        else:
            pos = (jnp.arange(t) + off).astype(jnp.float32)
            x = x + sinusoidal_at(pos, cfg.d_model)[None].astype(x.dtype)

    enc_out = None
    if cfg.is_encdec:
        from repro.models.encdec import encode                  # lazy: cycle
        enc_out = encode(cfg, params["enc"], enc_frames, policy=policy,
                         chunk_q=chunk_q, unroll=unroll,
                         attn_backend=prefill_backend, prune=prune_blocks)
        x = x + sinusoidal_positions(t, cfg.d_model)[None].astype(x.dtype)

    layout = (head_layout(cfg.n_heads, cfg.n_kv_heads, tp_width)
              if cfg.has_attention else None)
    windows = jnp.asarray(layer_windows(cfg))

    def body(carry, xs):
        lp, win, buf = xs
        y, (kc, vc, sst, aux) = decoder_layer(
            cfg, lp, carry, layout=layout, window=win, policy=policy,
            enc_out=enc_out, moe_groups=moe_groups, chunk_q=chunk_q,
            unroll=unroll, attn_backend=prefill_backend,
            ssd_backend=ssd_backend, prune=prune_blocks,
            kv_buffer=buf, q_offset=q_offset, seq_lens=seq_lens)
        outs = (kc, vc, sst, aux) if return_cache else \
            (None, None, None, aux)
        return y, outs

    bufs = (None if prefix_state is None
            else (prefix_state["kcache"], prefix_state["vcache"]))
    body_fn = jax.checkpoint(body) if remat else body
    x, (kc, vc, sst, aux) = jax.lax.scan(
        body_fn, x, (params["layers"], windows, bufs),
        unroll=cfg.n_layers if unroll else 1)

    x = rms_norm(x, params["ln_f"])
    head = params.get("lm_head")
    logits = x @ head if head is not None else x @ params["embed"].T
    logits = policy(logits, "dp", None, "tp")
    if cfg.softcap:
        logits = softcap(logits, cfg.softcap)
    # mask padded vocab rows so softmax/loss are exact
    vmask = jnp.where(jnp.arange(cfg.padded_vocab) < cfg.vocab, 0.0, -1e30)
    logits = logits + vmask.astype(logits.dtype)

    extras = {"aux_loss": jnp.sum(aux)}
    if return_cache:
        extras.update(kcache=kc, vcache=vc)
        if sst is not None:
            extras.update(ssm_conv=sst.conv, ssm_state=sst.ssm)
    if enc_out is not None:
        extras["enc_out"] = enc_out
    return logits, extras


def lm_loss(cfg: ArchConfig, logits, labels):
    """Mean next-token cross-entropy; labels [B,T] with -100 = ignore."""
    valid = labels >= 0
    lbl = jnp.maximum(labels, 0)
    logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
    nll = -jnp.take_along_axis(logp, lbl[..., None], axis=-1)[..., 0]
    return jnp.sum(nll * valid) / jnp.maximum(jnp.sum(valid), 1)
