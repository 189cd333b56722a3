"""Helix decode path: one full autoregressive step for every architecture.

``build_serve_step(cfg, mesh, hx)`` returns a jit-able

    serve_step(params, state, tokens) -> (next_tokens, new_state)

implementing the paper's per-layer temporal pipeline:

  attention phase — QKV projected per-rank (replicated batch), round-robin
  KV append (§2.3), helix_attention (shard_map: flash-decode over the local
  KV shard + single all-to-all over the query-head axis + LSE combine,
  optionally HOP-B batch-chunked, §2.1.3);

  FFN phase — the *same* device pool re-provisioned via GSPMD sharding
  constraints: dense FFN with TPF = N, or MoE with EP×TPF (§2.2).

``build_serve_multistep(cfg, mesh, hx, window=N)`` wraps the same forward
core in a ``lax.scan`` over N tokens — sample (serving/sampling.py fused
epilogue) -> fused KV append -> next step — entirely on device, with
per-row EOS / budget / forced-token control carried as masks, so the
serving engine's host round-trip drops from once per token to once per
window (``DecodeEngine --decode-window``).

Everything outside helix_attention is GSPMD (pjit constraints); that is the
TPU-idiomatic equivalent of the paper's GPU-pool reconfiguration.
"""
from __future__ import annotations

import functools
from typing import Any

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.configs.base import ArchConfig
from repro.core.helix import (append_kv, append_kv_quant,
                              fuse_append_applicable, helix_attention)
from repro.core.sharding import HelixConfig
from repro.models import ssm as ssm_lib
from repro.models.layers import (activation, apply_rope, full_precision,
                                 rms_norm, sinusoidal_at, softcap)
from repro.models.moe import MoEParams, moe_ffn
from repro.models.transformer import layer_windows


def quantize_lm_head(params):
    """Pre-quantize the lm_head (or tied-embedding) weights for the
    ``HelixConfig.lm_head_w8`` decode path: returns a copy of ``params``
    with ``lm_head_q8`` (int8 [H, V]) and ``lm_head_scale`` (f32 [V])
    added, so ``serve_step`` skips the per-step re-quantization.  Done once
    by the serving engine; decoding with unaugmented params still works
    (the step falls back to quantizing in-jit)."""
    from repro.kernels.w8a16_matmul.ref import quantize_w8
    head = params.get("lm_head")
    if head is None:
        head = params["embed"].T
    qw, scale = quantize_w8(head)
    out = dict(params)
    out["lm_head_q8"], out["lm_head_scale"] = qw, scale
    return out


def prepare_decode_params(params, hx: HelixConfig | None):
    """One-time decode-param preparation every ``serve_step`` caller should
    run before stepping: with ``hx.lm_head_w8`` it pre-quantizes the lm_head
    (``quantize_lm_head``) so the step doesn't re-quantize the ``[H, V]``
    matrix every token; otherwise it is the identity.  Idempotent — params
    already carrying ``lm_head_q8`` pass through untouched — so the serving
    engine, the launch/serve one-shot path and the benchmarks can all call
    it unconditionally."""
    if hx is not None and hx.lm_head_w8 and "lm_head_q8" not in params:
        return quantize_lm_head(params)
    return params


def _constrainer(mesh: Mesh):
    def c(x, *axes):
        return jax.lax.with_sharding_constraint(
            x, NamedSharding(mesh, P(*axes)))
    return c


def _resolve_overrides(hx: HelixConfig, **overrides_in) -> HelixConfig:
    """Apply the per-builder HelixConfig field overrides (None = keep)."""
    import dataclasses
    overrides = {field: val for field, val in overrides_in.items()
                 if val is not None and val != getattr(hx, field)}
    return dataclasses.replace(hx, **overrides) if overrides else hx


def _next_token(logits, state):
    """The decode epilogue's token decision: the on-device sampler
    (serving/sampling.py — greedy/temperature/top-k/top-p from the per-row
    ``sample_*`` state leaves) when the state carries sampling leaves,
    otherwise the historical plain argmax.  Structural gating on
    ``sample_seed`` mirrors the grouped-decode ``group_id`` pattern: engines
    built without sampling never pay for (or trace) the sampler."""
    with jax.named_scope("sample"):
        if "sample_seed" in state:
            from repro.serving.sampling import sample_tokens
            return sample_tokens(logits, state["sample_temp"],
                                 state["sample_topk"], state["sample_topp"],
                                 state["sample_seed"], state["sample_idx"])
        return jnp.argmax(logits, axis=-1).astype(jnp.int32)


def _build_step_logits(cfg: ArchConfig, mesh: Mesh, hx: HelixConfig, *,
                       hopb_chunks: int = 4, unroll: bool = False):
    """The shared forward core behind ``build_serve_step`` and
    ``build_serve_multistep``: returns

        step_logits(params, state, tokens) -> (logits, new_caches)

    one full decode forward pass — embed, layer-period scan (attention /
    SSM / FFN phases), final norm, (w8a16) lm_head matmul, softcap and
    vocab pad mask — *without* the token decision or state-dict rebuild, so
    the two builders can attach their own epilogues (single-step sampler vs
    the windowed ``lax.scan``)."""
    import math

    from repro.core.helix import helix_out_dim
    from repro.core.sharding import dense_ffn_mode

    kvp = hx.kvp(mesh)
    tpa_ax = hx.tpa_axis
    all_ax = hx.all_axes()
    n_all = math.prod(mesh.shape[a] for a in all_ax)
    tpf = tuple(a for a in ("pod", "model") if a in all_ax) or None
    windows = layer_windows(cfg)
    act = activation(cfg.act)
    cst = _constrainer(mesh)
    o_dim = helix_out_dim(cfg.q_dim, n_all)       # padded a2a output dim
    ffn2d = cfg.d_ff and dense_ffn_mode(cfg, mesh, hx) == "2d"
    dp_ish = tuple(a for a in mesh.axis_names if a != "model")
    kv8 = hx.kv_cache_bits == 8                   # int8 KV cache (§Perf)

    def head_matmul(x, head, params):
        """Logits matmul; ``hx.lm_head_w8`` routes it through the
        w8a16_matmul kernel family (the registry's end-to-end consumer):
        per-column int8 weight quantization, backend per
        ``hx.matmul_backend``.  Weight-only quantization — activations stay
        fp, so this changes numerics (unlike the exact kernel knobs).
        Pre-quantized weights (``lm_head_q8``/``lm_head_scale`` in params —
        ``quantize_lm_head``, done once by the serving engine) are used when
        present; otherwise the head is quantized in-step, which re-runs the
        O(d_model * vocab) quantization every token."""
        if not hx.lm_head_w8:
            return x @ head
        from repro.kernels import registry
        from repro.kernels.w8a16_matmul.ref import quantize_w8
        qw, scale = params.get("lm_head_q8"), params.get("lm_head_scale")
        if qw is None:
            qw, scale = quantize_w8(head)
        fn = registry.resolve("w8a16_matmul", hx.matmul_backend)
        if registry.uses_kernel(hx.matmul_backend):
            return fn(x, qw, scale,
                      interpret=registry.interpret_flag(hx.matmul_backend))
        return fn(x, qw, scale)

    def out_proj(out, wo):
        """Post-attention projection; pads wo rows when the a2a flat dim was
        padded (exact: pad rows multiply the zero pad lanes)."""
        if o_dim != wo.shape[0]:
            wo = jnp.pad(wo, ((0, o_dim - wo.shape[0]), (0, 0)))
        return cst(out @ wo, None, None)

    def attn_phase(lp, h, kc, vc, ks, vs, tl_attn, win, tables, groups=None):
        """Helix attention phase for one layer.  h [B,H] (replicated).
        ``tables`` is the paged pool's [B, max_pages] block table (None in
        the fixed-cap layout); kc/vc/ks/vs are then pool planes.
        ``groups`` is the grouped shared-prefix decode's (group_id,
        group_np) [B] pair (None = ungrouped; forces hopb_chunks=1)."""
        b = h.shape[0]
        # qkv_shard (§Perf, beyond-paper): weights over 'model', all-gather
        # the tiny activations — vs the paper's replicated per-rank QKV.
        qkv_ax = "model" if hx.qkv_shard and not tpa_ax else tpa_ax
        with jax.named_scope("qkv"):
            q = cst(cst(h @ lp["wq"], None, qkv_ax),
                    None, tpa_ax).reshape(b, cfg.n_heads, cfg.hsz)
            kn = cst(cst(h @ lp["wk"], None, qkv_ax),
                     None, tpa_ax).reshape(b, cfg.n_kv_heads, cfg.hsz)
            vn = cst(cst(h @ lp["wv"], None, qkv_ax),
                     None, tpa_ax).reshape(b, cfg.n_kv_heads, cfg.hsz)
            if cfg.use_rope:
                pos = (tl_attn - 1)
                pos = pos[..., None] if jnp.ndim(pos) else pos[None]
                q = apply_rope(q[:, None], pos, cfg.rope_theta)[:, 0]
                kn = apply_rope(kn[:, None], pos, cfg.rope_theta)[:, 0]
        chunks = hopb_chunks if b % hopb_chunks == 0 else 1
        if groups is not None:
            chunks = 1      # groups span the batch; chunks would split them
        paged = tables is not None
        # Fused KV-append epilogue (§Perf, roadmap): on the Pallas backends
        # the decode kernel writes kn/vn into the cache itself, skipping the
        # separate append pass (one cache HBM round-trip per layer per
        # step).  Static decision; int8 caches quantize the new token
        # in-kernel, and with block pruning on there is no cache-slice
        # conflict left to fall back over.
        if fuse_append_applicable(hx, kvp, win, tl_attn, kc.shape[2],
                                  quant=kv8, paged=paged):
            if kv8:
                out, kc, vc, ks, vs = helix_attention(
                    mesh, hx, q, kc, vc, tl_attn, window=win,
                    hopb_chunks=chunks, kscale=ks, vscale=vs,
                    k_new=kn, v_new=vn, block_tables=tables, groups=groups)
            else:
                out, kc, vc = helix_attention(
                    mesh, hx, q, kc, vc, tl_attn, window=win,
                    hopb_chunks=chunks, k_new=kn, v_new=vn,
                    block_tables=tables, groups=groups)
        else:
            with jax.named_scope("kv_pool"):
                if kv8:
                    kc, vc, ks, vs = append_kv_quant(
                        kc, vc, ks, vs, kn, vn, tl_attn, kvp=kvp,
                        rr_block=hx.rr_block, block_tables=tables)
                else:
                    kc, vc = append_kv(kc, vc, kn, vn, tl_attn, kvp=kvp,
                                       rr_block=hx.rr_block,
                                       block_tables=tables)
            out = helix_attention(mesh, hx, q, kc, vc, tl_attn, window=win,
                                  hopb_chunks=chunks,
                                  kscale=ks if kv8 else None,
                                  vscale=vs if kv8 else None,
                                  block_tables=tables, groups=groups)
        # post-attention projection: TP = N over the combined (tpa, kvp)
        # layout; the All-Reduce the paper describes is emitted by GSPMD from
        # wo's input-dim sharding.
        return out_proj(out, lp["wo"]), kc, vc, ks, vs

    def cross_phase(lp, h, xk, xv, s_enc):
        b = h.shape[0]
        q = cst(h @ lp["wq"], None, tpa_ax).reshape(b, cfg.n_heads, cfg.hsz)
        chunks = hopb_chunks if b % hopb_chunks == 0 else 1
        out = helix_attention(mesh, hx, q, xk, xv,
                              jnp.asarray(s_enc, jnp.int32),
                              contiguous=True, hopb_chunks=chunks)
        return out_proj(out, lp["wo"])

    def ssm_phase(lp, h, conv, sstate):
        # batch over 'data' (when divisible), heads/channels over 'model'
        # (DESIGN §4 mamba2: Helix's FFN half applies; KVP is inapplicable —
        # no KV cache).
        bax = "data" if h.shape[0] % mesh.shape["data"] == 0 else None
        hax = "model" if cfg.ssm_heads % mesh.shape["model"] == 0 else None
        cax = "model" if cfg.conv_dim % mesh.shape["model"] == 0 else None
        y, new = ssm_lib.ssm_decode_step(
            ssm_lib.SSMParams(**lp), cfg,
            cst(h, bax, None),
            ssm_lib.SSMState(cst(conv, bax, cax, None),
                             cst(sstate, bax, hax, None, None)))
        return cst(y, None, None), new

    def ffn_phase(lp_ffn, lp_moe, h2):
        delta = 0.0
        if lp_ffn is not None:
            # dense FFN: TPF = N — all devices amortize the weight read.
            # '2d' fallback (F % N != 0): H over dp-ish axes x F over model;
            # the contraction over the H shard emits a small all-reduce.
            fax = ("model",) if ffn2d else all_ax
            y = act(cst(h2 @ lp_ffn["w1"], None, fax))
            if "w3" in lp_ffn:
                y = y * cst(h2 @ lp_ffn["w3"], None, fax)
            delta = cst(y @ lp_ffn["w2"], None, None)
        if lp_moe is not None:
            m, _aux = moe_ffn(
                MoEParams(**lp_moe), h2, cfg.moe, activation("silu"),
                capacity_factor=cfg.moe.decode_capacity_factor, groups=1,
                c_disp=lambda v: cst(v, None, hx.ep_axis, None, None),
                c_exp=lambda v: cst(v, None, hx.ep_axis, None, None))
            delta = delta + cst(m, None, None)
        return delta

    def layer_fn(x, lp, win, kc, vc, ks, vs, conv, sstate, xk, xv, tl_attn,
                 s_enc, tables, groups=None):
        new_caches: dict[str, Any] = {}
        if cfg.has_attention:
            with jax.named_scope("attn"):
                h = rms_norm(x, lp["ln1"])
                a_out, kc, vc, ks, vs = attn_phase(lp["attn"], h, kc, vc, ks,
                                                   vs, tl_attn, win, tables,
                                                   groups)
            new_caches.update(kcache=kc, vcache=vc)
            if cfg.has_ssm:                            # hybrid (hymba)
                s_out, new_s = ssm_phase(lp["ssm"], h, conv, sstate)
                x = x + 0.5 * (a_out + s_out)
                new_caches.update(ssm_conv=new_s.conv, ssm_state=new_s.ssm)
            else:
                x = x + a_out
        else:                                          # pure ssm (mamba2)
            h = rms_norm(x, lp["ln1"])
            s_out, new_s = ssm_phase(lp["ssm"], h, conv, sstate)
            x = x + s_out
            new_caches.update(ssm_conv=new_s.conv, ssm_state=new_s.ssm)
        if kv8 and cfg.has_attention:
            new_caches.update(kscale=ks, vscale=vs)

        if cfg.is_encdec:
            hxn = rms_norm(x, lp["lnx"])
            x = x + cross_phase(lp["xattn"], hxn, xk, xv, s_enc)

        if cfg.d_ff or cfg.moe:
            with jax.named_scope("ffn"):
                h2 = rms_norm(x, lp["ln2"])
                x = x + ffn_phase(lp.get("ffn"), lp.get("moe"), h2)
        return x, new_caches

    @full_precision
    def step_logits(params, state, tokens):
        """tokens [B] int32 -> (logits [B, padded_vocab], new_caches)."""
        tl = state["total_len"]
        tl_attn = tl + 1                                # includes new token
        # paged pool: the [B, max_pages] block table rides in the state and
        # is shared by every layer (pool planes are per-layer, tables per
        # request); it passes through the step unchanged — the host-side
        # engine/scheduler owns page allocation.
        tables = state.get("block_tables") if hx.paged_kv else None
        # grouped shared-prefix decode: the engine recomputes the [B]
        # group_id/group_np leaves each step from the pool's page sharing
        groups = None
        if hx.grouped_decode and hx.paged_kv and "group_id" in state:
            groups = (state["group_id"], state["group_np"])
        with jax.named_scope("embed"):
            x = params["embed"][tokens]                 # [B, H]
            x = cst(x, None, None)
            if not cfg.use_rope:
                pos = tl if jnp.ndim(tl) else tl[None]
                pe = sinusoidal_at(pos.astype(jnp.float32), cfg.d_model)
                x = x + pe.astype(x.dtype)

        L = cfg.n_layers
        s_enc = state.get("enc_len", 0) if cfg.is_encdec else 0

        # Scan over layer *periods* (gemma3: 5 local + 1 global) so each
        # sub-layer's sliding window is a STATIC python int — this lets the
        # helix local attend slice O(window/KVP) cache bytes (§Perf).
        p = (cfg.local_ratio + 1) if cfg.local_ratio else 1
        nper = L // p
        win_static = [int(w) for w in windows[:p]]

        dummy = jnp.zeros((L, 1), jnp.int32)  # placeholder for absent leaves
        xs = (params["layers"],
              state.get("kcache", dummy), state.get("vcache", dummy),
              state.get("kscale", dummy), state.get("vscale", dummy),
              state.get("ssm_conv", dummy), state.get("ssm_state", dummy),
              state.get("xk", dummy), state.get("xv", dummy))
        xs = jax.tree.map(lambda a: a.reshape(nper, p, *a.shape[1:]), xs)

        def body(carry, xs_p):
            xcur = carry
            outs = []
            for i in range(p):
                with jax.named_scope("kv_pool"):
                    leaf_i = jax.tree.map(lambda a: a[i], xs_p)
                lp, kc, vc, ks, vs, conv, sstate, xk, xv = leaf_i
                xcur, nc = layer_fn(xcur, lp, win_static[i], kc, vc, ks, vs,
                                    conv, sstate, xk, xv, tl_attn, s_enc,
                                    tables, groups)
                outs.append(nc)
            stacked = jax.tree.map(lambda *a: jnp.stack(a), *outs)
            return xcur, stacked

        x, new_caches = jax.lax.scan(body, x, xs,
                                     unroll=nper if unroll else 1)
        with jax.named_scope("kv_pool"):
            new_caches = jax.tree.map(
                lambda a: a.reshape(L, *a.shape[2:]), new_caches)

        with jax.named_scope("lm_head"):
            x = rms_norm(x, params["ln_f"])
            head = params.get("lm_head")
            if head is None:
                head = params["embed"].T
            logits = head_matmul(x, head, params)
            logits = cst(logits, None, all_ax)
            if cfg.softcap:
                logits = softcap(logits, cfg.softcap)
            vmask = jnp.where(jnp.arange(cfg.padded_vocab) < cfg.vocab,
                              0.0, -1e30)
            logits = logits + vmask.astype(logits.dtype)
        return logits, new_caches

    return step_logits


def build_serve_step(cfg: ArchConfig, mesh: Mesh, hx: HelixConfig, *,
                     hopb_chunks: int = 4, return_logits: bool = False,
                     unroll: bool = False, attn_backend: str | None = None,
                     fuse_append: bool | None = None,
                     prune_blocks: bool | None = None,
                     matmul_backend: str | None = None,
                     lm_head_w8: bool | None = None,
                     paged_kv: bool | None = None):
    """Build one autoregressive Helix decode step for ``cfg`` on ``mesh``.

    Returns ``serve_step(params, state, tokens) -> (next_tokens, new_state)``
    (jit-able; ``state`` from ``make_prefill_step`` or
    ``core/kvcache.init_decode_state``).

    The token decision is the fused on-device epilogue ``_next_token``:
    plain argmax normally, or the serving/sampling.py sampler when the
    state carries the per-row ``sample_*`` leaves
    (``core/kvcache.sampling_leaf_shapes``) — in which case
    ``sample_idx`` also advances by one per step.

    Args:
      hopb_chunks: HOP-B batch chunking inside helix_attention (§2.1.3);
        degrades to 1 automatically when the batch doesn't divide.
      return_logits: also return the full next-token logits.
      unroll: unroll the layer-period scan (dry-run cost analysis).
      attn_backend: overrides ``hx.attn_backend`` (``ref`` |
        ``pallas-interpret`` | ``pallas``) — the flash_decode kernel family
        backend used inside helix_attention (kernels/registry.py).
      fuse_append: overrides ``hx.fuse_append`` — fuse the rr-slot KV append
        into the decode kernel epilogue (Pallas backends only).
      prune_blocks: overrides ``hx.prune_blocks`` — length/causality-aware
        K/V block pruning inside the Pallas decode kernel (bit-exact).
      matmul_backend: overrides ``hx.matmul_backend`` — the w8a16_matmul
        family backend for the quantized lm_head matmul.
      lm_head_w8: overrides ``hx.lm_head_w8`` — int8-quantize the lm_head
        weights and route the logits matmul through w8a16_matmul.
      paged_kv: overrides ``hx.paged_kv`` — shared-pool paged KV cache: the
        state carries pool planes ``[L, n_blocks, Kh, block_s, hsz]`` plus a
        ``block_tables`` [B, max_pages] leaf instead of fixed per-slot rows
        (core/kvcache.py paged layout; bit-exact vs fixed at the same
        ``attn_block_s`` partition).
    """
    hx = _resolve_overrides(hx, attn_backend=attn_backend,
                            fuse_append=fuse_append,
                            prune_blocks=prune_blocks,
                            matmul_backend=matmul_backend,
                            lm_head_w8=lm_head_w8, paged_kv=paged_kv)
    step_logits = _build_step_logits(cfg, mesh, hx, hopb_chunks=hopb_chunks,
                                     unroll=unroll)

    def serve_step(params, state, tokens):
        """tokens [B] int32 -> (next_tokens [B], new state)."""
        logits, new_caches = step_logits(params, state, tokens)
        next_tokens = _next_token(logits, state)
        new_state = dict(state)
        new_state.update(new_caches)
        new_state["total_len"] = state["total_len"] + 1
        if "sample_idx" in state:
            new_state["sample_idx"] = state["sample_idx"] + 1
        if cfg.is_encdec:                               # static cross KV
            new_state["xk"], new_state["xv"] = state["xk"], state["xv"]
        if return_logits:
            return (next_tokens, logits), new_state
        return next_tokens, new_state

    return serve_step


def build_serve_multistep(cfg: ArchConfig, mesh: Mesh, hx: HelixConfig, *,
                          window: int, hopb_chunks: int = 4,
                          unroll: bool = False,
                          attn_backend: str | None = None,
                          fuse_append: bool | None = None,
                          prune_blocks: bool | None = None,
                          matmul_backend: str | None = None,
                          lm_head_w8: bool | None = None,
                          paged_kv: bool | None = None):
    """Build the windowed decode inner loop: ``window`` tokens per call
    entirely on device (sample -> fused KV append -> next step via
    ``lax.scan``), so the host only intervenes — one blocking transfer,
    scheduling, admission — once per window instead of once per token.

    Returns

        serve_multistep(params, state, tokens, budgets, eos_ids,
                        forced, n_forced)
            -> (out_block [B, window], cur_tokens [B], new_state)

    with per-row control carried as data (no host round-trips inside the
    window):

      * ``budgets`` [B] i32 — device steps this row may take (its page /
        capacity grant from ``Scheduler.grow_for_window``; 0 freezes the
        row for the whole window, e.g. idle slots).
      * ``eos_ids`` [B] i32 — per-row EOS token (< 0 = none).  A row that
        *emits* EOS freezes for the rest of the window: state stops
        advancing (``total_len`` and the SSM recurrences hold; KV appends
        degenerate to masked-off rewrites of the frozen position) and its
        remaining ``out_block`` entries are the pad value ``-1``.
      * ``forced`` [B, window] + ``n_forced`` [B] — restore/session-KV
        catch-up tokens fed *instead of* the sampled token for the first
        ``n_forced[b]`` active steps of row ``b`` (they consume budget but
        emit pad and do not advance ``sample_idx``, exactly like the
        single-step engine's host-side forced replay).

    ``out_block[b, j]`` is the token row ``b`` emitted at in-window step
    ``j`` (pad ``-1`` where frozen/forced) — EOS itself is emitted so the
    host replay can observe it.  ``total_len`` must be per-row [B].
    Rows frozen mid-window (EOS / exhausted budget < window) must be
    retired by the caller at the window boundary — their in-flight
    activations are discarded, which is what makes windowed streams
    bit-identical to ``window`` single steps.

    Same builder knobs as ``build_serve_step``; grouped shared-prefix
    decode is rejected (the [B] group leaves are host-recomputed per token
    and would go stale mid-window)."""
    if window < 1:
        raise ValueError(f"window must be >= 1 (got {window})")
    hx = _resolve_overrides(hx, attn_backend=attn_backend,
                            fuse_append=fuse_append,
                            prune_blocks=prune_blocks,
                            matmul_backend=matmul_backend,
                            lm_head_w8=lm_head_w8, paged_kv=paged_kv)
    if hx.grouped_decode:
        raise ValueError("serve_multistep is incompatible with "
                         "grouped_decode: group_id/group_np are recomputed "
                         "by the host every token and would go stale inside "
                         "a multi-token window")
    step_logits = _build_step_logits(cfg, mesh, hx, hopb_chunks=hopb_chunks,
                                     unroll=unroll)

    def serve_multistep(params, state, tokens, budgets, eos_ids,
                        forced, n_forced):
        b = tokens.shape[0]
        sampling = "sample_seed" in state
        # SSM recurrences have no total_len masking protecting them, so
        # frozen rows must explicitly hold their previous value
        ssm_keys = [k for k in ("ssm_conv", "ssm_state") if k in state]

        def body(carry, j):
            st, cur, fpos, eos_seen = carry
            active = (j < budgets) & ~eos_seen
            logits, new_caches = step_logits(params, st, cur)
            sampled = _next_token(logits, st)
            is_forced = fpos < n_forced
            fvals = jnp.take_along_axis(
                forced, jnp.minimum(fpos, forced.shape[1] - 1)[:, None],
                axis=1)[:, 0]
            emit = active & ~is_forced
            out_j = jnp.where(emit, sampled, -1)
            new_state = dict(st)
            new_state.update(new_caches)
            for key in ssm_keys:
                sel = active.reshape((1, b) + (1,) * (st[key].ndim - 2))
                new_state[key] = jnp.where(sel, new_state[key], st[key])
            new_state["total_len"] = st["total_len"] + active.astype(jnp.int32)
            if sampling:
                new_state["sample_idx"] = (st["sample_idx"]
                                           + emit.astype(jnp.int32))
            if cfg.is_encdec:                           # static cross KV
                new_state["xk"], new_state["xv"] = st["xk"], st["xv"]
            eos_hit = emit & (eos_ids >= 0) & (sampled == eos_ids)
            nxt = jnp.where(is_forced, fvals, sampled)
            carry2 = (new_state,
                      jnp.where(active, nxt, cur),
                      fpos + (active & is_forced).astype(jnp.int32),
                      eos_seen | eos_hit)
            return carry2, out_j

        init = (state, tokens, jnp.zeros((b,), jnp.int32),
                jnp.zeros((b,), bool))
        (new_state, cur, _, _), outs = jax.lax.scan(
            body, init, jnp.arange(window))
        return outs.T, cur, new_state

    return serve_multistep
