"""Chunked-vs-oneshot prefill exactness across the mode lattice.

The chunked path attends each chunk to the already-cached prefix through
flash_prefill's runtime q_offset contract over a carry buffer sized to the
one-shot sequence length, so every backend must reproduce the one-shot
prefill: the first generated token and the decode stream that follow
exactly, the contiguous carry buffers and the round-robin decode-state
handoff to float32 rounding of the K/V projection (``CACHE_TOL``).
Lattice: {ref, pallas-interpret} x prune {on, off} x chunk sizes {1, 17,
T} x {global, sliding-window} x {fp16-ish, int8 kv}.  The KVP=8
shard_map case lives in tests/distributed/scripts/helix_exact.py."""
import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.core.kvcache import quantize_decode_state
from repro.core.sharding import HelixConfig
from repro.models.model_zoo import (build_serve_step, chunked_prefill_supported,
                                    finalize_chunked_prefill,
                                    init_prefill_buffers,
                                    make_chunk_prefill_step, make_prefill_step)
from repro.models.transformer import init_params
from repro.utils import make_mesh

T = 19
CHUNKS = (1, 17, T)
S_CAP = 64
# XLA:CPU picks its f32 dot kernel by the row count (a matrix-vector kernel
# for one row, blocked GEMM for more), so a token's K/V projection differs
# by a few ULP with the number of tokens in its chunk (observed: 3e-6 on
# values of order 1).  The attention is chunk-invariant; the caches agree
# to that rounding, and tokens agree exactly.
CACHE_TOL = dict(rtol=1e-5, atol=1e-5)


@functools.lru_cache(maxsize=None)
def _cfg(windowed: bool):
    cfg = get_config("granite-3-2b").reduced()
    if windowed:
        # one local + one global layer (gemma3-style mix) without paying for
        # gemma3's 6-layer reduced period
        cfg = dataclasses.replace(cfg, local_window=8, local_ratio=1)
    return cfg


@functools.lru_cache(maxsize=None)
def _params(windowed: bool):
    return init_params(_cfg(windowed), jax.random.PRNGKey(0))


def _mesh1():
    return make_mesh((1, 1), ("data", "model"))


def _toks(cfg, b=1, t=T, seed=1):
    return jax.random.randint(jax.random.PRNGKey(seed), (b, t), 0, cfg.vocab)


def _oneshot(cfg, mesh, hx, params, toks):
    prefill = jax.jit(make_prefill_step(cfg, mesh, hx, s_cap=S_CAP))
    last_logits, state = prefill(params, {"tokens": toks})
    return int(jnp.argmax(last_logits[0, :cfg.vocab])), state


def _chunked(cfg, mesh, hx, params, toks, chunk):
    step = jax.jit(make_chunk_prefill_step(cfg, mesh, hx))
    t = toks.shape[1]
    bufs = init_prefill_buffers(cfg, toks.shape[0], t)
    pos = 0
    while pos < t:
        c = min(chunk, t - pos)
        nt, bufs = step(params, toks[:, pos:pos + c], bufs,
                        jnp.asarray(pos, jnp.int32))
        pos += c
    state = finalize_chunked_prefill(cfg, hx, bufs, t, s_cap=S_CAP, kvp=1)
    return int(nt[0, -1]), state, bufs


def _decode_n(cfg, mesh, hx, params, state, first_tok, n=3):
    serve = jax.jit(build_serve_step(cfg, mesh, hx))
    state = dict(state)
    cur = jnp.full((1,), first_tok, jnp.int32)
    outs = []
    for _ in range(n):
        cur, state = serve(params, state, cur)
        outs.append(int(cur[0]))
    return outs, state


@pytest.mark.parametrize("backend,prune", [("ref", True),
                                           ("pallas-interpret", True),
                                           ("pallas-interpret", False)],
                         ids=["ref", "pallas-prune", "pallas-dense"])
@pytest.mark.parametrize("windowed", [False, True],
                         ids=["global", "windowed"])
def test_chunked_prefill_bit_exact(backend, prune, windowed):
    """Chunked == one-shot: rr-layout cache state bit-identical and the
    greedy continuation (first token + 3 decode steps incl. final caches)
    identical, for chunk sizes {1, 17, T}."""
    cfg, params = _cfg(windowed), _params(windowed)
    mesh = _mesh1()
    hx = HelixConfig(kvp_axes=("data",), tpa_axis=None,
                     prefill_backend=backend, prune_blocks=prune)
    toks = _toks(cfg)
    tok1, st1 = _oneshot(cfg, mesh, hx, params, toks)
    dec1, fin1 = _decode_n(cfg, mesh, hx, params, st1, tok1)
    for chunk in CHUNKS:
        tok2, st2, _ = _chunked(cfg, mesh, hx, params, toks, chunk)
        assert tok2 == tok1, (chunk, tok2, tok1)
        assert int(st2["total_len"]) == int(st1["total_len"])
        np.testing.assert_allclose(np.asarray(st2["kcache"]),
                                   np.asarray(st1["kcache"]),
                                   err_msg=f"chunk={chunk}", **CACHE_TOL)
        np.testing.assert_allclose(np.asarray(st2["vcache"]),
                                   np.asarray(st1["vcache"]), **CACHE_TOL)
        dec2, fin2 = _decode_n(cfg, mesh, hx, params, st2, tok2)
        assert dec2 == dec1, (chunk, dec2, dec1)
        for key in ("kcache", "vcache"):
            np.testing.assert_allclose(np.asarray(fin2[key]),
                                       np.asarray(fin1[key]), **CACHE_TOL)


def test_chunked_buffers_match_oneshot_contiguous_cache():
    """The contiguous carry buffers themselves (pre-handoff layout) equal
    the one-shot forward's return_cache extras row for row — the rr state
    comparison above can't silently pass via matching zero padding."""
    from repro.models.transformer import forward
    cfg, params = _cfg(False), _params(False)
    mesh = _mesh1()
    hx = HelixConfig(kvp_axes=("data",), tpa_axis=None)
    toks = _toks(cfg)
    _, extras = forward(cfg, params, toks, return_cache=True)
    _, _, bufs = _chunked(cfg, mesh, hx, params, toks, 5)
    np.testing.assert_array_equal(np.asarray(bufs["kcache"]),
                                  np.asarray(extras["kcache"]))
    np.testing.assert_array_equal(np.asarray(bufs["vcache"]),
                                  np.asarray(extras["vcache"]))


def test_chunked_prefill_int8_state_bit_exact():
    """int8 KV mode: quantizing the chunked and one-shot prefill states
    (the engine's kv8 handoff) yields the same payloads and scales — up to
    one quantization step where the projection's rounding (``CACHE_TOL``)
    straddles a rounding boundary — and the kv8 decode streams agree."""
    cfg, params = _cfg(False), _params(False)
    mesh = _mesh1()
    hx = HelixConfig(kvp_axes=("data",), tpa_axis=None, kv_cache_bits=8,
                     attn_backend="pallas-interpret")
    toks = _toks(cfg)
    tok1, st1 = _oneshot(cfg, mesh, hx, params, toks)
    q1 = quantize_decode_state(st1)
    for chunk in (1, 17):
        tok2, st2, _ = _chunked(cfg, mesh, hx, params, toks, chunk)
        q2 = quantize_decode_state(st2)
        assert tok2 == tok1
        for key in ("kcache", "vcache"):
            diff = np.abs(np.asarray(q2[key], np.int32)
                          - np.asarray(q1[key], np.int32))
            assert diff.max() <= 1, (key, diff.max())
        for key in ("kscale", "vscale"):
            np.testing.assert_allclose(np.asarray(q2[key]),
                                       np.asarray(q1[key]), err_msg=key,
                                       **CACHE_TOL)
    dec1, _ = _decode_n(cfg, mesh, hx, params, q1, tok1)
    dec2, _ = _decode_n(cfg, mesh, hx, params, q2, tok2)
    assert dec1 == dec2


def test_ragged_seq_lens_packing_matches_single():
    """Packed ragged chunk calls (per-request seq_lens) reproduce each
    request's solo prefill bit for bit on the valid rows: the seq_lens mask
    only ever affects pad rows for causal self-attention."""
    from repro.models.attention import prefill_attention
    ks = jax.random.split(jax.random.PRNGKey(3), 3)
    q = jax.random.normal(ks[0], (2, 8, 4, 16))
    k = jax.random.normal(ks[1], (2, 8, 2, 16))
    v = jax.random.normal(ks[2], (2, 8, 2, 16))
    lens = jnp.asarray([8, 5], jnp.int32)
    for backend in ("ref", "pallas-interpret"):
        packed = prefill_attention(q, k, v, causal=True, backend=backend,
                                   seq_lens=lens)
        solo0 = prefill_attention(q[:1], k[:1], v[:1], causal=True,
                                  backend=backend)
        np.testing.assert_array_equal(np.asarray(packed[0]),
                                      np.asarray(solo0[0]))
        # row 1: valid query rows [0, 5) match its solo run over its own
        # 5-long kv prefix padded into the same S=8 operand
        k1 = k.at[1, 5:].set(0.0)[1:]
        v1 = v.at[1, 5:].set(0.0)[1:]
        solo1 = prefill_attention(q[1:], k1, v1, causal=True,
                                  backend=backend)
        np.testing.assert_array_equal(np.asarray(packed[1, :5]),
                                      np.asarray(solo1[0, :5]))


def test_unsupported_archs_fall_back():
    """Non-attention-only archs refuse the chunked builders (the engine
    falls back to one-shot prefill for them)."""
    ssm = get_config("mamba2-780m").reduced()
    assert not chunked_prefill_supported(ssm)
    with pytest.raises(AssertionError):
        make_chunk_prefill_step(ssm, None, HelixConfig(kvp_axes=()))
    assert chunked_prefill_supported(_cfg(False))
