"""Pallas TPU SSD-prefill kernel: Mamba2 chunked state-space scan.

TPU mapping
-----------
  grid = (B, nh, T/Lc)  — chunks innermost; the running state [hd, ds] lives
                          in VMEM scratch, carried across chunk iterations
                          (sequential TPU grid), so HBM traffic is O(T) not
                          O(T·ds).
  per chunk (Lc tokens): the SSD block-matrix form —
    intra:  Y += (tril(C Bᵀ ∘ decay) · diag(dt)) X          (two MXU matmuls)
    inter:  Y += (C · h_in) ∘ exp(cum)
    state:  h_out = exp(cum_last) h_in + Σ_j exp(cum_last-cum_j) dt_j B_j⊗X_j

  Lc and hd/ds are chosen MXU-friendly (Lc=64/128, hd=64, ds=64/128).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def ssd_index_maps():
    """Named index_map callables for the SSD-prefill kernel.

    The single source of truth for the kernel's block addressing:
    ``ssd_prefill_kernel`` passes exactly these callables to
    ``pallas_call``, and ``ops.ssd_prefill_contract`` exposes them to the
    static index-space auditor (``repro.analysis``).  All maps are static
    functions of the grid coordinates ``(b, h, c)`` — the SSD scan
    prefetches no scalars.  Keys:

      chunk  token-chunk streams (x, dt, B, C, y) — block (1, 1, lc, ·)
      head   per-head constants (a, d) — block (1, 1)
      state  chunk-carry state (h0, h_out) — resident along the chunk axis
    """
    return {
        "chunk": lambda b, h, c: (b, h, c, 0),
        "head": lambda b, h, c: (h, 0),
        "state": lambda b, h, c: (b, h, 0, 0),
    }


def _ssd_kernel(x_ref, dt_ref, a_ref, b_ref, c_ref, d_ref, h0_ref, y_ref,
                hout_ref, h_ref, *, lc: int, hd: int, ds: int):
    ci = pl.program_id(2)

    @pl.when(ci == 0)
    def _init():
        # chunk-carry state seeded from the caller's initial state (prefill
        # continuation / engine re-prefill); zeros for a fresh sequence.
        h_ref[...] = h0_ref[0, 0].astype(jnp.float32)

    x = x_ref[0, 0].astype(jnp.float32)                  # [lc, hd]
    dt = dt_ref[0, 0].astype(jnp.float32)                # [lc, 1]
    a = a_ref[0]                                         # [1] f32
    bm = b_ref[0, 0].astype(jnp.float32)                 # [lc, ds]
    cm = c_ref[0, 0].astype(jnp.float32)                 # [lc, ds]
    dskip = d_ref[0]                                     # [1]

    dta = dt[:, 0] * a[0]                                # [lc]
    cum = jnp.cumsum(dta)                                # [lc]

    # intra-chunk: w[i,j] = (C_i·B_j) exp(cum_i - cum_j) dt_j  (i >= j)
    cb = jax.lax.dot_general(cm, bm, (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32)  # [lc, lc]
    decay = jnp.exp(cum[:, None] - cum[None, :])
    tri = jax.lax.broadcasted_iota(jnp.int32, (lc, lc), 0) >= \
        jax.lax.broadcasted_iota(jnp.int32, (lc, lc), 1)
    w = jnp.where(tri, cb * decay, 0.0) * dt[:, 0][None, :]
    y = jax.lax.dot_general(w, x, (((1,), (0,)), ((), ())),
                            preferred_element_type=jnp.float32)  # [lc, hd]

    # inter-chunk: y += exp(cum_i) * C_i · h_in
    h_in = h_ref[...]                                    # [hd, ds]
    y = y + jnp.exp(cum)[:, None] * jax.lax.dot_general(
        cm, h_in, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)

    y_ref[0, 0] = (y + dskip[0] * x).astype(y_ref.dtype)

    # state update: h_out = exp(cum_last) h_in + sum_j seg_j dt_j x_j ⊗ B_j
    seg = jnp.exp(cum[-1] - cum) * dt[:, 0]              # [lc]
    dbx = jax.lax.dot_general(x * seg[:, None], bm,
                              (((0,), (0,)), ((), ())),
                              preferred_element_type=jnp.float32)  # [hd, ds]
    h_ref[...] = jnp.exp(cum[-1]) * h_in + dbx

    @pl.when(ci == pl.num_programs(2) - 1)
    def _emit_state():
        hout_ref[0, 0] = h_ref[...]


def ssd_prefill_kernel(x, dt, a, bmat, cmat, d, h0, *, lc: int,
                       interpret: bool):
    """Pre-blocked shapes: x [B, nh, T, hd]; dt [B, nh, T, 1];
    a, d [nh, 1] f32; bmat, cmat [B, nh, T, ds]; h0 [B, nh, hd, ds] f32
    initial state.  T % lc == 0.

    Returns (y [B, nh, T, hd] f32, h_final [B, nh, hd, ds] f32).
    """
    b, nh, t, hd = x.shape
    ds = bmat.shape[-1]
    assert t % lc == 0
    grid = (b, nh, t // lc)
    kernel = functools.partial(_ssd_kernel, lc=lc, hd=hd, ds=ds)
    idx = ssd_index_maps()
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, lc, hd), idx["chunk"]),
            pl.BlockSpec((1, 1, lc, 1), idx["chunk"]),
            pl.BlockSpec((1, 1), idx["head"]),
            pl.BlockSpec((1, 1, lc, ds), idx["chunk"]),
            pl.BlockSpec((1, 1, lc, ds), idx["chunk"]),
            pl.BlockSpec((1, 1), idx["head"]),
            pl.BlockSpec((1, 1, hd, ds), idx["state"]),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, lc, hd), idx["chunk"]),
            pl.BlockSpec((1, 1, hd, ds), idx["state"]),
        ],
        scratch_shapes=[pltpu.VMEM((hd, ds), jnp.float32)],
        out_shape=[
            jax.ShapeDtypeStruct((b, nh, t, hd), jnp.float32),
            jax.ShapeDtypeStruct((b, nh, hd, ds), jnp.float32),
        ],
        interpret=interpret,
    )(x, dt, a, bmat, cmat, d, h0)
