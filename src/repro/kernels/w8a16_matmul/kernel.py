"""Pallas TPU w8a16 matmul: int8 weights dequantized on-the-fly in VMEM.

Beyond-paper optimization for the decode FFN weight-read bottleneck
(§Roofline memory term): weight bytes halve vs bf16 while the MXU still
computes in bf16/f32.  Per-output-channel scales are folded in at the end.

TPU mapping
-----------
  grid = (M/bm, N/bn, K/bk)   — K innermost; f32 accumulator in VMEM scratch
  x block  (bm, bk) bf16      streamed
  w block  (bk, bn) int8      streamed (half the HBM bytes of bf16)
  scale    (1, bn)  f32       resident per N block
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def w8a16_index_maps():
    """Named index_map callables for the w8a16 matmul kernel.

    The single source of truth for the kernel's block addressing:
    ``w8a16_matmul_kernel`` passes exactly these callables to
    ``pallas_call``, and ``ops.w8a16_matmul_contract`` exposes them to the
    static index-space auditor (``repro.analysis``).  All maps are static
    functions of the grid coordinates ``(i, j, ki)``.  Keys:

      x      activation blocks (bm, bk), streamed along K
      w      int8 weight blocks (bk, bn), streamed along K
      scale  per-N-block dequant scales (1, bn), resident along K
      out    output blocks (bm, bn)
    """
    return {
        "x": lambda i, j, ki: (i, ki),
        "w": lambda i, j, ki: (ki, j),
        "scale": lambda i, j, ki: (0, j),
        "out": lambda i, j, ki: (i, j),
    }


def _w8a16_kernel(x_ref, w_ref, s_ref, o_ref, acc_ref, *, bm, bn, bk):
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    x = x_ref[...].astype(jnp.float32)              # [bm, bk]
    w = w_ref[...].astype(jnp.float32)              # [bk, bn] dequant int8
    acc_ref[...] += jax.lax.dot_general(
        x, w, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)

    @pl.when(ki == pl.num_programs(2) - 1)
    def _finalize():
        o_ref[...] = (acc_ref[...] * s_ref[...]).astype(o_ref.dtype)


def w8a16_matmul_kernel(x, qw, scale, *, bm, bn, bk, interpret: bool):
    """x [M, K]; qw [K, N] int8; scale [1, N] f32 -> [M, N] (x.dtype).

    M % bm == K % bk == N % bn == 0 (ops.py pads).
    """
    m, k = x.shape
    n = qw.shape[1]
    grid = (m // bm, n // bn, k // bk)
    kernel = functools.partial(_w8a16_kernel, bm=bm, bn=bn, bk=bk)
    idx = w8a16_index_maps()
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bk), idx["x"]),
            pl.BlockSpec((bk, bn), idx["w"]),
            pl.BlockSpec((1, bn), idx["scale"]),
        ],
        out_specs=pl.BlockSpec((bm, bn), idx["out"]),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        out_shape=jax.ShapeDtypeStruct((m, n), x.dtype),
        interpret=interpret,
    )(x, qw, scale)
