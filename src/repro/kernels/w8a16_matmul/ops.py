"""jit'd public wrapper for w8a16_matmul with shape padding."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels.w8a16_matmul.kernel import w8a16_matmul_kernel
from repro.kernels.w8a16_matmul.ref import quantize_w8  # noqa: F401
from repro.utils import round_up


@functools.partial(jax.jit, static_argnames=("bm", "bn", "bk", "interpret"))
def w8a16_matmul(x, qw, scale, *, bm: int = 128, bn: int = 128, bk: int = 256,
                 interpret: bool):
    """int8-weight x bf16/f32-activation matmul via the Pallas kernel.

    The w8a16_matmul *family* entry point the kernel-backend registry
    routes to (``HelixConfig.matmul_backend``).  Weights are dequantized
    tile-by-tile in VMEM (per-output-column scales); shapes are padded to
    the block sizes and sliced back.

      x [M, K] bf16/f32; qw [K, N] int8; scale [N] f32 -> out [M, N].
    """
    m, k = x.shape
    n = qw.shape[1]
    bm = min(bm, round_up(m, 8))
    bn = min(bn, round_up(n, 128))
    bk = min(bk, round_up(k, 128))
    mp, kp, np_ = round_up(m, bm), round_up(k, bk), round_up(n, bn)
    xp = jnp.pad(x, ((0, mp - m), (0, kp - k)))
    qwp = jnp.pad(qw, ((0, kp - k), (0, np_ - n)))
    sp = jnp.pad(scale, (0, np_ - n))[None, :]
    out = w8a16_matmul_kernel(xp, qwp, sp, bm=bm, bn=bn, bk=bk,
                              interpret=interpret)
    return out[:m, :n]

# --- static-analysis contract -------------------------------------------

from repro.kernels.contract import KernelContract, Operand  # noqa: E402
from repro.kernels.w8a16_matmul.kernel import w8a16_index_maps  # noqa: E402


def w8a16_matmul_contract():
    """Contracts for the w8a16_matmul audit lattice (``repro.analysis``).

    No scalar prefetch or aliasing — the contract pins the static (i, j,
    ki) block addressing (``kernel.w8a16_index_maps``, the same callables
    ``w8a16_matmul_kernel`` uses) over a square and a rectangular blocked
    geometry so the auditor proves every streamed X/W tile and the
    resident scale/out tiles stay in bounds.
    """
    contracts = []
    for case, (m, n, k, bm, bn, bk) in (
            ("square", (8, 8, 8, 4, 4, 4)),
            ("rect", (8, 16, 12, 4, 8, 4))):
        idx = w8a16_index_maps()
        operands = [
            Operand("x", (m, k), (bm, bk), idx["x"], streamed=True),
            Operand("qw", (k, n), (bk, bn), idx["w"], streamed=True),
            Operand("scale", (1, n), (1, bn), idx["scale"]),
            Operand("out", (m, n), (bm, bn), idx["out"], kind="out"),
        ]
        contracts.append(KernelContract(
            family="w8a16_matmul", case=case,
            grid=(m // bm, n // bn, k // bk), operands=operands,
            stream_axis=2, notes=dict(bm=bm, bn=bn, bk=bk)))
    return contracts
