"""jit'd public wrapper for ssd_prefill: natural layouts + group expansion."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels.ssd_prefill.kernel import ssd_prefill_kernel
from repro.utils import round_up


@functools.partial(jax.jit, static_argnames=("lc", "interpret"))
def ssd_prefill(x, dt, a, bmat, cmat, d, *, h0=None, lc: int = 64,
                interpret: bool):
    """Mamba2 SSD prefill scan core via the Pallas kernel.

    The kernel-backed sibling of the ``models/ssm.ssd_chunked`` scan core —
    this is the ssd_prefill *family* entry point the kernel-backend registry
    routes to (``HelixConfig.ssd_backend``).  Natural shapes (matching
    ``ssd_prefill_ref``):

    Args:
      x: ``[B, T, nh, hd]`` inputs (post conv + silu).
      dt: ``[B, T, nh]`` softplus'd timestep.
      a: ``[nh]`` negative decay rate (``A = -exp(A_log)``).
      bmat, cmat: ``[B, T, nh, ds]`` in/out projections (group-expanded).
      d: ``[nh]`` skip.
      h0: optional ``[B, nh, hd, ds]`` initial state (prefill continuation);
        ``None`` = zeros.
      lc: chunk length (static; MXU-friendly 64/128).
      interpret: Pallas interpreter (any backend) vs compiled TPU kernel.

    Returns:
      ``(y [B, T, nh, hd] f32, h_final [B, nh, hd, ds] f32)``.
    """
    b, t, nh, hd = x.shape
    ds = bmat.shape[-1]
    lc = min(lc, round_up(t, 8))
    t_pad = round_up(t, lc)
    pad = ((0, 0), (0, t_pad - t), (0, 0), (0, 0))
    # pad timesteps with dt=0 => da=1, no state contribution; y rows sliced
    xb = jnp.pad(x, pad).transpose(0, 2, 1, 3)
    dtb = jnp.pad(dt, pad[:3]).transpose(0, 2, 1)[..., None]
    bb = jnp.pad(bmat, pad).transpose(0, 2, 1, 3)
    cb = jnp.pad(cmat, pad).transpose(0, 2, 1, 3)
    if h0 is None:
        h0 = jnp.zeros((b, nh, hd, ds), jnp.float32)
    y, h = ssd_prefill_kernel(
        xb, dtb, a.astype(jnp.float32)[:, None],
        bb, cb, d.astype(jnp.float32)[:, None], h0.astype(jnp.float32),
        lc=lc, interpret=interpret)
    return y.transpose(0, 2, 1, 3)[:, :t], h

# --- static-analysis contract -------------------------------------------

from repro.kernels.contract import KernelContract, Operand  # noqa: E402
from repro.kernels.ssd_prefill.kernel import ssd_index_maps  # noqa: E402


def ssd_prefill_contract():
    """Contracts for the ssd_prefill audit lattice (``repro.analysis``).

    The SSD scan has no scalar prefetch, pruning, or aliasing — the
    contract pins the static chunk/head/state block addressing
    (``kernel.ssd_index_maps``, the same callables ``ssd_prefill_kernel``
    passes to ``pallas_call``) over a small chunked and a single-chunk
    geometry so the auditor proves in-bounds access and that the
    chunk-carry state stays resident along the scan axis.
    """
    contracts = []
    for case, (b, nh, t, hd, ds, lc) in (
            ("chunked", (2, 2, 8, 8, 8, 4)),
            ("one-chunk", (1, 2, 4, 8, 8, 4))):
        idx = ssd_index_maps()
        operands = [
            Operand("x", (b, nh, t, hd), (1, 1, lc, hd), idx["chunk"],
                    streamed=True),
            Operand("dt", (b, nh, t, 1), (1, 1, lc, 1), idx["chunk"],
                    streamed=True),
            Operand("a", (nh, 1), (1, 1), idx["head"]),
            Operand("bmat", (b, nh, t, ds), (1, 1, lc, ds), idx["chunk"],
                    streamed=True),
            Operand("cmat", (b, nh, t, ds), (1, 1, lc, ds), idx["chunk"],
                    streamed=True),
            Operand("d", (nh, 1), (1, 1), idx["head"]),
            Operand("h0", (b, nh, hd, ds), (1, 1, hd, ds), idx["state"]),
            Operand("y", (b, nh, t, hd), (1, 1, lc, hd), idx["chunk"],
                    kind="out"),
            Operand("h_out", (b, nh, hd, ds), (1, 1, hd, ds), idx["state"],
                    kind="out"),
        ]
        contracts.append(KernelContract(
            family="ssd_prefill", case=case, grid=(b, nh, t // lc),
            operands=operands, stream_axis=2,
            notes=dict(lc=lc, hd=hd, ds=ds)))
    return contracts
