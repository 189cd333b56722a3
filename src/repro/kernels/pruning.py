"""Shared block-pruning helper for the Pallas attention kernels.

Both flash_decode and flash_prefill prune by clamping their K/V
``index_map``s to a per-request/per-row valid block span ``[lo, lo + nb)``
(see ``flash_decode.kernel.prune_block_range`` /
``flash_prefill.kernel.prefill_block_range``).  The clamp rule lives here
once because the DMA-elision correctness depends on it: a pruned grid step
must reference the *same* physical block as the previous step, so Pallas
TPU skips the HBM->VMEM copy instead of re-fetching a dead block.

Index_map purity requirement
----------------------------
Every ``index_map`` built on these helpers MUST be a *pure jnp function* of
the grid coordinates and the scalar-prefetch operands: no data-dependent
python branching (``if traced_value:``), no host lookups, no side effects.
Pallas requires this to trace the maps once at lowering time, and the
static auditor (``repro.analysis.index_audit``) relies on the same property
to host-evaluate the maps over every grid step with ``jax.vmap`` — a map
that branched in python on a traced scalar would either fail to trace or,
worse, be audited along a different path than the one the kernel runs.
Static *configuration* branches (``if paged:`` on a python bool closed over
at build time) are fine; branches on prefetched values must be expressed
with ``jnp.where``/``jnp.clip`` as below.
"""
from __future__ import annotations

import jax.numpy as jnp


def span_clamp(step, lo, nb, n_blocks: int):
    """Clamp grid step ``step`` into the valid span ``[lo, lo + nb)`` and
    the array bounds ``[0, n_blocks)``.

    The one in-bounds clamp shared through ``phys_block`` by every pruned
    fixed-layout kernel index_map, and replayed by the static
    auditor: ``lo + step`` while inside the span, then pinned to the span's
    last block — the same block as the previous step, so Pallas elides the
    HBM->VMEM copy.  Total (never out of ``[0, n_blocks)``) even for empty
    spans (``nb == 0``).  All of ``step``/``lo``/``nb`` may be traced
    scalars (this runs inside Pallas index_maps); the math is pure jnp per
    the module-level purity requirement.
    """
    last = jnp.maximum(lo + nb - 1, lo)
    return jnp.clip(jnp.minimum(lo + step, last), 0, n_blocks - 1)


def phys_block(step, lo, nb, n_blocks: int):
    """Physical block streamed at grid step ``step``: ``lo + step`` while
    inside the valid span, then clamped to the span's last block (same
    block as the previous step => the copy is elided).  ``lo``/``nb`` may
    be traced scalars; always in ``[0, n_blocks)`` even for empty spans.
    Alias of ``span_clamp`` — the fixed-layout kernels address physical
    blocks directly."""
    return span_clamp(step, lo, nb, n_blocks)

