"""Kernel contract descriptions for the static index-space auditor.

A *contract* is a host-side, declarative mirror of one ``pallas_call``: the
grid, the per-operand block shapes and ``index_map`` callables, the scalar
prefetch operands the maps close over, and the aliasing structure.  Each
kernel family exposes a ``contract()`` hook (see ``registry.FAMILIES``) that
returns the contracts for a lattice of configurations; ``repro.analysis``
enumerates every grid step of every contract and host-evaluates the
index_maps to prove in-bounds access, the DMA-elision invariant of pruned
steps, and alias-race freedom of the fused-append row windows.

The contract must reference the *same* index_map callables the kernel passes
to ``pallas_call`` (the families share them via module-level builders such as
``flash_decode.kernel.decode_index_maps``) — auditing a copy would prove
nothing.  Index_maps must be pure jnp functions of the grid coordinates and
prefetched scalars, with no data-dependent python branching; see
``kernels/pruning.py`` for the purity requirement this relies on.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable, Sequence


@dataclasses.dataclass
class Operand:
    """One ``pallas_call`` operand: a (padded) array, its BlockSpec block
    shape, and the index_map that addresses blocks of it per grid step.

    ``index_map`` receives ``(*grid_coords, *prefetch)`` — grid coordinates
    first, then the scalar-prefetch operands in declaration order — and
    returns a tuple of *block* indices (one per array axis; window axes
    return 0).  ``streamed`` marks HBM->VMEM streamed operands (subject to
    the DMA-elision check); ``alias_of`` names the input operand an output
    writes through (``input_output_aliases``); ``paged_axis`` is the array
    axis addressed through a block-table indirection, whose bounds
    violations are reported as ``bounds.page`` rather than ``bounds.block``;
    ``plane`` names the array several operands read (the paged decode
    kernel passes each pool plane once per page slot), so an aliased write
    is checked against every slot's reads of that plane.
    """

    name: str
    shape: tuple
    block: tuple
    index_map: Callable
    kind: str = "in"            # "in" | "out"
    streamed: bool = False
    alias_of: str | None = None
    paged_axis: int | None = None
    plane: str | None = None

    def grid_limits(self):
        """Number of valid blocks per array axis (ceil-div shape/block)."""
        return tuple(-(-s // b) for s, b in zip(self.shape, self.block))


@dataclasses.dataclass
class KernelContract:
    """Declarative mirror of one ``pallas_call`` configuration.

    ``prefetch`` holds the scalar-prefetch arrays (in declaration order)
    that every index_map closes over.  ``stream_axis`` is the innermost
    grid axis that streams blocks (None when no axis streams).  ``active``,
    when set, maps grid coordinates to a bool — False marks pruned steps
    whose streamed index_maps must repeat the previous step's block (DMA
    elision).  ``expected_row`` maps the non-stream grid coordinates to the
    block-index tuple a fused-append row window must address, letting the
    auditor cross-validate the row index_map against the in-kernel VMEM
    substitution.  ``table``/``n_pool`` describe the paged block table.
    """

    family: str
    case: str
    grid: tuple
    operands: list
    prefetch: tuple = ()
    stream_axis: int | None = None
    aliases: dict = dataclasses.field(default_factory=dict)
    active: Callable | None = None
    expected_row: Callable | None = None
    table: Any = None
    n_pool: int | None = None
    notes: dict = dataclasses.field(default_factory=dict)

    def describe(self) -> str:
        """One-line human summary (family, case, grid, operand count)."""
        return (f"{self.family}[{self.case}] grid={self.grid} "
                f"ops={len(self.operands)} aliases={len(self.aliases)}")


def operands_by_name(contract: KernelContract) -> dict:
    """Name -> Operand lookup for one contract."""
    return {op.name: op for op in contract.operands}
