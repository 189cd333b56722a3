"""Index-space auditor: host-evaluates kernel index_maps over the full grid.

For every ``KernelContract`` a family exposes (``registry.contract_suite``),
the auditor enumerates the grid and evaluates each operand's *real*
index_map callable (the one ``pallas_call`` runs) with ``jax.vmap`` over the
stacked grid coordinates, then checks three properties on the resulting
block-index table:

  bounds    every returned block index lies in ``[0, ceil(shape/block))``
            per axis — ``bounds.page`` for the table-indirected pool axis
            (an out-of-range page id reads foreign memory), ``bounds.block``
            elsewhere.  Paged contracts additionally get every table entry
            range-checked against the pool and cross-request page overlap
            checked (two requests sharing a non-sink page is a write race
            waiting to happen).
  dma.elision  for streamed operands of pruned contracts: every grid step
            the contract's ``active`` predicate marks pruned must address
            the *same* block as the previous step along the stream axis —
            that identity is what lets Pallas TPU elide the HBM->VMEM DMA,
            so a violation silently re-streams dead blocks.
  alias.race   fused-append aliased output windows must (a) stay fixed
            across stream steps (they are rewritten idempotently), (b) be
            pairwise disjoint across grid groups (one writer per window),
            (c) address exactly the row the in-kernel VMEM substitution
            targets (``KernelContract.expected_row``), and (d) overlap a
            same-step streamed K/V read only at that expected row.

All checks are exhaustive over the contract's toy grid — no sampling — and
rely on the index_map purity requirement documented in
``kernels/pruning.py``.
"""
from __future__ import annotations

import itertools

import jax
import jax.numpy as jnp
import numpy as np

from repro.analysis.findings import Finding, Report
from repro.kernels import registry
from repro.kernels.contract import KernelContract, Operand

# findings location convention for kernel contracts: the family's ops
# module, symbol "<family>[<case>]/<operand>"
_FAMILY_PATHS = {
    "flash_decode": "src/repro/kernels/flash_decode/kernel.py",
    "flash_prefill": "src/repro/kernels/flash_prefill/kernel.py",
    "ssd_prefill": "src/repro/kernels/ssd_prefill/kernel.py",
    "w8a16_matmul": "src/repro/kernels/w8a16_matmul/kernel.py",
}

_MAX_DETAIL = 3     # grid steps quoted per finding message


def _symbol(contract: KernelContract, op_name: str | None = None) -> str:
    base = f"{contract.family}[{contract.case}]"
    return f"{base}/{op_name}" if op_name else base


def _path(contract: KernelContract) -> str:
    return _FAMILY_PATHS.get(contract.family, contract.family)


def eval_index_table(contract: KernelContract, op: Operand) -> np.ndarray:
    """Evaluate ``op.index_map`` at every grid step.

    Returns an int array of shape ``grid + (ndim,)`` — the block-index
    tuple per grid coordinate.  One vmapped evaluation over the stacked
    coordinates; the prefetch operands are closed over as whole arrays
    (a contract index_map indexes them exactly like the Pallas scalar-
    prefetch refs).
    """
    grid = contract.grid
    coords = np.stack(np.meshgrid(*[np.arange(n) for n in grid],
                                  indexing="ij"), axis=-1)
    flat = coords.reshape(-1, len(grid)).astype(np.int32)
    prefetch = tuple(jnp.asarray(p) for p in contract.prefetch)

    def one(c):
        idx = op.index_map(*[c[i] for i in range(len(grid))], *prefetch)
        return jnp.stack([jnp.asarray(v, jnp.int32) for v in idx])

    table = np.asarray(jax.vmap(one)(jnp.asarray(flat)))
    return table.reshape(grid + (table.shape[-1],))


def _fmt_steps(steps) -> str:
    head = [tuple(int(x) for x in s) for s in steps[:_MAX_DETAIL]]
    more = f" (+{len(steps) - _MAX_DETAIL} more)" \
        if len(steps) > _MAX_DETAIL else ""
    return f"{head}{more}"


def _check_bounds(contract, op, table) -> list[Finding]:
    limits = op.grid_limits()
    findings = []
    for axis, lim in enumerate(limits):
        bad = np.argwhere((table[..., axis] < 0) | (table[..., axis] >= lim))
        if bad.size:
            check = ("bounds.page" if axis == op.paged_axis
                     else "bounds.block")
            what = ("pool page id" if axis == op.paged_axis
                    else f"axis-{axis} block index")
            vals = table[..., axis][tuple(bad[:_MAX_DETAIL].T)]
            findings.append(Finding(
                check=check, path=_path(contract),
                symbol=_symbol(contract, op.name),
                message=f"{what} out of [0, {lim}) at grid steps "
                        f"{_fmt_steps(bad)} -> {vals.tolist()}"))
    return findings


def _check_table(contract) -> list[Finding]:
    """Paged block-table sanity: pool-range + cross-request overlap.

    A non-sink page mapped by two request rows is an alias race by default.
    Contracts carrying the ``shared_ok`` note (refcounted prefix sharing
    with copy-on-write — serving/pool.py) may share *read-only* pages
    across rows; pages a fused-append row window writes
    (``contract.expected_row``) must stay exclusive even then, since the
    engine's CoW guard guarantees an appended page has refcount 1.
    """
    findings = []
    table = np.asarray(contract.table)
    n_pool = contract.n_pool
    bad = np.argwhere((table < 0) | (table >= n_pool))
    if bad.size:
        findings.append(Finding(
            check="bounds.page", path=_path(contract),
            symbol=_symbol(contract, "block_table"),
            message=f"table entries outside pool [0, {n_pool}) at "
                    f"{_fmt_steps(bad)} -> "
                    f"{table[tuple(bad[:_MAX_DETAIL].T)].tolist()}"))
        return findings
    shared_ok = bool(contract.notes.get("shared_ok"))
    write_pages: set[int] = set()
    if shared_ok and contract.expected_row is not None:
        kh = contract.grid[1] if len(contract.grid) > 1 else 1
        for bi in range(table.shape[0]):
            for h in range(kh):
                write_pages.add(int(contract.expected_row(bi, h)[0]))
    seen: dict[int, int] = {}
    for b in range(table.shape[0]):
        for p in table[b]:
            p = int(p)
            if p == 0:
                continue        # shared sink page: duplicates intended
            if p in seen and seen[p] != b:
                if shared_ok and p not in write_pages:
                    continue    # read-only refcounted prefix page
                what = ("append-target page shared across requests"
                        if shared_ok else "shared writable page")
                findings.append(Finding(
                    check="alias.race", path=_path(contract),
                    symbol=_symbol(contract, "block_table"),
                    message=f"non-sink pool page {p} mapped by requests "
                            f"{seen[p]} and {b} — {what}"))
            seen[p] = b
    return findings


def _stream_groups(grid, stream_axis):
    """Iterate (group_coords, slicer) pairs — all grid points that differ
    only in the stream coordinate."""
    other = [i for i in range(len(grid)) if i != stream_axis]
    for combo in itertools.product(*[range(grid[i]) for i in other]):
        full = [slice(None)] * len(grid)
        coords = {}
        for i, c in zip(other, combo):
            full[i] = c
            coords[i] = c
        yield coords, tuple(full)


def _grid_coords(group, stream_axis, s, ndim):
    out = [0] * ndim
    for i, c in group.items():
        out[i] = c
    out[stream_axis] = s
    return tuple(out)


def _check_elision(contract, op, table) -> list[Finding]:
    """Pruned steps must re-address the previous step's block."""
    ax = contract.stream_axis
    n_steps = contract.grid[ax]
    bad = []
    for group, slicer in _stream_groups(contract.grid, ax):
        rows = table[slicer]                       # [n_steps, ndim]
        for s in range(1, n_steps):
            c = _grid_coords(group, ax, s, len(contract.grid))
            if contract.active(*c):
                continue
            if not np.array_equal(rows[s], rows[s - 1]):
                bad.append((c, rows[s - 1].tolist(), rows[s].tolist()))
    if bad:
        steps = [c for c, _, _ in bad]
        was, now = bad[0][1], bad[0][2]
        return [Finding(
            check="dma.elision", path=_path(contract),
            symbol=_symbol(contract, op.name),
            message=f"pruned grid steps fetch a new block (DMA not "
                    f"elided) at {_fmt_steps(steps)}: step block {now} "
                    f"!= previous {was}")]
    return []


def _windows_overlap(idx_a, block_a, idx_b, block_b) -> bool:
    """Element-range intersection of two block windows of one array."""
    for ia, ba, ib, bb in zip(idx_a, block_a, idx_b, block_b):
        lo_a, hi_a = ia * ba, (ia + 1) * ba
        lo_b, hi_b = ib * bb, (ib + 1) * bb
        if hi_a <= lo_b or hi_b <= lo_a:
            return False
    return True


def _check_alias_races(contract, ops_by_name, tables) -> list[Finding]:
    """Fused-append aliased output windows: fixed, unique, expected,
    and disjoint from same-step streamed reads except at the target row."""
    findings = []
    ax = contract.stream_axis
    ndim = len(contract.grid)
    out_aliased = [op for op in contract.operands
                   if op.kind == "out" and op.alias_of]
    for op in out_aliased:
        table = tables[op.name]
        # (a) constant along the stream axis (idempotent rewrite)
        moved = []
        groups = {}
        for group, slicer in _stream_groups(contract.grid, ax):
            rows = table[slicer]
            if not (rows == rows[0]).all():
                moved.append(_grid_coords(group, ax, 0, ndim))
            groups[tuple(sorted(group.items()))] = rows[0]
        if moved:
            findings.append(Finding(
                check="alias.race", path=_path(contract),
                symbol=_symbol(contract, op.name),
                message=f"aliased output window moves across stream steps "
                        f"for groups {_fmt_steps(moved)} — the idempotent "
                        f"rewrite would scatter"))
        # (b) one writer per window across groups
        seen = {}
        for key, row in groups.items():
            t = tuple(int(x) for x in row)
            if t in seen and seen[t] != key:
                findings.append(Finding(
                    check="alias.race", path=_path(contract),
                    symbol=_symbol(contract, op.name),
                    message=f"two grid groups {dict(seen[t])} and "
                            f"{dict(key)} write the same window {t}"))
                break
            seen[t] = key
        # (c) window == the row the in-kernel VMEM substitution targets
        wrong = []
        if contract.expected_row is not None:
            for group, slicer in _stream_groups(contract.grid, ax):
                got = tuple(int(x) for x in table[slicer][0])
                bi = group.get(0, 0)
                h = group.get(1, 0)
                want = tuple(contract.expected_row(bi, h))[:len(got)]
                if got != want:
                    wrong.append((bi, h, got, want))
            if wrong:
                bi, h, got, want = wrong[0]
                findings.append(Finding(
                    check="alias.race", path=_path(contract),
                    symbol=_symbol(contract, op.name),
                    message=f"aliased window diverges from the in-kernel "
                            f"append slot: (b={bi}, h={h}) writes {got}, "
                            f"VMEM substitution targets {want} "
                            f"(+{len(wrong) - 1} more)"))
        # (d) overlap with a same-step streamed read of the aliased buffer
        # only at the expected row (the substituted one) — anywhere else
        # the write clobbers K/V data the attention still reads
        src = ops_by_name.get(op.alias_of)
        if src is None or contract.expected_row is None or wrong:
            continue
        # every streamed read of the aliased array: the source operand, or
        # all page slots reading its plane
        reads = [o for o in contract.operands
                 if o.name in tables and (o is src or (
                     src.plane is not None and o.plane == src.plane))]
        clashes = []
        for group, slicer in _stream_groups(contract.grid, ax):
            wrow = tuple(int(x) for x in tables[op.name][slicer][0])
            bi, h = group.get(0, 0), group.get(1, 0)
            want = tuple(contract.expected_row(bi, h))[:len(wrow)]
            if wrow == want:
                continue        # matching windows handled by (c)
            for s in range(contract.grid[ax]):
                if any(_windows_overlap(
                        wrow, op.block,
                        tuple(int(x) for x in tables[r.name][slicer][s]),
                        r.block) for r in reads):
                    clashes.append(_grid_coords(group, ax, s, ndim))
                    break
        if clashes:
            findings.append(Finding(
                check="alias.race", path=_path(contract),
                symbol=_symbol(contract, op.name),
                message=f"aliased write window overlaps same-step "
                        f"{src.plane or src.name} reads away from the "
                        f"append row at {_fmt_steps(clashes)}"))
    return findings


def audit_contract(contract: KernelContract) -> list[Finding]:
    """Run every index-space check over one contract; returns findings."""
    findings = []
    tables = {}
    for op in contract.operands:
        try:
            table = eval_index_table(contract, op)
        except Exception as e:                    # impure / broken map
            findings.append(Finding(
                check="bounds.block", path=_path(contract),
                symbol=_symbol(contract, op.name),
                message=f"index_map failed host evaluation (purity "
                        f"violation? see kernels/pruning.py): {e!r}"))
            continue
        tables[op.name] = table
        findings.extend(_check_bounds(contract, op, table))
        if (op.streamed and contract.active is not None
                and contract.stream_axis is not None):
            findings.extend(_check_elision(contract, op, table))
    if contract.table is not None:
        findings.extend(_check_table(contract))
    ops_by_name = {op.name: op for op in contract.operands}
    if contract.stream_axis is not None:
        findings.extend(
            _check_alias_races(contract, ops_by_name, tables))
    return findings


def run_index_audit(report: Report, families=None) -> None:
    """Audit every registered family's contract suite into ``report``.

    A family without a contract hook becomes a ``contract.missing`` error —
    loud, not skipped (the ``--strict`` CI contract).
    """
    for name in (families or sorted(registry.FAMILIES)):
        fam = registry.FAMILIES[name]
        if fam.contract is None:
            report.add(Finding(
                check="contract.missing",
                path="src/repro/kernels/registry.py",
                symbol=name,
                message=f"kernel family {name!r} registers no analysis "
                        f"contract hook; add <family>_contract() to its "
                        f"ops module (docs/analysis.md)"))
            continue
        for contract in registry.contract_suite(name):
            report.extend(audit_contract(contract))
    report.mark_run("index")
