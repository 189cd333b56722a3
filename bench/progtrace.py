"""The program's own spans and scopes in a traced window.

The engine opens ``serve.*`` host spans inside each ``DecodeEngine.step``
and the step programs put their ops under ``jax.named_scope`` names; both
are listed in the program's ``repro/runtime/trace.py`` (``SPANS``,
``SCOPES``).  A program without that module has neither, and every
reading here is then empty.

``load`` reads the run's ``.xplane.pb`` again for what ``devtrace.load``
leaves out: the program's host spans, and each device op's ``op_name``.
A TPU op event carries none; ``xspace.op_names`` reads it from the
compiled HLO the profile keeps for each program, by the instruction's
name in the program whose execution holds the op.  An op's scope is the
innermost program scope in its ``op_name``; an op whose metadata names a
pool argument of the step (``state['kcache']``, ``state['vcache']``)
counts as ``kv_pool``; every other op, with no ``op_name`` or one that
names no scope (XLA's own copies, the layer scan's slicing and
write-back), is ``(unscoped)``.

``attach`` bounds the offset between the device's clock and the host's
from causal pairs, and adds the program's spans, on the device's clock,
to the harness's ``Trace`` so that ``devtrace.breakdown`` names each idle
gap after the innermost program span open in it.  ``Trace.window()``
stays the extent of the harness's own spans.
"""
from __future__ import annotations

import bisect
import dataclasses
import pathlib
import re
import sys
import tempfile

import devtrace
import xspace

UNSCOPED = "(unscoped)"
POOL_ARG = re.compile(r"state\[\\?'[kv]cache\\?'\]")
STEP_PROGRAM = re.compile(r"serve_(step|multistep)")
SHIFT_NS = 50_000        # shift host spans once the offset is surely this


def program_names() -> tuple[tuple[str, ...], tuple[str, ...]]:
    """(span names, scope names) the program declares; empty without
    ``repro.runtime.trace``."""
    try:
        from repro.runtime import trace
    except ImportError:
        return (), ()
    return tuple(trace.SPANS), tuple(trace.SCOPES)


@dataclasses.dataclass
class ProgramTrace:
    # device -> (HLO name, start, end, op_name) of every op event
    ops: dict[str, list[tuple[str, int, int, str]]]
    spans: list[tuple[str, int, int]]        # program spans, host clock
    scopes: tuple[str, ...]
    programs: tuple[str, ...] = ()           # whose HLO gave op_names
    offset_ns: tuple[int, int] | None = None  # device - host clock bounds
    shift_ns: int = 0                        # applied to the spans added


def scope_of(op_name: str, scopes) -> str:
    """The scope an op with ``op_name`` counts in (see the module doc)."""
    for part in reversed(op_name.split("/")):
        if part in scopes:
            return part
    if POOL_ARG.search(op_name):
        return "kv_pool"
    return UNSCOPED


def instruction(name: str) -> str:
    """``%copy.84 = bf16[...] copy(...)`` -> ``copy.84``."""
    return name.split(" = ", 1)[0].lstrip("%")


def name_ops(ops, modules, hlo: dict[str, dict[str, str]]):
    """``ops`` (name, start, end) with the ``op_name`` of their
    instruction in the program whose execution holds their centre."""
    mods = sorted(modules, key=lambda m: m[1])
    starts = [m[1] for m in mods]
    out = []
    for n, s, e in ops:
        k = bisect.bisect_right(starts, (s + e) // 2) - 1
        names = hlo.get(mods[k][0], {}) if k >= 0 and (
            (s + e) // 2 < mods[k][2]) else {}
        out.append((n, s, e, names.get(instruction(n), "")))
    return out


def load(xplane: pathlib.Path, span_names, scopes) -> ProgramTrace:
    """Device ops with their ``op_name`` and the host spans named in
    ``span_names``."""
    trace = devtrace.load(xplane, span_names)
    programs = {n for evs in trace.modules.values() for n, _, _ in evs}
    hlo = xspace.op_names(xplane, programs)
    ops = {d: name_ops(evs, trace.modules.get(d, []), hlo)
           for d, evs in trace.ops.items()}
    return ProgramTrace(ops, trace.spans, tuple(scopes), tuple(sorted(hlo)))


# ------------------------------------------------------------ the clocks
def _children(spans, name: str, lo: int, hi: int):
    return [s for s in spans if s[0] == name and lo <= s[1] and s[2] <= hi]


def clock_offset(pt: ProgramTrace, modules) -> tuple[int, int] | None:
    """Bounds (ns) on the device clock less the host clock: each step
    program's execution starts after its ``serve.dispatch`` span starts
    and ends before its ``serve.readback`` span ends.  An execution's
    span is the ``serve.decode`` it overlaps most, which holds while the
    offset is small against a step.  None without a pair; the bounds
    cross where the pairs disagree."""
    lo, hi = None, None
    execs = [(s, e) for evs in modules.values() for n, s, e in evs
             if STEP_PROGRAM.search(n)]
    for _, s, e in (x for x in pt.spans if x[0] == "serve.decode"):
        disp = _children(pt.spans, "serve.dispatch", s, e)
        back = _children(pt.spans, "serve.readback", s, e)
        if not disp or not back:
            continue
        d0, r1 = disp[0][1], back[-1][2]
        # its execution: the one that overlaps the span most
        x0, x1 = max(execs, default=(0, 0),
                     key=lambda x: min(x[1], r1) - max(x[0], d0))
        if min(x1, r1) <= max(x0, d0):
            continue
        hi = x0 - d0 if hi is None else min(hi, x0 - d0)
        lo = x1 - r1 if lo is None else max(lo, x1 - r1)
    return None if lo is None else (lo, hi)


def attach(trace: devtrace.Trace, pt: ProgramTrace) -> None:
    """Add the program's spans, on the device's clock and clipped to the
    window, to ``trace.spans``; records the clock bounds and the shift
    applied in ``pt``."""
    if not trace.spans or not pt.spans:
        return
    lo, hi = trace.window()
    pt.offset_ns = clock_offset(pt, trace.modules)
    pt.shift_ns = 0
    if pt.offset_ns is not None:
        a, b = pt.offset_ns
        if a <= b and (a > SHIFT_NS or b < -SHIFT_NS):
            pt.shift_ns = (a + b) // 2
    d = pt.shift_ns
    added = [(n, max(s + d, lo), min(e + d, hi)) for n, s, e in pt.spans]
    trace.spans = sorted(trace.spans + [x for x in added if x[2] > x[1]],
                         key=lambda x: x[1])


# ------------------------------------------------------------ readings
def host_step_ms(pt: ProgramTrace, lo: int, hi: int) -> float | None:
    """Mean over the ``serve.step`` spans centred in [lo, hi) of the span
    less the union of its ``serve.readback`` spans (ms)."""
    vals = []
    for name, s, e in pt.spans:
        if name != "serve.step" or not lo <= (s + e) // 2 < hi:
            continue
        waits = devtrace._merge(
            [(a, b) for _, a, b in _children(pt.spans, "serve.readback",
                                              s, e)], s, e)
        vals.append((e - s) - sum(b - a for a, b in waits))
    return sum(vals) / len(vals) / 1e6 if vals else None


def scope_ms(pt: ProgramTrace, trace: devtrace.Trace) -> dict[str, float]:
    """Device ms of each scope per step-program execution centred in the
    window, mean over executions and devices (leaf ops, each counted in
    the execution that holds its centre)."""
    lo, hi = trace.window()
    tot: dict[str, float] = {}
    n = 0
    for dev, evs in pt.ops.items():
        leaves = devtrace.leaf_ops(evs)
        for name, s, e in trace.modules.get(dev, []):
            if not (STEP_PROGRAM.search(name) and lo <= (s + e) // 2 < hi):
                continue
            n += 1
            for op in leaves:
                if s <= (op[1] + op[2]) // 2 < e:
                    k = scope_of(op[3], pt.scopes)
                    tot[k] = tot.get(k, 0.0) + (op[2] - op[1]) / 1e6
    return {k: v / n for k, v in tot.items()} if n else {}


# ------------------------------------------------------------- the run
def locate(trace: devtrace.Trace) -> pathlib.Path | None:
    """The ``.xplane.pb`` that ``trace`` was read from: the harness keeps
    its profile in a ``bench_trace_*`` temporary directory until its
    readers are done; the newest whose harness spans are ``trace``'s."""
    dirs = sorted(pathlib.Path(tempfile.gettempdir()).glob("bench_trace_*"),
                  key=lambda p: p.stat().st_mtime, reverse=True)
    want = [s for s in trace.spans if s[0] == "engine.step"]
    for d in dirs:
        try:
            xp = devtrace.find_xplane(d)
        except FileNotFoundError:
            continue
        if devtrace.load(xp, ("engine.step",)).spans == want:
            return xp
    return None


def of_run(ctx) -> ProgramTrace | None:
    """The run's ``ProgramTrace``, read once and attached to
    ``ctx.trace``; None where the trace has no device or the program
    declares no spans."""
    if "program_trace" in vars(ctx):
        return ctx.program_trace
    pt = None
    span_names, scopes = program_names()
    if ctx.trace is not None and ctx.trace.ops and span_names:
        xp = locate(ctx.trace)
        if xp is not None:
            pt = load(xp, span_names, scopes)
            attach(ctx.trace, pt)
            _log(pt, ctx.trace)
    ctx.program_trace = pt
    return pt


def _log(pt: ProgramTrace, trace: devtrace.Trace) -> None:
    """One stderr line: where op_name came from, the clock bounds, and
    each scope's ms per step program with their sum over its time."""
    ms = scope_ms(pt, trace)
    lo, hi = trace.window()
    whole = [(e - s) / 1e6 for evs in trace.modules.values()
             for n, s, e in evs
             if STEP_PROGRAM.search(n) and lo <= (s + e) // 2 < hi]
    share = (sum(ms.values()) / (sum(whole) / len(whole))
             if whole and ms else None)
    off = ("none" if pt.offset_ns is None else
           f"[{pt.offset_ns[0] / 1e3:.1f}, {pt.offset_ns[1] / 1e3:.1f}] us")
    print(f"[bench] program trace: op_name from the HLO of "
          f"{', '.join(pt.programs) or 'no program'}; "
          f"device - host clock in {off}, spans shifted "
          f"{pt.shift_ns / 1e3:.1f} us; ms per step program: "
          + ", ".join(f"{k} {v:.3f}" for k, v in sorted(ms.items()))
          + f"; scopes over the program's time {share}",
          file=sys.stderr, flush=True)
