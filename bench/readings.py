"""Helpers the metric readers share: percentiles, device time of named
ops and programs, and the least time of the work the window did."""
from __future__ import annotations

import re

import numpy as np

import devtrace


def pct(values, q: float) -> float | None:
    vals = np.asarray(list(values), np.float64)
    return float(np.percentile(vals, q)) if vals.size else None


def ttl_samples(ctx) -> list[float]:
    """Every token after a request's first, delivered in the window."""
    return [g for _, _, _, gaps in ctx.deliveries for g in gaps]


def traced_steps(ctx):
    return [s for s in ctx.steps if s.traced]


def op_time(ctx, pattern: str) -> float:
    """Seconds of device ops whose kind (``devtrace.op_kind``) matches
    ``pattern``, summed over devices, for ops centred in the traced
    window (the device's clock runs up to a millisecond off the host's,
    so an op is not cut at the window's edge)."""
    if ctx.trace is None:
        return 0.0
    rx = re.compile(pattern)
    lo, hi = ctx.trace.window()
    return sum(e - s for evs in ctx.trace.ops.values()
               for n, s, e in devtrace.leaf_ops(evs)
               if lo <= (s + e) // 2 < hi
               and rx.search(devtrace.op_kind(n))) / 1e9


def program_calls(ctx, pattern: str) -> list[float]:
    """Seconds of each execution of programs matching ``pattern``,
    over all devices, centred in the traced window."""
    if ctx.trace is None:
        return []
    rx = re.compile(pattern)
    lo, hi = ctx.trace.window()
    return [(e - s) / 1e9 for evs in ctx.trace.modules.values()
            for n, s, e in evs if rx.search(n) and lo <= (s + e) // 2 < hi]


def least(ctx, work) -> float:
    """Least seconds the cell's chips need for ``work`` = (flops, bytes)
    of the whole call, summed over chips."""
    t, _ = ctx.costs.least_seconds(work[0], work[1], ctx.peak, ctx.chips)
    return t * ctx.chips


def least_wall(ctx, work) -> float:
    """Least wall seconds the cell's chips need for ``work`` together."""
    return ctx.costs.least_seconds(work[0], work[1], ctx.peak, ctx.chips)[0]


def roofline(ctx, pattern: str, calls) -> float | None:
    """Share (%) of the roofline: least time of ``calls`` (work tuples)
    over the device time of ops matching ``pattern``."""
    if ctx.trace is None or ctx.peak is None:
        return None
    spent = op_time(ctx, pattern)
    need = sum(least(ctx, w) for w in calls)
    if spent <= 0 or need <= 0:
        return None
    return 100.0 * need / spent


def device_busy_share(ctx) -> float | None:
    if ctx.trace is None or not ctx.trace.ops:
        return None
    lo, hi = ctx.trace.window()
    devs = ctx.trace.devices
    busy = sum(devtrace.busy_ns(ctx.trace, d, lo, hi) for d in devs)
    return busy / (len(devs) * (hi - lo))
