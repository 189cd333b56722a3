"""Device time of the Helix combine that nothing hid, in one execution of
the serve-step program, mean over executions and devices: the time in
which an op of the ``combine`` scope (the all-to-all, the all-gather of
the LSEs and their rescale; ``core/helix.py``) runs on a device and no op
of any other scope, or of none, runs on the same device.  HOP-B exists to
hide the exchange of one batch row behind the attention of the next; this
is what it did not hide.

Ops are leaf ops, each counted in the execution that holds its centre.
An asynchronous collective is two ops, ``*-start`` and ``*-done``: each
counts for its own device duration, and the time between them does not
count, since the transfer then runs beside whatever the device does.  The
``*-done`` op waits for the transfer to land, so a transfer that nothing
hid shows as the length of its ``*-done``.
"""
import bisect
import functools

import devtrace
import progtrace

SCOPE = "combine"


def exposed_ns(ops, lo: int, hi: int, scope_of) -> tuple[int, bool]:
    """Nanoseconds of [lo, hi) covered by ``SCOPE``'s ops of ``ops``
    ((name, start, end, op_name) leaf ops; ``scope_of(op_name)`` names an
    op's scope) and by no other op; and whether any op of ``SCOPE`` was
    there."""
    mine, other = [], []
    for _, s, e, on in ops:
        (mine if scope_of(on) == SCOPE else other).append((s, e))
    mine = devtrace._merge(mine, lo, hi)
    starts = [s for s, _ in mine]

    def meets(s, e):
        i = bisect.bisect_left(starts, e) - 1
        return i >= 0 and mine[i][1] > s

    busy = devtrace._merge([x for x in other if meets(*x)], lo, hi)
    total, k = 0, 0
    for s, e in mine:
        total += e - s
        while k < len(busy) and busy[k][1] <= s:
            k += 1
        j = k
        while j < len(busy) and busy[j][0] < e:
            total -= min(e, busy[j][1]) - max(s, busy[j][0])
            j += 1
    return total, bool(mine)


def read(ctx):
    pt = progtrace.of_run(ctx)
    if pt is None or SCOPE not in pt.scopes:
        return None
    lo, hi = ctx.trace.window()
    scope_of = functools.lru_cache(maxsize=None)(
        lambda on: progtrace.scope_of(on, pt.scopes))
    tot, n, seen = 0, 0, False
    for dev, evs in pt.ops.items():
        leaves = sorted(devtrace.leaf_ops(evs), key=lambda o: o[1] + o[2])
        centres = [(o[1] + o[2]) // 2 for o in leaves]
        for name, s, e in ctx.trace.modules.get(dev, []):
            if not (progtrace.STEP_PROGRAM.search(name)
                    and lo <= (s + e) // 2 < hi):
                continue
            mine = leaves[bisect.bisect_left(centres, s):
                          bisect.bisect_left(centres, e)]
            ns, found = exposed_ns(mine, s, e, scope_of)
            tot, n, seen = tot + ns, n + 1, seen or found
    return tot / n / 1e6 if seen else None
