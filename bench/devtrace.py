"""Reduction of a profiler trace to device busy/idle, op time and gaps.

``load`` reads the ``.xplane.pb`` that ``jax.profiler`` writes into a
``Trace``: for each device, the ops that ran on it (the ``XLA Ops`` line
of its plane) and the program executions (``XLA Modules``); and the host
spans that the harness opened around its calls into the engine.  All
times are nanoseconds on the trace's one clock.  ``save``/``from_json``
keep a reduced trace as JSON, so the reduction is tested on a recorded
chip trace without the chip.
"""
from __future__ import annotations

import dataclasses
import gzip
import json
import pathlib
import re

OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"


@dataclasses.dataclass
class Trace:
    ops: dict[str, list[tuple[str, int, int]]]
    modules: dict[str, list[tuple[str, int, int]]]
    spans: list[tuple[str, int, int]]

    @property
    def devices(self) -> list[str]:
        return sorted(self.ops)

    def window(self) -> tuple[int, int]:
        """From the first host span's start to the last one's end."""
        return (min(s for _, s, _ in self.spans),
                max(e for _, _, e in self.spans))

    def to_json(self) -> dict:
        return {"ops": self.ops, "modules": self.modules, "spans": self.spans}

    @classmethod
    def from_json(cls, d: dict) -> "Trace":
        tup = lambda evs: [tuple(e) for e in evs]          # noqa: E731
        return cls({k: tup(v) for k, v in d["ops"].items()},
                   {k: tup(v) for k, v in d["modules"].items()},
                   tup(d["spans"]))

    def save(self, path: pathlib.Path) -> None:
        with gzip.open(path, "wt") as f:
            json.dump(self.to_json(), f)


def load_json(path: pathlib.Path) -> Trace:
    with gzip.open(path, "rt") as f:
        return Trace.from_json(json.load(f))


def _events(line):
    return [(e.name, int(e.start_ns), int(e.start_ns + e.duration_ns))
            for e in line.events]


def load(xplane: pathlib.Path, span_names) -> Trace:
    """Device ops and modules of every device plane, and the host spans
    whose names are in ``span_names``."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(str(xplane))
    ops, modules, spans = {}, {}, []
    names = set(span_names)
    for plane in pd.planes:
        if plane.name.startswith("/device:"):
            for line in plane.lines:
                if line.name == OPS_LINE:
                    ops[plane.name] = _events(line)
                elif line.name == MODULES_LINE:
                    modules[plane.name] = _events(line)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                spans += [e for e in _events(line) if e[0] in names]
    return Trace(ops, modules, sorted(spans, key=lambda e: e[1]))


def find_xplane(directory: pathlib.Path) -> pathlib.Path:
    found = sorted(pathlib.Path(directory).rglob("*.xplane.pb"))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {directory}")
    return found[-1]


# ------------------------------------------------------------ intervals
def _merge(intervals, lo: int, hi: int) -> list[tuple[int, int]]:
    """Union of (start, end) intervals clipped to [lo, hi], sorted."""
    out: list[list[int]] = []
    for s, e in sorted((max(s, lo), min(e, hi)) for s, e in intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def busy_ns(trace: Trace, device: str, lo: int, hi: int) -> int:
    return sum(e - s for s, e in
               _merge([(s, e) for _, s, e in trace.ops[device]], lo, hi))


def gaps(trace: Trace, device: str, lo: int, hi: int
         ) -> list[tuple[int, int]]:
    """Intervals of [lo, hi] in which no op ran on ``device``."""
    busy = _merge([(s, e) for _, s, e in trace.ops[device]], lo, hi)
    out, t = [], lo
    for s, e in busy:
        if s > t:
            out.append((t, s))
        t = max(t, e)
    if hi > t:
        out.append((t, hi))
    return out


def span_at(trace: Trace, t: int) -> str:
    """Name of the innermost host span open at ``t``."""
    best = None
    for name, s, e in trace.spans:
        if s <= t < e and (best is None or e - s < best[1]):
            best = (name, e - s)
    return best[0] if best else "outside the harness's spans"


def leaf_ops(evs) -> list[tuple[str, int, int]]:
    """The ops that hold no other op: a loop or conditional is an event
    on the same line that spans the ops of its body."""
    evs = sorted(evs, key=lambda e: (e[1], -e[2]))
    return [e for i, e in enumerate(evs)
            if not (i + 1 < len(evs) and evs[i + 1][1] < e[2]
                    and evs[i + 1][2] <= e[2])]


def op_kind(name: str) -> str:
    """``%flash_decode.2 = (...) custom-call(...)`` -> ``flash_decode``."""
    head = name.split(" = ", 1)[0].lstrip("%")
    return re.sub(r"\.\d+$", "", head)


# ------------------------------------------------------------ breakdown
def breakdown(trace: Trace, n: int = 10) -> dict:
    """The kinds of device op that took most time (seconds of leaf ops,
    mean over devices) and the longest idle gaps, each named by the host
    span open in it."""
    lo, hi = trace.window()
    devs = trace.devices
    tot: dict[str, float] = {}
    for d in devs:
        for name, s, e in leaf_ops(trace.ops[d]):
            s, e = max(s, lo), min(e, hi)
            if e > s:
                k = op_kind(name)
                tot[k] = tot.get(k, 0.0) + (e - s) / 1e9 / len(devs)
    ops = sorted(tot.items(), key=lambda kv: -kv[1])[:n]
    idle = []
    for d in devs:
        for s, e in gaps(trace, d, lo, hi):
            idle.append((span_at(trace, (s + e) // 2), (e - s) / 1e9))
    idle.sort(key=lambda kv: -kv[1])
    return {"device_ops": [[k, v] for k, v in ops],
            "idle_gaps": [[k, v] for k, v in idle[:n]]}
