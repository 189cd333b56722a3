"""The ``op_name`` of each compiled instruction, from a profile.

``jax.profiler.ProfileData`` gives a device op's event its HLO text as
name, but not the instruction's metadata: a TPU op event carries no
``op_name``.  The profile keeps each program's compiled HLO as an
``HloProto`` stat ("Hlo Proto") of the ``/host:metadata`` plane's event
metadata, named as the device's ``XLA Modules`` events are
(``jit_serve_step(<id>)``).  This reads those protos with a minimal
protobuf wire-format reader, so nothing beyond the standard library is
needed.

Field numbers (xla/tsl/profiler/protobuf/xplane.proto, xla/xla.proto,
xla/service/hlo.proto):
XSpace.planes 1; XPlane.name 2, .event_metadata 4 (map entry value 2),
.stat_metadata 5; XEventMetadata.name 2, .stats 5; XStatMetadata.id 1,
.name 2; XStat.metadata_id 1, .bytes_value 6; HloProto.hlo_module 1;
HloModuleProto.computations 3; HloComputationProto.instructions 2;
HloInstructionProto.name 1, .metadata 7; OpMetadata.op_name 2.
"""
from __future__ import annotations

import pathlib

METADATA_PLANE = "/host:metadata"
HLO_PROTO_STAT = "Hlo Proto"


def _varint(b: bytes, i: int) -> tuple[int, int]:
    x = s = 0
    while True:
        c = b[i]
        i += 1
        x |= (c & 0x7F) << s
        if c < 0x80:
            return x, i
        s += 7


def fields(b: bytes, lo: int = 0, hi: int | None = None):
    """(field number, value) of the message in ``b[lo:hi]``: an int for a
    varint, the (start, end) of a length-delimited field, None else."""
    i = lo
    hi = len(b) if hi is None else hi
    while i < hi:
        key, i = _varint(b, i)
        wire = key & 7
        if wire == 0:
            v, i = _varint(b, i)
        elif wire == 2:
            n, i = _varint(b, i)
            v, i = (i, i + n), i + n
        elif wire in (1, 5):
            v, i = None, i + (8 if wire == 1 else 4)
        else:
            raise ValueError(f"protobuf wire type {wire} at byte {i}")
        yield key >> 3, v


def _sub(b: bytes, span, number: int):
    """Spans of the length-delimited fields ``number`` of a message."""
    return [v for f, v in fields(b, *span) if f == number]


def _text(b: bytes, span) -> str:
    return b[span[0]:span[1]].decode("utf-8", "replace")


def _op_names(b: bytes, hlo_proto) -> dict[str, str]:
    """Instruction name -> op_name of one serialized ``HloProto``."""
    out = {}
    for module in _sub(b, hlo_proto, 1):
        for comp in _sub(b, module, 3):
            for ins in _sub(b, comp, 2):
                name = op = ""
                for f, v in fields(b, *ins):
                    if f == 1:
                        name = _text(b, v)
                    elif f == 7:
                        op = next((_text(b, m) for g, m in fields(b, *v)
                                   if g == 2), "")
                if name:
                    out[name] = op
    return out


def hlo_protos(b: bytes) -> dict[str, tuple[int, int]]:
    """Program name -> span of its serialized ``HloProto`` in ``b``."""
    out = {}
    for plane in _sub(b, (0, len(b)), 1):
        names = _sub(b, plane, 2)
        if not names or _text(b, names[0]) != METADATA_PLANE:
            continue
        stat_ids = set()
        for entry in _sub(b, plane, 5):
            for meta in _sub(b, entry, 2):
                d = dict(fields(b, *meta))
                if d.get(2) and _text(b, d[2]) == HLO_PROTO_STAT:
                    stat_ids.add(d.get(1, 0))
        for entry in _sub(b, plane, 4):
            for meta in _sub(b, entry, 2):
                name = next((_text(b, v) for f, v in fields(b, *meta)
                             if f == 2), "")
                for stat in _sub(b, meta, 5):
                    s = dict(fields(b, *stat))
                    if s.get(1, 0) in stat_ids and 6 in s:
                        out[name] = s[6]
    return out


def _base(program: str) -> str:
    return program.split("(", 1)[0]


def op_names(xplane: pathlib.Path, programs) -> dict[str, dict[str, str]]:
    """For each program named in ``programs`` (as ``XLA Modules`` events
    name them, ``jit_serve_step(<id>)``), its instructions' ``op_name``s:
    from the HLO kept under the same name, else under the one name of
    the same module."""
    b = pathlib.Path(xplane).read_bytes()
    protos = hlo_protos(b)
    out = {}
    for p in programs:
        same = [n for n in protos if _base(n) == _base(p)]
        src = p if p in protos else (same[0] if len(same) == 1 else None)
        if src is not None:
            out[p] = _op_names(b, protos[src])
    return out
