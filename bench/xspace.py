"""Each program's op name paths, read from a profiler trace file.

A trace file (``*.xplane.pb``, an ``XSpace`` protobuf) keeps every
program that ran as an ``HloProto`` on its ``/host:metadata`` plane,
under the program's name as the device's ``XLA Modules`` line gives it
(``jit_step(12)``). Each HLO instruction there carries its ``op_name``:
the JAX name path, ``jax.named_scope``s included; a fusion's fused
computation keeps the op_names of the instructions it fuses. The device's op events
name only the instruction, and JAX's ``ProfileData`` does not expose the
metadata plane, so this reads the few fields it needs straight from the
protobuf wire format.
"""
from __future__ import annotations

#: field numbers (tsl/profiler/protobuf/xplane.proto, xla/service/hlo.proto,
#: xla/xla_data.proto)
_SPACE_PLANES = 1
_PLANE_NAME, _PLANE_EVENT_META, _PLANE_STAT_META = 2, 4, 5
_ENTRY_VALUE = 2
_META_NAME, _META_STATS = 2, 5
_STAT_META_ID, _STAT_BYTES = 1, 6
_STAT_META_NAME = 2
_HLO_MODULE, _MODULE_COMPUTATIONS = 1, 3
_COMPUTATION_INSTRUCTIONS, _COMPUTATION_ID = 2, 5
_INSTR_NAME, _INSTR_OPCODE, _INSTR_METADATA, _INSTR_CALLED = 1, 2, 7, 38
_OP_NAME = 2

METADATA_PLANE = "/host:metadata"
HLO_STAT = "Hlo Proto"


def _varint(buf, i: int) -> tuple[int, int]:
    x = shift = 0
    while True:
        b = buf[i]
        i += 1
        x |= (b & 0x7F) << shift
        if b < 0x80:
            return x, i
        shift += 7


def _fields(buf, lo: int, hi: int):
    """``(field, value)`` of the message in ``buf[lo:hi]``; a
    length-delimited value is its ``(start, end)`` in ``buf``."""
    i = lo
    while i < hi:
        key, i = _varint(buf, i)
        wire = key & 7
        if wire == 0:
            val, i = _varint(buf, i)
        elif wire == 2:
            n, i = _varint(buf, i)
            val = (i, i + n)
            i += n
        elif wire in (1, 5):
            n = 8 if wire == 1 else 4
            val = (i, i + n)
            i += n
        else:
            raise ValueError(f"unsupported protobuf wire type {wire}")
        yield key >> 3, val


def _str(buf, span) -> str:
    return bytes(buf[span[0]:span[1]]).decode()


def _one(buf, span, field):
    for f, v in _fields(buf, *span):
        if f == field:
            return v
    return None


def _ids(buf, v) -> list:
    """A repeated int64 field's value: one varint, or packed varints."""
    if isinstance(v, int):
        return [v]
    out, i = [], v[0]
    while i < v[1]:
        x, i = _varint(buf, i)
        out.append(x)
    return out


def _hlo_paths(buf, span) -> dict:
    """``{instruction: (op_name, *fused)}`` of one ``HloProto``, where
    ``fused`` are the op_names of the instructions a fusion fuses (XLA
    gives a fusion the op_name of one of them, or none)."""
    module = _one(buf, span, _HLO_MODULE)
    if module is None:
        return {}
    comps = {}     # computation id -> [(name, opcode, op_name, called)]
    for f, comp in _fields(buf, *module):
        if f != _MODULE_COMPUTATIONS:
            continue
        cid, instrs = None, []
        for g, v in _fields(buf, *comp):
            if g == _COMPUTATION_ID:
                cid = v
            elif g == _COMPUTATION_INSTRUCTIONS:
                name, opcode, path, called = None, "", "", []
                for h, w in _fields(buf, *v):
                    if h == _INSTR_NAME:
                        name = _str(buf, w)
                    elif h == _INSTR_OPCODE:
                        opcode = _str(buf, w)
                    elif h == _INSTR_METADATA:
                        op = _one(buf, w, _OP_NAME)
                        path = "" if op is None else _str(buf, op)
                    elif h == _INSTR_CALLED:
                        called.extend(_ids(buf, w))
                if name is not None:
                    instrs.append((name, opcode, path, called))
        comps[cid] = instrs
    out = {}
    for instrs in comps.values():
        for name, opcode, path, called in instrs:
            fused = [q for c in called if opcode == "fusion"
                     for _, _, q, _ in comps.get(c, ()) if q]
            out[name] = (path, *fused)
    return out


def op_paths(data: bytes) -> dict:
    """``{program: {instruction: (op_name, *fused)}}`` of a serialized
    ``XSpace`` (see ``_hlo_paths``); empty where it holds no metadata
    plane."""
    buf = memoryview(data)
    for f, plane in _fields(buf, 0, len(buf)):
        if f != _SPACE_PLANES:
            continue
        name = _one(buf, plane, _PLANE_NAME)
        if name is None or _str(buf, name) != METADATA_PLANE:
            continue
        hlo_stat, metas = None, []
        for g, entry in _fields(buf, *plane):
            if g == _PLANE_STAT_META:
                meta = _one(buf, entry, _ENTRY_VALUE)
                sid = _one(buf, meta, _STAT_META_ID)
                sname = _one(buf, meta, _STAT_META_NAME)
                if sname is not None and _str(buf, sname) == HLO_STAT:
                    hlo_stat = sid
            elif g == _PLANE_EVENT_META:
                metas.append(_one(buf, entry, _ENTRY_VALUE))
        out = {}
        for meta in metas:
            prog, proto = None, None
            for g, v in _fields(buf, *meta):
                if g == _META_NAME:
                    prog = _str(buf, v)
                elif g == _META_STATS and _one(
                        buf, v, _STAT_META_ID) == hlo_stat:
                    proto = _one(buf, v, _STAT_BYTES)
            if prog is not None and proto is not None:
                out[prog] = _hlo_paths(buf, proto)
        return out
    return {}
