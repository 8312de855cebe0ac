"""The programs' HLO as the profiler saved it beside the trace.

A profiler trace (``.xplane.pb``) keeps, in its ``/host:metadata`` plane,
one event metadata entry per compiled program whose ``Hlo Proto`` stat is
the optimized HLO module: every instruction with its ``op_name`` (the name
stack of the JAX operation it came from), and every fusion with the
computation it fuses. ``jax.profiler.ProfileData`` does not expose this, so
this module reads the few fields it needs straight off the protobuf wire
format (``tsl/profiler/protobuf/xplane.proto``,
``xla/service/hlo.proto``, ``xla/xla_data.proto``).
"""
from __future__ import annotations

METADATA_PLANE = "/host:metadata"
HLO_STAT = "Hlo Proto"


def _varint(buf, i):
    out = shift = 0
    while True:
        b = buf[i]
        i += 1
        out |= (b & 0x7F) << shift
        if b < 0x80:
            return out, i
        shift += 7


def fields(buf):
    """``(field number, value)`` of one message: an int for a varint or a
    fixed-width field, a memoryview for a length-delimited one."""
    buf = memoryview(buf)
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        num, wire = key >> 3, key & 7
        if wire == 0:
            val, i = _varint(buf, i)
        elif wire == 2:
            ln, i = _varint(buf, i)
            val, i = buf[i:i + ln], i + ln
        elif wire == 1:
            val, i = int.from_bytes(buf[i:i + 8], "little"), i + 8
        elif wire == 5:
            val, i = int.from_bytes(buf[i:i + 4], "little"), i + 4
        else:
            raise ValueError(f"unsupported protobuf wire type {wire}")
        yield num, val


def _first(buf, num, default=None):
    return next((v for k, v in fields(buf) if k == num), default)


def _str(v) -> str:
    return bytes(v).decode("utf-8", "replace") if v is not None else ""


def hlo_protos(xspace: bytes) -> list:
    """Every ``Hlo Proto`` stat of the trace's metadata plane."""
    out = []
    for num, plane in fields(xspace):
        if num != 1 or _str(_first(plane, 2)) != METADATA_PLANE:
            continue
        stat_names = {}
        for k, entry in fields(plane):           # stat_metadata map
            if k == 5:
                md = _first(entry, 2)
                stat_names[_first(md, 1)] = _str(_first(md, 2))
        for k, entry in fields(plane):           # event_metadata map
            if k != 4:
                continue
            for kk, stat in fields(_first(entry, 2, b"")):
                if kk != 5:
                    continue
                if stat_names.get(_first(stat, 1)) == HLO_STAT:
                    out.append(_first(stat, 6))
    return out


def module_ops(hlo_proto) -> tuple:
    """``(module name, module id, {instruction name: [op_name, ...]})``: for
    each instruction of the module, its own ``op_name`` and, for a fusion,
    those of every instruction it fuses."""
    module = _first(hlo_proto, 1, b"")
    name, mid = _str(_first(module, 1)), _first(module, 5, 0)
    comps = {}                    # computation id -> [(name, op_name, calls)]
    for k, comp in fields(module):
        if k != 3:
            continue
        instrs = []
        for kk, ins in fields(comp):
            if kk != 2:
                continue
            iname, opcode, op, calls = "", "", "", []
            for f, v in fields(ins):
                if f == 1:
                    iname = _str(v)
                elif f == 2:
                    opcode = _str(v)
                elif f == 7:
                    op = _str(_first(v, 2))
                elif f == 38:
                    calls.extend(_packed(v) if isinstance(v, memoryview)
                                 else [v])
            # only a fusion's computation is part of the operation; a
            # while's or a call's run as operations of their own
            instrs.append((iname, op, calls if opcode == "fusion" else []))
        comps[_first(comp, 5)] = instrs

    def held(calls, depth=0):
        for c in calls:
            for _, op, inner in comps.get(c, ()):
                yield op
                if depth < 4:
                    yield from held(inner, depth + 1)

    ops = {}
    for instrs in comps.values():
        for iname, op, calls in instrs:
            ops[iname] = [op] + list(held(calls))
    return name, mid, ops


def _packed(v) -> list:
    out, i = [], 0
    while i < len(v):
        x, i = _varint(v, i)
        out.append(x)
    return out
