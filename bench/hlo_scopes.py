"""The op path of every instruction of every program in a profiler trace.

``jax.profiler`` writes, beside the events, the optimized HLO of each
program it saw run: in the ``/host:metadata`` plane, one event metadata per
program, named as on the device's ``XLA Modules`` line (``jit_f(<id>)``),
with a stat ``Hlo Proto`` that holds the serialized ``HloProto``.  Each
instruction's ``metadata.op_name`` is the path of ``jax.named_scope``\\ s
and primitives that made it (``jit(decode)/while/body/attention/...``), so
a device operation, named by its instruction, can be put under the model's
layer that produced it.  ``module_op_names`` reads the same from one
compiled program's ``HloModuleProto`` (``bench/layer_time.py``).

``read`` walks the protobuf wire format with the standard library alone;
the field numbers are those of ``xplane.proto`` and ``hlo.proto``:

    XSpace.planes 1 -> XPlane.name 2, .event_metadata 4 (map, value 2),
    .stat_metadata 5 (map, value 2) -> XStatMetadata.id 1, .name 2;
    XEventMetadata.name 2, .stats 5 -> XStat.metadata_id 1, .bytes_value 6
    -> HloProto.hlo_module 1 -> HloModuleProto.computations 3
    -> HloComputationProto.instructions 2 -> HloInstructionProto.name 1,
    .metadata 7 -> OpMetadata.op_name 2.
"""
from __future__ import annotations

METADATA_PLANE = "/host:metadata"
HLO_STAT = "Hlo Proto"


def _varint(buf, i: int) -> tuple[int, int]:
    shift = value = 0
    while True:
        b = buf[i]
        i += 1
        value |= (b & 0x7F) << shift
        if b < 0x80:
            return value, i
        shift += 7


def fields(buf):
    """(field number, value) of each field of one message: an int for
    varint and fixed-width fields, a memoryview for length-delimited
    ones."""
    buf = memoryview(buf)
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        num, wire = key >> 3, key & 7
        if wire == 0:
            value, i = _varint(buf, i)
        elif wire == 2:
            size, i = _varint(buf, i)
            value, i = buf[i:i + size], i + size
        elif wire == 1:
            value, i = int.from_bytes(buf[i:i + 8], "little"), i + 8
        elif wire == 5:
            value, i = int.from_bytes(buf[i:i + 4], "little"), i + 4
        else:
            raise ValueError(f"protobuf wire type {wire} at byte {i}")
        yield num, value


def _first(buf, num: int, default=None):
    return next((v for f, v in fields(buf) if f == num), default)


def _text(buf, num: int) -> str:
    return bytes(_first(buf, num, b"")).decode()


def op_names(hlo_proto) -> dict[str, str]:
    """{instruction name: op_name} over every computation of one
    serialized ``HloProto``."""
    return module_op_names(_first(hlo_proto, 1, b""))


def module_op_names(module) -> dict[str, str]:
    """{instruction name: op_name} over every computation of one
    serialized ``HloModuleProto``."""
    out = {}
    for num, comp in fields(module):
        if num != 3:
            continue
        for num, inst in fields(comp):
            if num == 2:
                meta = _first(inst, 7)
                out[_text(inst, 1)] = _text(meta, 2) if meta else ""
    return out


def read(xspace) -> dict[str, dict[str, str]]:
    """{program name as on the ``XLA Modules`` line: {instruction name:
    op_name}} from the bytes of an ``.xplane.pb``; empty where the trace
    holds no HLO."""
    plane = next((p for num, p in fields(xspace)
                  if num == 1 and _text(p, 2) == METADATA_PLANE), None)
    if plane is None:
        return {}
    hlo_stat = None
    for num, entry in fields(plane):
        if num == 5:
            stat = _first(entry, 2, b"")
            if _text(stat, 2) == HLO_STAT:
                hlo_stat = _first(stat, 1, 0)
    programs = {}
    for num, entry in fields(plane):
        if num != 4 or hlo_stat is None:
            continue
        meta = _first(entry, 2, b"")
        for num, stat in fields(meta):
            if num == 5 and _first(stat, 1, 0) == hlo_stat:
                programs[_text(meta, 2)] = op_names(_first(stat, 6, b""))
    return programs
