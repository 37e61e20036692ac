"""The compiled programs' HLO out of an ``.xplane.pb``, with nothing but Python.

What a v5e's trace gives an executed operation (seen in PR 28): its name, the
instruction's text, and three statistics (``device_offset_ps``,
``device_duration_ps``, ``Time Scale Multiplier``): nothing that says which
JAX operations, under which ``jax.named_scope``, it was compiled from. The
same file does hold that, once per program: the plane ``/host:metadata`` has
one event metadata per compiled module (``jit_mixed(5)``) whose statistic
``Hlo Proto`` is the serialized ``HloProto``, and every instruction in it
carries ``metadata.op_name``, the scope path
(``jit(mixed)/moe/ebch,ehf->ebcf/dot_general``). ``jax.profiler.ProfileData``
does not expose a plane's metadata, so the two messages are walked here on
the wire format; the field numbers are those of ``xplane.proto`` (tsl) and
``hlo.proto`` (xla), written beside each use.
"""

from __future__ import annotations

import re
from typing import Dict, Iterator, Tuple

METADATA_PLANE = "/host:metadata"
HLO_PROTO_STAT = "Hlo Proto"


def _varint(buf, i: int) -> Tuple[int, int]:
    value = shift = 0
    while True:
        byte = buf[i]
        i += 1
        value |= (byte & 0x7F) << shift
        shift += 7
        if byte < 0x80:
            return value, i


def fields(buf) -> Iterator[Tuple[int, int, object]]:
    """``(field number, wire type, value)`` of one protobuf message: an int
    for a varint or a fixed-width value, a ``memoryview`` for a
    length-delimited one (a string, bytes, a sub-message, a packed list)."""
    buf = memoryview(buf)
    i, end = 0, len(buf)
    while i < end:
        key, i = _varint(buf, i)
        number, wire = key >> 3, key & 7
        if wire == 0:
            value, i = _varint(buf, i)
        elif wire == 2:
            length, i = _varint(buf, i)
            value, i = buf[i:i + length], i + length
        elif wire in (1, 5):
            width = 8 if wire == 1 else 4
            value, i = int.from_bytes(buf[i:i + width], "little"), i + width
        else:
            raise ValueError(f"wire type {wire} at byte {i}")
        yield number, wire, value


def first(message, number: int):
    return next((v for n, _, v in fields(message) if n == number), None)


def text(value) -> str:
    return "" if value is None else bytes(value).decode("utf-8", "replace")


def base_name(module_name: str) -> str:
    """``jit_mixed(5)`` and ``jit_mixed(12536509211202233264)`` -> ``jit_mixed``."""
    return re.sub(r"\(\d+\)$", "", module_name)


def hlo_modules(xspace) -> Dict[str, memoryview]:
    """``{module's base name: its serialized HloModuleProto}`` from the
    metadata plane of an ``XSpace``; empty if the file has none."""
    out: Dict[str, memoryview] = {}
    for number, _, plane in fields(xspace):
        if number != 1 or text(first(plane, 2)) != METADATA_PLANE:   # XSpace.planes, XPlane.name
            continue
        stat_names = {}
        for n, _, entry in fields(plane):
            if n == 5:                                               # XPlane.stat_metadata
                meta = first(entry, 2)                               # map value: XStatMetadata
                stat_names[first(meta, 1)] = text(first(meta, 2))    # id, name
        for n, _, entry in fields(plane):
            if n != 4:                                               # XPlane.event_metadata
                continue
            event = first(entry, 2)                                  # map value: XEventMetadata
            for m, _, stat in fields(event):
                if m == 5 and stat_names.get(first(stat, 1)) == HLO_PROTO_STAT:  # stats; metadata_id
                    proto = first(stat, 6)                           # XStat.bytes_value: HloProto
                    module = first(proto, 1) if proto is not None else None      # HloProto.hlo_module
                    if module is not None:
                        out[base_name(text(first(event, 2)))] = module           # XEventMetadata.name
    return out


def instruction_scopes(hlo_module, pattern: re.Pattern) -> Dict[str, str]:
    """``{instruction name: op_name}`` for the instructions of a module that
    lie in the scope ``pattern`` finds: an instruction's own ``op_name``
    decides; one that has none (some fusions) takes the first matching
    ``op_name`` among the instructions of the computations it calls."""
    own: Dict[str, str] = {}          # instruction name -> op_name
    calls: Dict[str, list] = {}       # instruction name -> called computation ids
    inside: Dict[int, list] = {}      # computation id -> its instructions' op_names
    for n, _, computation in fields(hlo_module):
        if n != 3:                                                   # HloModuleProto.computations
            continue
        names = inside.setdefault(first(computation, 5), [])         # HloComputationProto.id
        for m, _, instruction in fields(computation):
            if m != 2:                                               # .instructions
                continue
            name = text(first(instruction, 1))                       # HloInstructionProto.name
            metadata = first(instruction, 7)                         # .metadata (OpMetadata)
            own[name] = text(first(metadata, 2)) if metadata is not None else ""  # op_name
            names.append(own[name])
            called = []
            for k, wire, value in fields(instruction):
                if k == 38:                                          # .called_computation_ids
                    if wire == 0:
                        called.append(value)
                    else:                                            # packed
                        i = 0
                        while i < len(value):
                            item, i = _varint(value, i)
                            called.append(item)
            if called:
                calls[name] = called
    out = {}
    for name, op_name in own.items():
        if not op_name:
            op_name = next((o for c in calls.get(name, ()) for o in inside.get(c, ())
                            if pattern.search(o)), "")
        if pattern.search(op_name):
            out[name] = op_name
    return out
