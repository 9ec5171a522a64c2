"""The compiled modules a profiler trace carries, read with the standard
library alone.

An ``*.xplane.pb`` is one ``XSpace`` message. Its plane
``/host:metadata`` holds one ``event_metadata`` entry per executable
that ran while the profiler was on, named like the ``XLA Modules``
events of the device planes (``jit_chunk(1933382835734451975)``), whose
stat ``Hlo Proto`` is the serialized ``HloProto`` of the OPTIMISED
module: every computation, every instruction with its opcode, the
computations it calls, its operands and ``metadata.op_name``, the JAX
name stack it was traced under. ``jax.profiler.ProfileData`` does not
expose ``event_metadata``, and the generated protobuf classes exist
only inside ``tensorflow``, which the benchmark does not depend on; the
wire format is varints and length-delimited fields, and this module
walks the few field paths it needs:

    XSpace.planes 1 -> XPlane.name 2, .event_metadata 4 (map entry:
    value 2) -> XEventMetadata.name 2, .stats 5 -> XStat.bytes_value 6
    -> HloProto.hlo_module 1 -> HloModuleProto.computations 3 ->
    HloComputationProto.id 5, .root_id 6, .instructions 2 ->
    HloInstructionProto.name 1, .opcode 2, .metadata 7 (->
    OpMetadata.op_name 2), .id 35, .operand_ids 36 and
    .called_computation_ids 38 (varint or packed)

The device planes' own ``event_metadata`` (one entry per HLO op that
ran) carry XLA's cost estimates as stats named in the plane's
``stat_metadata`` (5: map entry value 2 -> XStatMetadata.id 1, .name
2); ``bytes_accessed`` reads them for a LOG line (an estimate of the
compiler's, never a metric)."""

from __future__ import annotations

from dataclasses import dataclass, field
from pathlib import Path
from typing import Dict, Iterator, List, Optional, Tuple

METADATA_PLANE = "/host:metadata"
VARINT, FIXED64, BYTES, FIXED32 = 0, 1, 2, 5


@dataclass(frozen=True)
class Instruction:
    name: str
    opcode: str
    op_name: str  # "" where the compiler made the instruction
    called: Tuple[int, ...]  # ids of the computations it calls
    operands: Tuple[str, ...]  # producers' names, same computation
    computation: int  # id of the computation that holds it


@dataclass
class Module:
    """One embedded executable. Instruction names are unique in a
    module, so the trace's event names join on them."""

    name: str
    instructions: Dict[str, Instruction] = field(default_factory=dict)
    computations: Dict[int, List[str]] = field(default_factory=dict)
    roots: Dict[int, str] = field(default_factory=dict)


def _varint(buf: bytes, at: int) -> Tuple[int, int]:
    value = shift = 0
    while True:
        b = buf[at]
        at += 1
        value |= (b & 0x7F) << shift
        if not b & 0x80:
            return value, at
        shift += 7


def fields(buf: bytes, lo: int = 0, hi: Optional[int] = None
           ) -> Iterator[Tuple[int, int, int, int]]:
    """(field number, wire type, a, b) of one message lying in
    ``buf[lo:hi]``: a varint's value in ``a``; a length-delimited or
    fixed field's bytes are ``buf[a:b]`` (nothing is copied)."""
    at = lo
    hi = len(buf) if hi is None else hi
    while at < hi:
        key, at = _varint(buf, at)
        number, wire = key >> 3, key & 7
        if wire == VARINT:
            value, at = _varint(buf, at)
            yield number, wire, value, 0
        elif wire == BYTES:
            size, at = _varint(buf, at)
            yield number, wire, at, at + size
            at += size
        elif wire in (FIXED64, FIXED32):
            size = 8 if wire == FIXED64 else 4
            yield number, wire, at, at + size
            at += size
        else:
            raise ValueError(f"wire type {wire} at byte {at}: not a "
                             f"protobuf message of this schema")


def _ints(buf: bytes, wire: int, a: int, b: int) -> List[int]:
    """A repeated int64 field's values: one varint, or a packed run."""
    if wire == VARINT:
        return [a]
    out = []
    while a < b:
        value, a = _varint(buf, a)
        out.append(value)
    return out


def _text(buf: bytes, a: int, b: int) -> str:
    return buf[a:b].decode("utf-8", "replace")


def _instruction(buf: bytes, lo: int, hi: int):
    name = opcode = op_name = ""
    ident = 0
    called: List[int] = []
    operand_ids: List[int] = []
    for number, wire, a, b in fields(buf, lo, hi):
        if number == 1:
            name = _text(buf, a, b)
        elif number == 2:
            opcode = _text(buf, a, b)
        elif number == 7:
            for n2, _, a2, b2 in fields(buf, a, b):
                if n2 == 2:
                    op_name = _text(buf, a2, b2)
        elif number == 35:
            ident = a
        elif number == 36:
            operand_ids += _ints(buf, wire, a, b)
        elif number == 38:
            called += _ints(buf, wire, a, b)
    return ident, name, opcode, op_name, tuple(called), operand_ids


def parse_hlo_proto(buf: bytes, name: str, lo: int = 0,
                    hi: Optional[int] = None) -> Module:
    """The ``Module`` of one serialized ``HloProto``."""
    mod = Module(name)
    for number, _, a, b in fields(buf, lo, hi):
        if number != 1:  # HloProto.hlo_module
            continue
        for n2, _, a2, b2 in fields(buf, a, b):
            if n2 != 3:  # HloModuleProto.computations
                continue
            comp_id = root_id = 0
            raw = []
            for n3, _, a3, b3 in fields(buf, a2, b2):
                if n3 == 5:
                    comp_id = a3
                elif n3 == 6:
                    root_id = a3
                elif n3 == 2:
                    raw.append(_instruction(buf, a3, b3))
            by_id = {r[0]: r[1] for r in raw}
            mod.computations[comp_id] = [r[1] for r in raw]
            if root_id in by_id:
                mod.roots[comp_id] = by_id[root_id]
            for ident, iname, opcode, op_name, called, operand_ids in raw:
                mod.instructions[iname] = Instruction(
                    iname, opcode, op_name, called,
                    tuple(by_id[i] for i in operand_ids if i in by_id),
                    comp_id)
    return mod


def _planes(buf: bytes) -> Iterator[Tuple[str, int, int]]:
    """(name, lo, hi) of every plane of an ``XSpace``."""
    for number, wire, a, b in fields(buf):
        if number == 1 and wire == BYTES:
            name = ""
            for n2, w2, a2, b2 in fields(buf, a, b):
                if n2 == 2 and w2 == BYTES:
                    name = _text(buf, a2, b2)
                    break  # the name precedes the lines
            yield name, a, b


def _map_values(buf: bytes, lo: int, hi: int, number: int
                ) -> Iterator[Tuple[int, int]]:
    """(lo, hi) of the value message of every entry of map field
    ``number``."""
    for n, w, a, b in fields(buf, lo, hi):
        if n == number and w == BYTES:
            for n2, w2, a2, b2 in fields(buf, a, b):
                if n2 == 2 and w2 == BYTES:
                    yield a2, b2


def read_modules(path: Path) -> Dict[str, Module]:
    """{module name: Module} of every executable the trace embeds."""
    return parse_modules(Path(path).read_bytes())


def parse_modules(buf: bytes) -> Dict[str, Module]:
    """``read_modules`` of a trace file's bytes."""
    out: Dict[str, Module] = {}
    for name, lo, hi in _planes(buf):
        if name != METADATA_PLANE:
            continue
        for a, b in _map_values(buf, lo, hi, 4):
            mod_name, protos = "", []
            for n, w, a2, b2 in fields(buf, a, b):
                if n == 2 and w == BYTES:
                    mod_name = _text(buf, a2, b2)
                elif n == 5 and w == BYTES:  # XEventMetadata.stats
                    protos += [(a3, b3) for n3, w3, a3, b3
                               in fields(buf, a2, b2)
                               if n3 == 6 and w3 == BYTES]
            for a3, b3 in protos:
                out[mod_name] = parse_hlo_proto(buf, mod_name, a3, b3)
    return out


def bytes_accessed(buf: bytes, plane_prefix: str = "/device:"
                   ) -> Dict[str, float]:
    """{op event name: XLA's estimate of the bytes one execution
    touches}, from the first device plane's ``event_metadata`` stat
    ``bytes_accessed`` of a trace file's bytes. Empty where the trace has
    no such stat."""
    for name, lo, hi in _planes(buf):
        if not name.startswith(plane_prefix):
            continue
        stat_id = None
        for a, b in _map_values(buf, lo, hi, 5):
            ident, sname = 0, ""
            for n, w, a2, b2 in fields(buf, a, b):
                if n == 1 and w == VARINT:
                    ident = a2
                elif n == 2 and w == BYTES:
                    sname = _text(buf, a2, b2)
            if sname == "bytes_accessed":
                stat_id = ident
        if stat_id is None:
            continue
        out: Dict[str, float] = {}
        for a, b in _map_values(buf, lo, hi, 4):
            ev_name, value = "", None
            for n, w, a2, b2 in fields(buf, a, b):
                if n == 2 and w == BYTES:
                    ev_name = _text(buf, a2, b2)
                elif n == 5 and w == BYTES:
                    stat = {n3: a3 for n3, w3, a3, _ in fields(buf, a2, b2)
                            if w3 == VARINT}
                    if stat.get(1) == stat_id:  # XStat.metadata_id
                        value = stat.get(3, stat.get(4))  # u/int64_value
            if value is not None:
                out[ev_name] = float(value)
        return out
    return {}
