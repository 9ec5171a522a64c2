"""Cut a recorded ``*.xplane.pb`` down to a test fixture: every plane,
line, event and stat stays byte for byte, and so does everything of the
embedded modules that ``harness/xmeta.py`` reads; what goes is the bulk
of the embedded ``HloProto`` s that no reader reads:

    HloProto.buffer_assignment (3)
    HloModuleProto.schedule (7), .stack_frame_index (17)
    HloInstructionProto.shape (3), .literal (8), .backend_config (43:
    the Mosaic kernels' serialized bodies)

    python3 benchmark/tools/slim_trace.py <in.xplane.pb> <out.xplane.pb.gz>

``benchmark/tests/data/tiny_phases.README.md`` says which fixture was
made with it."""

from __future__ import annotations

import gzip
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

# XSpace.planes / XPlane.event_metadata / map value / XEventMetadata.stats
# / XStat.bytes_value = one HloProto
TO_PROTO = (1, 4, 2, 5, 6)
INSTRUCTION = TO_PROTO + (1, 3, 2)  # hlo_module / computations / instructions
DROP = {TO_PROTO + (3,), TO_PROTO + (1, 7), TO_PROTO + (1, 17),
        INSTRUCTION + (3,), INSTRUCTION + (8,), INSTRUCTION + (43,)}
OPEN = {INSTRUCTION[:i] for i in range(1, len(INSTRUCTION) + 1)}


def _encode(n: int) -> bytes:
    out = bytearray()
    while True:
        out.append((n & 0x7F) | (0x80 if n > 0x7F else 0))
        n >>= 7
        if not n:
            return bytes(out)


def slim(buf: bytes, lo: int, hi: int, path=()) -> bytes:
    """The message ``buf[lo:hi]`` without the fields of ``DROP``."""
    from benchmark.harness.xmeta import BYTES, VARINT, _varint

    out = bytearray()
    at = lo
    while at < hi:
        start = at
        key, at = _varint(buf, at)
        number, wire = key >> 3, key & 7
        here = path + (number,)
        if wire == VARINT:
            _, at = _varint(buf, at)
        elif wire == BYTES:
            size, at = _varint(buf, at)
            if here in OPEN and here not in DROP:
                inner = slim(buf, at, at + size, here)
                out += _encode(key) + _encode(len(inner)) + inner
                at += size
                continue
            at += size
        else:
            at += 8 if wire == 1 else 4
        if here not in DROP:
            out += buf[start:at]
    return bytes(out)


def main() -> int:
    from benchmark.harness.xmeta import METADATA_PLANE, _planes

    buf = Path(sys.argv[1]).read_bytes()
    keep = {name: (lo, hi) for name, lo, hi in _planes(buf)}
    lo, hi = keep[METADATA_PLANE]
    plane = slim(buf, lo, hi, (1,))
    # the plane's own key and length precede `lo`: re-encode them
    head = lo - len(_encode(hi - lo)) - 1
    out = buf[:head] + _encode(1 << 3 | 2) + _encode(len(plane)) + plane \
        + buf[hi:]
    with gzip.GzipFile(sys.argv[2], "wb", 9, mtime=0) as f:
        f.write(out)
    print(f"{len(buf)} -> {len(out)} bytes, "
          f"{Path(sys.argv[2]).stat().st_size} gzipped")
    return 0


if __name__ == "__main__":
    sys.exit(main())
