"""What a trace holds, for looking at one by hand before writing a
reader against it: planes, lines, and per line the names with most total
time (count, seconds, the first event's stats).

    python3 benchmark/tools/trace_inventory.py <file.xplane.pb> [top]
"""

from __future__ import annotations

import json
import sys


def main() -> int:
    from jax.profiler import ProfileData

    top = int(sys.argv[2]) if len(sys.argv) > 2 else 40
    data = ProfileData.from_file(sys.argv[1])
    for plane in data.planes:
        for line in plane.lines:
            acc: dict = {}
            n = 0
            for ev in line.events:
                n += 1
                a = acc.setdefault(ev.name, [0, 0.0, None])
                a[0] += 1
                a[1] += ev.duration_ns / 1e9
                if a[2] is None:
                    a[2] = {k: (v if isinstance(v, (int, float))
                                else str(v)[:400])
                            for k, v in ev.stats}
            print(json.dumps({"plane": plane.name, "line": line.name,
                              "events": n, "names": len(acc)}))
            for name, (cnt, sec, st) in sorted(
                    acc.items(), key=lambda kv: -kv[1][1])[:top]:
                print(json.dumps({"name": name[:200], "count": cnt,
                                  "seconds": sec, "stats": st}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
