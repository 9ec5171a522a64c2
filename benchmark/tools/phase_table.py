"""Device time by the program's phases, for any profiler capture of a
lightgbm_tpu run (``profile_dir=``, ``jax.profiler.start_trace``, the
benchmark's ``--trace 1``): per phase events, ms, share of device time,
time inherited from producers, the ops with most self time, and the
fusions XLA mixed two phases in (``harness/device_phases.py`` has the
rule; ``docs/OBSERVABILITY.md`` the phases).

    python3 benchmark/tools/phase_table.py <file.xplane.pb> [trees]

With ``trees`` the times are per tree; without, ms in the capture (or
in the benchmark's window, where the capture holds one)."""

from __future__ import annotations

import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))


def main() -> int:
    from benchmark.harness import device_phases
    from benchmark.harness.manifest import load_plugin
    from benchmark.harness.trace import TraceView

    path = Path(sys.argv[1])
    trees = float(sys.argv[2]) if len(sys.argv) > 2 else 1.0
    pattern = load_plugin(ROOT, "rooflines", "hist_round").KERNEL_PATTERN
    table = device_phases.table_of(path, TraceView.from_file(path), pattern)
    print("\n".join(device_phases.lines(table, trees)))
    if not table.has_tokens:
        print("no lgbm. phase in any embedded module: a program from "
              "before the phases, or an executable loaded from a compile "
              "cache that such a program filled (clear .jax_cache/)")
    return 0


if __name__ == "__main__":
    sys.exit(main())
