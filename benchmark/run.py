"""The benchmark's one command:

    python3 benchmark/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Runs one cell of BENCHMARK.json on the accelerator this process finds
and prints the result as ONE JSON object on the last line of stdout
(everything else goes to stderr). Refuses a CPU backend, or fewer chips
than the cell asks for, with a non-zero exit and no result line.
benchmark/README.md has the layout and how to add a cell."""

from __future__ import annotations

import time

T_PROCESS = time.perf_counter()  # set-up is counted from here

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), required=True)
    a = ap.parse_args(argv)

    from benchmark.harness import cellrun, device
    from benchmark.harness.manifest import resolve_cell

    cell = resolve_cell(ROOT, a.workload)
    # the program's one place that configures the persistent compile
    # cache: the directory the environment names if it names one, else
    # <checkout>/.jax_cache
    from lightgbm_tpu._cache import ensure_compile_cache

    cache_dir = ensure_compile_cache()
    dev = device.require_accelerator(cell.chips)
    cellrun.log(f"{a.workload}: {dev}, compile cache {cache_dir}")
    result = cellrun.run_cell(
        ROOT, a.workload,
        cellrun.RunArgs(seed=a.seed, seconds=a.seconds,
                        trace=bool(a.trace), t_process=T_PROCESS),
        dev,
    )
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
