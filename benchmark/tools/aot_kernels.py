"""Rehearsal 3 (benchmark/README.md): compile the histogram kernels at a
configuration's real per-chip shapes for a DESCRIBED v5e, in the
sandbox, without a chip. What Mosaic refuses here (scoped VMEM, tiling)
costs no chip time. Nothing runs, so this says nothing about times or
results, and it is never reported as a chip run.

    JAX_PLATFORMS=cpu python3 benchmark/tools/aot_kernels.py --config higgs
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time
from pathlib import Path

os.environ.setdefault("TPU_LOG_DIR", "disabled")
ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

LADDER = (8, 32, 48)  # rounds.py: widths below the slot count, then it


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--config", required=True)
    a = ap.parse_args()

    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    from lightgbm_tpu.learner.histogram import HIST_BLK, int8_oh_shift
    from lightgbm_tpu.learner.pallas_hist import (hist_nat_tpu,
                                                  hist_round_tpu)

    cfg = json.loads(
        (ROOT / "benchmark" / "configs" / f"{a.config}.json").read_text())
    chips = int(cfg["expect"]["devices"])
    feats = int(cfg["dataset"]["features"])
    bins = int(cfg["params"]["max_bin"])
    int8 = cfg["expect"]["hist_dtype"] == "int8"
    blk = HIST_BLK * chips
    rows = -(-int(cfg["dataset"]["rows"]) // blk) * blk // chips
    shift = int8_oh_shift(
        rows, int(cfg["params"].get("num_grad_quant_bins", 256))
    ) if int8 else 0
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    one = SingleDeviceSharding(topo.devices[0])

    def arg(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one)

    bins_a = arg((feats, rows), jnp.int32)
    gh8 = arg((8, rows), jnp.float32)
    rowv = arg((rows,), jnp.int32)
    print(f"{a.config}: {rows} rows per chip x {feats} x {bins}, "
          f"{'int8' if int8 else 'bf16'} channels, for {topo.devices[0]}")
    for s in LADDER:
        t0 = time.perf_counter()
        fn = jax.jit(lambda b, g, p, pr, oh, s=s: hist_round_tpu(
            b, g, p, pr, oh, s, bins, 3, int8=int8, oh_shift=shift))
        c = fn.lower(bins_a, gh8, rowv, arg((s, 16), jnp.int32),
                     arg((s, feats), jnp.float32)).compile()
        print(f"  hist_round_tpu S={s}: compiled in "
              f"{time.perf_counter() - t0:.1f}s; {c.memory_analysis()}")
    t0 = time.perf_counter()
    fn = jax.jit(lambda b, g, sl: hist_nat_tpu(
        b, g, sl, 1, bins, nat_ch=3, int8=int8, oh_shift=shift))
    c = fn.lower(bins_a, gh8, rowv).compile()
    print(f"  hist_nat_tpu S=1: compiled in {time.perf_counter() - t0:.1f}s;"
          f" {c.memory_analysis()}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
