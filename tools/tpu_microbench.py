"""Micro-benchmarks for the TPU histogram kernels and growers.

Run on a live chip; prints one JSON line per measurement. Used to tune
the slot-packed kernel.
"""

import json
import sys
import time

import numpy as np


def sync(x):
    import jax

    jax.block_until_ready(x)
    return x


def timeit(fn, *args, reps=5, warmup=2):
    for _ in range(warmup):
        sync(fn(*args))
    t0 = time.time()
    for _ in range(reps):
        sync(fn(*args))
    return (time.time() - t0) / reps


def main() -> None:
    import jax
    import jax.numpy as jnp

    sys.path.insert(0, "/root/repo")
    from lightgbm_tpu.learner.histogram import (
        HIST_BLK,
        build_gh8,
        build_gh8_quant,
        hist_nat_slots,
        histogram,
    )

    platform = jax.devices()[0].platform
    print(json.dumps({"metric": "platform", "value": platform}), flush=True)
    if platform != "tpu":
        return

    rs = np.random.RandomState(0)
    N = 489 * HIST_BLK  # ~1M rows, HIGGS-like
    F, B = 28, 256
    bins = jnp.asarray(rs.randint(0, 255, (F, N)).astype(np.int32))
    g = jnp.asarray(rs.randn(N).astype(np.float32))
    h = jnp.asarray((rs.rand(N) * 0.25).astype(np.float32))
    ones = jnp.ones(N, jnp.float32)
    gh8 = build_gh8(g, h, ones)
    slot25 = jnp.asarray(rs.randint(0, 26, N).astype(np.int32))
    slot1 = jnp.zeros(N, jnp.int32)

    t = timeit(lambda: histogram(bins, gh8, B))
    print(json.dumps({"metric": "hist_full_M8_ms", "value": round(t * 1e3, 2),
                      "note": f"{N}x{F} single-leaf pass"}), flush=True)

    t = timeit(lambda: hist_nat_slots(bins, gh8, slot25, 25, B))
    print(json.dumps({"metric": "hist_nat_25slots_ms",
                      "value": round(t * 1e3, 2),
                      "note": "slot-packed M=125"}), flush=True)

    t = timeit(lambda: hist_nat_slots(bins, gh8, slot1, 1, B))
    print(json.dumps({"metric": "hist_nat_1slot_ms",
                      "value": round(t * 1e3, 2)}), flush=True)

    gq = jnp.asarray(rs.randint(-2, 3, N).astype(np.float32))
    hq = jnp.asarray(rs.randint(0, 5, N).astype(np.float32))
    gh8q = build_gh8_quant(gq, hq, ones)
    slot42 = jnp.asarray(rs.randint(0, 43, N).astype(np.int32))
    t = timeit(lambda: hist_nat_slots(bins, gh8q, slot42, 42, B, quant=True))
    print(json.dumps({"metric": "hist_nat_quant_42slots_ms",
                      "value": round(t * 1e3, 2),
                      "note": "3 int channels M=126"}), flush=True)

    # one full tree: rounds grower vs exact at 255 leaves
    from lightgbm_tpu.config import Config
    from lightgbm_tpu.dataset import BinnedDataset
    from lightgbm_tpu.learner import GrowerSpec, grow_tree, make_split_params

    X = rs.randn(N, F).astype(np.float32)
    w = rs.randn(F)
    cfg = Config({"max_bin": 255, "min_data_in_leaf": 20})
    ds = BinnedDataset.from_numpy(X, cfg)
    d = ds.device_arrays()
    Np = ds.num_rows_padded()
    grad = jnp.asarray(rs.randn(Np).astype(np.float32)) * d["valid"]
    hess = jnp.ones(Np, jnp.float32) * 0.25 * d["valid"]
    params = make_split_params(cfg)
    fm = jnp.ones(ds.num_used_features, bool)

    for name, kw in (
        ("tree_rounds25_ms", dict(rounds_slots=25)),
        ("tree_exact_ms", dict()),
    ):
        spec = GrowerSpec(num_leaves=255, num_bins=ds.max_num_bin,
                          max_depth=-1, **kw)

        def run(spec=spec):
            t_, rl = grow_tree(
                d["bins"], d["nan_bin"], d["num_bins"], d["mono"],
                d["is_cat"], grad, hess, d["valid"], fm, params, spec,
                valid=d["valid"],
            )
            return rl

        t = timeit(run, reps=3, warmup=1)
        print(json.dumps({"metric": name, "value": round(t * 1e3, 1),
                          "note": "255 leaves, 1M x 28"}), flush=True)


if __name__ == "__main__":
    main()
