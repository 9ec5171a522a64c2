"""Worker for the 2-process multi-host test (spawned by
test_multihost.py). Each process holds HALF the rows (pre_partition
semantics), binning samples are allgathered so mappers are identical,
and the data-parallel grower runs over the 2-process global mesh —
its psums ride the cross-process (Gloo, stand-in for DCN) collectives.
"""

import os
import sys

# CPU-only by construction: two processes on ONE machine, and a chip
# belongs to one process at a time
os.environ["JAX_PLATFORMS"] = "cpu"
# pytest's conftest exports an 8-virtual-device XLA_FLAGS; this worker
# needs exactly ONE local device per process (2-process global mesh)
os.environ["XLA_FLAGS"] = " ".join(
    f for f in os.environ.get("XLA_FLAGS", "").split()
    if "xla_force_host_platform_device_count" not in f
)

import jax

import numpy as np


def main() -> None:
    rank = int(sys.argv[1])
    nproc = int(sys.argv[2])
    port = sys.argv[3]

    sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
    from lightgbm_tpu.parallel import multihost

    got = multihost.init_distributed(
        machines=",".join(f"127.0.0.1:{int(port) + i}" for i in range(nproc)),
        machine_rank=rank,
    )
    assert got == rank == jax.process_index()
    assert jax.device_count() == nproc

    import jax.numpy as jnp

    from lightgbm_tpu.config import Config
    from lightgbm_tpu.dataset import BinnedDataset
    from lightgbm_tpu.learner import GrowerSpec, make_split_params
    from lightgbm_tpu.learner.histogram import HIST_BLK
    from lightgbm_tpu.parallel.data_parallel import DataParallelGrower, make_mesh

    # ---- per-rank row shard of one logical dataset (pre_partition)
    rs = np.random.RandomState(0)
    n_total, f = 4096, 6
    X_all = rs.randn(n_total, f).astype(np.float64)
    w = rs.randn(f)
    y_all = ((X_all @ w + 0.3 * rs.randn(n_total)) > 0).astype(np.float32)
    lo, hi = rank * n_total // nproc, (rank + 1) * n_total // nproc
    X_loc, y_loc = X_all[lo:hi], y_all[lo:hi]

    # ---- distributed binning: identical mappers everywhere
    sample = multihost.allgather_binning_sample(X_loc)
    cfg = Config({"max_bin": 63, "min_data_in_leaf": 5,
                  "tpu_row_block": HIST_BLK})
    ref = BinnedDataset.from_numpy(sample, cfg)
    ds = BinnedDataset.from_numpy(X_loc, cfg, label=y_loc, reference=ref)

    mesh = make_mesh()
    spec = GrowerSpec(num_leaves=15, num_bins=ds.max_num_bin, max_depth=-1)
    grower = DataParallelGrower(mesh, spec)
    params = make_split_params(cfg)

    # ---- global arrays from local shards
    npad_loc = ds.num_rows_padded()
    bins_loc = np.zeros((ds.num_used_features, npad_loc), np.int32)
    bins_loc[:, : ds.num_data] = ds.bins
    valid_loc = np.zeros(npad_loc, np.float32)
    valid_loc[: ds.num_data] = 1.0
    ylab = np.zeros(npad_loc, np.float32)
    ylab[: ds.num_data] = y_loc

    bins_g = multihost.global_rows(bins_loc, mesh, axis=1)
    valid_g = multihost.global_rows(valid_loc, mesh)
    label_g = multihost.global_rows(ylab, mesh)

    um = ds.used_mappers()
    rep = lambda a: jax.device_put(  # noqa: E731 — replicated small tables
        a, jax.sharding.NamedSharding(mesh, jax.sharding.PartitionSpec())
    )
    nan_bin = rep(np.asarray([m.nan_bin for m in um], np.int32))
    num_bins = rep(np.asarray([m.num_bin for m in um], np.int32))
    mono = rep(np.zeros(ds.num_used_features, np.int32))
    is_cat = rep(np.zeros(ds.num_used_features, bool))
    feat_mask = rep(np.ones(ds.num_used_features, bool))

    @jax.jit
    def step(score, bins, label, valid):
        p = jax.nn.sigmoid(score)
        g = (p - label) * valid
        h = jnp.maximum(p * (1.0 - p), 1e-6) * valid
        return grower._fn(
            bins, nan_bin, num_bins, mono, is_cat, g, h, valid, feat_mask,
            params, valid, None, None, None, None, None, None,
        )

    score = multihost.global_rows(np.zeros(npad_loc, np.float32), mesh)
    tree, row_leaf = step(score, bins_g, label_g, valid_g)

    n_nodes = int(tree.num_nodes)
    lv = np.asarray(tree.leaf_value)[: n_nodes + 1]
    feats = np.asarray(tree.node_feature)[:n_nodes]
    # identical trees on every process (lockstep from psum'd histograms)
    from jax.experimental import multihost_utils

    all_lv = np.asarray(multihost_utils.process_allgather(jnp.asarray(lv)))
    assert np.allclose(all_lv, all_lv[0], atol=1e-6), "ranks diverged"
    print(
        f"MULTIHOST_OK rank={rank} nodes={n_nodes} "
        f"feat0={int(feats[0])} lv0={lv[0]:.6f}",
        flush=True,
    )


if __name__ == "__main__":
    main()
