"""Worker for the 2-process FULL-API multi-host test: run_distributed
(the dask _train analog) drives lgb.train end-to-end — global binning,
tree_learner=data over the 2-process mesh, per-iteration device metric
eval, early stopping, rank-0 model save. Both ranks must converge to
byte-identical models."""

import hashlib
import os
import sys

# CPU-only by construction: two processes on ONE machine, and a chip
# belongs to one process at a time
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = " ".join(
    f for f in os.environ.get("XLA_FLAGS", "").split()
    if "xla_force_host_platform_device_count" not in f
)

import numpy as np


def main() -> None:
    rank = int(sys.argv[1])
    nproc = int(sys.argv[2])
    port = sys.argv[3]
    out_model = sys.argv[4]

    sys.path.insert(
        0, os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    )
    import lightgbm_tpu as lgb
    from lightgbm_tpu.parallel.multihost import run_distributed

    # one logical dataset; each rank holds a DIFFERENT, uneven shard
    rs = np.random.RandomState(7)
    n, f = 6000, 8
    X = rs.randn(n, f)
    w = rs.randn(f)
    y = ((X @ w + 0.5 * rs.randn(n)) > 0).astype(np.float64)
    cut = 2600  # deliberately uneven: 2600 vs 3400 rows
    sl = slice(0, cut) if rank == 0 else slice(cut, n)
    Xv = rs.randn(1000, f)
    yv = ((Xv @ w + 0.5 * rs.randn(1000)) > 0).astype(np.float64)
    vcut = 500
    vsl = slice(0, vcut) if rank == 0 else slice(vcut, None)

    evals = {}
    bst = run_distributed(
        {
            "objective": "binary",
            "num_leaves": 15,
            "learning_rate": 0.2,
            "metric": "auc",
            "min_data_in_leaf": 5,
            "verbosity": -1,
            "seed": 3,
        },
        X[sl], y[sl],
        machines=",".join(f"127.0.0.1:{int(port) + i}" for i in range(nproc)),
        machine_rank=rank,
        num_boost_round=30,
        valid=(Xv[vsl], yv[vsl]),
        callbacks=[
            lgb.early_stopping(stopping_rounds=5, verbose=False),
            lgb.record_evaluation(evals),
        ],
    )

    model_str = bst.model_to_string(num_iteration=-1)
    digest = hashlib.sha256(model_str.encode()).hexdigest()[:16]
    if rank == 0:
        bst.save_model(out_model)
    auc = list(evals["valid"].values())[0][-1]
    print(
        f"MULTIHOST_TRAIN_OK rank={rank} trees={bst.num_trees()} "
        f"best_it={bst.best_iteration} auc={auc:.4f} model={digest}",
        flush=True,
    )

    # renewal objective (regression_l1): boost_from_average percentile
    # + host leaf refit must use GLOBAL rows (lazy gathers cached before
    # the device arrays go global)
    rs3 = np.random.RandomState(11)
    yl1 = (X @ w + 0.3 * rs3.randn(n)).astype(np.float64)
    bst_l1 = run_distributed(
        {
            "objective": "regression_l1",
            "num_leaves": 15,
            "learning_rate": 0.2,
            "min_data_in_leaf": 5,
            "verbosity": -1,
        },
        X[sl], yl1[sl],
        machines=",".join(f"127.0.0.1:{int(port) + i}" for i in range(nproc)),
        machine_rank=rank,
        num_boost_round=5,
    )
    d_l1 = hashlib.sha256(
        bst_l1.model_to_string(num_iteration=-1).encode()
    ).hexdigest()[:16]
    print(f"MULTIHOST_L1_OK rank={rank} model={d_l1}", flush=True)


if __name__ == "__main__":
    main()
