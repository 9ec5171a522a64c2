"""Multi-host training support (the reference's distributed runtime).

The reference runs one CLI process per machine connected by a
hand-rolled socket/MPI collective layer (src/network/linkers_socket.cpp
full-mesh TCP, network.cpp ring/halving collectives). The TPU-native
equivalent is JAX's multi-controller runtime: one process per host,
`jax.distributed.initialize` forms the cluster, and every collective in
the growers (psum / all_gather / psum_scatter) rides ICI within a slice
and DCN across hosts through the SAME code path as single-host — no
separate network layer.

This module maps the reference's network configuration
(`machines` / `machine_list_filename` / `num_machines` /
`local_listen_port`, config.h network params; python
`lgb.set_network`) onto `jax.distributed.initialize`, and provides the
pre-partitioned data assembly (`pre_partition=true` semantics,
dataset_loader.cpp:210: each rank holds its own row shard):

- `init_distributed(...)`: join/form the cluster.
- `allgather_binning_sample(sample)`: the reference's distributed
  binning (dataset_loader.cpp:1174: per-rank FindBin samples are
  allgathered so every rank builds IDENTICAL bin mappers).
- `global_rows(host_array, mesh, row_axis)`: assemble a process-local
  row shard into one global device array over the mesh
  (jax.make_array_from_process_local_data).
"""

from __future__ import annotations

from typing import Optional, Sequence

import numpy as np


def resolve_rank(machines: Sequence[str], local_listen_port: int) -> int:
    """Best-effort self-rank discovery by local address match (the
    reference matches local IPs against the machine list,
    linkers_socket.cpp:38-49); falls back to the JAX_PROCESS_ID env."""
    import os
    import socket

    env = os.environ.get("JAX_PROCESS_ID")
    if env is not None:
        return int(env)
    local_names = {socket.gethostname(), "localhost", "127.0.0.1"}
    try:
        local_names.add(socket.gethostbyname(socket.gethostname()))
    except OSError:
        pass
    for i, m in enumerate(machines):
        host, _, port = m.partition(":")
        if host in local_names and (not port or int(port) == local_listen_port):
            return i
    raise RuntimeError(
        "cannot determine this process's rank: no machine entry matches a "
        "local address; set JAX_PROCESS_ID or pass machine_rank"
    )


def init_distributed(
    machines: Optional[str] = None,
    machine_list_file: Optional[str] = None,
    num_machines: Optional[int] = None,
    local_listen_port: int = 12400,
    machine_rank: Optional[int] = None,
) -> int:
    """Join the multi-host cluster from reference-style network params.

    The first machine in the list is the coordinator (the reference has
    no coordinator — its socket mesh is symmetric — but rank 0 is the
    canonical choice). Returns this process's rank. No-op when the
    cluster is already initialized.
    """
    import jax

    # NOTE: no jax.process_count()/devices() probe here — touching the
    # backend before jax.distributed.initialize() poisons it
    if jax.distributed.is_initialized():
        return jax.process_index()
    mlist = []
    if machine_list_file:
        with open(machine_list_file) as f:
            mlist = [ln.strip() for ln in f if ln.strip()]
    elif machines:
        mlist = [m.strip() for m in machines.split(",") if m.strip()]
    if not mlist:
        raise ValueError("init_distributed needs machines or machine_list_file")
    n = num_machines or len(mlist)
    rank = machine_rank if machine_rank is not None else resolve_rank(
        mlist, local_listen_port
    )
    coord = mlist[0]
    if ":" not in coord:
        coord = f"{coord}:{local_listen_port}"
    from ..resilience.backoff import retry_call

    # cluster join races the coordinator's startup: workers that boot
    # first see connection errors. Bounded retry-with-backoff instead
    # of failing the whole fleet on a few seconds' skew
    # (docs/RESILIENCE.md "Distributed recovery").
    retry_call(
        lambda: jax.distributed.initialize(
            coordinator_address=coord, num_processes=n, process_id=rank
        ),
        retries=3,
        base_s=1.0,
        retry_on=(OSError, RuntimeError),
        describe=f"jax.distributed.initialize({coord}, rank {rank})",
    )
    return rank


def gather_host_rows(arr: np.ndarray) -> np.ndarray:
    """Allgather a per-process host array (1-D or row-major N-D) with
    UNEVEN leading lengths into the process-order concatenation (every
    rank returns the same array): rows are padded to the cluster max and
    trimmed back after the gather. Used for global init-score statistics
    (gbdt.cpp BoostFromAverage must produce ONE value per cluster) and
    the distributed binning sample."""
    import jax

    if jax.process_count() == 1:
        return np.asarray(arr)
    from jax.experimental import multihost_utils

    arr = np.asarray(arr)
    n = arr.shape[0]
    counts = np.asarray(
        multihost_utils.process_allgather(np.asarray(n, np.int64))
    ).reshape(-1)
    mx = int(counts.max())
    pad = np.zeros((mx,) + arr.shape[1:], arr.dtype)
    pad[:n] = arr
    g = np.asarray(multihost_utils.process_allgather(pad))  # (P, mx, ...)
    return np.concatenate([g[i, : counts[i]] for i in range(len(counts))])


def allgather_binning_sample(sample: np.ndarray) -> np.ndarray:
    """Concatenate every process's binning sample (rows) so all ranks
    derive identical BinMappers (dataset_loader.cpp:1174-1250)."""
    return gather_host_rows(sample)


def host_global_array(a) -> np.ndarray:
    """Full host copy of a (possibly globally-sharded) device array on
    EVERY process — np.asarray raises on arrays spanning other
    processes' devices; those take a tiled process_allgather."""
    import jax

    if jax.process_count() == 1:
        return np.asarray(a)
    if isinstance(a, jax.Array) and not a.is_fully_addressable:
        from jax.experimental import multihost_utils

        return np.asarray(multihost_utils.process_allgather(a, tiled=True))
    return np.asarray(a)


def global_rows(arr: np.ndarray, mesh, axis: int = 0):
    """Assemble per-process row shards into one global array sharded
    over the mesh's 'data' axis (pre_partition semantics: this
    process's rows are its shard; shards concatenate in process order).
    """
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P

    spec = [None] * arr.ndim
    spec[axis] = "data"
    sharding = NamedSharding(mesh, P(*spec))
    if jax.process_count() == 1:
        return jax.device_put(arr, sharding)
    return jax.make_array_from_process_local_data(sharding, arr)


def write_metrics_snapshot(out_dir: str) -> str:
    """Dump THIS process's metrics registry as a snapshot file in a
    shared directory (obs/aggregate.py schema). Pure host-side I/O —
    deliberately not a jax collective, so fleet observability works on
    backends without cross-process collectives (the xfail'd CPU
    multihost configuration, docs/DESIGN_DECISIONS.md) and keeps
    working when the training fabric itself is what broke."""
    import os

    import jax

    from ..obs import aggregate

    os.makedirs(out_dir, exist_ok=True)
    rank = jax.process_index()
    path = os.path.join(out_dir, f"metrics_rank{rank:05d}.json")
    aggregate.write_snapshot(path, process=rank)
    return path


def merged_fleet_snapshot(out_dir: str):
    """Merge every worker's snapshot file from `out_dir` into one
    fleet view (counters sum across processes; gauges sum with min/max
    spread — see obs/aggregate.py). Any process can call this; it
    reads only files."""
    import glob
    import os

    from ..obs import aggregate

    paths = glob.glob(os.path.join(out_dir, "metrics_rank*.json"))
    if not paths:
        raise FileNotFoundError(
            f"no metrics_rank*.json snapshots under {out_dir}; call "
            "write_metrics_snapshot on each worker first"
        )
    return aggregate.merge_files(paths)


def run_distributed(
    params: dict,
    X: np.ndarray,
    y: np.ndarray,
    *,
    machines: Optional[str] = None,
    machine_list_file: Optional[str] = None,
    machine_rank: Optional[int] = None,
    num_machines: Optional[int] = None,
    local_listen_port: int = 12400,
    num_boost_round: int = 100,
    weight: Optional[np.ndarray] = None,
    group: Optional[np.ndarray] = None,
    valid: Optional[tuple] = None,  # (Xv, yv) — rank-local validation shard
    callbacks: Optional[list] = None,
    obs_snapshot_dir: Optional[str] = None,  # shared dir for fleet metrics
):
    """One-call multi-host training — the python-package analog of
    dask.py:415 `_train`: joins the cluster from reference-style network
    params, builds IDENTICAL bin mappers on every rank from the
    allgathered binning sample (dataset_loader.cpp:1174 distributed
    binning), equalizes per-rank row padding for the global mesh, and
    runs `lgb.train(tree_learner=data)` over all processes' devices.

    `X`/`y` are THIS RANK's row shard (`pre_partition=true` semantics,
    config.h). Returns the Booster — identical on every rank (lockstep
    guarantee); save from rank 0.
    """
    import jax

    from .. import engine
    from ..basic import Dataset

    rank = init_distributed(
        machines=machines,
        machine_list_file=machine_list_file,
        num_machines=num_machines,
        local_listen_port=local_listen_port,
        machine_rank=machine_rank,
    )

    params = dict(params)
    params.setdefault("tree_learner", "data")
    params["num_machines"] = jax.process_count()

    # ---- identical mappers everywhere: bin on the global sample
    sample_cnt = int(params.get("bin_construct_sample_cnt", 200000))
    per_rank = max(1, sample_cnt // max(jax.process_count(), 1))
    if len(X) > per_rank:
        rs = np.random.RandomState(int(params.get("data_random_seed", 1)))
        idx = np.sort(rs.choice(len(X), per_rank, replace=False))
        local_sample = np.ascontiguousarray(X[idx], dtype=np.float64)
    else:
        local_sample = np.ascontiguousarray(X, dtype=np.float64)
    global_sample = allgather_binning_sample(local_sample)
    bin_ref = Dataset(
        global_sample,
        label=np.zeros(len(global_sample)),
        params={k: v for k, v in params.items()
                if k not in ("tree_learner", "num_machines")},
        free_raw_data=True,
    )
    bin_ref.construct()

    ds = Dataset(
        X, label=y, weight=weight, group=group,
        reference=bin_ref, free_raw_data=False,
    )
    ds.construct()
    # per-rank row-padding equalization happens inside GBDT setup
    # (boosting.py data-parallel init) AFTER the final row_block is
    # known — doing it here would be undone by ensure_row_block

    valid_sets = None
    valid_names = None
    if valid is not None:
        # every rank evaluates the FULL validation set (rank-local valid
        # shards are allgathered) so metrics — and therefore early
        # stopping — are identical across the cluster; the reference
        # reaches the same property through its metric allreduce
        Xv = allgather_binning_sample(
            np.ascontiguousarray(valid[0], dtype=np.float64)
        )
        yv = gather_host_rows(np.asarray(valid[1], dtype=np.float64))
        vs = Dataset(Xv, label=yv, reference=bin_ref, free_raw_data=False)
        valid_sets = [vs]
        valid_names = ["valid"]

    heartbeat = None
    if obs_snapshot_dir:
        # per-worker liveness files next to the metrics snapshots: a
        # rank that dies mid-train stops beating, and rank 0's health
        # report (below) names it — without any collective, so death
        # detection works precisely when the training fabric is what
        # broke (docs/RESILIENCE.md "Distributed recovery")
        from ..resilience.heartbeat import HeartbeatWriter

        heartbeat = HeartbeatWriter(obs_snapshot_dir, rank)
        heartbeat.start()
    try:
        bst = engine.train(
            params, ds, num_boost_round=num_boost_round,
            valid_sets=valid_sets, valid_names=valid_names,
            callbacks=callbacks,
        )
    finally:
        if heartbeat is not None:
            # clean exits write a final beat; a crash here leaves the
            # file stale, which is exactly what flags the death
            heartbeat.stop()
    bst._distributed_rank = rank
    if obs_snapshot_dir:
        # fleet observability: every rank dumps its registry; rank 0
        # merges the files into one view (host-side only — works even
        # where jax cross-process collectives don't). Deliberately no
        # barrier: ranks that haven't flushed yet are just absent, so
        # the merge reports HOW MANY snapshots it saw and warns when
        # partial — re-merge offline via merged_fleet_snapshot once
        # every worker has written.
        write_metrics_snapshot(obs_snapshot_dir)
        if rank == 0:
            merged = merged_fleet_snapshot(obs_snapshot_dir)
            bst._fleet_metrics = merged
            from .. import log
            from ..resilience.heartbeat import health_report

            health = health_report(
                obs_snapshot_dir, expected=jax.process_count()
            )
            bst._fleet_health = health
            if not health["healthy"]:
                log.warning(
                    f"fleet health: stale rank(s) {health['stale']}, "
                    f"missing rank(s) {health['missing']} — a worker "
                    "likely died mid-train; restart the fleet with "
                    "resume=auto to continue from the last checkpoint"
                )

            n = merged.get("processes", 0)
            total = jax.process_count()
            if n < total:
                log.warning(
                    f"fleet metrics merged from only {n}/{total} worker "
                    f"snapshot(s) under {obs_snapshot_dir} — stragglers "
                    "missing; re-merge offline with "
                    "merged_fleet_snapshot for the complete view"
                )
            else:
                log.info(
                    f"fleet metrics merged from {n} worker snapshot(s) "
                    f"under {obs_snapshot_dir}"
                )
    return bst
