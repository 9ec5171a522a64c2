"""Benchmark: Higgs-1M-like GBDT training throughput on the local device.

Prints ONE JSON line: {"metric", "value", "unit", "vs_baseline"}.

Baseline: the reference's published Higgs result — 500 iterations of
255-leaf trees over 10.5M x 28 in 130.094 s on 2xE5-2690v4
(reference docs/Experiments.rst:104-121, see BASELINE.md). Scaled
linearly to this bench's row count (histogram GBDT cost is ~linear in
rows), i.e. baseline trees/sec at R rows = (500 / 130.094) * (10.5e6 / R).

The bench measures the accelerator or nothing: a backend that is not
an accelerator, a segment that raises, or a signal all end the process
with a non-zero exit code and no result line. It never changes
platform, never shrinks the workload, and carries no number forward
from an earlier run. Every result names the platform, device kind and
device count it ran on.

The timed loop trains WITH per-iteration validation metrics enabled
(device-resident eval on a held-out set) — deliberately a heavier
workload than the baseline's bare training time, because sustained
trees/sec with live eval is the number that matters for users.

Env overrides: BENCH_ROWS, BENCH_FEATURES, BENCH_LEAVES, BENCH_TREES,
BENCH_WARMUP, BENCH_MAX_BIN, BENCH_GROWTH_MODE, BENCH_MANIFEST_OUT
(run-manifest path; default chiprun_out/).
Voting segment (needs more than one chip):
BENCH_SKIP_VOTING, BENCH_VOTING_TREES, BENCH_VOTING_EXACT_TREES,
BENCH_VOTING_LEAVES, BENCH_VOTING_TOPK.
Ingest segment (out-of-core data plane, docs/DATA_PLANE.md):
BENCH_SKIP_INGEST, BENCH_INGEST_ROWS, BENCH_INGEST_TREES,
BENCH_INGEST_BUDGET_MB, BENCH_INGEST_CHUNK_ROWS.
"""

import json
import os
import sys
import time

import numpy as np

REPO = os.path.dirname(os.path.abspath(__file__))

_STATE = {}


def _final_json():
    """Build the single stdout JSON line of a run that completed."""
    rows = _STATE["rows"]
    leaves = _STATE["leaves"]
    baseline_tps = (500.0 / 130.094) * (10.5e6 / rows)
    tps = _STATE["trees_per_sec"]
    out = {
        "metric": f"higgs_synth_{rows // 1000}k_{leaves}leaves_trees_per_sec",
        "value": round(tps, 4),
        "unit": "trees/sec",
        "vs_baseline": round(tps / baseline_tps, 4),
        "platform": _STATE["platform"],
        "device_kind": _STATE["device_kind"],
        "device_count": _STATE["device_count"],
    }
    if _STATE.get("quantized_trees_per_sec"):
        out["quantized_vs_baseline"] = round(
            _STATE["quantized_trees_per_sec"] / baseline_tps, 4
        )
    for k in ("auc_valid", "trees_done", "warmup_s", "growth_mode",
              "total_trees_per_sec", "quantized", "quantized_trees_per_sec",
              "quantized_total_trees_per_sec", "quantized_auc_valid",
              "voting_trees_per_sec", "voting_exact_trees_per_sec",
              "voting_speedup_vs_exact", "voting_auc_valid",
              "voting_leaves", "voting_devices",
              "ingest_rows", "ingest_features", "ingest_chunks",
              "ingest_ram_budget_mb", "ingest_spool_rows_per_sec",
              "ingest_bin_rows_per_sec", "ingest_fit_trees_per_sec",
              "ingest_peak_rss_mb", "ingest_rss_spread_mb",
              "run_id", "run_manifest"):
        if k in _STATE:
            out[k] = _STATE[k]
    return out


def write_run_manifest(params) -> None:
    """Provenance link (docs/OBSERVABILITY.md): write a run manifest
    (config, device topology, versions, metrics snapshot) and stamp
    its path + run id into the BENCH json, so every result traces back
    to what exactly ran. Written under chiprun_out/ (what the chip tool
    brings back; git-ignored) unless BENCH_MANIFEST_OUT names a path."""
    from lightgbm_tpu.obs.manifest import write_manifest

    mpath = os.environ.get("BENCH_MANIFEST_OUT") or os.path.join(
        REPO, "chiprun_out", "run_manifest_bench.json"
    )
    os.makedirs(os.path.dirname(mpath), exist_ok=True)
    write_manifest(mpath, config=dict(params), extra={
        "bench": "train", "run_id": _STATE["run_id"],
    })
    record(run_manifest=mpath)


def record(**kw):
    """Add fields to the one result line printed at the end."""
    _STATE.update(kw)


def require_accelerator(who: str) -> dict:
    """Initialise the backend IN THIS PROCESS (no probe child: a child
    would hold the chip when the parent asks for it) and refuse
    anything that is not an accelerator. Returns the device identity
    every result carries."""
    import jax

    dev = jax.devices()[0]
    if dev.platform == "cpu":
        sys.exit(
            f"[{who}] backend is {dev.platform!r} "
            f"({dev.device_kind}, {len(jax.devices())} device(s)): this "
            "benchmark measures an accelerator and does not fall back — "
            "run it through the chip tool"
        )
    return {"platform": dev.platform, "device_kind": dev.device_kind,
            "device_count": len(jax.devices())}


def synthetic_higgs(rows: int, feats: int):
    """The Higgs-like generator every chip record of this repo is about
    (seed 17): (X, y) plus min(rows/10, 100k) held-out valid rows that
    are NOT part of the training matrix. chip_smoke.py trains on it."""
    rs = np.random.RandomState(17)
    X = rs.randn(rows, feats).astype(np.float32)
    w = rs.randn(feats)
    logits = X[:, : feats // 2] @ w[: feats // 2] + np.sin(X[:, feats // 2]) * 2.0
    y = (logits + rs.randn(rows) > 0).astype(np.float32)
    nv = min(rows // 10, 100_000)
    Xv = rs.randn(nv, feats).astype(np.float32)
    lv = Xv[:, : feats // 2] @ w[: feats // 2] + np.sin(Xv[:, feats // 2]) * 2.0
    yv = (lv + rs.randn(nv) > 0).astype(np.float32)
    return X, y, Xv, yv


def main() -> None:
    _STATE["run_id"] = f"{int(time.time())}-{os.getpid()}"
    rows = int(os.environ.get("BENCH_ROWS", 1_000_000))
    feats = int(os.environ.get("BENCH_FEATURES", 28))
    leaves = int(os.environ.get("BENCH_LEAVES", 255))
    trees = int(os.environ.get("BENCH_TREES", 100))
    warmup = int(os.environ.get("BENCH_WARMUP", 2))
    max_bin = int(os.environ.get("BENCH_MAX_BIN", 255))
    growth_mode = os.environ.get("BENCH_GROWTH_MODE", "auto")

    sys.path.insert(0, REPO)
    from lightgbm_tpu._cache import ensure_compile_cache

    # persistent XLA compilation cache, placed by the one function that
    # decides (lightgbm_tpu/_cache.py)
    cache_dir = ensure_compile_cache()
    device = require_accelerator("bench")
    sys.stderr.write(f"[bench] {device}, compile cache {cache_dir}\n")

    import lightgbm_tpu as lgb

    record(rows=rows, leaves=leaves, growth_mode=growth_mode,
                 **device)

    X, y, Xv, yv = synthetic_higgs(rows, feats)

    params = {
        "objective": "binary",
        "num_leaves": leaves,
        "max_bin": max_bin,
        "learning_rate": 0.1,
        "min_data_in_leaf": 20,
        "metric": "auc",
        "verbosity": -1,
        "tpu_growth_mode": growth_mode,
    }
    if os.environ.get("BENCH_SLOTS"):
        params["tpu_round_slots"] = int(os.environ["BENCH_SLOTS"])
    if os.environ.get("BENCH_QUANT"):
        # quantized-gradient training (use_quantized_grad): int8 MXU
        # histograms, 48 slots/pass — the reference's quantized mode
        # with its recommended leaf renewal
        params.update(use_quantized_grad=True, num_grad_quant_bins=4,
                      quant_train_renew_leaf=True)
        record(quantized=True)
    t0 = time.time()
    ds = lgb.Dataset(X, label=y, free_raw_data=False)
    ds.construct()
    vs = lgb.Dataset(Xv, label=yv, reference=ds, free_raw_data=False)
    sys.stderr.write(f"[bench] dataset built in {time.time()-t0:.1f}s\n")

    t0 = time.time()
    lgb.train(dict(params), ds, num_boost_round=warmup,
              valid_sets=[vs], valid_names=["v"])
    compile_s = time.time() - t0
    sys.stderr.write(f"[bench] warmup ({warmup} trees) in {compile_s:.1f}s\n")
    record(warmup_s=round(compile_s, 2))

    # Callbacks replay at fused-loop chunk boundaries (engine chunk =
    # _check_every = 64), so consecutive callback wall times within one
    # chunk are compressed; chunk-boundary deltas are REAL sync points.
    # Steady-state trees/s = trees between the first and last boundary
    # over the wall time between them — this excludes the one-time jit
    # trace+lowering the first dispatch pays (the XLA compile itself is
    # served by the persistent cache). Both numbers are reported;
    # `value` is steady-state when >= 2 boundaries exist.
    def timed_train(run_params, n_trees, tag=""):
        """One timed training run; returns (steady, total_tps, auc).

        Steady-state = trees between the first and last chunk-boundary
        callback burst over the wall time between them (excludes the
        one-time jit trace+lowering the first dispatch pays)."""
        marks = []  # (trees_done, wall_time) at observed callback bursts

        def progress(env):
            done = env.iteration + 1
            now = time.time()
            if not marks or done > marks[-1][0]:
                if marks and now - marks[-1][1] < 0.05:
                    marks[-1] = (done, now)  # same replay burst; keep last
                else:
                    marks.append((done, now))
            if done % 10 == 0 or done == n_trees or done <= 3:
                dt = now - t0
                tps = done / dt if dt > 0 else 0.0
                sys.stderr.write(
                    f"[bench] {tag}{done}/{n_trees} trees, {tps:.3f} trees/s\n"
                )
                if not tag:
                    record(trees_done=done, elapsed_s=round(dt, 2),
                                 trees_per_sec=round(tps, 4))

        t0 = time.time()
        bst2 = lgb.train(dict(run_params), ds, num_boost_round=n_trees,
                         valid_sets=[vs], valid_names=["v"],
                         callbacks=[progress])
        dt = time.time() - t0
        total_tps = n_trees / dt
        steady = None
        if len(marks) >= 2:
            # collapse replay bursts: marks within 1 s of the previous
            # mark belong to the same chunk-boundary replay; the LAST
            # mark of each burst is the real sync point
            bursts = [marks[0]]
            for d, w in marks[1:]:
                if w - bursts[-1][1] < 1.0:
                    bursts[-1] = (d, w)
                else:
                    bursts.append((d, w))
            if len(bursts) >= 2:
                (d0, w0), (d1, w1) = bursts[0], bursts[-1]
                if d1 > d0 and w1 > w0:
                    steady = (d1 - d0) / (w1 - w0)
        from sklearn.metrics import roc_auc_score

        auc = round(float(roc_auc_score(yv, bst2.predict(Xv))), 5)
        return steady, total_tps, auc

    steady, total_tps, auc = timed_train(params, trees)
    record(
        trees_per_sec=round(steady if steady else total_tps, 4),
        total_trees_per_sec=round(total_tps, 4),
        trees_done=trees,
    )
    if auc is not None:
        record(auc_valid=auc)

    # second segment: quantized training (use_quantized_grad int8 MXU
    # path — the reference's own "fast mode") as a first-class headline
    # alongside the default run. Skipped when the whole bench is already
    # quantized (BENCH_QUANT) or explicitly disabled.
    if (not os.environ.get("BENCH_QUANT")
            and not os.environ.get("BENCH_SKIP_QUANT")):
        qtrees = int(os.environ.get("BENCH_QUANT_TREES", trees))
        qparams = dict(params, use_quantized_grad=True,
                       num_grad_quant_bins=4, quant_train_renew_leaf=True)
        qsteady, qtotal, qauc = timed_train(
            qparams, qtrees, tag="quant ")
        record(
            quantized_trees_per_sec=round(qsteady or qtotal, 4),
            quantized_total_trees_per_sec=round(qtotal, 4),
        )
        if qauc is not None:
            record(quantized_auc_valid=qauc)

    # ingest segment: the out-of-core data plane (docs/DATA_PLANE.md) —
    # spool the bench matrix to a disk chunk store, stream the two-pass
    # binning, then fit with the double-buffered assembly under a RAM
    # budget far below the raw footprint. Reports spool and bin rows/sec
    # plus the per-chunk RSS spread the flat-memory contract promises.
    if not os.environ.get("BENCH_SKIP_INGEST"):
        irows = int(os.environ.get("BENCH_INGEST_ROWS", rows))
        itrees = int(os.environ.get("BENCH_INGEST_TREES", min(trees, 10)))
        ibudget = int(os.environ.get("BENCH_INGEST_BUDGET_MB", 256))
        from lightgbm_tpu.data import last_stats, reset_stats

        if irows <= rows:
            Xi, yi = X[:irows], y[:irows]
        else:
            # ingest is an I/O-plane measurement — it can run far
            # bigger than the training matrix
            rsi = np.random.RandomState(29)
            Xi = rsi.randn(irows, feats).astype(np.float32)
            yi = (Xi[:, 0] + rsi.randn(irows) > 0).astype(np.float32)
        reset_stats()
        iparams = dict(params, data_source="chunked",
                       ram_budget_mb=ibudget)
        if os.environ.get("BENCH_INGEST_CHUNK_ROWS"):
            iparams["data_chunk_rows"] = int(
                os.environ["BENCH_INGEST_CHUNK_ROWS"])
        ids = lgb.Dataset(Xi, label=yi, params=iparams,
                          free_raw_data=False)
        t0 = time.time()
        if itrees > 0:
            lgb.train(dict(iparams), ids, num_boost_round=itrees)
        else:
            # trees=0: measure the data plane alone — spool, two-pass
            # bin, and the prefetched device assembly — without a
            # training run
            ids.construct()
            ids._binned.device_arrays()
        fit_s = time.time() - t0
        st = last_stats() or {}
        asm = st.get("assemble", {})
        record(
            ingest_rows=irows,
            ingest_features=feats,
            ingest_ram_budget_mb=ibudget,
            ingest_chunks=asm.get("chunks"),
            ingest_spool_rows_per_sec=st.get("spool", {}).get(
                "rows_per_sec"),
            ingest_bin_rows_per_sec=st.get("pass2", {}).get(
                "rows_per_sec"),
            ingest_peak_rss_mb=asm.get("peak_rss_mb"),
            ingest_rss_spread_mb=asm.get("rss_spread_mb"),
        )
        if itrees > 0:
            record(
                ingest_fit_trees_per_sec=round(itrees / fit_s, 4))
        del ids, Xi, yi

    # third segment: voting-parallel (tree_learner=voting riding the
    # rounds grower) against the sequential exact oracle
    # (tpu_growth_mode=exact, permuted.py) on the SAME dataset and leaf
    # budget — so the reported speedup is a same-run measurement, not a
    # cross-artifact quote. The election is a cross-shard psum, so the
    # segment needs more than one chip; both sides
    # downshift leaves (BENCH_VOTING_LEAVES) because the oracle pays one
    # dispatched step per SPLIT and would otherwise eat the budget.
    if not os.environ.get("BENCH_SKIP_VOTING"):
        import jax

        if jax.device_count() > 1:
            vtrees = int(os.environ.get("BENCH_VOTING_TREES",
                                        min(trees, 15)))
            etrees = int(os.environ.get("BENCH_VOTING_EXACT_TREES", 2))
            vleaves = int(os.environ.get("BENCH_VOTING_LEAVES",
                                         min(leaves, 63)))
            vparams = dict(params, tree_learner="voting",
                           top_k=int(os.environ.get("BENCH_VOTING_TOPK", 8)),
                           num_leaves=vleaves, tpu_growth_mode="rounds")
            record(voting_leaves=vleaves,
                         voting_devices=jax.device_count())
            vsteady, vtotal, vauc = timed_train(
                vparams, vtrees, tag="voting ")
            vtps = vsteady or vtotal
            record(voting_trees_per_sec=round(vtps, 4))
            if vauc is not None:
                record(voting_auc_valid=vauc)
            esteady, etotal, _ = timed_train(
                dict(vparams, tpu_growth_mode="exact"), etrees,
                tag="voting-exact ")
            etps = esteady or etotal
            record(
                voting_exact_trees_per_sec=round(etps, 4),
                voting_speedup_vs_exact=(
                    round(vtps / etps, 2) if etps else None),
            )
        else:
            sys.stderr.write(
                "[bench] voting segment skipped: single-device run\n"
            )

    write_run_manifest(params)
    print(json.dumps(_final_json()), flush=True)


if __name__ == "__main__":
    main()
