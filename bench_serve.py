"""Serving benchmark: QPS + latency percentiles for the scoring path.

Prints ONE JSON line and writes it to BENCH_SERVE_rNN.json next to the
training BENCH files, so serving performance is tracked
round-over-round exactly like training throughput (ROADMAP item 4; the
artifact always carries "qps", "p50_ms", "p99_ms").

Three phases, one artifact — the comparison is same-run so the two
sides share the trained model, the process, and the machine state:

1. **baseline** — the model in a single-replica ModelRegistry, one
   closed-loop client calling ``registry.predict`` directly (no
   queue).  This is the floor a naive deployment gets.
2. **loaded** (the headline "qps"/"p99_ms") — the same model behind
   ``replicas`` predictor replicas with the continuous-batching
   MicroBatcher front (``registry.batcher``); pipelined async clients
   keep a window of futures outstanding so requests coalesce into
   shared padded device calls.  A fixed probe batch is scored through
   BOTH paths and compared bit-for-bit ("bit_identical") — the speedup
   must not come from answering a different question.
   "speedup_x" = loaded/baseline QPS.
3. **fleet** — the same booster loaded under ``fleet_size`` names into
   a ModelFleet whose HBM ``capacity`` is smaller than the fleet, then
   scored round-robin so LRU paging churns; per-model p99 and the
   pager's counters land in "fleet".
4. **gateway** — cross-process scale-out (docs/RESILIENCE.md "Serving
   gateway"): the same model behind 1 vs N real ``task=serve`` backend
   processes fronted by an in-process Gateway; tenants are fan-out
   loaded and a Zipfian-skewed tenant replay is fired by concurrent
   clients. Per-config QPS/p50/p99 plus the hedge/retry/breaker
   counters read back from the MERGED ``/metrics`` snapshot land in
   "gateway"; "scaleout_x" = many-backend / one-backend QPS.
   REFUSED on an accelerator: a chip belongs to one process, this
   process holds it after phases 1-3, and every ``task=serve`` child
   would need it — "gateway" then carries the reason, not a number.

Like bench.py this measures an accelerator or nothing: a CPU backend
is refused with a non-zero exit, a phase that raises ends the run, and
every result names platform, device kind and device count.

The dispatcher's own observability (queue depth, padded-row waste,
coalesce ratio — what /metrics exports) is snapshotted per phase into
"dispatcher" so the benchmark numbers and the metrics numbers can be
cross-checked.

Env overrides: BENCH_SERVE_TRAIN_ROWS, BENCH_SERVE_FEATURES,
BENCH_SERVE_TREES, BENCH_SERVE_LEAVES, BENCH_SERVE_REQUESTS,
BENCH_SERVE_BATCH (rows per request — 1 by default: the online-request
shape continuous batching exists for), BENCH_SERVE_THREADS
(loaded-phase clients), BENCH_SERVE_WINDOW (outstanding futures per
client), BENCH_SERVE_BASE_REQUESTS, BENCH_SERVE_REPLICAS,
BENCH_SERVE_FLEET_MODELS, BENCH_SERVE_FLEET_CAPACITY,
BENCH_SERVE_FLEET_REQUESTS, BENCH_SERVE_GATEWAY_BACKENDS
(comma-separated backend counts to compare, default "1,4"; empty
skips the phase), BENCH_SERVE_GATEWAY_REQUESTS,
BENCH_SERVE_GATEWAY_THREADS, BENCH_SERVE_GATEWAY_TENANTS,
BENCH_SERVE_GATEWAY_ZIPF (skew exponent),
BENCH_SERVE_OUT (explicit output path),
BENCH_SERVE_DIR (output directory, default: repo root),
BENCH_MANIFEST_OUT (run-manifest path; default under chiprun_out/).
"""

from __future__ import annotations

import glob
import json
import os
import re
import sys
import tempfile
import threading
import time

import numpy as np

REPO = os.path.dirname(os.path.abspath(__file__))
SCHEMA = "lightgbm-tpu/bench-serve/v1"


def _env_int(name: str, default: int) -> int:
    try:
        return int(os.environ.get(name, default))
    except ValueError:
        return default


def _pct(sorted_vals, p: float) -> float:
    if not sorted_vals:
        return 0.0
    i = min(len(sorted_vals) - 1, int(p * (len(sorted_vals) - 1) + 0.5))
    return sorted_vals[i]


def _lat_summary(latencies, wall: float, batch: int) -> dict:
    lat = sorted(latencies)
    done = len(lat)
    return {
        "qps": round(done / wall, 2) if wall > 0 else 0.0,
        "p50_ms": round(1e3 * _pct(lat, 0.50), 4),
        "p95_ms": round(1e3 * _pct(lat, 0.95), 4),
        "p99_ms": round(1e3 * _pct(lat, 0.99), 4),
        "mean_ms": round(1e3 * sum(lat) / done, 4) if lat else 0.0,
        "rows_per_sec": round(done * batch / wall, 1) if wall > 0 else 0.0,
        "requests": done,
        "wall_s": round(wall, 3),
    }


def _fire(predict, n_requests: int, n_threads: int, batch: int,
          n_feat: int) -> dict:
    """Closed-loop clients: n_threads threads each fire their share of
    n_requests calls to ``predict(rows)``; returns the latency summary."""
    latencies: list = []
    lock = threading.Lock()
    per_thread = max(n_requests // max(n_threads, 1), 1)

    def worker(seed: int) -> None:
        wrs = np.random.RandomState(seed)
        mine = []
        for _ in range(per_thread):
            rows = wrs.randn(batch, n_feat).astype(np.float32)
            t = time.perf_counter()
            predict(rows)
            mine.append(time.perf_counter() - t)
        with lock:
            latencies.extend(mine)

    threads = [
        threading.Thread(target=worker, args=(i,), daemon=True)
        for i in range(n_threads)
    ]
    t0 = time.perf_counter()
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    return _lat_summary(latencies, time.perf_counter() - t0, batch)


def _fire_pipelined(submit, n_requests: int, n_threads: int, window: int,
                    batch: int, n_feat: int) -> dict:
    """Pipelined async clients: each thread keeps up to ``window``
    futures outstanding (submit without blocking, collect the oldest
    once the window fills) so the continuous-batching queue stays fed.
    Latency is submit→completion per request."""
    latencies: list = []
    lock = threading.Lock()
    per_thread = max(n_requests // max(n_threads, 1), 1)

    def worker(seed: int) -> None:
        wrs = np.random.RandomState(seed)
        mine: list = []
        outstanding: list = []

        def collect(pair) -> None:
            t_submit, fut = pair
            fut.result()
            mine.append(time.perf_counter() - t_submit)

        for _ in range(per_thread):
            rows = wrs.randn(batch, n_feat).astype(np.float32)
            outstanding.append((time.perf_counter(), submit(rows)))
            if len(outstanding) >= window:
                collect(outstanding.pop(0))
        for pair in outstanding:
            collect(pair)
        with lock:
            latencies.extend(mine)

    threads = [
        threading.Thread(target=worker, args=(i,), daemon=True)
        for i in range(n_threads)
    ]
    t0 = time.perf_counter()
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    return _lat_summary(latencies, time.perf_counter() - t0, batch)


def _serve_counters() -> dict:
    """Summed lgbmtpu_serve_* counter values from the metrics registry
    (labels collapsed) — diffed around a phase to attribute traffic."""
    from lightgbm_tpu.obs.metrics import default_registry

    out: dict = {}
    for name, by_label in default_registry().snapshot().items():
        if name.startswith(("lgbmtpu_serve_", "lgbmtpu_fleet_")):
            out[name] = sum(by_label.values())
    return out


def _dispatcher_view(before: dict, after: dict, rows_scored: int) -> dict:
    """The observability view of one phase: coalescing efficiency and
    padding waste derived from the /metrics counters."""
    d = {k: after.get(k, 0.0) - before.get(k, 0.0)
         for k in after}
    drains = d.get("lgbmtpu_serve_coalesced_batch_rows_count", 0.0)
    coalesced = d.get("lgbmtpu_serve_coalesced_requests_total", 0.0)
    padded = d.get("lgbmtpu_serve_padded_rows_total", 0.0)
    calls = d.get("lgbmtpu_serve_bucket_dispatch_total", 0.0)
    return {
        "device_calls": int(calls),
        "coalesced_requests": int(coalesced),
        "coalesce_ratio": round(coalesced / drains, 3) if drains else 0.0,
        "padded_rows": int(padded),
        "padding_waste_frac": round(
            padded / (padded + rows_scored), 4
        ) if rows_scored else 0.0,
        "queue_depth": after.get("lgbmtpu_serve_queue_depth", 0.0),
    }


def _counter_family(merged: dict, name: str) -> dict:
    fam = (merged.get("metrics") or {}).get(name) or {}
    return {k: v for k, v in (fam.get("values") or {}).items()}


# the resilience counters the gateway phase reports per config
_GW_FAMILIES = (
    "lgbmtpu_gateway_hedges_total",
    "lgbmtpu_gateway_retries_total",
    "lgbmtpu_gateway_breaker_transitions_total",
    "lgbmtpu_gateway_attempts_total",
)


def _diff_counters(cur: dict, floor: dict) -> dict:
    """Per-config view of process-cumulative counters: cur - floor,
    zero rows dropped (label keys render identically in the registry
    snapshot and the merged pane)."""
    out = {}
    for k, v in cur.items():
        d = float(v) - float(floor.get(k, 0.0))
        if d:
            out[k] = int(d) if d.is_integer() else d
    return out


def _gateway_phase(model_file: str, model_str: str, n_feat: int,
                   batch: int) -> dict | None:
    """Phase 4: 1 vs N real task=serve backend processes behind an
    in-process Gateway, Zipfian tenant replay, counters read back from
    the merged /metrics snapshot. Returns None when disabled
    (BENCH_SERVE_GATEWAY_BACKENDS empty) and a {"refused": reason}
    record on an accelerator (one process per chip)."""
    import socket
    import subprocess
    import urllib.request

    import jax

    from lightgbm_tpu.serving.gateway import Gateway

    spec = os.environ.get("BENCH_SERVE_GATEWAY_BACKENDS", "1,4")
    counts = [int(x) for x in spec.split(",") if x.strip()]
    if not counts:
        return None
    if jax.default_backend() != "cpu":
        return {"refused": (
            f"this process holds the {jax.default_backend()} after "
            "phases 1-3 and every task=serve backend process would need "
            "it too (one process per chip); the phase needs a parent "
            "that never initialises a backend"
        )}
    n_requests = _env_int("BENCH_SERVE_GATEWAY_REQUESTS", 600)
    n_threads = _env_int("BENCH_SERVE_GATEWAY_THREADS", 6)
    n_tenants = _env_int("BENCH_SERVE_GATEWAY_TENANTS", 4)
    zipf_a = float(os.environ.get("BENCH_SERVE_GATEWAY_ZIPF", "1.2"))

    # Zipf-by-rank tenant weights: tenant r gets 1/(r+1)^a of the
    # traffic — the skew multi-tenant serving actually sees
    tenants = [f"tenant{t:02d}" for t in range(n_tenants)]
    w = np.array([1.0 / (r + 1) ** zipf_a for r in range(n_tenants)])
    w /= w.sum()
    replay = np.random.RandomState(11).choice(n_tenants,
                                              size=n_requests, p=w)

    def free_port() -> int:
        s = socket.socket()
        s.bind(("127.0.0.1", 0))
        port = s.getsockname()[1]
        s.close()
        return port

    def spawn(port: int):
        return subprocess.Popen(
            [sys.executable, "-m", "lightgbm_tpu", "task=serve",
             f"input_model={model_file}", f"serve_port={port}",
             "serve_buckets=16,64", "serve_warmup=true",
             "verbosity=-1"],
            stdout=subprocess.DEVNULL, stderr=subprocess.DEVNULL)

    def wait_ready(url: str, proc, timeout: float = 600.0) -> None:
        deadline = time.monotonic() + timeout
        while time.monotonic() < deadline:
            if proc.poll() is not None:
                raise RuntimeError(f"bench backend died "
                                   f"rc={proc.returncode}")
            try:
                with urllib.request.urlopen(url + "/readyz",
                                            timeout=2) as r:
                    if r.status == 200:
                        return
            except OSError:
                pass
            time.sleep(0.2)
        raise RuntimeError(f"bench backend at {url} never ready")

    rs = np.random.RandomState(3)
    rows = rs.randn(batch, n_feat).astype(np.float32).tolist()
    configs: dict = {}
    for k in counts:
        ports = [free_port() for _ in range(k)]
        urls = [f"http://127.0.0.1:{p}" for p in ports]
        procs = [spawn(p) for p in ports]
        gw = None
        try:
            for u, p in zip(urls, procs):
                wait_ready(u, p)
            gw = Gateway(urls, retries=3, backoff_base_s=0.02,
                         health_interval_s=0.5, hedge_budget=0.1,
                         attempt_timeout_s=60.0)
            gw.start(wait_ready_s=30.0)
            # the gateway records into the bench process's registry, so
            # counters are cumulative across configs: floor them here
            # and report per-config deltas
            from lightgbm_tpu.obs.metrics import default_registry
            snap = default_registry().snapshot()
            floor = {name: dict(snap.get(name) or {})
                     for name in _GW_FAMILIES}
            for t in tenants:
                status, resp = gw.handle("load", {
                    "model": t, "model_str": model_str,
                    "num_features": n_feat})
                if status != 200:
                    raise RuntimeError(f"tenant load failed: {resp}")
            # warm every (tenant, backend) pair off the clock
            for _ in range(2 * k):
                for t in tenants:
                    status, _ = gw.handle("score",
                                          {"model": t, "rows": rows})
                    if status != 200:
                        raise RuntimeError("warmup score failed")
            lat: list = []
            lat_lock = threading.Lock()
            failures = [0]
            cursor = [0]

            def worker() -> None:
                local: list = []
                while True:
                    with lat_lock:
                        i = cursor[0]
                        if i >= n_requests:
                            break
                        cursor[0] += 1
                    t0 = time.perf_counter()
                    status, _resp = gw.handle("score", {
                        "model": tenants[replay[i]], "rows": rows,
                        "deadline_ms": 60000})
                    dt = time.perf_counter() - t0
                    if status == 200:
                        local.append(dt)
                    else:
                        with lat_lock:
                            failures[0] += 1
                with lat_lock:
                    lat.extend(local)

            t0 = time.perf_counter()
            threads = [threading.Thread(target=worker)
                       for _ in range(n_threads)]
            for th in threads:
                th.start()
            for th in threads:
                th.join()
            wall = time.perf_counter() - t0
            summary = _lat_summary(lat, wall, batch)
            summary["threads"] = n_threads
            summary["failures"] = failures[0]
            # resilience counters come from the MERGED /metrics pane —
            # the same single-pane view operators scrape
            merged = gw.merged_metrics()
            summary["merged_processes"] = merged.get("processes")
            for label, fam in (("hedges", _GW_FAMILIES[0]),
                               ("retries", _GW_FAMILIES[1]),
                               ("breaker_transitions", _GW_FAMILIES[2]),
                               ("attempts", _GW_FAMILIES[3])):
                summary[label] = _diff_counters(
                    _counter_family(merged, fam), floor[fam])
            configs[f"backends_{k}"] = summary
        finally:
            if gw is not None:
                gw.stop()
            for p in procs:
                if p.poll() is None:
                    p.kill()
            for p in procs:
                try:
                    p.wait(timeout=30)
                except subprocess.TimeoutExpired:
                    pass
    lo, hi = min(counts), max(counts)
    out = {
        "requests": n_requests,
        "threads": n_threads,
        "tenants": n_tenants,
        "zipf_a": zipf_a,
        "configs": configs,
    }
    if lo != hi:
        base_qps = configs[f"backends_{lo}"]["qps"]
        out["scaleout_x"] = (
            round(configs[f"backends_{hi}"]["qps"] / base_qps, 2)
            if base_qps else 0.0)
    return out


def run_bench() -> dict:
    from bench import require_accelerator

    device = require_accelerator("bench_serve")

    import lightgbm_tpu as lgb
    from lightgbm_tpu.serving import ModelFleet, ModelRegistry

    train_rows = _env_int("BENCH_SERVE_TRAIN_ROWS", 20000)
    n_feat = _env_int("BENCH_SERVE_FEATURES", 16)
    n_trees = _env_int("BENCH_SERVE_TREES", 50)
    n_leaves = _env_int("BENCH_SERVE_LEAVES", 31)
    n_requests = _env_int("BENCH_SERVE_REQUESTS", 8192)
    base_requests = _env_int("BENCH_SERVE_BASE_REQUESTS", 256)
    batch = _env_int("BENCH_SERVE_BATCH", 1)
    n_threads = _env_int("BENCH_SERVE_THREADS", 8)
    window = _env_int("BENCH_SERVE_WINDOW", 128)
    replicas = _env_int("BENCH_SERVE_REPLICAS", 2)
    fleet_models = _env_int("BENCH_SERVE_FLEET_MODELS", 6)
    fleet_capacity = _env_int("BENCH_SERVE_FLEET_CAPACITY", 4)
    fleet_requests = _env_int("BENCH_SERVE_FLEET_REQUESTS", 60)

    rs = np.random.RandomState(0)
    X = rs.randn(train_rows, n_feat).astype(np.float32)
    y = (X[:, 0] + 0.5 * X[:, 1] > 0).astype(np.float32)
    ds = lgb.Dataset(X, label=y, free_raw_data=False)
    t0 = time.perf_counter()
    bst = lgb.train(
        {"objective": "binary", "num_leaves": n_leaves, "verbosity": -1},
        ds, num_boost_round=n_trees,
    )
    train_s = time.perf_counter() - t0
    probe = rs.randn(64, n_feat).astype(np.float32)
    warm = rs.randn(batch, n_feat).astype(np.float32)

    # ---- phase 1: single replica, direct path, one closed-loop client
    # (raw margins on both sides: the comparison measures serving, not
    # the objective's output transform)
    baseline_reg = ModelRegistry(warmup=True)
    baseline_reg.load("bench", bst, num_features=n_feat)
    for _ in range(3):  # compiles + first-dispatch costs off the clock
        baseline_reg.predict("bench", warm, raw_score=True)
        baseline_reg.predict("bench", probe, raw_score=True)
    baseline = _fire(
        lambda rows: baseline_reg.predict("bench", rows, raw_score=True),
        base_requests, 1, batch, n_feat,
    )
    baseline["threads"] = 1
    baseline_pred = np.asarray(baseline_reg.predict("bench", probe))

    # ---- phase 2: N replicas + continuous batching, pipelined clients
    loaded_reg = ModelRegistry(warmup=True, replicas=replicas)
    loaded_reg.load("bench", bst, num_features=n_feat)
    batcher = loaded_reg.batcher("bench")
    for _ in range(3):
        batcher.submit(warm).result()
        loaded_reg.predict("bench", probe, via_queue=True)
    before = _serve_counters()
    loaded = _fire_pipelined(
        batcher.submit, n_requests, n_threads, window, batch, n_feat,
    )
    loaded["threads"] = n_threads
    dispatcher = _dispatcher_view(
        before, _serve_counters(), loaded["requests"] * batch)
    # the speedup must answer the SAME question: probe scored through
    # the coalescing multi-replica path must match the direct baseline
    # bit for bit
    loaded_pred = np.asarray(
        loaded_reg.predict("bench", probe, via_queue=True))
    bit_identical = bool(np.array_equal(baseline_pred, loaded_pred))
    speedup = (round(loaded["qps"] / baseline["qps"], 2)
               if baseline["qps"] else 0.0)

    # ---- phase 3: multi-tenant fleet with LRU paging churn
    fleet = ModelFleet(capacity=fleet_capacity)
    names = [f"bench{i:02d}" for i in range(fleet_models)]
    for name in names:
        fleet.load(name, bst, num_features=n_feat)
    per_model: dict = {name: [] for name in names}
    t0 = time.perf_counter()
    for i in range(fleet_requests):
        name = names[i % len(names)]
        rows = rs.randn(batch, n_feat).astype(np.float32)
        t = time.perf_counter()
        fleet.predict(name, rows)
        per_model[name].append(time.perf_counter() - t)
    fleet_wall = time.perf_counter() - t0
    fstats = fleet.fleet_stats()
    fleet_result = {
        "fleet_size": fleet_models,
        "capacity": fleet_capacity,
        "resident": fstats.get("resident"),
        "pages_in": fstats.get("pages_in"),
        "evictions": fstats.get("evictions"),
        "qps": round(fleet_requests / fleet_wall, 2) if fleet_wall else 0.0,
        "per_model_p99_ms": {
            name: round(1e3 * _pct(sorted(v), 0.99), 4)
            for name, v in per_model.items()
        },
    }
    fleet.close()

    # ---- phase 4: cross-process scale-out behind the gateway
    with tempfile.NamedTemporaryFile(
            mode="w", suffix=".txt", delete=False) as f:
        model_file = f.name
        f.write(bst.model_to_string())
    try:
        gateway_result = _gateway_phase(
            model_file, bst.model_to_string(), n_feat, batch)
    finally:
        os.unlink(model_file)

    result = {
        "schema": SCHEMA,
        "metric": "serve_score_qps",
        **loaded,  # headline qps/p50/p99 = the replicated, batched path
        "batch_rows": batch,
        "via_queue": True,
        "window": window,
        "replicas": replicas,
        "baseline": baseline,
        "speedup_x": speedup,
        "bit_identical": bit_identical,
        "dispatcher": dispatcher,
        "fleet_size": fleet_models,
        "models": names,
        "fleet": fleet_result,
        "gateway": gateway_result,
        "model": {"trees": n_trees, "leaves": n_leaves,
                  "features": n_feat, "train_rows": train_rows,
                  "train_s": round(train_s, 2)},
        **device,
        # the observability view of the same run (LatencyStats ring —
        # what /metrics and the stats op report)
        "stats": loaded_reg.stats().get("bench", {}),
        "created_unix": time.time(),
        "run_id": f"{int(time.time())}-{os.getpid()}",
    }
    return result


def _next_out_path() -> str:
    if os.environ.get("BENCH_SERVE_OUT"):
        return os.environ["BENCH_SERVE_OUT"]
    out_dir = os.environ.get("BENCH_SERVE_DIR", REPO)
    rounds = [0]
    for p in glob.glob(os.path.join(out_dir, "BENCH_SERVE_r*.json")):
        m = re.search(r"BENCH_SERVE_r(\d+)\.json$", p)
        if m:
            rounds.append(int(m.group(1)))
    return os.path.join(out_dir, f"BENCH_SERVE_r{max(rounds) + 1:02d}.json")


def _manifest_path(out: str) -> str:
    """Run manifests live under chiprun_out/ (what the chip tool brings
    back; git-ignored) like bench.py's. The path is stamped into the
    artifact so the result still traces back to what ran;
    BENCH_MANIFEST_OUT overrides."""
    if os.environ.get("BENCH_MANIFEST_OUT"):
        return os.environ["BENCH_MANIFEST_OUT"]
    run_dir = os.path.join(REPO, "chiprun_out")
    os.makedirs(run_dir, exist_ok=True)
    m = re.search(r"BENCH_SERVE_r(\d+)\.json$", out)
    name = (f"run_manifest_serve_r{m.group(1)}.json" if m
            else "run_manifest_serve.json")
    return os.path.join(run_dir, name)


def main() -> int:
    result = run_bench()
    out = _next_out_path()
    # provenance link: a run manifest (config + device topology +
    # metrics snapshot) under the run dir, path stamped into the json
    # so the trajectory point traces back to what ran
    mpath = _manifest_path(out)
    from lightgbm_tpu.obs.manifest import write_manifest

    write_manifest(mpath, extra={
        "bench": "serve", "run_id": result["run_id"],
        "artifact": out,
    })
    result["run_manifest"] = mpath
    with open(out, "w") as f:
        json.dump(result, f, indent=2, sort_keys=True)
        f.write("\n")
    result["artifact"] = out
    print(json.dumps(result))
    return 0


if __name__ == "__main__":
    sys.exit(main())
