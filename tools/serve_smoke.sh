#!/usr/bin/env bash
# End-to-end serving smoke test (docs/SERVING.md): train a tiny model
# through the CLI, start the task=serve JSONL loop, score a batch
# through it, and assert parity against Booster.predict on the same
# model file; then bring up the HTTP transport and assert /healthz +
# /metrics Prometheus exposition (docs/OBSERVABILITY.md); then a fleet
# smoke — ~100 models hot-loaded under serve_fleet=true with a small
# residency capacity, scored so the LRU pager churns, one hot-swap,
# one device-TreeSHAP contrib request, and a /metrics scrape asserting
# per-model series; finally an online-loop smoke — task=loop serving
# v0 over HTTP while /v1/ingest streams microbatches, one gated
# promotion to v1, and a /metrics scrape asserting the promotion +
# ingest counters (docs/RESILIENCE.md "Online loop"). CPU-ONLY: it
# starts one serving process after another while earlier ones may still
# hold the backend, and a chip belongs to one process at a time.
set -euo pipefail
cd "$(dirname "$0")/.."
export JAX_PLATFORMS=cpu

WORK=$(mktemp -d)
trap 'rm -rf "$WORK"' EXIT

python - "$WORK" <<'EOF'
import sys
import numpy as np

work = sys.argv[1]
rs = np.random.RandomState(0)
X = rs.randn(800, 5)
y = (X[:, 0] + X[:, 1] > 0).astype(int)
np.savetxt(f"{work}/train.csv",
           np.column_stack([y, X]), delimiter=",", fmt="%.6g")
np.savetxt(f"{work}/score.csv", X[:64, :], delimiter=",", fmt="%.6g")
EOF

python -m lightgbm_tpu task=train "data=$WORK/train.csv" \
    objective=binary num_leaves=15 num_trees=10 verbosity=-1 \
    "output_model=$WORK/model.txt"

python - "$WORK" <<'EOF'
import io
import json
import subprocess
import sys

import numpy as np

work = sys.argv[1]
rows = np.loadtxt(f"{work}/score.csv", delimiter=",").tolist()
reqs = "\n".join(json.dumps(r) for r in [
    {"op": "ping"},
    {"op": "score", "model": "default", "rows": rows},
    {"op": "stats"},
    {"op": "quit"},
])
proc = subprocess.run(
    [sys.executable, "-m", "lightgbm_tpu", "task=serve",
     f"input_model={work}/model.txt", "serve_buckets=16,64",
     "verbosity=-1"],
    input=reqs, capture_output=True, text=True, timeout=300,
)
assert proc.returncode == 0, proc.stderr[-2000:]
resp = [json.loads(l) for l in proc.stdout.splitlines()
        if l.startswith("{")]
assert resp[0]["pong"], resp[0]
served = np.asarray(resp[1]["pred"])
assert resp[2]["stats"]["default"]["count"] >= 1

# parity vs the Python API on the same model file
import lightgbm_tpu as lgb

bst = lgb.Booster(model_file=f"{work}/model.txt")
host = bst.predict(np.asarray(rows))
err = float(np.abs(served - host).max())
assert err < 1e-5, f"serve/host mismatch: {err}"
print(f"serve_smoke: OK ({len(rows)} rows scored, max |diff| {err:.2e})")
EOF

# HTTP transport: /healthz liveness + /readyz readiness + /metrics
# Prometheus exposition (docs/OBSERVABILITY.md) — scrape after scoring
# and assert the exposition carries the serving counters. Liveness and
# readiness are split endpoints (docs/RESILIENCE.md "Serving
# gateway"): the gateway routes traffic on /readyz only.
python - "$WORK" <<'EOF2'
import json
import socket
import subprocess
import sys
import time
import urllib.request

work = sys.argv[1]
s = socket.socket()
s.bind(("127.0.0.1", 0))
port = s.getsockname()[1]
s.close()
proc = subprocess.Popen(
    [sys.executable, "-m", "lightgbm_tpu", "task=serve",
     f"input_model={work}/model.txt", f"serve_port={port}",
     "serve_buckets=16,64", "verbosity=-1"],
    stdout=subprocess.DEVNULL, stderr=subprocess.PIPE, text=True,
)
base = f"http://127.0.0.1:{port}"
try:
    for _ in range(240):
        if proc.poll() is not None:
            raise SystemExit(f"serve exited early: {proc.stderr.read()[-2000:]}")
        try:
            with urllib.request.urlopen(base + "/healthz", timeout=2) as r:
                assert json.loads(r.read())["ok"]
            break
        except OSError:
            time.sleep(0.5)
    else:
        raise SystemExit("serve_http never became healthy")
    # readiness: model loaded + queue under cap + heartbeat fresh
    with urllib.request.urlopen(base + "/readyz", timeout=5) as r:
        ready = json.loads(r.read())
    assert r.status == 200 and ready["ok"], ready
    assert ready["models"] >= 1, ready
    req = urllib.request.Request(
        base + "/v1/score",
        data=json.dumps({"rows": [[0.0] * 5, [1.0] * 5]}).encode(),
        headers={"Content-Type": "application/json"},
    )
    with urllib.request.urlopen(req, timeout=60) as r:
        assert json.loads(r.read())["ok"]
    with urllib.request.urlopen(base + "/metrics", timeout=30) as r:
        ctype = r.headers["Content-Type"]
        text = r.read().decode()
    assert ctype.startswith("text/plain"), ctype
    assert "lgbmtpu_serve_requests_total" in text, text[:500]
    assert "lgbmtpu_serve_protocol_requests_total" in text, text[:500]
    assert "# TYPE" in text
    print("serve_smoke http: OK (/healthz + /metrics exposition)")
finally:
    proc.terminate()
    try:
        proc.wait(timeout=10)
    except subprocess.TimeoutExpired:
        proc.kill()
EOF2

# Fleet smoke (docs/SERVING.md "Fleet serving"): ~100 tenants behind
# one HTTP fleet with residency capacity << fleet size. Asserts: every
# model scores correctly cold or resident, resident stays under the
# cap while evictions climb, hot-swap + contrib work under the fleet,
# and /metrics carries per-model series + the pager gauges.
python - "$WORK" <<'EOF3'
import json
import socket
import subprocess
import sys
import time
import urllib.request

import numpy as np

work = sys.argv[1]
FLEET = 100
CAPACITY = 12
s = socket.socket()
s.bind(("127.0.0.1", 0))
port = s.getsockname()[1]
s.close()
proc = subprocess.Popen(
    [sys.executable, "-m", "lightgbm_tpu", "task=serve",
     f"input_model={work}/model.txt", f"serve_port={port}",
     "serve_fleet=true", f"serve_fleet_capacity={CAPACITY}",
     "serve_buckets=16,64", "verbosity=-1"],
    stdout=subprocess.DEVNULL, stderr=subprocess.PIPE, text=True,
)
base = f"http://127.0.0.1:{port}"


def post(path, body, timeout=120):
    req = urllib.request.Request(
        base + path, data=json.dumps(body).encode(),
        headers={"Content-Type": "application/json"},
    )
    with urllib.request.urlopen(req, timeout=timeout) as r:
        return json.loads(r.read())


try:
    for _ in range(240):
        if proc.poll() is not None:
            raise SystemExit(
                f"fleet serve exited early: {proc.stderr.read()[-2000:]}")
        try:
            with urllib.request.urlopen(base + "/healthz", timeout=2) as r:
                assert json.loads(r.read())["ok"]
            break
        except OSError:
            time.sleep(0.5)
    else:
        raise SystemExit("fleet serve_http never became healthy")

    model_str = open(f"{work}/model.txt").read()
    import lightgbm_tpu as lgb

    bst = lgb.Booster(model_str=model_str)
    rows = np.loadtxt(f"{work}/score.csv", delimiter=",")[:16]
    host = bst.predict(rows)

    for i in range(FLEET):
        out = post("/v1/load", {"model": f"tenant{i:03d}",
                                "model_str": model_str,
                                "deadline_ms": 10000})
        assert out["ok"] and out["version"] == 1, out
    # score every tenant: only CAPACITY can be resident, so this sweep
    # forces ~FLEET-CAPACITY LRU page-outs and every cold hit re-pages
    for i in range(FLEET):
        out = post(f"/v1/score", {"model": f"tenant{i:03d}",
                                  "rows": rows.tolist()})
        err = float(np.abs(np.asarray(out["pred"]) - host).max())
        assert err < 1e-5, f"tenant{i:03d} mismatch: {err}"

    with urllib.request.urlopen(base + "/v1/fleet", timeout=30) as r:
        fl = json.loads(r.read())["fleet"]
    assert fl["models"] >= FLEET, fl  # +1: the CLI's input_model tenant
    assert fl["capacity"] == CAPACITY, fl
    assert fl["resident"] <= CAPACITY < FLEET, fl
    assert fl["evictions"] >= FLEET - CAPACITY, fl
    assert fl["pages_in"] >= FLEET, fl

    # hot-swap one tenant to a fresh version and roll it back
    out = post("/v1/load", {"model": "tenant000", "model_str": model_str})
    assert out["version"] == 2, out
    out = post("/v1/score", {"model": "tenant000", "rows": rows.tolist()})
    assert out["ok"], out
    out = post("/v1/rollback", {"model": "tenant000"})
    assert out["active"] == 1, out

    # device TreeSHAP through the fleet: contributions sum to the
    # booster's raw score per row
    out = post("/v1/contrib", {"model": "tenant001",
                               "rows": rows.tolist()})
    contrib = np.asarray(out["pred"])
    assert contrib.shape == (len(rows), rows.shape[1] + 1), contrib.shape
    raw = bst.predict(rows, raw_score=True)
    serr = float(np.abs(contrib.sum(axis=1) - raw).max())
    assert serr < 1e-3, f"contrib row-sum mismatch: {serr}"

    with urllib.request.urlopen(base + "/metrics", timeout=30) as r:
        text = r.read().decode()
    assert 'model="tenant000"' in text, text[:500]
    assert "lgbmtpu_fleet_page_events_total" in text
    assert "lgbmtpu_fleet_resident_models" in text
    print(f"serve_smoke fleet: OK ({FLEET} tenants, capacity {CAPACITY}, "
          f"resident {fl['resident']}, pages_in {fl['pages_in']}, "
          f"evictions {fl['evictions']})")
finally:
    proc.terminate()
    try:
        proc.wait(timeout=10)
    except subprocess.TimeoutExpired:
        proc.kill()
EOF3

# Online-loop smoke (docs/RESILIENCE.md "Online loop"): task=loop
# serves v0 while /v1/ingest spools labeled microbatches; the loop
# refits, gates on the holdout shard, and promotes v1; /healthz shows
# the loop's durable progress and /metrics carries the promotion,
# ingest, and loop-progress series tools/chaos.sh and dashboards key
# on.
python - "$WORK" <<'EOF4'
import json
import socket
import subprocess
import sys
import time
import urllib.request

import numpy as np

work = sys.argv[1]
rs = np.random.RandomState(17)
HX = rs.randn(200, 5)
Hy = (HX[:, 0] + HX[:, 1] > 0).astype(float)
np.savetxt(f"{work}/holdout.csv", np.column_stack([Hy, HX]),
           delimiter=",", fmt="%.6g")
s = socket.socket()
s.bind(("127.0.0.1", 0))
port = s.getsockname()[1]
s.close()
proc = subprocess.Popen(
    [sys.executable, "-m", "lightgbm_tpu", "task=loop",
     f"input_model={work}/model.txt", f"valid_data={work}/holdout.csv",
     f"serve_port={port}", "objective=binary", "metric=auc",
     "num_leaves=15", f"loop_dir={work}/loop", "loop_min_rows=64",
     "loop_rounds=4", "loop_gate_margin=0.02", "loop_poll_s=0.1",
     "serve_buckets=16,64", "verbosity=-1"],
    stdout=subprocess.DEVNULL, stderr=subprocess.PIPE, text=True,
)
base = f"http://127.0.0.1:{port}"


def post(path, body, timeout=60):
    req = urllib.request.Request(
        base + path, data=json.dumps(body).encode(),
        headers={"Content-Type": "application/json"},
    )
    with urllib.request.urlopen(req, timeout=timeout) as r:
        return json.loads(r.read())


try:
    for _ in range(240):
        if proc.poll() is not None:
            raise SystemExit(
                f"loop serve exited early: {proc.stderr.read()[-2000:]}")
        try:
            with urllib.request.urlopen(base + "/healthz", timeout=2) as r:
                hz = json.loads(r.read())
            assert hz["ok"]
            break
        except OSError:
            time.sleep(0.5)
    else:
        raise SystemExit("loop serve_http never became healthy")
    # /healthz carries the loop's durable state from the first reply
    assert hz["health"]["loop"]["version"] == 0, hz

    # stream two labeled microbatches through the ingest op
    for seed in (61, 62):
        rb = np.random.RandomState(seed)
        X = rb.randn(40, 5)
        y = (X[:, 0] + X[:, 1] > 0).astype(float)
        out = post("/v1/ingest", {"rows": X.tolist(),
                                  "labels": y.tolist()})
        assert out["ok"] and out["rows"] == 40, out

    # await the gated promotion (durable state drives /healthz)
    for _ in range(600):
        if proc.poll() is not None:
            raise SystemExit(
                f"loop serve died: {proc.stderr.read()[-2000:]}")
        with urllib.request.urlopen(base + "/healthz", timeout=5) as r:
            hz = json.loads(r.read())
        if hz["health"]["loop"]["version"] >= 1:
            break
        time.sleep(0.5)
    else:
        raise SystemExit("online loop never promoted v1")
    assert hz["health"]["loop"]["counts"]["promoted"] >= 1, hz

    # v1 serves
    out = post("/v1/score", {"rows": HX[:4].tolist()})
    assert out["ok"], out

    # the promotion/ingest/progress counters are on /metrics
    with urllib.request.urlopen(base + "/metrics", timeout=30) as r:
        text = r.read().decode()
    assert ('lgbmtpu_promotion_events_total{outcome="promoted"}'
            in text), text[:800]
    assert "lgbmtpu_ingest_batches_total" in text
    assert "lgbmtpu_ingest_rows_total" in text
    assert "lgbmtpu_online_version" in text
    print(f"serve_smoke online loop: OK (promoted v1 after 2 ingest "
          f"batches, cycle {hz['health']['loop']['cycle']})")
finally:
    proc.terminate()
    try:
        proc.wait(timeout=10)
    except subprocess.TimeoutExpired:
        proc.kill()
EOF4
