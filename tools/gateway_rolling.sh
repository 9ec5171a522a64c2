#!/usr/bin/env bash
# Zero-downtime rolling restart of a task=gateway fleet
# (docs/RESILIENCE.md "Serving gateway" — the runbook this script
# automates, end to end). CPU-ONLY: it starts N+1 serving processes on
# one machine, and a chip belongs to one process at a time:
#
#   1. train a tiny model and start N task=serve backends + the
#      task=gateway front end;
#   2. run a continuous client against the GATEWAY for the whole
#      exercise, counting every non-200;
#   3. roll each backend in turn: SIGTERM (readyz flips 503, the
#      gateway health loop deregisters it, in-flight requests finish,
#      clean exit) -> restart on the same port -> wait until the
#      gateway routes to it again;
#   4. assert the client saw ZERO failures across the whole roll;
#   5. drain the gateway itself (SIGTERM): new work sheds 503
#      error_kind=shutdown, in-flight finishes, clean exit.
#
# Usage: tools/gateway_rolling.sh [N_BACKENDS]   (default 3)
set -euo pipefail
cd "$(dirname "$0")/.."
export JAX_PLATFORMS=cpu

N="${1:-3}"
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
EOF

python -m lightgbm_tpu task=train "data=$WORK/train.csv" \
    objective=binary num_leaves=15 num_trees=10 verbosity=-1 \
    "output_model=$WORK/model.txt"

python - "$WORK" "$N" <<'EOF'
import json
import signal
import socket
import subprocess
import sys
import threading
import time
import urllib.request

work, n_backends = sys.argv[1], int(sys.argv[2])


def free_port():
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


# readiness-gated warmup is the load-bearing runbook step: with
# serve_warmup=true the registry precompiles every bucket BEFORE the
# HTTP listener binds, so /readyz green implies warm — the gateway
# never routes live traffic onto a cold restarted process (a cold
# first score would stall past the client deadline and shed 503).
# The persistent compile cache (placed by lightgbm_tpu/_cache.py)
# makes each restart's re-warm a cache hit instead of a recompile.


def spawn_backend(port):
    return subprocess.Popen(
        [sys.executable, "-m", "lightgbm_tpu", "task=serve",
         f"input_model={work}/model.txt", f"serve_port={port}",
         "serve_buckets=16,64", "serve_warmup=true", "verbosity=-1"],
        stdout=subprocess.DEVNULL, stderr=subprocess.DEVNULL)


def wait_ready(url, proc, timeout=300):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if proc.poll() is not None:
            raise SystemExit(f"process at {url} died "
                             f"rc={proc.returncode}")
        try:
            with urllib.request.urlopen(url + "/readyz", timeout=2) as r:
                if r.status == 200:
                    return
        except OSError:
            pass
        time.sleep(0.2)
    raise SystemExit(f"{url} never became ready")


ports = [free_port() for _ in range(n_backends)]
urls = [f"http://127.0.0.1:{p}" for p in ports]
procs = [spawn_backend(p) for p in ports]
for u, p in zip(urls, procs):
    wait_ready(u, p)

gw_port = free_port()
gw_url = f"http://127.0.0.1:{gw_port}"
gw = subprocess.Popen(
    [sys.executable, "-m", "lightgbm_tpu", "task=gateway",
     f"gateway_backends={','.join(urls)}", f"gateway_port={gw_port}",
     "gateway_health_interval_s=0.25", "gateway_retries=3",
     "gateway_backoff_base_s=0.02", "verbosity=-1"],
    stdout=subprocess.DEVNULL, stderr=subprocess.DEVNULL)
wait_ready(gw_url, gw)

rows = [[0.1 * i] * 5 for i in range(4)]


def score(timeout=30):
    req = urllib.request.Request(
        gw_url + "/v1/score",
        data=json.dumps({"rows": rows, "deadline_ms": 20000}).encode(),
        headers={"Content-Type": "application/json"})
    with urllib.request.urlopen(req, timeout=timeout) as r:
        return r.status, json.loads(r.read())


# warm every backend through the gateway before the roll
for _ in range(3 * n_backends):
    status, resp = score(timeout=300)
    assert status == 200 and resp["ok"], resp

failures = []
count = [0]
stop = threading.Event()


def client():
    while not stop.is_set():
        try:
            status, resp = score()
            if status != 200:
                failures.append((status, resp))
        except Exception as e:  # noqa: BLE001 — any client error is a failure
            failures.append(repr(e))
        else:
            count[0] += 1


threads = [threading.Thread(target=client, daemon=True)
           for _ in range(3)]
for t in threads:
    t.start()

# roll every backend: SIGTERM -> clean exit -> restart -> ready again
for i, (port, url) in enumerate(zip(ports, urls)):
    procs[i].send_signal(signal.SIGTERM)
    rc = procs[i].wait(timeout=120)
    assert rc == 0, f"backend {url} drain exited rc={rc}"
    procs[i] = spawn_backend(port)
    wait_ready(url, procs[i])
    # let the gateway's health loop fold it back into the pool
    time.sleep(1.0)
    print(f"gateway_rolling: rolled backend {i + 1}/{n_backends} "
          f"({url})", flush=True)

time.sleep(1.0)
stop.set()
for t in threads:
    t.join(timeout=60)
assert not failures, f"client-visible failures during roll: {failures[:5]}"
print(f"gateway_rolling: OK — {count[0]} requests, 0 failures "
      f"across a full roll of {n_backends} backends", flush=True)

# finally: drain the gateway itself
gw.send_signal(signal.SIGTERM)
rc = gw.wait(timeout=120)
assert rc == 0, f"gateway drain exited rc={rc}"
try:
    score(timeout=5)
    raise SystemExit("gateway still answering after drain")
except OSError:
    pass
print("gateway_rolling: OK — gateway drained clean (rc=0)", flush=True)

for p in procs:
    p.terminate()
for p in procs:
    try:
        p.wait(timeout=30)
    except subprocess.TimeoutExpired:
        p.kill()
EOF
