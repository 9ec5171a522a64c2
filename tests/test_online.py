"""Online train-and-serve loop (lightgbm_tpu/online, docs/RESILIENCE.md
"Online loop").

The contract under test, end to end: the loop serves v(n) from a
ModelRegistry while microbatches stream through the serving ``ingest``
op into a durable spool; each verdict cycle refits a warm-started
candidate (``init_score`` = v(n)'s raw margins, spliced with
``boosting.splice_continued`` so v(n) is a bit-exact prefix of v(n+1)),
judges it on a fixed holdout shard with device metrics, and atomically
promotes — or rejects a regression, or auto-reverts a poisoned
microbatch — while concurrent scorers only ever see a complete version.
Crash consistency: a fault injected at ANY loop phase
(``loop_ingest`` / ``loop_refit`` / ``loop_eval`` / ``loop_promote``,
resilience/faultinject.py) leaves a restart serving the last PERSISTED
promotion, in-process (raise) and for the real CLI process (SIGKILL).
The ``chaos`` marker ties the fault matrix to tools/chaos.sh."""

from __future__ import annotations

import json
import os
import subprocess
import sys
import threading
import time
from pathlib import Path

import numpy as np
import pytest

import lightgbm_tpu as lgb
from lightgbm_tpu.obs.metrics import default_registry
from lightgbm_tpu.online import (
    IngestSpool,
    OnlineLoop,
    decide,
    fresh_state,
    load_state,
    model_path,
    save_state,
    spool_path,
    stack_batches,
    state_path,
)
from lightgbm_tpu.resilience import faultinject
from lightgbm_tpu.resilience.errors import CheckpointError, InjectedFault

REPO = Path(__file__).resolve().parent.parent


@pytest.fixture(autouse=True)
def _disarm_fault_plan():
    """Chaos tests arm process-global fault plans; none may leak."""
    yield
    faultinject.disarm()


# ------------------------------------------------------------- fixtures
def _xy(seed: int, n: int):
    rs = np.random.RandomState(seed)
    X = rs.randn(n, 4)
    y = (X[:, 0] + X[:, 1] > 0).astype(np.float64)
    return X, y


_CORE = {
    "objective": "binary", "metric": "auc", "num_leaves": 7,
    "min_data_in_leaf": 5, "learning_rate": 0.2, "verbosity": -1,
    "seed": 7,
}


def _train_v0():
    X, y = _xy(5, 300)
    ds = lgb.Dataset(X, label=y, free_raw_data=False)
    return lgb.train(dict(_CORE), ds, num_boost_round=6)


def _holdout():
    return _xy(9, 200)


def _params(tmp_path, **over):
    p = dict(_CORE)
    p.update({
        "loop_dir": str(tmp_path / "loop"), "loop_min_rows": 64,
        "loop_rounds": 4, "loop_poll_s": 0.05,
    })
    p.update(over)
    return p


def _batch(seed: int, n: int = 40):
    X, y = _xy(seed, n)
    return X.tolist(), y.tolist()


# ========================================================= ingest spool
def test_spool_roundtrip_and_torn_tail(tmp_path):
    sp = IngestSpool(spool_path(str(tmp_path)))
    rows, labels = _batch(20, 3)
    out = sp.append(rows, labels)
    assert out["rows"] == 3 and out["offset"] == sp.size()
    out2 = sp.append(rows, labels, weights=[1.0, 2.0, 3.0])
    batches, end = sp.read_from(0)
    assert len(batches) == 2 and end == out2["offset"] == sp.size()
    X, y, w = stack_batches(batches)
    assert X.shape == (6, 4) and y.shape == (6,)
    # mixed weighted/unweighted batches: missing weights become 1.0
    np.testing.assert_array_equal(w, [1, 1, 1, 1, 2, 3])
    # resuming from the end sees nothing new
    assert sp.read_from(end) == ([], end)

    # a torn tail (crash mid-append: no trailing newline) is left
    # unconsumed — the offset never advances past the tear
    with open(sp.path, "a") as f:
        f.write('{"rows": [[1.0')
    batches2, end2 = sp.read_from(0)
    assert len(batches2) == 2 and end2 == end

    # validation: bad microbatches are rejected before touching disk
    for bad in (lambda: sp.append([], []),
                lambda: sp.append(rows, labels[:-1]),
                lambda: sp.append([[1.0], [1.0, 2.0]], [0.0, 1.0]),
                lambda: sp.append(rows, labels, weights=[1.0])):
        with pytest.raises(ValueError):
            bad()
    assert sp.size() == end + len('{"rows": [[1.0')


def test_state_roundtrip_and_errors(tmp_path):
    sp = state_path(str(tmp_path))
    st = fresh_state()
    st["version"] = 3
    st["model_path"] = model_path(str(tmp_path), 3)
    save_state(sp, st)
    assert load_state(sp) == st
    assert not os.path.exists(sp + ".tmp")  # atomic publish, no residue

    torn = tmp_path / "torn.json"
    torn.write_text('{"schema": "lightgbm-tpu/online-loop/v1", "ver')
    with pytest.raises(CheckpointError, match="corrupt"):
        load_state(str(torn))
    alien = tmp_path / "alien.json"
    alien.write_text(json.dumps({"schema": "something/else"}))
    with pytest.raises(CheckpointError, match="schema"):
        load_state(str(alien))
    inc = tmp_path / "inc.json"
    inc.write_text(json.dumps(
        {"schema": "lightgbm-tpu/online-loop/v1", "version": 1}))
    with pytest.raises(CheckpointError, match="missing"):
        load_state(str(inc))
    with pytest.raises(CheckpointError, match="cannot read"):
        load_state(str(tmp_path / "absent.json"))


# ======================================================= promotion gate
def test_gate_decide():
    # anomaly trips veto before any metric comparison
    out, why = decide([0.9], [0.5], ["auc"], [True], 0.0,
                      {"loss_spike": 1})
    assert out == "rolled_back" and "loss_spike" in why
    # zero-count trips do not
    assert decide([0.9], [0.5], ["auc"], [True], 0.0,
                  {"loss_spike": 0})[0] == "promoted"
    # higher_better: candidate must not fall below incumbent - margin
    assert decide([0.84], [0.85], ["auc"], [True], 0.0, {})[0] == \
        "rejected"
    assert decide([0.84], [0.85], ["auc"], [True], 0.02, {})[0] == \
        "promoted"
    # lower-better metrics compare the other way
    assert decide([0.50], [0.40], ["binary_logloss"], [False],
                  0.0, {})[0] == "rejected"
    assert decide([0.39], [0.40], ["binary_logloss"], [False],
                  0.0, {})[0] == "promoted"
    # only the FIRST metric gates; a fresh start has no incumbent
    assert decide([0.9, 9.9], [0.5, 0.1], ["auc", "binary_logloss"],
                  [True, False], 0.0, {})[0] == "promoted"
    assert decide([0.2], None, ["auc"], [True], 0.0, {})[0] == \
        "promoted"


# ================================= end-to-end: promote under scoring
@pytest.mark.chaos
@pytest.mark.slow
def test_promote_splice_exact_and_concurrent_swap(tmp_path):
    """Serve v0, stream microbatches, refit v1, gate, auto-promote:
    v0 is a bit-exact prefix of v1 (splice_continued), the registry
    swap is atomic under concurrent scoring (every prediction matches
    v0 or v1, never a torn mix), and the verdict lands in the durable
    state + /metrics counters + the loop's event log."""
    from lightgbm_tpu.serving import ModelRegistry

    v0 = _train_v0()
    HX, Hy = _holdout()
    loop = OnlineLoop(_params(tmp_path), (HX, Hy), initial_model=v0)
    registry = ModelRegistry()
    loop.attach(registry)
    assert registry.ingest_sink is loop.spool
    assert registry.health_probe == loop.health

    # ingest through the registry attachment, as the serving op does
    for seed in (31, 32):
        registry.ingest_sink.append(*_batch(seed, 40))

    probe = HX[:16]
    pred_v0 = v0.predict(probe)
    stop = threading.Event()
    seen, errs = [], []

    def scorer():
        try:
            while not stop.is_set():
                seen.append(np.asarray(registry.predict("default", probe)))
        except Exception as e:  # pragma: no cover - surfaced below
            errs.append(e)

    threads = [threading.Thread(target=scorer) for _ in range(2)]
    for t in threads:
        t.start()
    promo = default_registry().counter(
        "lgbmtpu_promotion_events_total", labels=("outcome",))
    before = promo.value(outcome="promoted")
    try:
        outcome = loop.cycle()
    finally:
        stop.set()
        for t in threads:
            t.join(timeout=30)
    assert not errs, errs
    assert outcome == "promoted"
    assert promo.value(outcome="promoted") == before + 1

    st = load_state(state_path(loop.loop_dir))
    assert st["version"] == 1 and st["counts"]["promoted"] == 1
    assert st["last_outcome"] == "promoted"
    assert st["ingest_offset"] == loop.spool.size()
    v1 = lgb.Booster(model_file=st["model_path"])
    assert v1.num_trees() == v0.num_trees() + loop.rounds

    # warm-start splice exactness: the first num_trees(v0) trees of v1
    # ARE v0 — raw scores bit-match
    np.testing.assert_array_equal(
        v1.predict(HX, raw_score=True, num_iteration=v0.num_trees()),
        v0.predict(HX, raw_score=True),
    )

    # atomicity under swap: every concurrent prediction is exactly one
    # whole version's output
    pred_v1 = v1.predict(probe)
    assert len(seen) > 0
    for p in seen:
        ok_v0 = np.allclose(p, pred_v0, rtol=1e-5, atol=1e-6)
        ok_v1 = np.allclose(p, pred_v1, rtol=1e-5, atol=1e-6)
        assert ok_v0 or ok_v1, "scored a torn model version"
    # and the registry now serves v1
    np.testing.assert_allclose(registry.predict("default", probe),
                               pred_v1, rtol=1e-5, atol=1e-6)

    # provenance: event log + health reflect the verdict
    events = [json.loads(l) for l in
              open(os.path.join(loop.loop_dir, "loop_events.jsonl"))]
    assert events[-1]["outcome"] == "promoted"
    assert events[-1]["serving_version"] == 1
    h = loop.health()
    assert h["loop"]["version"] == 1
    assert h["loop"]["spool_backlog_bytes"] == 0


@pytest.mark.chaos
@pytest.mark.slow
def test_poison_reverts_regression_rejects_then_recovers(tmp_path):
    """The gate's three verdicts in sequence on one loop: a poisoned
    microbatch (labels the trainer rejects) auto-reverts, label-flipped
    rows regress the holdout metric and are rejected, and a clean batch
    then promotes — the spool offset advances past EVERY verdict so bad
    data is discarded, never re-consumed."""
    v0 = _train_v0()
    HX, Hy = _holdout()
    loop = OnlineLoop(_params(tmp_path), (HX, Hy), initial_model=v0)

    # poison: NaN labels fail objective label validation inside refit
    rows, labels = _batch(41, 80)
    loop.spool.append(rows, [float("nan")] * len(labels))
    assert loop.cycle() == "rolled_back"
    st = load_state(state_path(loop.loop_dir))
    assert st["version"] == 0 and st["counts"]["rolled_back"] == 1
    off_after_poison = st["ingest_offset"]
    assert off_after_poison == loop.spool.size()  # poison discarded

    # regression: flipped labels train a candidate whose holdout auc
    # falls below the incumbent's -> rejected, v0 keeps serving
    rows, labels = _batch(42, 80)
    loop.spool.append(rows, [1.0 - v for v in labels])
    assert loop.cycle() == "rejected"
    st = load_state(state_path(loop.loop_dir))
    assert st["version"] == 0 and st["counts"]["rejected"] == 1
    assert st["ingest_offset"] > off_after_poison

    # a clean batch after the bad ones promotes normally
    loop.spool.append(*_batch(43, 80))
    assert loop.cycle() == "promoted"
    st = load_state(state_path(loop.loop_dir))
    assert st["version"] == 1 and st["counts"] == \
        {"promoted": 1, "rejected": 1, "rolled_back": 1}
    # below loop_min_rows new bytes: no verdict
    loop.spool.append(*_batch(44, 8))
    assert loop.cycle() is None


# ==================================== fault matrix: raise + restart
@pytest.mark.chaos
@pytest.mark.slow
def test_loop_fault_matrix_inprocess(tmp_path):
    """A fault at EVERY loop phase leaves a restart serving the last
    persisted promotion: state untouched (version 0, offset 0), the
    spool replayable, and the re-attached registry scoring v0 exactly.
    A delay clause only stretches the cycle."""
    from lightgbm_tpu.serving import ModelRegistry

    v0 = _train_v0()
    HX, Hy = _holdout()
    params = _params(tmp_path)
    loop = OnlineLoop(params, (HX, Hy), initial_model=v0)
    for seed in (51, 52):
        loop.spool.append(*_batch(seed, 40))
    probe = HX[:8]
    pred_v0 = v0.predict(probe)

    for site in ("loop_ingest", "loop_refit", "loop_eval",
                 "loop_promote"):
        plan = f"{site}:0:raise"
        faultinject.configure(plan)
        crash = OnlineLoop(dict(params, fault_plan=plan), (HX, Hy))
        with pytest.raises(InjectedFault):
            crash.cycle()
        faultinject.disarm()
        # "restart": a fresh loop over the same durable directory
        re = OnlineLoop(params, (HX, Hy))
        st = re.state
        assert st["version"] == 0, site
        assert st["ingest_offset"] == 0, site  # cycle will replay
        assert st["counts"] == {"promoted": 0, "rejected": 0,
                                "rolled_back": 0}, site
        reg = ModelRegistry(warmup=False)
        re.attach(reg)
        np.testing.assert_allclose(reg.predict("default", probe),
                                   pred_v0, rtol=1e-5, atol=1e-6)

    # delayed ingest: the cycle completes, just late
    plan = "loop_ingest:0:delay:0.2"
    faultinject.configure(plan)
    slow = OnlineLoop(dict(params, fault_plan=plan), (HX, Hy))
    t0 = time.monotonic()
    assert slow.cycle() == "promoted"
    assert time.monotonic() - t0 >= 0.2
    assert slow.state["version"] == 1
    # the loop_eval crash left an orphan candidate file; the completed
    # cycle overwrote it with the promoted v1
    v1 = lgb.Booster(model_file=model_path(slow.loop_dir, 1))
    np.testing.assert_array_equal(
        v1.predict(HX, raw_score=True, num_iteration=v0.num_trees()),
        v0.predict(HX, raw_score=True))


# ============================== fault matrix: SIGKILL'd CLI process
@pytest.mark.chaos
@pytest.mark.slow
@pytest.mark.parametrize("site", ["loop_ingest", "loop_refit",
                                  "loop_eval", "loop_promote"])
def test_sigkill_cli_loop_restart(tmp_path, site):
    """The real thing, per loop phase: a ``task=loop`` CLI process
    SIGKILLed by fault plan ``<site>:0:kill`` (no cleanup, no flush)
    restarts with the last promoted version serving, replays the
    spooled microbatches, and promotes v1 — scored through the
    restarted process's own transport."""
    v0 = _train_v0()
    (tmp_path / "model.txt").write_text(v0.model_to_string())
    HX, Hy = _holdout()
    np.savetxt(tmp_path / "holdout.csv",
               np.column_stack([Hy, HX]), delimiter=",", fmt="%.8g")
    loop_dir = str(tmp_path / "loop")
    args = [
        sys.executable, "-m", "lightgbm_tpu", "task=loop",
        f"input_model={tmp_path}/model.txt",
        f"valid_data={tmp_path}/holdout.csv",
        "objective=binary", "metric=auc", "num_leaves=7",
        "min_data_in_leaf=5", "learning_rate=0.2", "seed=7",
        f"loop_dir={loop_dir}", "loop_min_rows=64", "loop_rounds=4",
        # v0 nearly saturates this holdout (auc ~0.987): allow the
        # usual tiny refit jitter or the near-tie gate rejects forever
        "loop_gate_margin=0.02",
        "loop_poll_s=0.1", "verbosity=-1",
    ]
    # cwd=REPO so the package resolves
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop(faultinject.ENV_VAR, None)
    ingest_lines = "".join(
        json.dumps({"op": "ingest", "rows": r, "labels": l}) + "\n"
        for r, l in (_batch(61, 40), _batch(62, 40)))

    # phase 1: arm the kill, feed the spool, watch the process die -9
    proc = subprocess.Popen(
        args + [f"fault_plan={site}:0:kill"], cwd=str(REPO),
        env=env, stdin=subprocess.PIPE, stdout=subprocess.PIPE,
        stderr=subprocess.PIPE, text=True,
    )
    try:
        proc.stdin.write(ingest_lines)
        proc.stdin.flush()
    except BrokenPipeError:
        pass  # loop_ingest kills on the first poll, before any ingest
    try:
        proc.wait(timeout=300)
    finally:
        if proc.poll() is None:
            proc.kill()
    assert proc.returncode == -9, (site, proc.stderr.read()[-2000:])

    # the kill left the durable floor intact: v0 promoted, offset 0
    st = load_state(state_path(loop_dir))
    assert st["version"] == 0 and st["ingest_offset"] == 0, site
    assert Path(st["model_path"]).exists()

    # phase 2: restart WITHOUT the plan; replay/ingest, await the
    # promotion in the durable state, then score through the server
    proc = subprocess.Popen(
        args, cwd=str(REPO), env=env, stdin=subprocess.PIPE,
        stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True,
    )
    try:
        proc.stdin.write(ingest_lines)
        proc.stdin.flush()
        deadline = time.monotonic() + 300
        while time.monotonic() < deadline:
            if proc.poll() is not None:
                raise AssertionError(
                    f"restart died: {proc.stderr.read()[-2000:]}")
            try:
                if load_state(state_path(loop_dir))["version"] >= 1:
                    break
            except CheckpointError:
                pass
            time.sleep(0.2)
        else:
            raise AssertionError(f"{site}: restart never promoted v1")
        probe = HX[:8]
        proc.stdin.write(json.dumps(
            {"op": "score", "model": "default",
             "rows": probe.tolist()}) + "\n")
        proc.stdin.write(json.dumps({"op": "quit"}) + "\n")
        proc.stdin.flush()
        out, err = proc.communicate(timeout=300)
    finally:
        if proc.poll() is None:
            proc.kill()
    assert proc.returncode == 0, err[-2000:]
    resp = [json.loads(l) for l in out.splitlines() if l.startswith("{")]
    scored = next(r for r in resp if "pred" in r)
    st = load_state(state_path(loop_dir))
    assert st["version"] == 1 and st["counts"]["promoted"] == 1
    v1 = lgb.Booster(model_file=st["model_path"])
    np.testing.assert_allclose(np.asarray(scored["pred"]),
                               v1.predict(probe), rtol=1e-5, atol=1e-6)
