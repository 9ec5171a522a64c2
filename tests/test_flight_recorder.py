"""Flight recorder + anomaly sentinels (obs/recorder.py,
obs/anomaly.py, docs/OBSERVABILITY.md "Flight recorder & anomaly
policies"): JSONL stream round-trip, per-round records from both the
fused and eager loops, sentinel unit red-to-greens, the end-to-end
divergence abort, and the abort-path flush guarantees."""

from __future__ import annotations

import json
import math
from pathlib import Path

import numpy as np
import pytest

import lightgbm_tpu as lgb
from lightgbm_tpu import boosting, timer
from lightgbm_tpu.obs import tracing
from lightgbm_tpu.obs.anomaly import AnomalyAbort, AnomalySentinel
from lightgbm_tpu.obs.metrics import default_registry
from lightgbm_tpu.obs.recorder import (
    SCHEMA,
    FlightRecorder,
    last_summary,
    read_stream,
)

REPO = Path(__file__).resolve().parents[1]


def _binary_sets(rng, n=400, nv=150, f=4):
    X = rng.randn(n, f)
    y = (X[:, 0] > 0).astype(np.float32)
    ds = lgb.Dataset(X, label=y, free_raw_data=False)
    Xv = rng.randn(nv, f)
    yv = (Xv[:, 0] > 0).astype(np.float32)
    vs = lgb.Dataset(Xv, label=yv, reference=ds, free_raw_data=False)
    return ds, vs


# ------------------------------------------------------------ round-trip
def test_recorder_jsonl_roundtrip(tmp_path):
    path = tmp_path / "fr.jsonl"
    rec = FlightRecorder(str(path))
    rows = [
        {"round": 0, "evals": {"v l2": 1.0}},
        {"round": 1, "evals": {"v l2": 0.5}, "trees_per_sec": 3.0},
    ]
    for r in rows:
        rec.record(r)
    summary = rec.close()
    assert summary["rounds"] == 2
    assert summary["last_evals"] == {"v l2": 0.5}
    # first line is the schema header; read_stream skips it
    first = json.loads(path.read_text().splitlines()[0])
    assert first["schema"] == SCHEMA
    assert read_stream(str(path)) == rows
    # idempotent close; post-close records are dropped, not errors
    rec.record({"round": 2})
    assert rec.close()["rounds"] == 2
    assert last_summary()["rounds"] == 2


def test_recorder_memory_only():
    rec = FlightRecorder(None)
    rec.record({"round": 0})
    s = rec.close()
    assert s["rounds"] == 1 and s["path"] is None


# ------------------------------------------------------- training streams
def test_fused_loop_streams_full_records(rng, tmp_path):
    """The fused loop records round index, the per-round fused-step
    phase, chunk throughput, gh norms (from the eval-row tail — no
    extra readback), evals with higher-better flags, and tree stats."""
    ds, vs = _binary_sets(rng)
    path = tmp_path / "fused.jsonl"
    lgb.train({"objective": "binary", "num_leaves": 7, "verbosity": -1,
               "record_file": str(path)},
              ds, num_boost_round=5, valid_sets=[vs], valid_names=["v"])
    recs = read_stream(str(path))
    assert [r["round"] for r in recs] == [0, 1, 2, 3, 4]
    for r in recs:
        assert boosting.FUSED_ROUND_PHASE in r["phases"]
        assert r["trees_per_sec"] > 0
        assert r["gnorm"] > 0 and r["hnorm"] > 0
        assert "v binary_logloss" in r["evals"]
        assert r["evals_hb"]["v binary_logloss"] is False
        assert len(r["trees"]) == 1
        t = r["trees"][0]
        assert t["leaves"] > 1 and t["depth"] >= 1 and t["leaf_finite"]
        assert t["best_gain"] > 0
    # chunk-level scopes ride the chunk's first record
    assert "fused dispatch" in recs[0]["chunk_phases"]


def test_fused_chunk_records_match_per_round_dispatch(rng, tmp_path):
    """Chunk-scan equivalence (ISSUE 18): with rounds dispatched as one
    lax.scan per chunk, the recorder must stream the SAME story as one
    dispatch per round (chunk ladder (1,)) — round indices, eval values,
    and gh norms bit-equal, and the apportioned FUSED_ROUND_PHASE span
    present in every record on both sides."""
    import lightgbm_tpu.config as cfg

    X = rng.randn(400, 4)
    y = (X[:, 0] > 0).astype(np.float32)
    Xv = rng.randn(150, 4)
    yv = (Xv[:, 0] > 0).astype(np.float32)

    def run(ladder):
        ds = lgb.Dataset(X, label=y, free_raw_data=False)
        vs = lgb.Dataset(Xv, label=yv, reference=ds,
                         free_raw_data=False)
        path = tmp_path / f"fr_{len(ladder)}.jsonl"
        with pytest.MonkeyPatch.context() as mp:
            mp.setattr(cfg, "DEFAULT_CHUNK_LADDER", ladder)
            lgb.train({"objective": "binary", "num_leaves": 7,
                       "verbosity": -1, "record_file": str(path)},
                      ds, num_boost_round=6, valid_sets=[vs],
                      valid_names=["v"])
        return read_stream(str(path))

    chunked, eager = run(cfg.DEFAULT_CHUNK_LADDER), run((1,))
    assert [r["round"] for r in chunked] == [r["round"] for r in eager] \
        == list(range(6))
    assert [r["evals"] for r in chunked] == [r["evals"] for r in eager]
    assert [(r["gnorm"], r["hnorm"]) for r in chunked] == \
        [(r["gnorm"], r["hnorm"]) for r in eager]
    assert [[t["leaves"] for t in r["trees"]] for r in chunked] == \
        [[t["leaves"] for t in r["trees"]] for r in eager]
    for r in chunked + eager:
        assert boosting.FUSED_ROUND_PHASE in r["phases"]
        assert r["trees_per_sec"] > 0
    assert "fused dispatch" in chunked[0]["chunk_phases"]


def test_eager_fast_loop_streams_records(rng, tmp_path):
    """A pre-iteration callback forces the eager loop: every record
    carries the three ROUND_PHASES spans and gh norms (tree stats are
    deferred on the async fast path and legitimately absent)."""
    ds, vs = _binary_sets(rng)

    def cb(env):
        return None

    cb.before_iteration = True
    path = tmp_path / "eager.jsonl"
    lgb.train({"objective": "binary", "num_leaves": 7, "verbosity": -1,
               "record_file": str(path)},
              ds, num_boost_round=3, valid_sets=[vs], valid_names=["v"],
              callbacks=[cb])
    recs = read_stream(str(path))
    assert len(recs) == 3
    for r in recs:
        for phase in boosting.ROUND_PHASES:
            assert phase in r["phases"], r["phases"]
        assert r["gnorm"] > 0 and r["hnorm"] > 0
        assert "v binary_logloss" in r["evals"]


@pytest.mark.slow
def test_eager_sync_loop_records_tree_stats(rng, tmp_path):
    """DART forces the per-iteration sync loop, whose host trees are
    materialized every round — tree stats appear in every record."""
    ds, vs = _binary_sets(rng)
    path = tmp_path / "dart.jsonl"
    lgb.train({"objective": "binary", "boosting": "dart",
               "num_leaves": 7, "verbosity": -1,
               "record_file": str(path)},
              ds, num_boost_round=3, valid_sets=[vs], valid_names=["v"])
    recs = read_stream(str(path))
    assert len(recs) == 3
    for r in recs:
        assert len(r["trees"]) == 1 and r["trees"][0]["leaves"] > 1


def test_record_evaluation_callback_matches_stream(rng, tmp_path):
    """Satellite contract: the recorder's learning curve and the
    reference record_evaluation callback see the SAME values."""
    ds, vs = _binary_sets(rng)
    result = {}
    path = tmp_path / "curve.jsonl"
    lgb.train({"objective": "binary", "num_leaves": 7, "verbosity": -1,
               "record_file": str(path)},
              ds, num_boost_round=4, valid_sets=[vs], valid_names=["v"],
              callbacks=[lgb.record_evaluation(result)])
    recs = read_stream(str(path))
    curve = result["v"]["binary_logloss"]
    assert len(curve) == 4
    assert [r["evals"]["v binary_logloss"] for r in recs] == \
        pytest.approx(curve)


def test_eval_values_land_on_metrics_gauge(rng):
    ds, vs = _binary_sets(rng)
    lgb.train({"objective": "binary", "num_leaves": 7, "verbosity": -1},
              ds, num_boost_round=2, valid_sets=[vs], valid_names=["v"])
    snap = default_registry().snapshot()
    gauges = snap.get("lgbmtpu_eval_metric", {})
    key = '{dataset="v",metric="binary_logloss"}'
    assert key in gauges and math.isfinite(gauges[key])


# ------------------------------------------------------- sentinel units
def _rec(i, **kw):
    return dict({"round": i}, **kw)


def test_sentinel_nan_metric_and_policy():
    s = AnomalySentinel("warn")
    s.check(_rec(0, evals={"v l2": 1.0}, evals_hb={"v l2": False}))
    assert not s.trips
    s.check(_rec(1, evals={"v l2": float("nan")},
                 evals_hb={"v l2": False}))
    assert [t["kind"] for t in s.trips] == ["nan_metric"]

    hard = AnomalySentinel("abort")
    with pytest.raises(AnomalyAbort) as ei:
        hard.check(_rec(0, evals={"v l2": float("inf")},
                        evals_hb={"v l2": False}))
    assert ei.value.kind == "nan_metric" and ei.value.round_idx == 0

    off = AnomalySentinel("off")
    off.check(_rec(0, evals={"v l2": float("nan")}))
    assert not off.trips
    with pytest.raises(ValueError):
        AnomalySentinel("explode")


def test_sentinel_nan_leaf():
    s = AnomalySentinel("warn")
    s.check(_rec(0, trees=[{"leaves": 3, "best_gain": 1.0,
                            "leaf_finite": True}]))
    s.check(_rec(1, trees=[{"leaves": 3, "best_gain": 1.0,
                            "leaf_finite": False}]))
    assert [t["kind"] for t in s.trips] == ["nan_leaf"]


def test_sentinel_loss_spike_rolling_median():
    s = AnomalySentinel("warn")
    for i, v in enumerate([1.0, 1.1, 0.9]):
        s.check(_rec(i, evals={"v l2": v}, evals_hb={"v l2": False}))
    assert not s.trips
    s.check(_rec(3, evals={"v l2": 5.0}, evals_hb={"v l2": False}))
    assert [t["kind"] for t in s.trips] == ["loss_spike"]
    # higher-better metrics never spike-trip (NaN check only)
    s2 = AnomalySentinel("warn")
    for i, v in enumerate([0.5, 0.5, 0.5, 50.0]):
        s2.check(_rec(i, evals={"v auc": v}, evals_hb={"v auc": True}))
    assert not s2.trips


def test_sentinel_throughput_collapse():
    s = AnomalySentinel("warn")
    for i, tps in enumerate([10.0, 11.0, 10.0]):
        s.check(_rec(i, trees_per_sec=tps))
    assert not s.trips
    s.check(_rec(3, trees_per_sec=1.0))
    assert [t["kind"] for t in s.trips] == ["throughput_collapse"]


def test_sentinel_dead_rounds_streak():
    s = AnomalySentinel("warn", max_dead_rounds=3)
    dead = [{"leaves": 1, "best_gain": 0.0, "leaf_finite": True}]
    alive = [{"leaves": 5, "best_gain": 2.0, "leaf_finite": True}]
    s.check(_rec(0, trees=dead))
    s.check(_rec(1, trees=alive))  # streak resets
    for i in range(2, 5):
        s.check(_rec(i, trees=dead))
    assert [t["kind"] for t in s.trips] == ["dead_rounds"]


def test_sentinel_trip_emits_counter_and_trace_instant():
    reg = default_registry()
    c = reg.counter("lgbmtpu_anomaly_trips_total", labels=("kind",))
    before = c.value(kind="nan_metric")
    with tracing.tracing() as rec:
        s = AnomalySentinel("warn")
        s.check(_rec(7, evals={"v l2": float("nan")},
                     evals_hb={"v l2": False}))
    assert c.value(kind="nan_metric") == before + 1
    instants = [e for e in rec.events()
                if e.get("ph") == "i" and e["name"] == "anomaly: nan_metric"]
    assert instants and instants[0]["args"]["round"] == 7


# -------------------------------------------------------- end-to-end abort
def test_divergence_trips_loss_spike_within_bounded_rounds(rng, tmp_path):
    """ACCEPTANCE: a deliberately diverging config (learning_rate=5 on
    l2: the residual quadruples per round) trips the loss-spike
    sentinel within a bounded number of rounds under abort, the
    recorder JSONL + manifest survive the abort, and the trip is
    visible as a metrics counter."""
    X = rng.randn(400, 4)
    y = X[:, 0] + 0.1 * rng.randn(400)
    ds = lgb.Dataset(X, label=y, free_raw_data=False)
    Xv = rng.randn(150, 4)
    vs = lgb.Dataset(Xv, label=Xv[:, 0], reference=ds,
                     free_raw_data=False)
    path = tmp_path / "diverge.jsonl"
    reg = default_registry()
    c = reg.counter("lgbmtpu_anomaly_trips_total", labels=("kind",))
    before = c.value(kind="loss_spike")
    sinks_before = len(timer._trace_sinks)

    with pytest.raises(AnomalyAbort) as ei:
        lgb.train({"objective": "regression", "metric": "l2",
                   "num_leaves": 7, "learning_rate": 5.0,
                   "verbosity": -1, "record_file": str(path),
                   "anomaly_policy": "abort"},
                  ds, num_boost_round=14,
                  valid_sets=[vs], valid_names=["v"])
    assert ei.value.kind == "loss_spike"
    assert ei.value.round_idx <= 10  # bounded: spike_min_rounds + slack
    # the trip is a metrics counter
    assert c.value(kind="loss_spike") == before + 1
    # flush-and-close is exception-safe: no torn timer sink...
    assert len(timer._trace_sinks) == sinks_before
    # ...every line of the stream parses, the tail is a complete record
    lines = path.read_text().splitlines()
    parsed = [json.loads(l) for l in lines]  # raises on a torn tail
    assert parsed[0]["schema"] == SCHEMA
    tail = parsed[-1]
    assert tail["round"] == ei.value.round_idx
    assert "evals" in tail
    # ...and the manifest written AFTER the abort carries the summary
    from lightgbm_tpu.obs.manifest import write_manifest

    m = write_manifest(str(tmp_path / "manifest.json"))
    fr = m["flight_recorder"]
    assert fr["path"] == str(path)
    assert fr["rounds"] == len(parsed) - 1
    assert fr["anomalies"]["loss_spike"] == 1
    on_disk = json.loads((tmp_path / "manifest.json").read_text())
    assert on_disk["flight_recorder"]["anomalies"]["loss_spike"] == 1


def test_unrecorded_run_clears_stale_summary(rng, tmp_path):
    """A manifest written after an UNRECORDED run must not carry the
    previous recorded run's flight-record section (regression: the
    module-global summary used to leak into every later manifest)."""
    from lightgbm_tpu.obs.manifest import build_manifest

    ds, vs = _binary_sets(rng)
    lgb.train({"objective": "binary", "num_leaves": 7, "verbosity": -1,
               "record_file": str(tmp_path / "one.jsonl")},
              ds, num_boost_round=2, valid_sets=[vs], valid_names=["v"])
    assert build_manifest().get("flight_recorder") is not None
    lgb.train({"objective": "binary", "num_leaves": 7, "verbosity": -1},
              ds, num_boost_round=2, valid_sets=[vs], valid_names=["v"])
    assert build_manifest().get("flight_recorder") is None


def test_warn_policy_does_not_abort(rng, tmp_path):
    """Same diverging config under warn: training runs to completion,
    trips are counted into the recorder summary."""
    X = rng.randn(300, 4)
    y = X[:, 0]
    ds = lgb.Dataset(X, label=y, free_raw_data=False)
    vs = lgb.Dataset(rng.randn(100, 4), label=np.zeros(100),
                     reference=ds, free_raw_data=False)
    path = tmp_path / "warn.jsonl"
    bst = lgb.train({"objective": "regression", "metric": "l2",
                     "num_leaves": 7, "learning_rate": 5.0,
                     "verbosity": -1, "record_file": str(path),
                     "anomaly_policy": "warn"},
                    ds, num_boost_round=6,
                    valid_sets=[vs], valid_names=["v"])
    assert bst.num_trees() == 6
    assert last_summary()["anomalies"].get("loss_spike", 0) >= 1
