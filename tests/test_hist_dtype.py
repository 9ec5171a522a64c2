"""Int-packed histogram channels on the DEFAULT path (ISSUE 12
tentpole): the tpu_hist_dtype policy resolution, training parity of the
int16/int8 channel layouts against bf16x2 across tasks, stochastic-
rounding determinism under a fixed seed, the narrowest-exact
reduce-scatter wire dtype policy, hist_dtype provenance through the run
manifest and the flight recorder, and the bench backend-probe
fail-fast."""

from __future__ import annotations

import importlib.util
import json
from pathlib import Path

import numpy as np
import pytest

import lightgbm_tpu as lgb
from lightgbm_tpu.learner.histogram import rs_wire_dtype
from lightgbm_tpu.learner.quantize import (
    HIST_DTYPE_LEVELS,
    resolve_hist_dtype,
)

REPO = Path(__file__).resolve().parents[1]


# --------------------------------------------------- policy resolution
def test_resolve_hist_dtype_default_path():
    # auto: int-packed on the on-chip rounds path, bf16x2 off it
    assert resolve_hist_dtype("auto", False, 16, True) == ("int16", 256,
                                                           None)
    assert resolve_hist_dtype("auto", False, 16, False) == ("bf16x2", 0,
                                                            None)
    # auto stays bit-exact bf16x2 on non-TPU backends (same contract as
    # tpu_growth_mode=auto); an EXPLICIT request is honored anywhere
    assert resolve_hist_dtype("auto", False, 16, True,
                              on_tpu=False)[0] == "bf16x2"
    assert resolve_hist_dtype("int16", False, 16, True,
                              on_tpu=False)[0] == "int16"
    # explicit narrow layouts carry their level counts
    assert resolve_hist_dtype("int16", False, 16, True) == ("int16", 256,
                                                            None)
    assert resolve_hist_dtype("int8", False, 16, True) == ("int8", 127,
                                                           None)
    assert HIST_DTYPE_LEVELS == {"int16": 256, "int8": 127}


def test_hist_dtype_float32_rejected_with_accepted_values():
    """The legacy synonym went: the validator refuses it and names what
    it accepts, before any Booster exists."""
    from lightgbm_tpu.basic import LightGBMError
    from lightgbm_tpu.config import Config

    with pytest.raises(LightGBMError) as ei:
        Config({"tpu_hist_dtype": "float32"})
    msg = str(ei.value)
    assert "tpu_hist_dtype=float32" in msg
    assert "auto, bf16x2, int16, int8" in msg
    for ok in ("auto", "bf16x2", "int16", "int8"):
        assert Config({"hist_dtype": ok}).tpu_hist_dtype == ok


def test_resolve_hist_dtype_off_rounds_falls_back_with_warning():
    resolved, levels, warn = resolve_hist_dtype("int16", False, 16, False)
    assert (resolved, levels) == ("bf16x2", 0)
    assert warn is not None and "rounds" in warn


def test_resolve_hist_dtype_quant_api_governs():
    # under use_quantized_grad the PUBLIC quant levels decide; the
    # internal policy must not override them (levels stays 0)
    assert resolve_hist_dtype("auto", True, 16, True) == ("int8", 0, None)
    assert resolve_hist_dtype("auto", True, 200, True) == ("int16", 0,
                                                           None)
    assert resolve_hist_dtype("auto", True, 16, False) == ("bf16x2", 0,
                                                           None)
    # even an explicit narrow request defers to the quant API
    assert resolve_hist_dtype("int16", True, 16, True)[1] == 0


# ------------------------------------------------------ rs wire policy
def test_rs_wire_dtype_narrowest_exact():
    # 128 rows * 8 ranks * 16 levels = 16384 < 2^15: int16
    assert rs_wire_dtype(128, 8, 16) == "int16"
    # 256 rows hits exactly 2^15 — one short of exact, steps to int32
    assert rs_wire_dtype(256, 8, 16) == "int32"
    # inside the int32 bounds (2048*8*16 < 2^31, 2048*16 < 2^24)
    assert rs_wire_dtype(2048, 8, 16) == "int32"
    # past the per-rank f32 exactness bound (131072*256 > 2^24): None
    assert rs_wire_dtype(131072, 8, 256) is None


# ----------------------------------------------------- training parity
def _train(X, y, params, hd, n_rounds, **ds_kw):
    ds = lgb.Dataset(X, label=y, free_raw_data=False, **ds_kw)
    return lgb.train(
        dict(params, tpu_hist_dtype=hd, tpu_growth_mode="rounds",
             verbose=-1, seed=3, deterministic=True),
        ds, num_boost_round=n_rounds,
    )


@pytest.mark.parametrize("hd", ["int16", "int8"])
def test_binary_parity_int_packed(hd):
    from sklearn.datasets import make_classification
    from sklearn.metrics import roc_auc_score

    X, y = make_classification(2000, 10, random_state=7)
    X = X.astype(np.float32)
    params = {"objective": "binary", "num_leaves": 15,
              "learning_rate": 0.1}
    auc_ref = roc_auc_score(y, _train(X, y, params, "bf16x2",
                                      12).predict(X))
    b = _train(X, y, params, hd, 12)
    assert b._gbdt.hist_dtype == hd
    assert b._gbdt._int_packed
    auc = roc_auc_score(y, b.predict(X))
    # stochastic rounding perturbs individual splits; the model-level
    # metric must stay within noise of the bf16x2 channels
    assert abs(auc - auc_ref) < 2e-3
    assert auc > 0.95


def test_regression_parity_int_packed():
    from sklearn.datasets import make_regression

    X, y = make_regression(2000, 8, noise=10.0, random_state=1)
    X, y = X.astype(np.float32), y.astype(np.float32)
    params = {"objective": "regression", "num_leaves": 15,
              "learning_rate": 0.1}
    p_ref = _train(X, y, params, "bf16x2", 12).predict(X)
    p = _train(X, y, params, "int16", 12).predict(X)
    rmse_ref = float(np.sqrt(np.mean((p_ref - y) ** 2)))
    rmse = float(np.sqrt(np.mean((p - y) ** 2)))
    assert abs(rmse - rmse_ref) / rmse_ref < 0.01


def test_multiclass_parity_int_packed():
    from sklearn.datasets import make_classification
    from sklearn.metrics import log_loss

    X, y = make_classification(1500, 10, n_informative=6, n_classes=3,
                               random_state=5)
    X = X.astype(np.float32)
    params = {"objective": "multiclass", "num_class": 3,
              "num_leaves": 15, "learning_rate": 0.1}
    ll_ref = log_loss(y, _train(X, y, params, "bf16x2", 8).predict(X))
    ll = log_loss(y, _train(X, y, params, "int16", 8).predict(X))
    assert abs(ll - ll_ref) < 5e-3


def test_int_packed_deterministic_under_fixed_seed():
    """Stochastic rounding is keyed on (data_random_seed, iteration):
    two identical runs must produce bit-identical predictions."""
    from sklearn.datasets import make_classification

    X, y = make_classification(800, 8, random_state=2)
    X = X.astype(np.float32)
    params = {"objective": "binary", "num_leaves": 11,
              "learning_rate": 0.1}
    p1 = _train(X, y, params, "int16", 6).predict(X)
    p2 = _train(X, y, params, "int16", 6).predict(X)
    np.testing.assert_array_equal(p1, p2)


def test_int_packed_off_rounds_path_resolves_bf16x2():
    """Explicit int16 off the rounds growth path (CPU auto mode) must
    fall back to bf16x2 — the sequential growers have no integer
    channels — and still train."""
    from sklearn.datasets import make_classification

    X, y = make_classification(600, 6, random_state=4)
    ds = lgb.Dataset(X.astype(np.float32), label=y, free_raw_data=False)
    b = lgb.train({"objective": "binary", "num_leaves": 7, "verbose": -1,
                   "tpu_hist_dtype": "int16", "tpu_growth_mode": "auto"},
                  ds, num_boost_round=3)
    assert b._gbdt.hist_dtype == "bf16x2"
    assert not b._gbdt._int_packed


# ------------------------------------------------- provenance round-trip
def test_hist_dtype_in_manifest_and_flight_recorder(tmp_path):
    from sklearn.datasets import make_classification

    from lightgbm_tpu.obs.manifest import build_manifest
    from lightgbm_tpu.obs.recorder import read_stream

    X, y = make_classification(800, 6, random_state=9)
    ds = lgb.Dataset(X.astype(np.float32), label=y, free_raw_data=False)
    fr = tmp_path / "fr.jsonl"
    params = {"objective": "binary", "num_leaves": 7, "verbose": -1,
              "tpu_hist_dtype": "int16", "tpu_growth_mode": "rounds",
              "record_file": str(fr)}
    bst = lgb.train(params, ds, num_boost_round=3)

    # the explicit request sticks on the rounds path (auto only flips
    # on TPU hardware); the booster reports the RESOLVED layout
    assert bst._gbdt.hist_dtype == "int16"
    from lightgbm_tpu.config import Config

    m = build_manifest(config=Config(params), booster=bst)
    assert m["config"]["resolved"]["tpu_hist_dtype"] == "int16"
    assert m["model"]["hist_dtype"] == "int16"

    recs = read_stream(str(fr))
    assert recs and all(r.get("hist_dtype") == "int16" for r in recs)
    # and the stream survives a JSON round-trip with the new key
    assert json.loads(json.dumps(recs))[0]["hist_dtype"] == "int16"


# ------------------------------------------- bench refuses a CPU backend
def _load_bench():
    spec = importlib.util.spec_from_file_location(
        "_bench_under_test", REPO / "bench.py"
    )
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_bench_refuses_cpu_backend_in_process(capsys):
    """No probe child, no fallback: on a CPU backend the bench's one
    backend check exits non-zero and says what it found."""
    bench = _load_bench()
    with pytest.raises(SystemExit) as exc:
        bench.require_accelerator("bench")
    assert exc.value.code not in (0, None)
    assert "'cpu'" in str(exc.value.code)


def test_bench_has_no_probe_fallback_or_carried_number():
    """What hid the chip is gone for good: no subprocess probe, no
    platform switch, no workload downshift, no carried chip number, no
    rc-0 exit from a signal or a failed segment."""
    for script in ("bench.py", "bench_serve.py"):
        src = (REPO / script).read_text()
        for gone in ("probe_backend", "LAST_TPU_VERIFIED", "stale",
                     "BENCH_FORCE_CPU", "BENCH_CPU_ROWS", "os._exit",
                     "jax_platforms", "signal.", "except Exception",
                     "jax_compilation_cache_dir"):
            assert gone not in src, (script, gone)
