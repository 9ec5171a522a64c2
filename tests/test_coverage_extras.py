"""Coverage the reference suite has that ours lacked: weighted
training, large-leaf (255) trees, multiclass through the
fused loop."""

import numpy as np
import pytest

import lightgbm_tpu as lgb


def test_weighted_training_shifts_model():
    rs = np.random.RandomState(2)
    n = 4000
    X = rs.randn(n, 5)
    y = ((X[:, 0] + 0.3 * rs.randn(n)) > 0).astype(np.float64)
    # upweight the positive class 10x — predictions must shift up
    w = np.where(y > 0, 10.0, 1.0)
    params = dict(objective="binary", num_leaves=15, verbosity=-1)
    b0 = lgb.train(params, lgb.Dataset(X, label=y, free_raw_data=False),
                   num_boost_round=10)
    b1 = lgb.train(params, lgb.Dataset(X, label=y, weight=w,
                                       free_raw_data=False),
                   num_boost_round=10)
    assert b1.predict(X).mean() > b0.predict(X).mean() + 0.05
    # weighted metric eval runs
    rec = {}
    ds = lgb.Dataset(X, label=y, weight=w, free_raw_data=False)
    lgb.train({**params, "metric": "binary_logloss"}, ds, num_boost_round=5,
              valid_sets=[ds], valid_names=["t"],
              callbacks=[lgb.record_evaluation(rec)])
    assert len(rec["t"]["binary_logloss"]) == 5


def test_large_leaf_255_tree():
    """One 255-leaf tree at the benchmark's leaf budget (the while_loop
    capacity ladder must handle deep growth)."""
    rs = np.random.RandomState(3)
    n = 20000
    X = rs.randn(n, 8)
    y = X[:, 0] * np.sin(X[:, 1] * 2) + 0.5 * X[:, 2] ** 2 + 0.05 * rs.randn(n)
    ds = lgb.Dataset(X, label=y, free_raw_data=False)
    bst = lgb.train(
        {"objective": "regression", "num_leaves": 255,
         "min_data_in_leaf": 20, "learning_rate": 0.5, "verbosity": -1},
        ds, num_boost_round=3,
    )
    t = bst._gbdt.models[0]
    assert t.num_leaves > 200  # rich signal: near-full budget used
    mse = float(np.mean((bst.predict(X) - y) ** 2))
    assert mse < float(np.var(y)) * 0.4


def test_multiclass_fused_loop():
    rs = np.random.RandomState(4)
    n = 6000
    X = rs.randn(n, 6)
    logits = np.stack([X[:, 0], X[:, 1], -(X[:, 0] + X[:, 1])], 1)
    y = np.argmax(logits + 0.3 * rs.randn(n, 3), axis=1).astype(np.float64)
    ds = lgb.Dataset(X, label=y, free_raw_data=False)
    vs = lgb.Dataset(X[:1000], label=y[:1000], reference=ds,
                     free_raw_data=False)
    rec = {}
    bst = lgb.train(
        {"objective": "multiclass", "num_class": 3, "num_leaves": 15,
         "metric": "multi_logloss", "verbosity": -1},
        ds, num_boost_round=8, valid_sets=[vs], valid_names=["v"],
        callbacks=[lgb.record_evaluation(rec)],
    )
    assert bst._gbdt.fused_eligible()  # device metric set covers this
    p = bst.predict(X)
    assert p.shape == (n, 3)
    np.testing.assert_allclose(p.sum(axis=1), 1.0, atol=1e-5)
    assert (np.argmax(p, 1) == y).mean() > 0.7
    assert rec["v"]["multi_logloss"][-1] < rec["v"]["multi_logloss"][0]


def test_sequence_streaming_construction():
    """lgb.Sequence streaming ingest (reference basic.py:905): binned
    matrix built in chunks matches the all-at-once numpy path."""
    rs = np.random.RandomState(9)
    X = rs.randn(3000, 5)
    y = ((X[:, 0] + 0.5 * X[:, 2]) > 0).astype(np.float64)

    class ArrSeq(lgb.Sequence):
        batch_size = 256

        def __init__(self, a):
            self._a = a

        def __len__(self):
            return len(self._a)

        def __getitem__(self, idx):
            return self._a[idx]

    params = dict(objective="binary", num_leaves=15, verbosity=-1)
    # split across two sequences to exercise multi-sequence concat
    ds_seq = lgb.Dataset([ArrSeq(X[:1000]), ArrSeq(X[1000:])], label=y)
    ds_np = lgb.Dataset(X, label=y, free_raw_data=False)
    ds_seq.construct()
    ds_np.construct()
    np.testing.assert_array_equal(ds_seq._binned.bins, ds_np._binned.bins)
    b1 = lgb.train(params, ds_seq, num_boost_round=5)
    b2 = lgb.train(params, ds_np, num_boost_round=5)
    np.testing.assert_allclose(b1.predict(X), b2.predict(X), rtol=1e-6)


def test_auc_mu_matches_bruteforce():
    """auc_mu (multiclass_metric.hpp:183) against a direct O(n^2)
    pairwise computation of the Kleiman-Page definition."""
    import numpy as np

    from lightgbm_tpu.config import Config
    from lightgbm_tpu.metrics import AucMuMetric

    rs = np.random.RandomState(0)
    K, N = 3, 400
    y = rs.randint(0, K, N).astype(np.float64)
    score = rs.randn(K, N)
    cfg = Config({"objective": "multiclass", "num_class": K})
    m = AucMuMetric(cfg)
    m.init(y, None, None)
    (_, got, _), = m.eval(score.reshape(-1))

    W = np.ones((K, K)) - np.eye(K)
    total = 0.0
    for i in range(K):
        for j in range(i + 1, K):
            v = W[i] - W[j]
            t1 = v[i] - v[j]
            d = t1 * (v @ score)
            di = d[y == i]
            dj = d[y == j]
            wins = (di[:, None] > dj[None, :]).sum()
            ties = (np.abs(di[:, None] - dj[None, :]) < 1e-15).sum()
            total += (wins + 0.5 * ties) / (len(di) * len(dj))
    expect = 2.0 * total / K / (K - 1)
    assert abs(got - expect) < 1e-10, (got, expect)


def test_auc_mu_via_train_api():
    import numpy as np

    import lightgbm_tpu as lgb

    rs = np.random.RandomState(1)
    X = rs.randn(1500, 6)
    y = (X[:, 0] > 0.3).astype(int) + (X[:, 1] > 0.1).astype(int)
    ds = lgb.Dataset(X, label=y, free_raw_data=False)
    evals = {}
    bst = lgb.train(
        {"objective": "multiclass", "num_class": 3, "metric": "auc_mu",
         "num_leaves": 15, "verbosity": -1},
        ds, num_boost_round=5, valid_sets=[ds], valid_names=["tr"],
        callbacks=[lgb.record_evaluation(evals)],
    )
    vals = evals["tr"]["auc_mu"]
    assert len(vals) == 5
    assert vals[-1] > 0.9  # separable-ish problem


def test_single_row_fast_predict_matches_batch():
    """The packed single-row predictor (c_api.cpp:66
    SingleRowPredictorInner analog) must agree exactly with the batch
    tree walk, including missing values and num_iteration slicing."""
    import numpy as np

    import lightgbm_tpu as lgb

    rs = np.random.RandomState(3)
    X = rs.randn(2000, 8)
    X[rs.rand(2000, 8) < 0.05] = np.nan
    w = rs.randn(8)
    y = ((np.nan_to_num(X) @ w) > 0).astype(float)
    ds = lgb.Dataset(X, label=y, free_raw_data=False)
    bst = lgb.train({"objective": "binary", "num_leaves": 15,
                     "verbosity": -1}, ds, num_boost_round=20)
    Xq = X[:6].copy()
    batch = bst.predict(Xq)  # 6 rows -> batch path
    single = np.array([bst.predict(Xq[i:i + 1])[0] for i in range(6)])
    np.testing.assert_allclose(single, batch, atol=1e-14)
    b5 = bst.predict(Xq[:1], num_iteration=5)
    s5 = bst.predict(np.vstack([Xq[:1]] * 6), num_iteration=5)[:1]
    np.testing.assert_allclose(b5, s5, atol=1e-14)


def test_debug_check_split_passes_and_detects():
    """tpu_debug_check_split (serial_tree_learner.h:174 CheckSplit):
    green on healthy training; a corrupted tree trips the fatal."""
    import numpy as np

    import lightgbm_tpu as lgb
    from lightgbm_tpu.log import LightGBMError

    rs = np.random.RandomState(4)
    X = rs.randn(3000, 6)
    y = ((X[:, 0] + X[:, 1]) > 0).astype(float)
    ds = lgb.Dataset(X, label=y, free_raw_data=False)
    bst = lgb.train(
        {"objective": "binary", "num_leaves": 15, "verbosity": -1,
         "tpu_debug_check_split": True},
        ds, num_boost_round=3,
    )
    assert bst.num_trees() == 3

    # corrupt: a GBDT whose grower returns a wrong leaf_count
    g = bst._gbdt
    orig = g._grow_maybe_quantized

    def bad(*a, **k):
        arrays, rl = orig(*a, **k)
        return arrays._replace(leaf_count=arrays.leaf_count + 7.0), rl

    g._grow_maybe_quantized = bad
    import pytest as _pytest

    with _pytest.raises(LightGBMError, match="CheckSplit"):
        g.train_one_iter(None, None)


def test_xentropy_family_metrics():
    """kullback_leibler and cross_entropy_lambda eval metrics
    (xentropy_metric.hpp:249, :165 — the objectives existed, the
    metrics were missing)."""
    rs = np.random.RandomState(3)
    n = 1200
    X = rs.randn(n, 6)
    w = rs.randn(6)
    y = 1.0 / (1.0 + np.exp(-(X @ w)))  # continuous labels in [0, 1]

    evals = {}
    def record(env):
        for item in env.evaluation_result_list:
            evals.setdefault(item[1], []).append(item[2])

    ds = lgb.Dataset(X, label=y, free_raw_data=False)
    lgb.train({"objective": "cross_entropy", "num_leaves": 15,
               "metric": ["cross_entropy", "kullback_leibler"],
               "verbosity": -1},
              ds, num_boost_round=10, valid_sets=[ds], valid_names=["tr"],
              callbacks=[record])
    # KL = CE - H(y): the label-entropy offset is score-independent
    yent = np.where(y > 0, y * np.log(y), 0.0) \
        + np.where(1 - y > 0, (1 - y) * np.log(1 - y), 0.0)
    for ce, kl in zip(evals["cross_entropy"], evals["kullback_leibler"]):
        np.testing.assert_allclose(kl, ce + float(np.mean(yent)),
                                   rtol=1e-6, atol=1e-9)
    assert evals["kullback_leibler"][-1] < evals["kullback_leibler"][0]

    evals.clear()
    ds2 = lgb.Dataset(X, label=y, free_raw_data=False)
    lgb.train({"objective": "cross_entropy_lambda", "num_leaves": 15,
               "metric": "cross_entropy_lambda", "verbosity": -1},
              ds2, num_boost_round=10, valid_sets=[ds2], valid_names=["tr"],
              callbacks=[record])
    vals = evals["cross_entropy_lambda"]
    assert vals[-1] < vals[0]  # the loss must improve under its objective


def test_r2_metric_reference_parity():
    """r2 (the one missing entry of the reference metric.cpp:21
    regression family): host and fused-device evals must
    both match the closed-form weighted 1 - SSres/SStot on the final
    scores, and agree with sklearn on the unweighted case."""
    from lightgbm_tpu.metrics import R2Metric
    from lightgbm_tpu.config import Config

    rs = np.random.RandomState(7)
    n = 2000
    X = rs.randn(n, 6)
    y = X @ rs.randn(6) + 0.1 * rs.randn(n)
    w = rs.uniform(0.5, 2.0, n)

    rec = {}
    ds = lgb.Dataset(X, label=y, weight=w, free_raw_data=False)
    booster = lgb.train(
        {"objective": "regression", "num_leaves": 15, "verbosity": -1,
         "metric": ["l2", "r2"]},
        ds, num_boost_round=8, valid_sets=[ds], valid_names=["tr"],
        callbacks=[lgb.record_evaluation(rec)],
    )
    pred = booster.predict(X)
    ybar = np.sum(w * y) / np.sum(w)
    expect = 1.0 - np.sum(w * (y - pred) ** 2) / np.sum(w * (y - ybar) ** 2)
    got = rec["tr"]["r2"][-1]
    np.testing.assert_allclose(got, expect, rtol=1e-5, atol=1e-7)
    assert rec["tr"]["r2"][-1] > rec["tr"]["r2"][0]  # higher_better

    # host Metric object parity vs sklearn (unweighted)
    from sklearn.metrics import r2_score

    m = R2Metric(Config({}))
    m.init(y, None, None)
    [(name, val, hb)] = m.eval(pred)
    assert name == "r2" and hb is True
    np.testing.assert_allclose(val, r2_score(y, pred), rtol=1e-9)


def test_device_eval_host_metric_fallback():
    """A valid metric string with no device implementation must NOT
    crash DeviceEvalSet: it computes on host via
    metrics.py through a pure_callback, warns once, and matches the
    host metric exactly — padding rows masked out."""
    import jax
    import jax.numpy as jnp

    from lightgbm_tpu import metrics as host_metrics
    from lightgbm_tpu.config import Config
    from lightgbm_tpu.device_metrics import (
        DeviceEvalSet,
        _warned_host_fallback,
    )

    rs = np.random.RandomState(3)
    n, npad = 500, 512
    lab = (rs.rand(n) > 0.4).astype(np.float32)
    score = rs.randn(n).astype(np.float32)
    lab_pad = np.zeros(npad, np.float32)
    lab_pad[:n] = lab
    sc_pad = np.zeros(npad, np.float32)
    sc_pad[:n] = score
    valid = jnp.asarray(np.arange(npad) < n, jnp.float32)
    cfg = Config({})
    # average_precision is host-only; kullback_leibler too — both must
    # build, and device metrics in the same set keep their fast path
    _warned_host_fallback.clear()
    des = DeviceEvalSet(
        cfg, ["average_precision", "kullback_leibler", "l2"],
        [True, False, False], jnp.asarray(lab_pad), None, valid, 1,
    )
    vals = np.asarray(jax.jit(des)(jnp.asarray(sc_pad)[None, :]))
    m = host_metrics.AveragePrecisionMetric(cfg)
    m.init(lab, None, None)
    np.testing.assert_allclose(
        vals[0], m.eval(score.astype(np.float64))[0][1], rtol=1e-6
    )
    m2 = host_metrics.KullbackLeiblerMetric(cfg)
    m2.init(lab, None, None)
    np.testing.assert_allclose(
        vals[1], m2.eval(score.astype(np.float64))[0][1], rtol=1e-5
    )
    assert _warned_host_fallback == {"average_precision",
                                     "kullback_leibler"}
    # a genuinely invalid name still raises
    import pytest

    with pytest.raises(NotImplementedError):
        DeviceEvalSet(cfg, ["no_such_metric"], [False],
                      jnp.asarray(lab_pad), None, valid, 1)


def test_bench_result_names_device_and_carries_nothing():
    """BENCH json: every result names the platform, device kind and
    device count it ran on, and carries no number from an earlier
    run."""
    import importlib.util
    import os

    spec = importlib.util.spec_from_file_location(
        "bench_mod",
        os.path.join(os.path.dirname(__file__), "..", "bench.py"),
    )
    bench = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(bench)
    bench._STATE.update(platform="tpu", device_kind="TPU v5 lite",
                        device_count=1, rows=1000, leaves=31,
                        trees_per_sec=2.0)
    out = bench._final_json()
    assert (out["platform"], out["device_kind"], out["device_count"]) \
        == ("tpu", "TPU v5 lite", 1)
    assert "last_tpu_verified" not in out
    # a result without a device identity cannot be built at all
    bench._STATE.pop("device_kind")
    import pytest

    with pytest.raises(KeyError):
        bench._final_json()


def test_device_eval_host_metric_fallback_traced_construction():
    """The memoized fused step constructs DeviceEvalSet INSIDE the
    trace with label/valid as jit arguments — the host fallback must
    build from tracers (operands ride the callback) instead of
    crashing on np.asarray(tracer)."""
    import jax
    import jax.numpy as jnp

    from lightgbm_tpu.config import Config
    from lightgbm_tpu.device_metrics import DeviceEvalSet
    from lightgbm_tpu import metrics as host_metrics

    rs = np.random.RandomState(4)
    n = 256
    lab = (rs.rand(n) > 0.5).astype(np.float32)
    score = rs.randn(n).astype(np.float32)
    cfg = Config({})

    @jax.jit
    def step(lab_t, valid_t, score_t):
        des = DeviceEvalSet(cfg, ["average_precision"], [True],
                            lab_t, None, valid_t, 1)
        return des(score_t[None, :])

    vals = np.asarray(step(jnp.asarray(lab), jnp.ones(n, jnp.float32),
                           jnp.asarray(score)))
    m = host_metrics.AveragePrecisionMetric(cfg)
    m.init(lab, None, None)
    np.testing.assert_allclose(
        vals[0], m.eval(score.astype(np.float64))[0][1], rtol=1e-6
    )
