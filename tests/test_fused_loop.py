"""Fused device loop (one dispatch per iteration, chunked eval fetch)
must be bit-for-bit equivalent in behavior to the synchronous path."""

import numpy as np
import pytest

import lightgbm_tpu as lgb
import lightgbm_tpu.boosting as bmod
import lightgbm_tpu.callback as cbm


def _train_both(params, X, y, Xv, yv, rounds, callbacks_factory=lambda r: [cbm.record_evaluation(r)]):
    res_f, res_s = {}, {}
    ds = lgb.Dataset(X, label=y, free_raw_data=False)
    dv = lgb.Dataset(Xv, label=yv, free_raw_data=False)
    bst_f = lgb.train(dict(params), ds, num_boost_round=rounds,
                      valid_sets=[dv], valid_names=["va"],
                      callbacks=callbacks_factory(res_f))
    orig = bmod.GBDT.fused_eligible
    bmod.GBDT.fused_eligible = lambda self: False
    try:
        ds2 = lgb.Dataset(X, label=y, free_raw_data=False)
        dv2 = lgb.Dataset(Xv, label=yv, free_raw_data=False)
        bst_s = lgb.train(dict(params), ds2, num_boost_round=rounds,
                          valid_sets=[dv2], valid_names=["va"],
                          callbacks=callbacks_factory(res_s))
    finally:
        bmod.GBDT.fused_eligible = orig
    return bst_f, bst_s, res_f, res_s


def test_fused_equals_sync_binary():
    rs = np.random.RandomState(3)
    X = rs.randn(1200, 6)
    w = rs.randn(6)
    y = ((X @ w + 0.3 * rs.randn(1200)) > 0).astype(float)
    bst_f, bst_s, res_f, res_s = _train_both(
        {"objective": "binary", "num_leaves": 7,
         "metric": ["auc", "binary_logloss"], "verbosity": -1},
        X[:800], y[:800], X[800:], y[800:], 15,
    )
    np.testing.assert_allclose(
        bst_f.predict(X[800:]), bst_s.predict(X[800:]), rtol=1e-5, atol=1e-6
    )
    np.testing.assert_allclose(res_f["va"]["auc"], res_s["va"]["auc"],
                               rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(
        res_f["va"]["binary_logloss"], res_s["va"]["binary_logloss"],
        rtol=1e-4, atol=1e-6,
    )


def test_fused_early_stopping_matches_reference_timing():
    rs = np.random.RandomState(5)
    X = rs.randn(900, 5)
    y = (X[:, 0] + 0.5 * rs.randn(900) > 0).astype(float)
    ds = lgb.Dataset(X[:600], label=y[:600], free_raw_data=False)
    dv = lgb.Dataset(X[600:], label=y[600:], free_raw_data=False)
    res = {}
    bst = lgb.train(
        {"objective": "binary", "num_leaves": 7, "metric": "auc",
         "verbosity": -1, "early_stopping_round": 3},
        ds, num_boost_round=300, valid_sets=[dv],
        callbacks=[cbm.record_evaluation(res)],
    )
    # reference semantics: training stops exactly early_stopping_round
    # iterations after the best one; trained-ahead chunk iters truncated
    assert bst.best_iteration >= 1
    assert bst.num_trees() == bst.best_iteration + 3


def test_fused_nonzero_mean_regression_bias():
    rs = np.random.RandomState(11)
    X = rs.randn(1000, 5)
    y = 25.0 + X[:, 0] + 0.1 * rs.randn(1000)
    ds = lgb.Dataset(X, label=y, free_raw_data=False)
    bst = lgb.train(
        {"objective": "regression", "num_leaves": 15, "learning_rate": 0.2,
         "metric": "l2", "verbosity": -1},
        ds, num_boost_round=30,
    )
    pred = bst.predict(X)
    assert float(np.sqrt(np.mean((pred - y) ** 2))) < 0.5


def test_fused_bagging_and_feature_fraction():
    rs = np.random.RandomState(13)
    X = rs.randn(1500, 8)
    w = rs.randn(8)
    y = ((X @ w) > 0).astype(float)
    ds = lgb.Dataset(X, label=y, free_raw_data=False)
    res = {}
    bst = lgb.train(
        {"objective": "binary", "num_leaves": 15, "metric": "auc",
         "bagging_fraction": 0.6, "bagging_freq": 2,
         "feature_fraction": 0.7, "verbosity": -1},
        ds, num_boost_round=25, valid_sets=[ds], valid_names=["tr"],
        callbacks=[cbm.record_evaluation(res)],
    )
    assert res["tr"]["auc"][-1] > 0.9


def test_fused_step_memo_across_boosters():
    """cv folds / repeated trains with identical shapes+config reuse one
    traced+compiled fused step: the second Booster
    must skip trace+compile entirely."""
    import time

    import numpy as np

    import lightgbm_tpu as lgb
    from lightgbm_tpu.boosting import _FUSED_STEP_CACHE

    rs = np.random.RandomState(0)
    n, f = 4096, 6
    params = {"objective": "binary", "num_leaves": 15, "verbosity": -1,
              "metric": "auc", "min_data_in_leaf": 5}

    def one(seed):
        X = rs.randn(n, f)
        w = rs.randn(f)
        y = ((X @ w + 0.3 * rs.randn(n)) > 0).astype(np.float64)
        ds = lgb.Dataset(X, label=y, free_raw_data=False)
        vs = lgb.Dataset(X[:1024].copy(), label=y[:1024].copy(),
                         reference=ds, free_raw_data=False)
        t0 = time.time()
        bst = lgb.train(dict(params), ds, num_boost_round=8,
                        valid_sets=[vs], valid_names=["v"])
        return time.time() - t0, bst

    _FUSED_STEP_CACHE.clear()
    t1, b1 = one(1)
    assert len(_FUSED_STEP_CACHE) == 1  # step was built and memoized
    t2, b2 = one(2)
    assert len(_FUSED_STEP_CACHE) == 1  # second Booster reused it
    # the reuse must actually skip trace+compile: fold 2 pays only the
    # run itself (fold 1 includes a multi-second trace+compile even
    # with a warm persistent cache)
    assert t2 < max(t1 * 0.6, 5.0), (t1, t2)
    # both trained sane models
    p1, p2 = b1.predict(rs.randn(50, f)), b2.predict(rs.randn(50, f))
    assert np.isfinite(p1).all() and np.isfinite(p2).all()


def test_fused_step_memo_includes_ranking():
    """The ranking objectives and metrics read their query layout from
    the step's arguments, so those configs share the memoized step
    (tests/test_ranking.py holds the fold-safety checks)."""
    import numpy as np

    import lightgbm_tpu as lgb
    from lightgbm_tpu.boosting import _FUSED_STEP_CACHE

    rs = np.random.RandomState(3)
    n, f = 2048, 5
    X = rs.randn(n, f)
    y = rs.randint(0, 4, n).astype(np.float64)
    group = np.full(n // 16, 16, np.int64)
    _FUSED_STEP_CACHE.clear()
    ds = lgb.Dataset(X, label=y, group=group, free_raw_data=False)
    lgb.train({"objective": "lambdarank", "num_leaves": 15,
               "verbosity": -1, "metric": "ndcg", "eval_at": [3]},
              ds, num_boost_round=3, valid_sets=[ds], valid_names=["t"])
    assert len(_FUSED_STEP_CACHE) == 1


_KINDS_JOB = """
import sys, numpy as np, lightgbm_tpu as lgb
rs = np.random.RandomState(11)
X = rs.randn(2048, 6)
y = ((X @ rs.randn(6) + 0.3 * rs.randn(2048)) > 0).astype(np.float64)
kind = sys.argv[1]
cat = []
if kind == "nan":
    X[rs.rand(2048) < 0.2, 2] = np.nan
if kind == "cat":
    # three codes + the other bin = 4 bins = max_cat_to_onehot: the
    # column stays one-vs-rest (a fourth code would make it a subset
    # column, as in the reference, whose bin count holds the other bin)
    X[:, 2] = rs.randint(0, 3, 2048)
    cat = [2]
ds = lgb.Dataset(X, label=y, categorical_feature=cat, free_raw_data=False)
bst = lgb.train({"objective": "binary", "num_leaves": 15, "verbosity": -1,
                 "max_bin": 15, "min_data_in_leaf": 5}, ds,
                num_boost_round=6)
"""


@pytest.mark.parametrize("kind,fact,reads", [
    ("nan", "has_nan", (1, 1, 0, 0, 0)), ("cat", "has_cat", (1, 0, 1, 0, 0))])
def test_column_kinds_are_in_the_memo_key_and_the_directions_gauge(
        kind, fact, reads):
    """Two Datasets of equal shapes in one process, the second with a NaN
    (a categorical) column: the split search traces another direction
    list, so the second job may not reuse the first's memoized step; its
    model is the one a fresh process trains."""
    import subprocess
    import sys

    from lightgbm_tpu.boosting import _FUSED_STEP_CACHE
    from lightgbm_tpu.obs.metrics import default_registry

    def gauge():
        g = default_registry().snapshot()["lgbmtpu_split_search_directions"]
        return tuple(int(g['{kind="%s"}' % k]) for k in (
            "default_right", "default_left", "categorical", "cat_subset",
            "monotone_test"))

    def job(k):
        scope = {}
        exec(compile(_KINDS_JOB.replace("sys.argv[1]", repr(k)), "job",
                     "exec"), scope)
        return scope["bst"]

    def cat_words():
        # rows the category sets add to the valid traversal's table
        snap = default_registry().snapshot()["lgbmtpu_traverse_cat_words"]
        return int(snap[""])

    _FUSED_STEP_CACHE.clear()
    plain = job("plain")
    assert len(_FUSED_STEP_CACHE) == 1 and gauge() == (1, 0, 0, 0, 0)
    assert cat_words() == 0
    other = job(kind)
    assert len(_FUSED_STEP_CACHE) == 2 and gauge() == reads
    # 15 bins + the other bin ride one 16-bit word
    assert cat_words() == (1 if kind == "cat" else 0)
    # the column's kind is ALL that parts the two programs
    assert other._gbdt.spec == plain._gbdt.spec._replace(**{fact: True})
    fresh = subprocess.run(
        [sys.executable, "-c",
         _KINDS_JOB + "sys.stdout.write(bst.model_to_string())", kind],
        capture_output=True, text=True, timeout=600, check=True).stdout
    assert other.model_to_string() == fresh
