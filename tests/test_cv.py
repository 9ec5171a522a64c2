"""lgb.cv (reference engine.py:627): fused chunked per-fold training
with ONE shared traced step across folds."""

from __future__ import annotations

import numpy as np

import lightgbm_tpu as lgb


def _problem(n=4000, f=6, seed=0):
    rs = np.random.RandomState(seed)
    X = rs.randn(n, f)
    w = rs.randn(f)
    y = ((X @ w + 0.3 * rs.randn(n)) > 0).astype(np.float64)
    return X, y


PARAMS = {"objective": "binary", "num_leaves": 15, "metric": "auc",
          "verbosity": -1, "min_data_in_leaf": 5}


def test_cv_basic_and_single_trace():
    from lightgbm_tpu.boosting import _FUSED_STEP_CACHE

    X, y = _problem()
    ds = lgb.Dataset(X, label=y, free_raw_data=False)
    _FUSED_STEP_CACHE.clear()
    res = lgb.cv(dict(PARAMS), ds, num_boost_round=8, nfold=4,
                 stratified=False)
    assert len(res["valid auc-mean"]) == 8
    assert len(res["valid auc-stdv"]) == 8
    assert res["valid auc-mean"][-1] > 0.85
    # the memoized fused step: 4 folds, ONE trace
    assert len(_FUSED_STEP_CACHE) == 1


def test_cv_matches_sync_fold_loop():
    """The fused chunked cv must aggregate the same per-iteration
    numbers as a hand-rolled sync fold loop (same folds, same seeds)."""
    X, y = _problem(seed=3)
    ds = lgb.Dataset(X, label=y, free_raw_data=False)
    res = lgb.cv(dict(PARAMS), ds, num_boost_round=5, nfold=3,
                 stratified=False, seed=7)

    from lightgbm_tpu.engine import _make_n_folds

    ds2 = lgb.Dataset(X, label=y, free_raw_data=False)
    folds = list(_make_n_folds(ds2, 3, dict(PARAMS), 7, False, True))
    per_iter = [[] for _ in range(5)]
    for tr_idx, te_idx in folds:
        tr = ds2.subset(tr_idx)
        te = ds2.subset(te_idx)
        bst = lgb.Booster(params=dict(PARAMS), train_set=tr)
        bst.add_valid(te, "valid")
        bst._gbdt._force_sync = True
        for i in range(5):
            bst.update()
            per_iter[i].append(bst.eval_valid()[0][2])
    ref_means = [float(np.mean(v)) for v in per_iter]
    np.testing.assert_allclose(res["valid auc-mean"], ref_means,
                               rtol=1e-5, atol=1e-6)


def test_cv_early_stopping_and_cvbooster():
    X, y = _problem(seed=5)
    ds = lgb.Dataset(X, label=y, free_raw_data=False)
    res = lgb.cv(dict(PARAMS, early_stopping_round=3,
                      early_stopping_min_delta=0.2), ds,
                 num_boost_round=50, nfold=3, stratified=False,
                 return_cvbooster=True)
    cvb = res["cvbooster"]
    assert 1 <= cvb.best_iteration < 47  # the stop actually fired
    assert len(res["valid auc-mean"]) == cvb.best_iteration
    assert len(cvb.boosters) == 3
    # every fold keeps trees THROUGH the stop iteration (best + k),
    # matching the sync fold loop
    for b in cvb.boosters:
        assert b.num_trees() == cvb.best_iteration + 3


def test_cv_eval_train_metric():
    X, y = _problem(seed=8)
    ds = lgb.Dataset(X, label=y, free_raw_data=False)
    res = lgb.cv(dict(PARAMS), ds, num_boost_round=4, nfold=3,
                 stratified=False, eval_train_metric=True)
    assert any(k.startswith("training ") for k in res), list(res)
    assert any(k.startswith("valid ") for k in res), list(res)


def test_cv_custom_feval_falls_back_to_sync():
    """Custom feval can't ride the fused device loop; cv must still
    work through the per-iteration sync path."""
    X, y = _problem(seed=9)
    ds = lgb.Dataset(X, label=y, free_raw_data=False)

    def feval(preds, eval_data):
        lab = eval_data.get_label()
        return "half_err", float(np.mean((preds > 0.5) != lab)), False

    res = lgb.cv(dict(PARAMS, metric="none"), ds, num_boost_round=3,
                 nfold=3, stratified=False, feval=feval)
    assert "valid half_err-mean" in res, list(res)
    assert len(res["valid half_err-mean"]) == 3
