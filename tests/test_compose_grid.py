"""Pairwise composability grid: every
(tree_learner x feature-flag) pair must either train cleanly or fail
with a documented LightGBMError — never crash mid-iteration or train
silently-wrong trees. The reference composes these freely
(tree_learner.cpp:17-59); where this build degrades (warn + fallback)
the degraded path must still produce a working model."""

import numpy as np
import pytest

import lightgbm_tpu as lgb

LEARNERS = ["serial", "data", "voting", "feature"]

FLAGS = {
    "plain": {},
    "efb_sparse": {},  # sparse data triggers bundling (marker handled below)
    "extra_trees": {"extra_trees": True},
    "bynode": {"feature_fraction_bynode": 0.5},
    "cegb": {"cegb_tradeoff": 0.5, "cegb_penalty_split": 1e-5},
    "interaction": {"interaction_constraints": [[0, 1, 2], [3, 4, 5]]},
    "quantized": {"use_quantized_grad": True},
    "rounds": {"tpu_growth_mode": "rounds"},
    "monotone": {"monotone_constraints": [1, -1, 0, 0, 0, 0]},
    "linear": {"linear_tree": True},
}


def _data(sparse: bool, seed=0):
    rs = np.random.RandomState(seed)
    n, f = 2048, 6
    if sparse:
        X = np.zeros((n, f))
        for j in range(f):
            m = rs.rand(n) < 0.15
            X[m, j] = rs.randn(int(m.sum()))
    else:
        X = rs.randn(n, f)
    y = (X[:, 0] + X[:, 1] - X[:, 2] + 0.3 * rs.randn(n) > 0).astype(float)
    return X, y


@pytest.mark.parametrize("learner", LEARNERS)
@pytest.mark.parametrize("flag", sorted(FLAGS))
def test_pairwise_compose(learner, flag, tmp_path):
    sparse = flag == "efb_sparse"
    X, y = _data(sparse)
    params = dict(
        objective="binary",
        num_leaves=8,
        min_data_in_leaf=5,
        verbosity=-1,
        tree_learner=learner,
        **FLAGS[flag],
    )
    if flag == "linear" and learner in ("data", "feature", "voting"):
        pytest.skip("linear_tree is host-side (sync loop), mesh-agnostic")
    ds = lgb.Dataset(X, label=y, free_raw_data=False,
                     params={"linear_tree": True} if flag == "linear" else None)
    try:
        bst = lgb.train(params, ds, num_boost_round=3)
    except lgb.basic.LightGBMError as e:  # documented hard failure is OK
        pytest.skip(f"documented fatal: {e}")
    assert bst.num_trees() == 3
    pred = bst.predict(X[:64])
    assert np.isfinite(pred).all()
    assert pred.min() >= 0.0 and pred.max() <= 1.0


def test_voting_with_forced_falls_back(tmp_path):
    """voting + forcedsplits: the election is disabled (stale non-elected
    histogram columns would corrupt forced splits) but training runs."""
    import json

    X, y = _data(False, seed=2)
    p = tmp_path / "forced.json"
    p.write_text(json.dumps({"feature": 0, "threshold": 0.0}))
    params = dict(objective="binary", num_leaves=8, verbosity=-1,
                  tree_learner="voting", forcedsplits_filename=str(p))
    ds = lgb.Dataset(X, label=y, free_raw_data=False)
    bst = lgb.train(params, ds, num_boost_round=2)
    assert bst.num_trees() == 2
    for t in bst._gbdt.models:
        assert int(t.split_feature[0]) == 0


def test_voting_composes_with_efb():
    """voting + EFB: bundle-column election (no enable_bundle=false
    requirement); the elected-column model must still learn."""
    from sklearn.metrics import roc_auc_score

    X, y = _data(True, seed=3)
    params = dict(objective="binary", num_leaves=8, min_data_in_leaf=5,
                  verbosity=-1, tree_learner="voting", top_k=3)
    ds = lgb.Dataset(X, label=y, free_raw_data=False)
    bst = lgb.train(params, ds, num_boost_round=10)
    assert bst.num_trees() == 10
    assert roc_auc_score(y, bst.predict(X)) > 0.75
