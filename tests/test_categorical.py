"""Categorical sorted-subset splits (feature_histogram.cpp:246
FindBestThresholdCategoricalInner, non-onehot branch).

Checks the vectorized scan against a literal numpy transcription of the
reference algorithm, end-to-end training quality on data whose signal
one-vs-rest splits cannot capture, and model-file interop (multi-category
bitsets) with the reference CLI."""

from __future__ import annotations

import subprocess
from pathlib import Path

import numpy as np
import pytest

import jax.numpy as jnp

import lightgbm_tpu as lgb
from lightgbm_tpu.learner.split import SearchDirections, best_split

from test_learner import _params

REPO = Path(__file__).resolve().parent.parent
CLI = REPO / ".refbuild" / "lightgbm"


def _oracle_cat_subset(g, h, c, params):
    """Literal numpy port of the reference sorted-subset scan for ONE
    categorical feature. Returns (best_gain_unshifted, left_bins)."""
    B = len(g)
    cat_smooth = params["cat_smooth"]
    l2 = params["lambda_l2"] + params["cat_l2"]
    l1 = params["lambda_l1"]
    eps = 1e-15

    def leaf_gain(G, H):
        t = np.sign(G) * max(abs(G) - l1, 0.0)
        return t * t / (H + l2 + eps)

    valid = [b for b in range(B) if c[b] >= cat_smooth]
    order = sorted(valid, key=lambda b: g[b] / (h[b] + cat_smooth))
    used = len(order)
    max_num_cat = min(params["max_cat_threshold"], (used + 1) // 2)
    sum_g, sum_h, sum_c = g.sum(), h.sum(), c.sum()

    best_gain, best_set = -np.inf, []
    for dir_, start in ((1, 0), (-1, used - 1)):
        lg, lh, lc = 0.0, eps, 0.0
        grp = 0.0
        pos = start
        chosen = []
        for i in range(min(used, max_num_cat)):
            t = order[pos]
            pos += dir_
            chosen = chosen + [t]
            lg += g[t]
            lh += h[t]
            lc += c[t]
            grp += c[t]
            if lc < params["min_data_in_leaf"] or lh < params["min_sum_hessian_in_leaf"]:
                continue
            rc = sum_c - lc
            if rc < params["min_data_in_leaf"] or rc < params["min_data_per_group"]:
                break
            rh = sum_h - lh
            if rh < params["min_sum_hessian_in_leaf"]:
                break
            if grp < params["min_data_per_group"]:
                continue
            grp = 0.0
            gain = leaf_gain(lg, lh) + leaf_gain(sum_g - lg, rh)
            if gain > best_gain:
                best_gain, best_set = gain, list(chosen)
    return best_gain, sorted(best_set)


def test_cat_subset_matches_reference_oracle():
    rs = np.random.RandomState(0)
    B = 32
    F = 1
    g = rs.randn(B).astype(np.float64) * 5
    h = (1.0 + rs.rand(B) * 50).astype(np.float64)
    c = np.round(h).astype(np.float64)

    pd = dict(
        lambda_l1=0.0, lambda_l2=0.0, min_data_in_leaf=1.0,
        min_sum_hessian_in_leaf=0.0, cat_smooth=10.0, cat_l2=10.0,
        max_cat_threshold=32, max_cat_to_onehot=4, min_data_per_group=25.0,
    )
    params = _params(**pd)

    hist = jnp.asarray(
        np.stack([g, h, c])[:, None, :], dtype=jnp.float32
    )  # (3, F, B)
    rec = best_split(
        hist,
        jnp.float32(g.sum()), jnp.float32(h.sum()), jnp.float32(c.sum()),
        jnp.asarray([B], jnp.int32),
        jnp.asarray([-1], jnp.int32),
        jnp.zeros(F, jnp.int32),
        jnp.ones(F, bool),
        params,
        dirs=SearchDirections(cat_subset=True),
    )
    oracle_gain, oracle_set = _oracle_cat_subset(g, h, c, pd)
    parent = g.sum() ** 2 / (h.sum() + 1e-15)
    assert float(rec.gain) > 0
    np.testing.assert_allclose(
        float(rec.gain), oracle_gain - parent, rtol=2e-4, atol=1e-3
    )
    got_set = sorted(np.nonzero(np.asarray(rec.cat_mask))[0].tolist())
    assert got_set == oracle_set


def _cat_problem(n=4000, n_cat=24, seed=7):
    """Binary target driven by membership in a scattered category subset —
    invisible to any single one-vs-rest split."""
    rs = np.random.RandomState(seed)
    cats = rs.randint(0, n_cat, size=n)
    good = set(rs.choice(n_cat, size=n_cat // 2, replace=False).tolist())
    base = np.isin(cats, list(good)).astype(float)
    y = (base + 0.2 * rs.randn(n) > 0.5).astype(float)
    X = np.column_stack([cats.astype(float), rs.randn(n)])
    return X, y


def test_categorical_training_quality():
    X, y = _cat_problem()
    ds = lgb.Dataset(X, label=y, categorical_feature=[0], free_raw_data=False)
    bst = lgb.train(
        {"objective": "binary", "num_leaves": 7, "verbosity": -1,
         "min_data_per_group": 10, "learning_rate": 0.5},
        ds, num_boost_round=10,
    )
    from sklearn.metrics import roc_auc_score

    auc = roc_auc_score(y, bst.predict(X))
    # subset splits separate the good categories in one or two splits;
    # one-vs-rest with 7 leaves cannot reach this
    assert auc > 0.97, auc
    # the model must contain a multi-category bitset node
    dumped = bst.dump_model()
    found_multi = False
    for tree in dumped["tree_info"]:
        stack = [tree["tree_structure"]]
        while stack:
            node = stack.pop()
            if "split_feature" in node:
                if node.get("decision_type") == "==" and "||" in str(
                    node.get("threshold", "")
                ):
                    found_multi = True
                stack.extend(
                    node[k] for k in ("left_child", "right_child") if k in node
                )
    assert found_multi, "no sorted-subset (multi-category) split in model"


def test_categorical_save_load_roundtrip():
    X, y = _cat_problem(seed=9)
    ds = lgb.Dataset(X, label=y, categorical_feature=[0], free_raw_data=False)
    bst = lgb.train(
        {"objective": "binary", "num_leaves": 7, "verbosity": -1,
         "min_data_per_group": 10},
        ds, num_boost_round=5,
    )
    p1 = bst.predict(X)
    s = bst.model_to_string()
    b2 = lgb.Booster(model_str=s)
    np.testing.assert_allclose(b2.predict(X), p1, rtol=1e-6)


@pytest.mark.skipif(not CLI.exists(), reason="reference CLI not built")
def test_categorical_model_predicts_same_in_reference_cli(tmp_path):
    X, y = _cat_problem(seed=11)
    ds = lgb.Dataset(X, label=y, categorical_feature=[0], free_raw_data=False)
    bst = lgb.train(
        {"objective": "binary", "num_leaves": 7, "verbosity": -1,
         "min_data_per_group": 10},
        ds, num_boost_round=5,
    )
    ours = bst.predict(X)
    bst.save_model(tmp_path / "model.txt")
    data = np.column_stack([y, X])
    np.savetxt(tmp_path / "data.tsv", data, delimiter="\t", fmt="%.6f")
    r = subprocess.run(
        [str(CLI), "task=predict", "data=data.tsv", "input_model=model.txt",
         "output_result=pred.txt", "header=false"],
        cwd=tmp_path, capture_output=True, text=True, timeout=300,
    )
    assert r.returncode == 0, r.stdout + r.stderr
    theirs = np.loadtxt(tmp_path / "pred.txt")
    np.testing.assert_allclose(ours, theirs, rtol=1e-5, atol=1e-6)
