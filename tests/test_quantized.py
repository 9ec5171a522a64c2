"""Quantized-gradient training (use_quantized_grad,
gradient_discretizer.cpp:22 semantics through the dequantized-value
formulation in learner/quantize.py)."""

from __future__ import annotations

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import lightgbm_tpu as lgb
from lightgbm_tpu.learner.quantize import discretize_gradients


def test_discretize_levels_and_scales():
    rs = np.random.RandomState(0)
    g = jnp.asarray(rs.randn(5000).astype(np.float32))
    h = jnp.asarray((0.1 + rs.rand(5000)).astype(np.float32))
    nb = 4
    gq, hq = discretize_gradients(g, h, jax.random.key(0), nb, True)
    g_scale = float(jnp.max(jnp.abs(g))) / (nb // 2)
    h_scale = float(jnp.max(jnp.abs(h))) / nb
    # dequantized values sit exactly on the level grid
    lev_g = np.asarray(gq) / g_scale
    lev_h = np.asarray(hq) / h_scale
    np.testing.assert_allclose(lev_g, np.round(lev_g), atol=1e-4)
    np.testing.assert_allclose(lev_h, np.round(lev_h), atol=1e-4)
    assert np.abs(lev_g).max() <= nb // 2 + 1e-6
    assert lev_h.min() >= 0 and lev_h.max() <= nb + 1e-6
    # stochastic rounding is unbiased: mean error ~ 0
    assert abs(float(jnp.mean(gq - g))) < 3 * g_scale / np.sqrt(len(lev_g))


def test_deterministic_rounding():
    g = jnp.asarray(np.linspace(-1, 1, 101, dtype=np.float32))
    h = jnp.ones(101, jnp.float32)
    gq, _ = discretize_gradients(g, h, jax.random.key(0), 4, False)
    # plain rounding: nearest level (truncate after +0.5 toward zero)
    g_scale = 1.0 / 2
    np.testing.assert_allclose(
        np.asarray(gq) / g_scale,
        np.trunc(np.asarray(g) / g_scale + np.sign(np.asarray(g)) * 0.5),
        atol=1e-6,
    )


def _problem(n=4000, seed=1):
    rs = np.random.RandomState(seed)
    X = rs.randn(n, 8)
    w = rs.randn(8)
    y = ((X @ w + 0.5 * rs.randn(n)) > 0).astype(float)
    return X, y


@pytest.mark.parametrize("renew", [False, True])
def test_quantized_training_quality(renew):
    """AUC with 4-bin quantized gradients stays within tolerance of full
    precision (the reference's quantized-training guarantee)."""
    from sklearn.metrics import roc_auc_score

    X, y = _problem()
    params = {
        "objective": "binary",
        "num_leaves": 31,
        "learning_rate": 0.1,
        "verbosity": -1,
    }
    ds = lgb.Dataset(X, label=y, free_raw_data=False)
    full = lgb.train(dict(params), ds, num_boost_round=30)
    auc_full = roc_auc_score(y, full.predict(X))

    ds2 = lgb.Dataset(X, label=y, free_raw_data=False)
    quant = lgb.train(
        {**params, "use_quantized_grad": True,
         "quant_train_renew_leaf": renew},
        ds2, num_boost_round=30,
    )
    auc_q = roc_auc_score(y, quant.predict(X))
    assert auc_q > auc_full - 0.01, (auc_q, auc_full)
    # quantization must actually change the model
    assert not np.allclose(quant.predict(X[:100]), full.predict(X[:100]))


def test_quantized_rides_fused_loop():
    X, y = _problem(seed=3)
    ds = lgb.Dataset(X, label=y, free_raw_data=False)
    bst = lgb.train(
        {"objective": "binary", "num_leaves": 15, "verbosity": -1,
         "use_quantized_grad": True, "metric": "auc"},
        ds, num_boost_round=10, valid_sets=[ds], valid_names=["t"],
    )
    assert bst._gbdt.fused_eligible()
    assert bst.num_trees() == 10


def test_quantized_regression_l2():
    X, _ = _problem(seed=5)
    rs = np.random.RandomState(6)
    y = X @ rs.randn(8) + 0.2 * rs.randn(len(X))
    ds = lgb.Dataset(X, label=y, free_raw_data=False)
    q = lgb.train(
        {"objective": "regression", "num_leaves": 31, "verbosity": -1,
         "use_quantized_grad": True},
        ds, num_boost_round=30,
    )
    mse = float(np.mean((q.predict(X) - y) ** 2))
    assert mse < 0.3 * float(np.var(y)), mse


def test_quantized_rounds_matches_dequantized_semantics():
    """The rounds grower's exact-int histogram path (spec.quant) must
    produce the same trees as feeding the DEQUANTIZED values through the
    standard channels: int sums x scale == sums of (level x scale)."""
    from lightgbm_tpu.config import Config
    from lightgbm_tpu.dataset import BinnedDataset
    from lightgbm_tpu.learner import GrowerSpec, grow_tree, make_split_params
    from lightgbm_tpu.learner.quantize import discretize_gradients_int

    rs = np.random.RandomState(3)
    X = rs.randn(4096, 6).astype(np.float32)
    cfg = Config({"max_bin": 63, "min_data_in_leaf": 5})
    ds = BinnedDataset.from_numpy(X, cfg)
    d = ds.device_arrays()
    N = ds.num_rows_padded()
    F = ds.num_used_features
    g = jnp.asarray(rs.randn(N).astype(np.float32)) * d["valid"]
    h = (jnp.ones(N, jnp.float32) * 0.25) * d["valid"]
    gq, hq, scale = discretize_gradients_int(g, h, jax.random.key(1), 4, False)
    params = make_split_params(Config({"num_leaves": 31, "max_bin": 63,
                                       "min_data_in_leaf": 5}))
    base = dict(num_leaves=31, num_bins=ds.max_num_bin, max_depth=-1)
    spec_q = GrowerSpec(**base, rounds_slots=25, quant=True)
    spec_f = GrowerSpec(**base, rounds_slots=25)
    tq, rlq = grow_tree(
        d["bins"], d["nan_bin"], d["num_bins"], d["mono"], d["is_cat"],
        gq, hq, d["valid"], jnp.ones(F, bool), params, spec_q,
        valid=d["valid"], gh_scale=scale,
    )
    tf, rlf = grow_tree(
        d["bins"], d["nan_bin"], d["num_bins"], d["mono"], d["is_cat"],
        gq * scale[0], hq * scale[1], d["valid"], jnp.ones(F, bool), params,
        spec_f, valid=d["valid"],
    )
    assert int(tq.num_nodes) == int(tf.num_nodes)
    np.testing.assert_array_equal(np.asarray(rlq), np.asarray(rlf))
    np.testing.assert_allclose(np.asarray(tq.leaf_value),
                               np.asarray(tf.leaf_value), atol=1e-5)


def test_quantized_multiclass_parity():
    """use_quantized_grad on multiclass (K gradient channels per
    iteration): accuracy and logloss stay within tolerance of the
    unquantized path."""
    rs = np.random.RandomState(11)
    n = 3000
    X = rs.randn(n, 8)
    centers = rs.randn(3, 8)
    y = np.argmax(X @ centers.T + 0.5 * rs.randn(n, 3), axis=1)
    params = {"objective": "multiclass", "num_class": 3, "num_leaves": 15,
              "verbosity": -1, "min_data_in_leaf": 5}
    full = lgb.train(dict(params),
                     lgb.Dataset(X, label=y, free_raw_data=False),
                     num_boost_round=20)
    quant = lgb.train({**params, "use_quantized_grad": True},
                      lgb.Dataset(X, label=y, free_raw_data=False),
                      num_boost_round=20)
    pf, pq = full.predict(X), quant.predict(X)
    acc_f = float(np.mean(np.argmax(pf, axis=1) == y))
    acc_q = float(np.mean(np.argmax(pq, axis=1) == y))
    eps = 1e-15
    ll_f = -float(np.mean(np.log(np.clip(pf[np.arange(n), y], eps, 1))))
    ll_q = -float(np.mean(np.log(np.clip(pq[np.arange(n), y], eps, 1))))
    assert acc_q > acc_f - 0.02, (acc_q, acc_f)
    assert ll_q < ll_f + 0.05, (ll_q, ll_f)
    # quantization must actually change the model
    assert not np.allclose(pf[:100], pq[:100])


def test_quantized_lambdarank_parity():
    """use_quantized_grad on LambdaRank: NDCG@5 parity with the
    unquantized path."""
    from sklearn.metrics import ndcg_score

    rs = np.random.RandomState(12)
    n_q, per_q = 40, 50
    n = n_q * per_q
    X = rs.randn(n, 8)
    rel = np.clip((X[:, 0] + X[:, 1] + 0.4 * rs.randn(n)) + 2, 0, 4)
    y = rel.astype(int)
    group = np.full(n_q, per_q)
    params = {"objective": "lambdarank", "num_leaves": 15, "verbosity": -1,
              "min_data_in_leaf": 2}

    def ndcg5(bst):
        s = bst.predict(X)
        return float(np.mean([
            ndcg_score(y[q * per_q:(q + 1) * per_q][None, :],
                       s[q * per_q:(q + 1) * per_q][None, :], k=5)
            for q in range(n_q)
        ]))

    full = lgb.train(dict(params),
                     lgb.Dataset(X, label=y, group=group,
                                 free_raw_data=False),
                     num_boost_round=20)
    quant = lgb.train({**params, "use_quantized_grad": True},
                      lgb.Dataset(X, label=y, group=group,
                                  free_raw_data=False),
                      num_boost_round=20)
    nf, nq = ndcg5(full), ndcg5(quant)
    assert nq > nf - 0.02, (nq, nf)
    assert not np.allclose(full.predict(X[:100]), quant.predict(X[:100]))


def test_quantized_rounds_via_train_api():
    rs = np.random.RandomState(6)
    X = rs.randn(3000, 6)
    y = (X[:, 0] + X[:, 1] ** 2 + 0.3 * rs.randn(3000) > 1).astype(float)
    from sklearn.metrics import roc_auc_score

    params = dict(objective="binary", num_leaves=15, min_data_in_leaf=5,
                  verbosity=-1, use_quantized_grad=True,
                  tpu_growth_mode="rounds")
    ds = lgb.Dataset(X, label=y, free_raw_data=False)
    bst = lgb.train(params, ds, num_boost_round=8)
    assert roc_auc_score(y, bst.predict(X)) > 0.9
