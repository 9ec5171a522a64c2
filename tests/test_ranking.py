"""Device LambdaRank + device NDCG (learner/ranking.py).

Gradient values are checked against a literal numpy transcription of
the reference GetGradientsForOneQuery (rank_objective.hpp:182-271,
including the norm path's (0.01+|ds|) regularization and the
log2(1+sum)/sum rescale); NDCG against the host metric; end-to-end
ranking trains through the FUSED loop and learns."""

from __future__ import annotations

import numpy as np
import pytest

import jax.numpy as jnp

import lightgbm_tpu as lgb
import lightgbm_tpu.callback as cbm
from lightgbm_tpu.learner.ranking import (
    build_query_layout,
    default_label_gain,
    inverse_max_dcg,
    lambdarank_gradients,
    ndcg_at,
)


def _oracle_one_query(score, label, lg, imd, sigmoid, trunc, norm):
    """Literal port of GetGradientsForOneQuery."""
    cnt = len(score)
    lam = np.zeros(cnt)
    hes = np.zeros(cnt)
    order = sorted(range(cnt), key=lambda a: -score[a])
    best, worst = score[order[0]], score[order[cnt - 1]]
    sum_lambdas = 0.0
    for i in range(min(cnt - 1, trunc)):
        for j in range(i + 1, cnt):
            if label[order[i]] == label[order[j]]:
                continue
            hr, lr = (i, j) if label[order[i]] > label[order[j]] else (j, i)
            high, low = order[hr], order[lr]
            ds = score[high] - score[low]
            dndcg = (
                abs(lg[int(label[high])] - lg[int(label[low])])
                * abs(1 / np.log2(hr + 2.0) - 1 / np.log2(lr + 2.0))
                * imd
            )
            if norm and best != worst:
                dndcg /= 0.01 + abs(ds)
            p = 1.0 / (1.0 + np.exp(sigmoid * ds))
            ph = p * (1.0 - p)
            pl = -sigmoid * dndcg * p
            ph = sigmoid * sigmoid * dndcg * ph
            lam[low] -= pl
            hes[low] += ph
            lam[high] += pl
            hes[high] += ph
            sum_lambdas -= 2 * pl
    if norm and sum_lambdas > 0:
        f = np.log2(1 + sum_lambdas) / sum_lambdas
        lam *= f
        hes *= f
    return lam, hes


@pytest.mark.parametrize("norm", [True, False])
def test_lambdarank_gradients_match_reference_oracle(norm):
    rs = np.random.RandomState(0)
    group = np.asarray([7, 3, 12, 1, 5])
    n = int(group.sum())
    npad = 32
    label = np.zeros(npad)
    label[:n] = rs.randint(0, 4, n)
    score = np.zeros(npad, np.float32)
    score[:n] = rs.randn(n)
    lg = default_label_gain(3)
    layout = build_query_layout(group, npad)
    imd = inverse_max_dcg(label, layout, lg, trunc := 20)

    g, h = lambdarank_gradients(
        layout, jnp.asarray(score), jnp.asarray(label, jnp.float32),
        jnp.asarray(lg, jnp.float32), jnp.asarray(imd, jnp.float32),
        sigmoid=2.0, truncation_level=trunc, norm=norm,
    )
    g, h = np.asarray(g), np.asarray(h)

    qb = np.concatenate([[0], np.cumsum(group)])
    for q in range(len(group)):
        lo, hi = qb[q], qb[q + 1]
        eg, eh = _oracle_one_query(
            score[lo:hi].astype(np.float64), label[lo:hi], lg, imd[q],
            2.0, trunc, norm,
        )
        np.testing.assert_allclose(g[lo:hi], eg, rtol=2e-4, atol=1e-6)
        np.testing.assert_allclose(h[lo:hi], eh, rtol=2e-4, atol=1e-6)
    assert np.all(g[n:] == 0) and np.all(h[n:] == 0)


def test_device_ndcg_matches_host_metric():
    rs = np.random.RandomState(1)
    group = np.asarray([10, 4, 8, 6])
    n = int(group.sum())
    npad = 32
    label = np.zeros(npad)
    label[:n] = rs.randint(0, 3, n)
    score = np.zeros(npad, np.float32)
    score[:n] = rs.randn(n)
    lg = default_label_gain(2)
    layout = build_query_layout(group, npad)

    vals = np.asarray(ndcg_at(
        layout, jnp.asarray(score), jnp.asarray(label, jnp.float32),
        jnp.asarray(lg, jnp.float32), [1, 3, 5],
    ))

    from lightgbm_tpu.config import Config
    from lightgbm_tpu.metrics import NDCGMetric

    m = NDCGMetric(Config({"eval_at": [1, 3, 5]}))
    m.init(label[:n], None, group)
    host = m.eval(score[:n].astype(np.float64))
    for (nm, hv, _), dv in zip(host, vals):
        np.testing.assert_allclose(dv, hv, rtol=1e-5, atol=1e-6)


def _rank_problem(nq=60, seed=3):
    rs = np.random.RandomState(seed)
    sizes = rs.randint(5, 25, nq)
    n = int(sizes.sum())
    X = rs.randn(n, 6)
    w = rs.randn(6)
    rel = X @ w + 0.5 * rs.randn(n)
    label = np.zeros(n)
    # per-query relevance quartiles -> graded labels 0..3
    qb = np.concatenate([[0], np.cumsum(sizes)])
    for q in range(nq):
        r = rel[qb[q]:qb[q + 1]]
        label[qb[q]:qb[q + 1]] = np.digitize(r, np.quantile(r, [0.5, 0.75, 0.9]))
    return X, label, sizes


def test_lambdarank_end_to_end_fused():
    X, y, group = _rank_problem()
    ds = lgb.Dataset(X, label=y, group=group, free_raw_data=False)
    bst = lgb.train(
        {"objective": "lambdarank", "metric": "ndcg", "eval_at": [5],
         "num_leaves": 15, "learning_rate": 0.1, "verbosity": -1,
         "min_data_in_leaf": 5},
        ds, num_boost_round=20,
        valid_sets=[ds], valid_names=["t"],
    )
    # ranking must now be fused-eligible (device grads + device ndcg)
    assert bst._gbdt.fused_eligible()
    from lightgbm_tpu.config import Config
    from lightgbm_tpu.metrics import NDCGMetric

    m = NDCGMetric(Config({"eval_at": [5]}))
    m.init(y, None, group)
    before = m.eval(np.zeros(len(y)))[0][1]
    after = m.eval(bst.predict(X))[0][1]
    assert after > before + 0.15, (before, after)


def test_lambdarank_document_weights_scale_gradients():
    """RankingObjective::GetGradients multiplies lambdas/hessians by the
    per-document weights (rank_objective.hpp:84-90)."""
    from lightgbm_tpu.config import Config
    from lightgbm_tpu.objectives import create_objective

    X, y, group = _rank_problem(nq=10, seed=5)
    rs = np.random.RandomState(6)
    w = 0.5 + rs.rand(len(y))

    def grads(weight):
        ds = lgb.Dataset(X, label=y, group=group, weight=weight,
                         free_raw_data=False)
        ds.construct()
        obj = create_objective(Config({"objective": "lambdarank"}))
        obj.init(ds._binned)
        npad = ds._binned.num_rows_padded()
        import jax.numpy as jnp

        return obj.get_gradients(jnp.zeros(npad, jnp.float32))

    g0, h0 = grads(None)
    gw, hw = grads(w)
    n = len(y)
    wp = np.zeros(np.asarray(g0).shape)
    wp[:n] = w
    np.testing.assert_allclose(np.asarray(gw), np.asarray(g0) * wp,
                               rtol=1e-5, atol=1e-7)
    # hessians: compare where the pre-floor value dominates (docs in no
    # pair sit at the 2e-7 floor in both runs regardless of weight)
    h0n, hwn = np.asarray(h0)[:n], np.asarray(hw)[:n]
    live = h0n > 1e-6
    assert live.any()
    np.testing.assert_allclose(hwn[live], h0n[live] * w[live],
                               rtol=1e-5, atol=1e-7)


def test_lambdarank_sklearn():
    X, y, group = _rank_problem(seed=9)
    rk = lgb.LGBMRanker(n_estimators=8, num_leaves=7, verbosity=-1,
                        min_data_in_leaf=5)
    rk.fit(X, y, group=group)
    assert np.isfinite(rk.predict(X)).all()


def test_rank_xendcg_trains_and_learns():
    X, y, group = _rank_problem(nq=50, seed=13)
    ds = lgb.Dataset(X, label=y, group=group, free_raw_data=False)
    bst = lgb.train(
        {"objective": "rank_xendcg", "metric": "ndcg", "eval_at": [5],
         "num_leaves": 15, "learning_rate": 0.1, "verbosity": -1,
         "min_data_in_leaf": 5},
        ds, num_boost_round=25,
        valid_sets=[ds], valid_names=["t"],
    )
    assert bst._gbdt.fused_eligible()
    from lightgbm_tpu.config import Config
    from lightgbm_tpu.metrics import NDCGMetric

    m = NDCGMetric(Config({"eval_at": [5]}))
    m.init(y, None, group)
    before = m.eval(np.zeros(len(y)))[0][1]
    after = m.eval(bst.predict(X))[0][1]
    assert after > before + 0.1, (before, after)


def test_xentlambda_weighted_and_unweighted():
    rs = np.random.RandomState(4)
    X = rs.randn(1500, 5)
    w = rs.randn(5)
    y = 1.0 / (1.0 + np.exp(-(X @ w)))  # probabilistic labels in [0,1]
    ds = lgb.Dataset(X, label=y, free_raw_data=False)
    bst = lgb.train({"objective": "xentlambda", "num_leaves": 15,
                     "verbosity": -1}, ds, num_boost_round=15)
    pred = bst.predict(X)  # normalized exponential parameter (>0)
    assert (pred > 0).all()
    # with unit weights the gradient reduces to plain cross-entropy:
    # implied probability 1-exp(-pred) should track the labels
    p = 1.0 - np.exp(-pred)
    assert np.corrcoef(p, y)[0, 1] > 0.9

    wts = 0.5 + rs.rand(1500)
    ds2 = lgb.Dataset(X, label=y, weight=wts, free_raw_data=False)
    b2 = lgb.train({"objective": "xentlambda", "num_leaves": 15,
                    "verbosity": -1}, ds2, num_boost_round=5)
    assert np.isfinite(b2.predict(X)).all()


def test_lambdarank_position_bias():
    """Position debiasing (rank_objective.hpp:302): with click-style
    labels biased toward early positions, the learned per-position bias
    factors must be (roughly) decreasing in position."""
    rs = np.random.RandomState(3)
    n_q, docs = 120, 8
    n = n_q * docs
    rel = rs.randint(0, 3, n).astype(np.float64)  # true relevance
    pos = np.tile(np.arange(docs), n_q)
    # observed label: relevance observed only when the position is seen
    seen = rs.rand(n) < (1.0 / (1.0 + 0.7 * pos))
    label = np.where(seen, rel, 0.0)
    X = rs.randn(n, 5)
    X[:, 0] += rel  # informative feature
    group = np.full(n_q, docs)

    ds = lgb.Dataset(X, label=label, group=group, position=pos,
                     free_raw_data=False)
    bst = lgb.train(
        {"objective": "lambdarank", "num_leaves": 7, "min_data_in_leaf": 3,
         "lambdarank_position_bias_regularization": 0.5, "verbosity": -1},
        ds, num_boost_round=10,
    )
    biases = np.asarray(bst._gbdt.objective.position_biases)
    assert biases.shape == (docs,)
    assert np.any(biases != 0.0)
    # later positions get lower (more negative) bias factors
    assert biases[0] > biases[-1]


def test_device_map_matches_host_metric():
    from lightgbm_tpu.learner.ranking import map_at

    rs = np.random.RandomState(2)
    group = np.asarray([10, 4, 8, 6])
    n = int(group.sum())
    npad = 32
    label = np.zeros(npad)
    label[:n] = (rs.rand(n) > 0.6).astype(float)
    score = np.zeros(npad, np.float32)
    score[:n] = rs.randn(n)
    layout = build_query_layout(group, npad)

    vals = np.asarray(map_at(
        layout, jnp.asarray(score), jnp.asarray(label, jnp.float32),
        [1, 3, 5],
    ))

    from lightgbm_tpu.config import Config
    from lightgbm_tpu.metrics import MapMetric

    m = MapMetric(Config({"eval_at": [1, 3, 5]}))
    m.init(label[:n], None, group)
    host = m.eval(score[:n].astype(np.float64))
    for (nm, hv, _), dv in zip(host, vals):
        np.testing.assert_allclose(dv, hv, rtol=1e-5, atol=1e-6,
                                   err_msg=nm)


def test_map_metric_stays_fused():
    """metric=map must keep lambdarank configs on the fused device loop."""
    X, y, group = _rank_problem()
    params = dict(objective="lambdarank", num_leaves=15, min_data_in_leaf=3,
                  metric="map", eval_at=[3, 5], verbosity=-1,
                  lambdarank_position_bias=False)
    params = {k: v for k, v in params.items()
              if k != "lambdarank_position_bias"}
    ds = lgb.Dataset(X, label=y, group=group, free_raw_data=False)
    evals = {}
    bst = lgb.train(params, ds, num_boost_round=8,
                    valid_sets=[ds], valid_names=["tr"],
                    callbacks=[cbm.record_evaluation(evals)])
    assert bst._gbdt.fused_eligible()
    assert "map@3" in evals["tr"] and len(evals["tr"]["map@3"]) == 8
    assert evals["tr"]["map@5"][-1] > evals["tr"]["map@5"][0] - 1e-9
