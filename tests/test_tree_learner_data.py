"""tree_learner=data through the PUBLIC API on the virtual 8-device mesh.

The reference selects a distributed learner by config
(tree_learner.cpp:17-59) and its data-parallel algorithm guarantees all
ranks grow identical trees from globally-reduced histograms
(data_parallel_tree_learner.cpp:286). Here the same config routes
lgb.train through the shard_map'd grower: rows sharded over the mesh,
histograms psum'd, trees replicated — predictions must match serial
training."""

from __future__ import annotations

import gc

import numpy as np
import pytest

import lightgbm_tpu as lgb


@pytest.fixture(scope="module", autouse=True)
def _fresh_heap():
    """Free every cached executable before this module's 8-device mesh
    compiles: late in the full suite the process heap holds hundreds of
    live executables, and serializing THIS module's large shard_map'd
    fused-step executable into the persistent compile cache has
    segfaulted inside jax's put_executable_and_time under that memory
    pressure (exit 139 at ~76% of the suite; standalone runs pass).
    Clearing first costs a few recompiles and removes the crash."""
    import jax

    from lightgbm_tpu.boosting import _FUSED_STEP_CACHE

    _FUSED_STEP_CACHE.clear()
    jax.clear_caches()
    gc.collect()
    yield


def _binary_problem(n=4096, f=10, seed=3):
    rs = np.random.RandomState(seed)
    X = rs.randn(n, f)
    w = rs.randn(f)
    y = ((X @ w + 0.3 * rs.randn(n)) > 0).astype(np.float64)
    return X, y


def _train(params, X, y, rounds=15, **kw):
    ds = lgb.Dataset(X, label=y, free_raw_data=False)
    return lgb.train(dict(params), ds, num_boost_round=rounds, **kw)


BASE = {
    "objective": "binary",
    "num_leaves": 15,
    "learning_rate": 0.2,
    "metric": "auc",
    "verbosity": -1,
}


def test_data_parallel_matches_serial_binary():
    X, y = _binary_problem()
    b_serial = _train(BASE, X, y)
    b_data = _train({**BASE, "tree_learner": "data"}, X, y)
    assert b_data.num_trees() == b_serial.num_trees()
    np.testing.assert_allclose(
        b_data.predict(X), b_serial.predict(X), rtol=1e-4, atol=1e-5
    )


def test_data_parallel_matches_serial_regression_with_valid():
    rs = np.random.RandomState(5)
    X = rs.randn(4096, 8)
    w = rs.randn(8)
    y = X @ w + 0.1 * rs.randn(4096)
    Xv, yv = X[:512], y[:512]
    params = {
        "objective": "regression",
        "num_leaves": 31,
        "learning_rate": 0.1,
        "metric": "l2",
        "verbosity": -1,
    }

    def go(extra):
        ds = lgb.Dataset(X, label=y, free_raw_data=False)
        vs = lgb.Dataset(Xv, label=yv, reference=ds, free_raw_data=False)
        return lgb.train({**params, **extra}, ds, num_boost_round=12,
                         valid_sets=[vs], valid_names=["v"])

    b_serial = go({})
    b_data = go({"tree_learner": "data"})
    np.testing.assert_allclose(
        b_data.predict(X[:200]), b_serial.predict(X[:200]), rtol=1e-4, atol=1e-5
    )


def test_voting_parallel_aliases_data():
    X, y = _binary_problem(n=2048)
    b = _train({**BASE, "tree_learner": "voting"}, X, y, rounds=5)
    assert b.num_trees() == 5


def test_data_parallel_multiclass():
    rs = np.random.RandomState(11)
    X = rs.randn(3000, 6)
    y = (X[:, 0] + 0.5 * rs.randn(3000) > 0).astype(int) + (
        X[:, 1] > 0.5
    ).astype(int)
    params = {
        "objective": "multiclass",
        "num_class": 3,
        "num_leaves": 7,
        "verbosity": -1,
    }
    b_serial = _train(params, X, y.astype(float), rounds=8)
    b_data = _train({**params, "tree_learner": "data"}, X, y.astype(float), rounds=8)
    ps, pd = b_serial.predict(X[:100]), b_data.predict(X[:100])
    np.testing.assert_allclose(pd, ps, rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(pd.sum(axis=1), 1.0, rtol=1e-5)


def test_voting_parallel_trains():
    """tree_learner=voting: top-k election restricts the search and the
    psum payload; trees differ from tree_learner=data only by the
    election approximation (voting_parallel_tree_learner.cpp)."""
    from sklearn.metrics import roc_auc_score

    X, y = _binary_problem(n=4096, f=12, seed=9)
    b_vote = _train({**BASE, "tree_learner": "voting", "top_k": 4}, X, y)
    assert b_vote.num_trees() == 15
    auc = roc_auc_score(y, b_vote.predict(X))
    assert auc > 0.9

    # with top_k >= num_features the election is a no-op: identical to
    # tree_learner=data
    b_vote_full = _train({**BASE, "tree_learner": "voting", "top_k": 12}, X, y)
    b_data = _train({**BASE, "tree_learner": "data"}, X, y)
    np.testing.assert_allclose(
        b_vote_full.predict(X), b_data.predict(X), rtol=1e-4, atol=1e-5
    )


def test_voting_on_rounds_matches_data_saturated():
    """tree_learner=voting on the rounds grower (ISSUE 14): with
    top_k >= num_features every column wins election, so the per-round
    election is exact and predictions must match tree_learner=data on
    the same rounds path — the 8-mesh lockstep contract. With a small
    top_k the election restricts the search (and the wire) but the
    model must still learn."""
    from sklearn.metrics import roc_auc_score

    X, y = _binary_problem(n=4096, f=12, seed=9)
    r = {"tpu_growth_mode": "rounds"}
    b_vote = _train({**BASE, **r, "tree_learner": "voting", "top_k": 12},
                    X, y)
    b_data = _train({**BASE, **r, "tree_learner": "data"}, X, y)
    assert b_vote.num_trees() == b_data.num_trees()
    np.testing.assert_allclose(
        b_vote.predict(X), b_data.predict(X), rtol=1e-4, atol=1e-5
    )

    b_small = _train({**BASE, **r, "tree_learner": "voting", "top_k": 3},
                     X, y)
    assert b_small.num_trees() == 15
    assert roc_auc_score(y, b_small.predict(X)) > 0.9
    # provenance attrs the flight recorder / manifest read
    g = b_small._gbdt
    assert g.tree_learner_resolved == "voting"
    assert g.voting_elected_cols == 6  # 2 * top_k, no forced columns
    assert g.voting_wire_bytes_est and g.voting_wire_bytes_est > 0
    # the elected-only estimate must undercut the all-feature payload
    full = 3 * 12 * g.spec.num_bins * 4 * g.spec.num_leaves
    assert g.voting_wire_bytes_est < full


def test_voting_rounds_jaxpr_wire():
    """The voting grower's compiled program must contain NO full-width
    reduce-scatter: the election ships only elected columns, as an
    int16 psum payload when the quantized sums provably fit
    (rounds.vote_reduce + histogram.rs_wire_dtype). Asserted off the
    jaxpr with the same walkers the static audits use."""
    import jax
    import jax.numpy as jnp

    from lightgbm_tpu.analysis.cost_audit import collect_wire
    from lightgbm_tpu.analysis.jaxpr_audit import summarize
    from lightgbm_tpu.config import Config
    from lightgbm_tpu.dataset import BinnedDataset
    from lightgbm_tpu.learner import GrowerSpec, make_split_params
    from lightgbm_tpu.parallel.data_parallel import (
        DataParallelGrower,
        make_mesh,
    )

    X, _ = _binary_problem(seed=13)
    cfg = Config({"max_bin": 63, "min_data_in_leaf": 5})
    ds = BinnedDataset.from_numpy(X.astype(np.float32), cfg)
    d = ds.device_arrays()
    Np = ds.num_rows_padded()
    spec = GrowerSpec(num_leaves=15, num_bins=ds.max_num_bin,
                      max_depth=-1, rounds_slots=8, has_cat=False,
                      quant=True, quant_levels=4, voting_k=2)
    g = DataParallelGrower(make_mesh(), spec)
    gq = jnp.asarray(
        np.random.RandomState(0).randint(-2, 3, Np).astype(np.float32))
    hq = jnp.ones(Np, jnp.float32)
    closed = jax.make_jaxpr(lambda *a: g._fn(*a))(
        d["bins"], d["nan_bin"], d["num_bins"], d["mono"], d["is_cat"],
        gq, hq, d["valid"], jnp.ones(ds.num_used_features, bool),
        make_split_params(cfg), d["valid"], None, None, None, None, None,
        jnp.asarray(np.float32([0.1, 0.1])),
    )
    s = summarize(closed)
    assert s.prim_counts.get("reduce_scatter", 0) == 0, (
        "full-width reduce-scatter wire survived under voting"
    )
    assert s.prim_counts.get("psum", 0) > 0
    wire = collect_wire(closed)
    assert any(w.prim == "psum" and w.dtype == "int16" for w in wire), (
        f"elected-column payload did not ride int16: {wire}"
    )


def test_rounds_and_efb_on_mesh():
    """The rounds grower and EFB under shard_map: the round's psums
    (global child counts, slot histograms over bundle columns) only
    execute on a mesh — cover them here."""
    # sparse blocks so EFB actually bundles
    rs = np.random.RandomState(13)
    n = 4096
    Xs = np.zeros((n, 9))
    idx = rs.randint(0, 9, n)
    on = rs.rand(n) < 0.5
    Xs[np.arange(n)[on], idx[on]] = rs.rand(int(on.sum())) + 0.5
    Xd = rs.randn(n, 3)
    X = np.hstack([Xd, Xs])
    y = ((X[:, 0] + Xs.sum(1) + 0.3 * rs.randn(n)) > 0.7).astype(np.float64)
    serial = _train({**BASE, "tpu_growth_mode": "rounds"}, X, y, rounds=8)
    mesh = _train(
        {**BASE, "tree_learner": "data", "tpu_growth_mode": "rounds"}, X, y,
        rounds=8,
    )
    assert mesh._gbdt.spec.rounds_slots > 0 and mesh._gbdt.spec.efb
    np.testing.assert_allclose(
        mesh.predict(X), serial.predict(X), rtol=1e-4, atol=1e-5
    )


def test_feature_parallel_matches_serial():
    """tree_learner=feature: features sharded over the mesh, every
    device holds all rows; the all-gathered winner records must
    reproduce serial trees exactly (feature_parallel_tree_learner.cpp:
    all ranks hold all data, so results equal serial by construction)."""
    X, y = _binary_problem(n=2048, f=10, seed=21)
    params = {**BASE, "enable_bundle": False}
    b_serial = _train(params, X, y, rounds=8)
    b_feat = _train({**params, "tree_learner": "feature"}, X, y, rounds=8)
    assert b_feat.num_trees() == b_serial.num_trees()
    np.testing.assert_allclose(
        b_feat.predict(X), b_serial.predict(X), rtol=1e-4, atol=1e-5
    )


def test_data_parallel_quant_reduce_scatter_wire():
    """Quantized data-parallel training rides the int32 reduce-scatter
    histogram wire with per-rank feature ownership (reference
    bin.h:63-81 + data_parallel_tree_learner.cpp:286).
    Lockstep contract: predictions match serial quantized training, and
    the compiled program actually contains an integer reduce-scatter."""
    X, y = _binary_problem(seed=11)
    q = {"use_quantized_grad": True, "num_grad_quant_bins": 4,
         "tpu_growth_mode": "rounds"}
    b_serial = _train({**BASE, **q}, X, y)
    b_data = _train({**BASE, **q, "tree_learner": "data"}, X, y)
    assert b_data.num_trees() == b_serial.num_trees()
    np.testing.assert_allclose(
        b_data.predict(X), b_serial.predict(X), rtol=1e-4, atol=1e-5
    )

    # wire-dtype assertion: the grower's jaxpr must reduce-scatter an
    # int32 histogram instead of full-psumming f32
    import jax

    from lightgbm_tpu.config import Config
    from lightgbm_tpu.dataset import BinnedDataset
    from lightgbm_tpu.learner import GrowerSpec, make_split_params
    from lightgbm_tpu.parallel.data_parallel import (
        DataParallelGrower,
        make_mesh,
    )

    cfg = Config({"max_bin": 63, "min_data_in_leaf": 5})
    ds = BinnedDataset.from_numpy(X.astype(np.float32), cfg)
    d = ds.device_arrays()
    Np = ds.num_rows_padded()
    spec = GrowerSpec(num_leaves=15, num_bins=ds.max_num_bin, max_depth=-1,
                      rounds_slots=8, has_cat=False, quant=True,
                      quant_levels=4)
    g = DataParallelGrower(make_mesh(), spec)
    import jax.numpy as jnp

    gq = jnp.asarray(
        np.random.RandomState(0).randint(-2, 3, Np).astype(np.float32))
    hq = jnp.ones(Np, jnp.float32)
    jaxpr = jax.make_jaxpr(
        lambda *a: g._fn(*a)
    )(
        d["bins"], d["nan_bin"], d["num_bins"], d["mono"], d["is_cat"],
        gq, hq, d["valid"], jnp.ones(ds.num_used_features, bool),
        make_split_params(cfg), d["valid"], None, None, None, None, None,
        jnp.asarray(np.float32([0.1, 0.1])),
    )
    txt = str(jaxpr)
    assert "reduce_scatter" in txt or "psum_scatter" in txt, (
        "integer reduce-scatter wire not found in the compiled grower"
    )
