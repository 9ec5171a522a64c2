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


# ---- PR 32: tree_learner=data on the NORMAL path (resident sharded
# Dataset, memoized fused step, round counts), four of the eight
# virtual devices. One shape and one parameter set for all of these, so
# they share the mesh program's compile (and the persistent cache).
DP4 = {**BASE, "min_data_in_leaf": 5, "tpu_growth_mode": "rounds",
       "tpu_hist_dtype": "int16"}
N4, F4, NV4 = 4096, 6, 1000


def _problem4(seed):
    rs = np.random.RandomState(seed)
    X = rs.randn(N4, F4)
    y = (X @ rs.randn(F4) + 0.3 * rs.randn(N4) > 0).astype(np.float64)
    return X, y


def _train4(X, y, extra, valid=True, rounds=4):
    ds = lgb.Dataset(X, label=y, free_raw_data=False)
    kw = {}
    if valid:
        vs = lgb.Dataset(X[:NV4], label=y[:NV4], reference=ds)
        kw = dict(valid_sets=[vs], valid_names=["v"])
    return lgb.train({**DP4, **extra}, ds, num_boost_round=rounds, **kw)


def _assert_same_trees(got, want, leaf_rtol):
    """Structure and leaf counts exact; leaf values within `leaf_rtol`."""
    a, b = got._gbdt.models, want._gbdt.models
    assert len(a) == len(b)
    for ta, tb in zip(a, b):
        assert ta.num_leaves == tb.num_leaves
        for f in ("split_feature", "threshold", "decision_type",
                  "left_child", "right_child", "leaf_count",
                  "internal_count"):
            np.testing.assert_array_equal(getattr(ta, f), getattr(tb, f), f)
        np.testing.assert_allclose(ta.leaf_value, tb.leaf_value,
                                   rtol=leaf_rtol, atol=1e-7)


@pytest.mark.parametrize("valid", [True, False], ids=["valid", "no_valid"])
def test_mesh_model_equals_the_one_device_rounds_program(mesh4, valid):
    """Same data, same rounds program, int16 channels: the trees'
    structure and every leaf count are exact. The integer histograms
    cross the mesh exactly at this size (rs_int32), so the leaf values
    differ only by the f32 sums of the true-gradient renewal, which a
    mesh adds in another order (1.4e-5 relative seen over four trees):
    1e-4 relative, stated."""
    X, y = _problem4(31)
    serial = _train4(X, y, {}, valid)
    mesh = _train4(X, y, {"tree_learner": "data"}, valid)
    g = mesh._gbdt
    assert g.tree_learner_resolved == "data" and g._mesh.devices.size == 4
    assert g.hist_wire_resolved == "rs_int32"
    assert not g._force_sync and g._f_program is not None  # the fused loop
    _assert_same_trees(mesh, serial, leaf_rtol=1e-4)


def test_memoized_mesh_step_bakes_no_array_of_its_first_dataset(mesh4):
    """A second Dataset of the same shapes and other data goes through
    the first one's memoized step and yields ITS OWN trees: those a
    process that never saw the first Dataset grows (the step memo and
    the shared grower dropped, so the program is traced afresh from the
    second Dataset alone)."""
    from lightgbm_tpu.boosting import _FUSED_STEP_CACHE
    from lightgbm_tpu.parallel import data_parallel

    Xa, ya = _problem4(41)
    Xb, yb = _problem4(43)
    dp = {"tree_learner": "data"}
    first = _train4(Xa, ya, dp)
    second = _train4(Xb, yb, dp)
    assert second._gbdt._f_program is first._gbdt._f_program
    assert second._gbdt._dp is first._gbdt._dp
    assert second.model_to_string() != first.model_to_string()
    _FUSED_STEP_CACHE.clear()
    data_parallel._GROWERS.clear()
    fresh = _train4(Xb, yb, dp)
    assert fresh._gbdt._f_program is not first._gbdt._f_program
    assert second.model_to_string() == fresh.model_to_string()


def test_mesh_grower_counts_the_one_device_growers_rounds(mesh4):
    """lgbmtpu_grower_rounds_total ticks under a mesh as on one chip:
    the same tree takes the same rounds at the same widths."""
    from lightgbm_tpu.obs.metrics import default_registry

    def rounds_of(extra):
        c = default_registry().counter("lgbmtpu_grower_rounds_total",
                                       labels=("width",))
        widths = ("8", "14", "route")
        before = [c.value(width=w) for w in widths]
        bst = _train4(*_problem4(31), extra)
        assert bst._gbdt._f_ladder_widths == (8, 14)
        return [c.value(width=w) - b for w, b in zip(widths, before)]

    one = rounds_of({})
    assert sum(one) > 0 and one[2] >= 0
    assert rounds_of({"tree_learner": "data"}) == one


def test_cv_folds_of_equal_shape_share_one_mesh_step(mesh4):
    from lightgbm_tpu.boosting import _FUSED_STEP_CACHE

    X, y = _problem4(47)
    ds = lgb.Dataset(X, label=y, free_raw_data=False)
    n_before = len(_FUSED_STEP_CACHE)
    res = lgb.cv({**DP4, "tree_learner": "data"}, ds, num_boost_round=3,
                 nfold=4, stratified=False, shuffle=False,
                 return_cvbooster=True)
    boosters = res["cvbooster"].boosters
    assert len(boosters) == 4
    gs = [b._gbdt for b in boosters]
    assert all(g.tree_learner_resolved == "data" for g in gs)
    # four folds of 3,072 + 1,024 rows: one traced step, one grower
    assert len({id(g._f_program) for g in gs}) == 1
    assert len({id(g._dp) for g in gs}) == 1
    assert len(_FUSED_STEP_CACHE) - n_before <= 1
    assert len(res["valid auc-mean"]) == 3


def test_mesh_recounts_leaves_past_the_float32_range(mesh4, monkeypatch):
    """Past 2**24 global rows the float32 count arithmetic of the grower
    is no longer exact, so the mesh grower recounts every leaf from the
    rows (per shard, summed as integers). The limit is lowered here so
    that a small table takes that path, with bagging so that the count
    is of IN-BAG rows: the counts are the one-device program's."""
    from lightgbm_tpu.boosting import _FUSED_STEP_CACHE
    from lightgbm_tpu.parallel import data_parallel

    def fresh():
        _FUSED_STEP_CACHE.clear()
        data_parallel._GROWERS.clear()

    monkeypatch.setattr(data_parallel, "F32_EXACT_ROWS", 1024)
    # the chip's kernels under the interpreter, on whole row blocks a
    # shard (as a TPU run pads): the recount's seg_sum_tpu runs INSIDE
    # the grower's shard_map and must not wrap itself in another
    monkeypatch.setenv("LGBM_TPU_PALLAS_INTERPRET", "1")
    monkeypatch.setattr(
        lgb.basic.Dataset, "construct", _padded_to_whole_blocks(
            lgb.basic.Dataset.construct))
    fresh()
    try:
        X, y = _problem4(53)
        bag = {"bagging_fraction": 0.7, "bagging_freq": 1}
        serial = _train4(X, y, bag)
        mesh = _train4(X, y, {**bag, "tree_learner": "data"})
        assert mesh._gbdt.dev["bins"].shape[1] == 4 * 2048
        jaxpr = str(_grower_jaxpr(mesh._gbdt))
        assert "psum" in jaxpr and "i32[15]" in jaxpr  # the integer sum
        _assert_same_trees(mesh, serial, leaf_rtol=1e-4)
        counts = [int(t.leaf_count.sum()) for t in mesh._gbdt.models]
        assert all(0 < c < N4 for c in counts), counts  # in-bag rows only
    finally:
        fresh()


def _padded_to_whole_blocks(construct):
    """Dataset.construct that pads the rows to 4 x HIST_BLK, what
    GBDT.__init__ asks of a training set on a TPU."""
    from lightgbm_tpu.learner.histogram import HIST_BLK

    def padded(self, *a, **kw):
        out = construct(self, *a, **kw)
        self._binned.ensure_row_block(4 * HIST_BLK)
        return out

    return padded


def _grower_jaxpr(g):
    """The jaxpr of a data-parallel Booster's shared grower on its own
    training arrays."""
    import jax
    import jax.numpy as jnp

    d = g.dev
    n = d["bins"].shape[1]
    ones = jnp.ones(n, jnp.float32)
    return jax.make_jaxpr(lambda *a: g._dp._fn(*a))(
        d["bins"], d["nan_bin"], d["num_bins"], d["mono"], d["is_cat"],
        ones, ones, d["valid"], jnp.ones(d["bins"].shape[0], bool),
        g.params, d["valid"], None, None, None, None, None,
        jnp.asarray(np.float32([0.1, 0.1])),
    )
