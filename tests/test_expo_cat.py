"""Native categorical columns and missing values on the normal path
(PR 36): the rounds program against the benchmark's plain reference
(`benchmark/references/cat_audit.py`: a walker and a split search of its
own, float64 NumPy) on small `synthetic_expo` tables; the categorical
binning rule and its other bin; `categorical_feature` through a
Dataset's parameters; the save / load round trip with cut and unseen
codes; the column-kind gauges and the split counter."""

import hashlib
import sys
from pathlib import Path

import numpy as np
import pytest

import lightgbm_tpu as lgb
from lightgbm_tpu.binning import BinMapper, BinType, MissingType
from lightgbm_tpu.obs.metrics import default_registry

REPO = Path(__file__).resolve().parents[1]
if str(REPO) not in sys.path:
    sys.path.insert(0, str(REPO))

from benchmark.harness.manifest import load_plugin  # noqa: E402

ROWS, VALID = 20480, 4096
CAT = "0,1,2,4,5,6"
PARAMS = {
    "objective": "binary", "metric": "auc", "num_leaves": 31,
    "learning_rate": 0.1, "max_bin": 255, "min_data_in_leaf": 0,
    "min_sum_hessian_in_leaf": 1.0, "categorical_feature": CAT,
    "verbosity": -1, "tpu_growth_mode": "rounds",
    "tpu_hist_dtype": "bf16x2",
}
CAT_DEFAULTS = {"max_cat_to_onehot": 4, "max_cat_threshold": 32,
                "cat_smooth": 10.0, "cat_l2": 10.0,
                "min_data_per_group": 100}


@pytest.fixture(scope="module")
def audit():
    return load_plugin(REPO, "references", "cat_audit")


@pytest.fixture(scope="module")
def table():
    gen = load_plugin(REPO, "datasets", "synthetic_expo")
    return gen.make(7, ROWS, VALID, 8)


def _variant(table, name):
    X, y, Xv, yv = (a.copy() for a in table)
    params = dict(PARAMS)
    if name == "origin_cut_at_max_bin":
        params["max_bin"] = 64
    if name == "nan_in_distance_too":
        X[::7, 7] = np.nan
        Xv[::5, 7] = np.nan
    if name == "valid_only_category":
        Xv[::3, 4] = 77  # no training row has carrier 77
        Xv[1::3, 5] = 999
    return X, y, Xv, yv, params


@pytest.mark.parametrize("name", [
    "full_table", "origin_cut_at_max_bin", "nan_in_distance_too",
    "valid_only_category"])
def test_rounds_program_on_f32_channels_against_the_plain_search(
        monkeypatch, audit, table, name):
    """Trees 1 and 2, node by node: the chosen split's exact gain (from
    the plain walker's two sides, by its kind's formula) equals the best
    exact gain the plain search finds under the reference's rules, within
    the f32 channels' rounding; every leaf count and leaf value is the
    walk's; the binned matrix is what `feature_infos` says; the device's
    valid AUC is the plain walker's, with cut, missing and valid-only
    categories routed right everywhere."""
    monkeypatch.setenv("LGBM_TPU_PALLAS_INTERPRET", "1")
    X, y, Xv, yv, params = _variant(table, name)
    ds = lgb.Dataset(X, label=y, params=dict(params),
                     free_raw_data=False).construct()
    vs = lgb.Dataset(Xv, label=yv, reference=ds,
                     free_raw_data=False).construct()
    evals = {}
    bst = lgb.train(dict(params), ds, num_boost_round=3, valid_sets=[vs],
                    valid_names=["valid"],
                    callbacks=[lgb.record_evaluation(evals)])
    g = bst._gbdt
    assert g.spec.rounds_slots > 0 and not g._force_sync
    assert g.spec.has_cat and g.spec.has_nan and g.spec.cat_subset
    text = bst.model_to_string()
    header, trees = audit.parse(text)
    problems = []
    cols = audit.columns_of(header, X, ds._binned.bins,
                            [int(c) for c in CAT.split(",")], problems)
    assert not problems
    assert [c.categorical for c in cols] == [
        True, True, True, False, True, True, True, False]
    if name == "origin_cut_at_max_bin":
        assert cols[5].bins == 64 and cols[6].bins == 64
    # a cut / unseen / missing value is in the other bin on the valid
    # side too, and in no kept category's
    for j in (4, 5, 6):
        vb = vs._binned.bins[j]
        kept = np.isin(Xv[:, j], cols[j].cats)
        assert (vb[~kept] == cols[j].nan_bin).all()
        assert (vb[kept] < cols[j].nan_bin).all()

    p_bar = float(np.mean(y, dtype=np.float64))
    init = float(np.log(p_bar / (1.0 - p_bar)))
    score = np.full(X.shape[0], init)
    seen = {}
    for k, t in enumerate(trees[:2], start=1):
        pr = 1.0 / (1.0 + np.exp(-score))
        gr, he = pr - y, pr * (1.0 - pr)
        leaf = audit.route(t, X)
        # every leaf count is the walk's; every leaf value is
        # -sum(g) / (sum(h) + l2) x lr, l2 = cat_l2 below a sorted-subset
        # split (the reference's rule; this program renews no leaf)
        L = t.num_leaves
        assert (np.bincount(leaf, minlength=L) == t.leaf_count).all()
        wide = np.array([cols[int(f)].bins > 4 for f in t.split_feature])
        subset = ((t.decision_type & 1) != 0) & wide
        l2 = np.zeros(L)
        for kids in (t.left_child, t.right_child):
            l2[~kids[kids < 0]] = np.where(subset[kids < 0], 10.0, 0.0)
        want = -np.bincount(leaf, weights=gr, minlength=L) / (
            np.bincount(leaf, weights=he, minlength=L) + l2) * 0.1
        # f32 sums: a small right child is its parent less its sibling
        np.testing.assert_allclose(
            t.leaf_value - (init if k == 1 else 0.0), want, rtol=2e-3,
            atol=2e-6)
        chosen, best, kinds = audit.node_gains(
            t, leaf, gr, he, ds._binned.bins, cols, 0.0, 0, 1.0,
            CAT_DEFAULTS)
        # the same candidate, or one whose exact gain ties it
        np.testing.assert_allclose(chosen, best, rtol=2e-5, atol=1e-9)
        for kind, n in kinds.items():
            seen[kind] = seen.get(kind, 0) + n
        score = score - (init if k == 1 else 0.0) + t.leaf_value[leaf]
    assert seen["cat_subset"] > 0
    assert seen["numerical"] + seen["default_left"] > 0
    host = audit._ta.auc(yv, audit.predict_raw(trees, Xv))
    assert abs(host - evals["valid"]["auc"][-1]) < 1e-6
    # host prediction and a reloaded model walk the same way
    raw = audit.predict_raw(trees, Xv)
    np.testing.assert_allclose(bst.predict(Xv, raw_score=True), raw,
                               rtol=0, atol=1e-9)
    again = lgb.Booster(model_str=text)
    np.testing.assert_allclose(again.predict(Xv, raw_score=True), raw,
                               rtol=0, atol=1e-9)


# ------------------------------------------------------------- binning
def _zipf_codes(n, codes, rs):
    p = 1.0 / np.arange(1, codes + 1)
    return rs.choice(codes, n, p=p / p.sum()).astype(np.float64)


def test_categorical_bins_by_count_with_the_other_bin_last():
    rs = np.random.RandomState(0)
    v = _zipf_codes(20000, 305, rs)
    m = BinMapper.from_sample(v, len(v), 255, bin_type=BinType.CATEGORICAL)
    cats, cnts = np.unique(v.astype(int), return_counts=True)
    by_count = cats[np.argsort(-cnts, kind="stable")]
    assert m.num_bin <= 255 and m.nan_bin == m.num_bin - 1
    assert m.categories == tuple(by_count[:len(m.categories)])
    assert m.missing_type == MissingType.NAN  # something was cut
    bins = m.values_to_bins(np.array([by_count[0], by_count[1], 999, -3,
                                      np.nan, by_count[-1]]))
    assert list(bins[:2]) == [0, 1]
    # unseen, negative, NaN and the rarest (cut) code: the other bin,
    # never bin 0, which is the most frequent category's alone
    assert (bins[2:] == m.nan_bin).all() and m.nan_bin != 0
    assert by_count[-1] not in m.categories


@pytest.mark.parametrize("max_bin,min_data_in_bin,want", [
    # the hard cap: max_bin bins INCLUDING the other bin
    (16, 3, 15),
    # the rare tail: codes with fewer than min_data_in_bin rows go
    (255, 40, None),
])
def test_the_cut_rule(max_bin, min_data_in_bin, want):
    rs = np.random.RandomState(1)
    v = _zipf_codes(5000, 100, rs)
    m = BinMapper.from_sample(v, len(v), max_bin,
                              min_data_in_bin=min_data_in_bin,
                              bin_type=BinType.CATEGORICAL)
    cnts = np.sort(np.unique(v, return_counts=True)[1])[::-1]
    if want is None:
        want = max(int((cnts >= min_data_in_bin).sum()), 2)
    assert len(m.categories) == want and m.num_bin == want + 1


def test_ninety_nine_percent_drops_the_rarest_of_a_full_column():
    """The reference's loop stops when the kept categories cover 99% AND
    the bins (the other bin among them) have reached the category count:
    the rarest of a skewed column goes to the other bin; uniform columns
    keep every category."""
    v = np.concatenate([np.zeros(600), np.ones(397), np.full(3, 2.0)])
    m = BinMapper.from_sample(v, len(v), 255, bin_type=BinType.CATEGORICAL)
    assert m.categories == (0, 1) and m.num_bin == 3
    assert m.missing_type == MissingType.NAN
    u = np.repeat(np.arange(12.0), 50)
    m = BinMapper.from_sample(u, len(u), 255, bin_type=BinType.CATEGORICAL)
    assert len(m.categories) == 12 and m.num_bin == 13
    assert m.missing_type == MissingType.NONE and m.nan_bin == 12


def test_all_numerical_datasets_bin_as_the_parent_did():
    """SHA-256 of the bin matrix the parent of PR 36 built from this
    table (NaN column, small-integer column, half-zero column)."""
    rs = np.random.RandomState(5)
    X = rs.randn(5000, 6).astype(np.float32)
    X[rs.rand(5000) < 0.1, 1] = np.nan
    X[:, 2] = rs.randint(0, 7, 5000)
    X[rs.rand(5000) < 0.5, 3] = 0.0
    ds = lgb.Dataset(X, label=(X[:, 0] > 0).astype(float),
                     params={"max_bin": 63}).construct()
    b = ds._binned
    assert [(m.num_bin, m.nan_bin) for m in b.mappers] == [
        (63, -1), (63, 62), (7, -1), (63, -1), (63, -1), (63, -1)]
    assert hashlib.sha256(np.ascontiguousarray(b.bins).tobytes()
                          ).hexdigest() == (
        "17d4b940172c425658f7e43a1089c0ffdd17b0e0beb5e37f79943991dbd236b8")


# ----------------------------------------------- categorical_feature
def _kinds(ds):
    return [m.bin_type == BinType.CATEGORICAL for m in ds._binned.mappers]


@pytest.mark.parametrize("how", ["params", "constructor", "both", "alias",
                                 "params_list", "neither"])
def test_categorical_feature_of_an_in_memory_dataset(how, capsys):
    rs = np.random.RandomState(2)
    X = np.stack([rs.randint(0, 9, 800), rs.randn(800),
                  rs.randint(0, 5, 800)], 1).astype(np.float64)
    y = (X[:, 1] > 0).astype(float)
    kw, params = {}, {"verbosity": 1}
    if how in ("params", "both"):
        params["categorical_feature"] = "0" if how == "both" else "0,2"
    if how == "alias":
        params["cat_column"] = "0,2"
    if how == "params_list":
        params["categorical_feature"] = [0, 2]
    if how in ("constructor", "both"):
        kw["categorical_feature"] = [2]
    ds = lgb.Dataset(X, label=y, params=params, **kw).construct()
    want = {"params": [True, False, True], "alias": [True, False, True],
            "params_list": [True, False, True],
            "constructor": [False, False, True],
            "both": [False, False, True],  # the constructor wins
            "neither": [False, False, False]}[how]
    assert _kinds(ds) == want
    warned = "categorical_feature keyword has been found in `params`" in (
        capsys.readouterr().err)
    assert warned == (how == "both")
    # a valid set built with reference= takes the training set's mappers
    vs = lgb.Dataset(X[:100], label=y[:100], reference=ds).construct()
    assert vs._binned.mappers is ds._binned.mappers


# --------------------------------------------------- save / load / predict
def test_round_trip_with_cut_and_unseen_codes(tmp_path, table):
    X, y, Xv, yv = table
    params = dict(PARAMS, max_bin=32, tpu_growth_mode="auto",
                  tpu_hist_dtype="auto", num_leaves=15)
    ds = lgb.Dataset(X[:6000], label=y[:6000], params=params)
    bst = lgb.train(params, ds, num_boost_round=5)
    m = ds._binned.mappers[5]
    cut = sorted(set(range(305)) - set(m.categories))
    assert len(m.categories) == 31 and cut
    T = Xv[:512].copy()
    T[:128, 5] = cut[0]
    T[128:256, 5] = 4242  # never seen
    T[256:384, 5] = np.nan
    T[384:, 5] = -1
    want = bst.predict(T, raw_score=True)
    # one row of each kind scores alike: all sit in the other bin, which
    # no split sends left; not as the most frequent category does
    same = T[:128].copy()
    for other in (4242, np.nan, -1):
        same[:, 5] = other
        np.testing.assert_array_equal(bst.predict(same, raw_score=True),
                                      want[:128])
    same[:, 5] = m.categories[0]
    assert np.abs(bst.predict(same, raw_score=True) - want[:128]).max() > 0
    path = tmp_path / "model.txt"
    bst.save_model(str(path))
    again = lgb.Booster(model_file=str(path))
    np.testing.assert_array_equal(again.predict(T, raw_score=True), want)
    assert "cat_boundaries=" in path.read_text()
    # the device traversal over the binned rows agrees with the host walk
    vs = lgb.Dataset(T, label=yv[:512], reference=ds).construct()
    evals = {}
    lgb.train(params, ds, num_boost_round=5, valid_sets=[vs],
              valid_names=["v"], callbacks=[lgb.record_evaluation(evals)])
    audit = load_plugin(REPO, "references", "cat_audit")
    assert abs(audit._ta.auc(yv[:512], want) - evals["v"]["auc"][-1]) < 1e-6


# ------------------------------------------------------------ metrics
def _series(name):
    return default_registry().snapshot().get(name, {})


def test_column_gauges_and_the_split_counter(table):
    X, y, _, _ = table
    params = dict(PARAMS, tpu_growth_mode="auto", tpu_hist_dtype="auto",
                  num_leaves=15)
    before = dict(_series("lgbmtpu_tree_splits_total"))
    ds = lgb.Dataset(X[:8192], label=y[:8192], params=params).construct()
    cols = _series("lgbmtpu_dataset_columns")
    assert {k: int(v) for k, v in cols.items()} == {
        '{kind="numerical"}': 2, '{kind="with_nan"}': 1,
        '{kind="categorical"}': 6, '{kind="cat_subset"}': 6,
        '{kind="bundle"}': 0, '{kind="merged_feature"}': 0}
    b = ds._binned
    other = sum(int((b.bins[j] == b.mappers[j].nan_bin).sum())
                for j in (0, 1, 2, 4, 5, 6))
    assert other > 0
    assert int(_series("lgbmtpu_dataset_cat_other_rows")[""]) == other
    bst = lgb.train(params, ds, num_boost_round=3)
    after = _series("lgbmtpu_tree_splits_total")
    grown = {k: int(after[k] - before.get(k, 0)) for k in after}
    text = bst.model_to_string()
    n_cat = sum(int(d) & 1 for ln in text.split("\n")
                if ln.startswith("decision_type=")
                for d in ln.split("=")[1].split())
    assert grown['{kind="cat_subset"}'] == n_cat > 0
    assert grown['{kind="cat_onehot"}'] == 0
    assert sum(grown.values()) == sum(
        len(ln.split()) for ln in text.split("\n")
        if ln.startswith("split_feature="))
    # a numerical table sets the gauges anew
    lgb.Dataset(X[:2048, [3, 7]], label=y[:2048]).construct()
    cols = _series("lgbmtpu_dataset_columns")
    assert int(cols['{kind="categorical"}']) == 0
    assert int(cols['{kind="numerical"}']) == 2


# ------------------------------------------------- counts past float32
def test_leaf_counts_are_recounted_from_the_rows_past_the_f32_limit(
        monkeypatch):
    """Past 2**25 rows a node's count may have no float32 value, so the
    one-chip grower's leaf counts come from the rows (PR 36; the chip
    read 2 of 255 leaf counts off at 34.6M rows). With the limit lowered
    the recount runs at a small size: the model is the one the carried
    counts give, bagging included, and the counts are the in-bag rows'."""
    import lightgbm_tpu.boosting as boosting_mod
    from lightgbm_tpu.boosting import _FUSED_STEP_CACHE

    mod = sys.modules[boosting_mod.GBDT.__module__]
    rs = np.random.RandomState(0)
    X = rs.randn(8192, 5).astype(np.float32)
    y = (X[:, 0] + 0.3 * rs.randn(8192) > 0).astype(np.float32)
    params = {"objective": "binary", "num_leaves": 15, "verbosity": -1,
              "tpu_growth_mode": "rounds", "tpu_hist_dtype": "int16",
              "bagging_fraction": 0.7, "bagging_freq": 1}

    def text():
        _FUSED_STEP_CACHE.clear()
        return lgb.train(params, lgb.Dataset(X, label=y),
                         num_boost_round=3).model_to_string()

    assert mod.F32_EVEN_ROWS == 1 << 25
    carried = text()
    monkeypatch.setattr(mod, "F32_EVEN_ROWS", 100)
    assert text() == carried
    _FUSED_STEP_CACHE.clear()
