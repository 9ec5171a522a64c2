"""EFB bundles searched in their own layout (PR 38).

The rounds grower's split search reads a bundled Dataset's (3, G, Bc)
histograms through static cell tables (`bundling.cell_tables`,
`learner.bundle.best_split_bundled`) where it used to expand them to
(3, F, Bf) first (`bundle.expand_hist`, which the sequential growers
keep). Here: the search against the expansion + `split.best_split` on
seeded histograms of simulated rows; the rounds grower's model against
the same grower searching through the expansion; grouping on the
bin-construction sample against the full-row grouping it replaces; the
gauges and the counter the benchmark reads."""

import numpy as np
import pytest
import scipy.sparse as sp

import jax
import jax.numpy as jnp

import lightgbm_tpu as lgb
from lightgbm_tpu import bundling
from lightgbm_tpu.config import Config
from lightgbm_tpu.learner import make_split_params
from lightgbm_tpu.learner import bundle as bundle_mod
from lightgbm_tpu.learner.split import SearchDirections, best_split
from lightgbm_tpu.obs.metrics import default_registry


# ------------------------------------------------------------ tables
def _sparse_table(n, seed, levels=(500, 300, 200), nan_member=False):
    """One-hot columns of a few Zipf codes beside four dense numerical
    columns; with `nan_member`, a sparse numerical column that is NaN in
    some rows (a member EFB may merge that has a NaN bin)."""
    rs = np.random.RandomState(seed)
    blocks, score = [], np.zeros(n)
    for k, L in enumerate(levels):
        p = 1.0 / np.arange(1, L + 1)
        code = rs.choice(L, n, p=p / p.sum())
        eff = rs.randn(L) * 0.8
        score += eff[code]
        blocks.append(sp.csr_matrix(
            (np.ones(n), (np.arange(n), code)), shape=(n, L)))
    num = rs.randn(n, 4)
    score += 0.7 * num[:, 0]
    blocks.append(sp.csr_matrix(num))
    if nan_member:
        v = np.zeros(n)
        on = rs.rand(n) < 0.03
        v[on] = rs.randn(on.sum()) + 2.0
        v[on & (rs.rand(n) < 0.3)] = np.nan
        score += np.where(np.isnan(v), 1.0, 0.3 * v)
        blocks.append(sp.csr_matrix(v[:, None]))
    X = sp.hstack(blocks).tocsr()
    y = (score + rs.randn(n) > np.median(score)).astype(np.float32)
    return X, y


# ---------------------------------------------- the search, one node
def _simulated(seed, n=3000, with_nan=True):
    """A bundle layout, and rows whose bundle and per-feature histograms
    agree (no two members of a bundle away from their most-frequent bin
    in one row): merged members of 2..9 bins with the most-frequent bin
    anywhere, one of them with its NaN bin stored and one whose NaN bin
    is its most-frequent one, direct numerical columns with and without
    a NaN bin."""
    rs = np.random.RandomState(seed)
    groups, nb, nan, mfb_of = [], [], [], []
    fb = []  # per-feature (n,) bins

    def feature(bins, nbin, nanb):
        fb.append(bins)
        nb.append(nbin)
        nan.append(nanb)
        return len(fb) - 1

    # two merged bundles
    for gsize in (5, 7):
        owner = rs.randint(0, gsize + 1, n)  # gsize = nobody away
        members = []
        for j in range(gsize):
            nbin = rs.randint(2, 10)
            m = rs.randint(0, nbin)
            nanb = -1
            if j == 1:
                nanb = nbin - 1
                m = rs.randint(0, nbin - 1)
            elif j == 2 and nbin > 2:
                nanb = m = nbin - 1
            other = [b for b in range(nbin) if b != m]
            b = np.where(owner == j, rs.choice(other, n), m)
            members.append(feature(b, nbin, nanb))
            mfb_of.append(m)
        groups.append(members)
    # direct columns (placed as singleton groups)
    for nanb_on in (False, True, False):
        nbin = rs.randint(12, 40)
        b = rs.randint(0, nbin, n)
        groups.append([feature(b, nbin, nbin - 1 if nanb_on else -1)])
        mfb_of.append(int(np.bincount(b).argmax()))
    F = len(fb)
    bins = np.stack(fb)
    layout = bundling.build_layout(groups, nb)
    merged, layout = bundling.encode(bins, layout, nb, mfb_of)
    Bf = max(nb)
    col_bins = max(layout.col_bins, Bf)
    if not with_nan:  # the NaN bins are plain bins
        nan = [-1] * F
    cells = bundling.cell_tables(layout, nb, nan, [False] * F, col_bins)
    width = np.array([nb[f] - (1 if layout.mfb[f] >= 0 else 0)
                      for f in range(F)], np.int32)
    binfo = bundle_mod.BundleInfo(
        bundle_of=jnp.asarray(layout.bundle_of),
        off_lo=jnp.asarray(layout.off_lo), mfb=jnp.asarray(layout.mfb),
        expand_idx=jnp.asarray(bundling.build_expand_idx(layout, nb, Bf)),
        width=jnp.asarray(width),
        cells=bundle_mod.BundleCells(*(jnp.asarray(t) for t in cells)))
    g = rs.randn(n)
    h = rs.rand(n) + 0.1
    return dict(F=F, Bf=Bf, Bc=col_bins, merged=merged, g=g, h=h,
                nb=np.array(nb, np.int32), nan=np.array(nan, np.int32),
                binfo=binfo, rs=rs, layout=layout)


def _bundle_hist(t, rows):
    G, Bc = t["merged"].shape[0], t["Bc"]
    out = np.zeros((3, G, Bc))
    for gi in range(G):
        b = t["merged"][gi, rows]
        out[0, gi] = np.bincount(b, weights=t["g"][rows], minlength=Bc)
        out[1, gi] = np.bincount(b, weights=t["h"][rows], minlength=Bc)
        out[2, gi] = np.bincount(b, minlength=Bc)
    return jnp.asarray(out, jnp.float32)


@pytest.mark.parametrize("seed", [0, 1, 2])
@pytest.mark.parametrize("default_left", [False, True])
def test_the_bundle_layout_search_is_the_expansions(seed, default_left):
    t = _simulated(seed, with_nan=default_left)
    F = t["F"]
    params = make_split_params(Config({"min_data_in_leaf": 3,
                                       "min_sum_hessian_in_leaf": 1.0}))
    dirs = SearchDirections(default_left=default_left, categorical=False,
                            cat_subset=False, monotone_test=False)
    mono = jnp.zeros(F, jnp.int32)
    is_cat = jnp.zeros(F, bool)
    nb, nan = jnp.asarray(t["nb"]), jnp.asarray(t["nan"])
    n = t["g"].size
    searched = 0
    for node in range(12):  # nested row sets: a node and its children
        rows = np.flatnonzero(t["rs"].rand(n) < 0.9 ** node)
        hb = _bundle_hist(t, rows)
        sums = [jnp.float32(t["g"][rows].sum()),
                jnp.float32(t["h"][rows].sum()), jnp.float32(rows.size)]
        want = best_split(
            bundle_mod.expand_hist(hb, *sums, t["binfo"]), *sums, nb, nan,
            mono, is_cat, params, dirs=dirs)
        got, col_gain = bundle_mod.best_split_bundled(
            hb, *sums, nb, nan, mono, is_cat, params, t["binfo"].cells,
            t["Bf"], dirs=dirs)
        assert float(want.gain) > 0
        assert int(got.feature) == int(want.feature)
        assert int(got.bin) == int(want.bin)
        assert bool(got.default_left) == bool(want.default_left)
        assert not bool(got.is_cat)
        np.testing.assert_allclose(float(got.gain), float(want.gain),
                                   rtol=1e-4)
        for k in ("left_g", "left_h", "left_c"):
            np.testing.assert_allclose(float(getattr(got, k)),
                                       float(getattr(want, k)),
                                       rtol=1e-5, atol=1e-4)
        # the column gains are the best over each column's features
        assert float(col_gain.max()) == pytest.approx(float(got.gain),
                                                      rel=1e-6)
        searched += 1
    assert searched == 12


def test_cell_tables_place_every_threshold_once():
    """Every numerical threshold of every feature is exactly one cell;
    bin 0 of a merged column and the padding are no feature's."""
    t = _simulated(5)
    cells = bundling.cell_tables(t["layout"], list(t["nb"]),
                                 list(t["nan"]), [False] * t["F"], t["Bc"])
    for f in range(t["F"]):
        nbin, nanb = int(t["nb"][f]), int(t["nan"][f])
        last = nbin - 2 if nanb >= 0 else nbin - 1
        mine = cells.feat == f
        assert sorted(cells.thr[mine & cells.cand]) == list(range(last))
        assert int((mine & cells.extra).sum()) == (1 if nanb >= 0 else 0)
    merged = ~cells.direct[:, 0]
    assert np.all(cells.feat[merged, 0] == t["F"])


# ------------------------------------------------ the grower's model
def _fields(text):
    trees = []
    for block in text.split("\nTree=")[1:]:
        kv = dict(ln.split("=", 1) for ln in block.split("\n") if "=" in ln)
        trees.append(kv)
    return trees


@pytest.mark.parametrize("case", ["one_hot", "nan_member", "categorical"])
def test_the_rounds_grower_splits_as_through_the_expansion(case,
                                                           monkeypatch):
    """The rounds grower on a bundled table grows the same trees split
    by split (features, thresholds, directions, leaf counts) as the same
    grower searching through `expand_hist` + `best_split` (the parent's
    search); gains and leaf values agree to rounding: the left sums are
    formed in another order of additions. `categorical`: a dense table
    whose categorical column keeps its own column and search beside the
    bundles."""
    from lightgbm_tpu.learner import rounds, split

    nan_member = case == "nan_member"
    X, y = _sparse_table(20000, 3 + nan_member, nan_member=nan_member)
    p = {"objective": "binary", "num_leaves": 31, "verbosity": -1,
         "min_data_in_leaf": 5, "tpu_growth_mode": "rounds",
         "tpu_hist_dtype": "int16", "max_bin": 63}
    if case == "categorical":
        rs = np.random.RandomState(8)
        code = rs.randint(0, 24, X.shape[0])
        y = np.where(rs.rand(X.shape[0]) < 0.3, (code % 3 == 0), y
                     ).astype(np.float32)
        X = np.hstack([np.asarray(X.todense()), code[:, None]])
        p["categorical_feature"] = str(X.shape[1] - 1)

    def train():
        ds = lgb.Dataset(X, label=y, params=dict(p), free_raw_data=False)
        bst = lgb.train(dict(p), ds, num_boost_round=3)
        g = bst._gbdt
        assert g.spec.efb and g.spec.rounds_slots > 0
        return bst.model_to_string(), ds._binned

    text, binned = train()
    lay = binned.bundle_layout
    assert sum(len(gr) for gr in lay.groups if len(gr) > 1) >= 900
    if case == "categorical":
        assert all(int(t["num_cat"]) > 0 for t in _fields(text))
    if nan_member:
        um = binned.used_mappers()
        merged_nan = [i for gr in lay.groups if len(gr) > 1 for i in gr
                      if um[i].nan_bin >= 0]
        assert merged_nan, "the NaN member was not merged"
    binfo = binned._bundle_info()

    def through_expansion(h, *args, **kw):
        a = list(args)
        cells, B = a.pop(8), a.pop(8)
        fm = a.pop(8) if len(a) > 8 else kw.pop("feat_mask", None)
        rec, cg = split._best_split_impl(
            bundle_mod.expand_hist(h, a[0], a[1], a[2], binfo), *a[:8],
            fm, kw.get("dirs"), kw.get("parent_output", 0.0),
            kw.get("cmin", split.BIG), kw.get("cmax", split.BIG),
            kw.get("penalty"), kw.get("rand_bin"))
        return rec, cg

    monkeypatch.setattr(rounds, "best_split_bundled", through_expansion)
    jax.clear_caches()
    oracle, _ = train()
    jax.clear_caches()
    mine, theirs = _fields(text), _fields(oracle)
    assert len(mine) == len(theirs) == 3
    for a, b in zip(mine, theirs):
        for k in ("split_feature", "threshold", "decision_type",
                  "left_child", "right_child", "leaf_count"):
            assert a[k] == b[k], k
        for k in ("split_gain", "leaf_value"):
            np.testing.assert_allclose(
                np.array(a[k].split(), float), np.array(b[k].split(), float),
                rtol=1e-3, atol=1e-6)


# ---------------------------------------------------- grouping
def _full_row_groups(nd_rows, num_bins, n_rows, max_group_bins):
    """The grouping before PR 38: every row, sorted intersections."""
    F = len(nd_rows)
    budget = n_rows // 10000
    cnts = np.array([n_rows if r is None else len(r) for r in nd_rows])
    order = np.argsort(-cnts, kind="stable")
    groups, rows_of, bins_of, conf = [], [], [], []
    for f in order:
        f = int(f)
        width = int(num_bins[f]) - 1
        placed = False
        if nd_rows[f] is not None and cnts[f] < n_rows:
            searched = 0
            for gid in range(len(groups)):
                if searched >= bundling.MAX_SEARCH_GROUP:
                    break
                if rows_of[gid] is None or \
                        bins_of[gid] + width > max_group_bins:
                    continue
                rest = budget - conf[gid]
                if rest < 0:
                    continue
                searched += 1
                cnt = np.intersect1d(rows_of[gid], nd_rows[f]).size
                if cnt <= rest and cnt <= cnts[f] // 2:
                    groups[gid].append(f)
                    rows_of[gid] = np.union1d(rows_of[gid], nd_rows[f])
                    bins_of[gid] += width
                    conf[gid] += cnt
                    placed = True
                    break
        if not placed:
            groups.append([f])
            rows_of.append(nd_rows[f])
            bins_of.append(1 + width)
            conf.append(0)
    return groups


def test_sample_grouping_is_full_row_grouping_at_most_the_sample():
    """Where a table has no more rows than bin_construct_sample_cnt, the
    sample is every row and the groups are those of the full-row
    grouping; past it, the groups are those of the sample's rows."""
    X, y = _sparse_table(30000, 9)
    for cnt in (200000, 12000):
        p = {"verbosity": -1, "bin_construct_sample_cnt": cnt}
        ds = lgb.Dataset(X, label=y, params=p).construct()
        b = ds._binned
        um = b.used_mappers()
        csc = X.tocsc()
        n_rows = min(cnt, X.shape[0])
        if cnt < X.shape[0]:
            idx = np.sort(np.random.RandomState(
                Config(p).data_random_seed).choice(X.shape[0], cnt,
                                                   replace=False))
            csc = X.tocsr()[idx].tocsc()
            csc.sort_indices()
        nd = []
        for i, f in enumerate(b.used_features):
            m = um[i]
            if m.most_freq_bin != m.default_bin:
                nd.append(None)
                continue
            lo, hi = csc.indptr[f], csc.indptr[f + 1]
            bins = m.values_to_bins(csc.data[lo:hi])
            nd.append(csc.indices[lo:hi][bins != m.most_freq_bin])
        want = _full_row_groups(nd, [m.num_bin for m in um], n_rows, 256)
        assert b.bundle_layout.groups == want
        assert default_registry().snapshot()[
            "lgbmtpu_dataset_bundle_rows"][""] == n_rows


def test_dense_grouping_on_the_sample_rows():
    """The dense path groups on the sample's bins: all rows when the
    table is no larger than the sample (the layout it had before)."""
    rs = np.random.RandomState(4)
    code = rs.randint(0, 30, 5000)
    X = np.hstack([np.eye(30)[code], rs.randn(5000, 3)])
    y = (rs.rand(5000) < 0.3).astype(np.float32)
    b = lgb.Dataset(X, label=y, params={"verbosity": -1}).construct()._binned
    um = b.used_mappers()
    res = bundling.bundle_features(_unbundled(X, y), um, 255)
    assert res is not None and res[1].groups == b.bundle_layout.groups
    again = bundling.bundle_features(_unbundled(X, y), um, 255,
                                     sample_idx=np.arange(5000))
    assert again[1].groups == b.bundle_layout.groups


def _unbundled(X, y):
    return lgb.Dataset(X, label=y, params={"verbosity": -1,
                                           "enable_bundle": False}
                       ).construct()._binned.bins


# ---------------------------------------------- gauges and counter
def test_gauges_and_counter_read_as_specified():
    X, y = _sparse_table(8000, 11)
    reg = default_registry()
    reg.reset()
    p = {"objective": "binary", "num_leaves": 15, "verbosity": -1,
         "tpu_growth_mode": "rounds", "tpu_hist_dtype": "int16"}
    ds = lgb.Dataset(X, label=y, params=dict(p)).construct()
    snap = reg.snapshot()
    lay = ds._binned.bundle_layout
    merged = [gr for gr in lay.groups if len(gr) > 1]
    cols = snap["lgbmtpu_dataset_columns"]
    assert cols['{kind="bundle"}'] == len(merged)
    assert cols['{kind="merged_feature"}'] == sum(map(len, merged))
    assert snap["lgbmtpu_dataset_bundle_rows"][""] == 8000
    assert snap["lgbmtpu_dataset_bundle_seconds_total"][""] > 0
    bst = lgb.train(dict(p), ds, num_boost_round=2)
    snap = reg.snapshot()
    assert snap["lgbmtpu_split_bundle_layout"][""] == 1
    bundled = ds._binned.bundled_features()
    want = sum(int(f) in bundled for t in bst._gbdt._models
               for f in t.split_feature)
    assert snap["lgbmtpu_tree_bundled_splits_total"][""] == want > 0
    assert sum(snap["lgbmtpu_tree_splits_total"].values()) >= want
    # the sequential grower keeps the expansion: the gauge reads 0
    lgb.train(dict(p, tpu_growth_mode="exact"), ds, num_boost_round=1)
    assert reg.snapshot()["lgbmtpu_split_bundle_layout"][""] == 0
    # an unbundled Dataset: no bundle, no bundled-splits tick
    reg.reset()
    Xd = np.asarray(X[:, -4:].todense())
    dd = lgb.Dataset(Xd, label=y, params=dict(p)).construct()
    assert dd._binned.bundle_layout is None
    lgb.train(dict(p), dd, num_boost_round=1)
    snap = reg.snapshot()
    assert snap["lgbmtpu_dataset_columns"]['{kind="bundle"}'] == 0
    assert snap["lgbmtpu_split_bundle_layout"][""] == 0
    assert "lgbmtpu_tree_bundled_splits_total" not in snap


def test_forced_splits_on_a_bundled_table_match_exact(tmp_path):
    """A forced split reads one feature's left sums from a bundle row of
    the pool (`rounds.feature_left`: the member's merged range and its
    most-frequent bin's mass) where the rounds grower used to expand the
    whole histogram: the model matches the sequential exact grower's,
    which still expands (the plan forces a merged one-hot member at the
    root and a numerical column below it)."""
    import json

    rs = np.random.RandomState(12)
    n = 4000
    code = rs.randint(0, 30, n)
    num = rs.randn(n, 3)
    X = np.hstack([np.eye(30)[code], num])
    y = ((code % 4 == 0) + num[:, 0] + 0.3 * rs.randn(n) > 0.8
         ).astype(float)
    plan = tmp_path / "forced.json"
    plan.write_text(json.dumps({
        "feature": 4, "threshold": 0.5,
        "left": {"feature": 30, "threshold": 0.0}}))
    preds = {}
    for mode in ("rounds", "exact"):
        params = dict(objective="binary", num_leaves=256,
                      min_data_in_leaf=40, min_gain_to_split=0.5,
                      verbosity=-1, tpu_growth_mode=mode,
                      forcedsplits_filename=str(plan))
        ds = lgb.Dataset(X, label=y, params=dict(params),
                         free_raw_data=False)
        bst = lgb.train(params, ds, num_boost_round=3)
        assert ds._binned.bundle_layout is not None
        assert 4 in ds._binned.bundled_features()
        for t in bst._gbdt.models:
            assert int(t.split_feature[0]) == 4
        preds[mode] = bst.predict(X)
    np.testing.assert_allclose(preds["rounds"], preds["exact"],
                               rtol=1e-4, atol=1e-5)
