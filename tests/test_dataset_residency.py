"""Label-sized state is resident per Dataset, not per Booster (ISSUE 25).

The padded label / weight cross the host-device boundary once per
Dataset (``BinnedDataset.device_label`` / ``device_weight``), the host
statistics of the label are kept per Dataset (``label_stat``), and every
route that changes what they were computed from makes the next Booster
miss and train on the NEW arrays. The init scores stay the floats the
per-Booster NumPy formulas gave, bit for bit.
"""

import numpy as np
import pytest

import lightgbm_tpu as lgb
from lightgbm_tpu.config import Config
from lightgbm_tpu.obs.metrics import default_registry
from lightgbm_tpu.objectives import create_objective

N, F = 600, 6
PARAMS = {"objective": "binary", "num_leaves": 7, "min_data_in_leaf": 5,
          "verbose": -1}


def _problem(seed=3):
    rs = np.random.RandomState(seed)
    X = rs.randn(N, F)
    z = X @ rs.randn(F) + 0.3 * rs.randn(N)
    return X, z, rs


def _counts():
    """{(kind, result): lookups so far} of the label cache counter."""
    c = default_registry().counter(
        "lgbmtpu_dataset_label_cache_total", labels=("kind", "result"))
    return {(k, r): c.value(kind=k, result=r)
            for k in ("label", "weight", "stats") for r in ("hit", "miss")}


def _delta(before):
    after = _counts()
    return {k: int(after[k] - before[k]) for k in after}


def _text(params, ds, rounds=4, **kw):
    return lgb.train(dict(params), ds, num_boost_round=rounds,
                     **kw).model_to_string()


def test_second_booster_shares_the_datasets_arrays():
    X, z, rs = _problem()
    y = (z > 0).astype(np.float64)
    w = 0.5 + rs.rand(N)
    ds = lgb.Dataset(X, label=y, weight=w, free_raw_data=False).construct()
    vs = lgb.Dataset(X[:200], label=y[:200], reference=ds).construct()
    kw = dict(valid_sets=[vs], valid_names=["v"])

    c0 = _counts()
    b1 = lgb.train(dict(PARAMS), ds, num_boost_round=4, **kw)
    d1 = _delta(c0)
    # train + valid label and the train weight: pushed once each; the
    # three statistics of `binary` (label check, counts, init score)
    assert (d1["label", "miss"], d1["weight", "miss"]) == (2, 1), d1
    assert d1["stats", "miss"] == 3 and d1["stats", "hit"] == 0, d1

    c1 = _counts()
    b2 = lgb.train(dict(PARAMS), ds, num_boost_round=4, **kw)
    d2 = _delta(c1)
    assert d2["label", "miss"] == d2["weight", "miss"] == 0, d2
    assert d2["stats", "miss"] == 0 and d2["stats", "hit"] == 3, d2
    assert d2["label", "hit"] >= 2 and d2["weight", "hit"] >= 1, d2

    binned = ds._binned
    for b in (b1, b2):
        g = b._gbdt
        assert g.objective.label is binned.device_label()
        assert g._label_dev is binned.device_label()
        assert g.objective.weight is binned.device_weight()
    # each Booster's score is its own (the fused step donates it)
    assert b1._gbdt.train.score is not b2._gbdt.train.score

    fresh = lgb.Dataset(X, label=y, weight=w, free_raw_data=False)
    fv = lgb.Dataset(X[:200], label=y[:200], reference=fresh)
    want = _text(PARAMS, fresh, valid_sets=[fv], valid_names=["v"])
    assert b1.model_to_string() == want
    assert b2.model_to_string() == want


def test_n_plus_one_jobs_read_one_miss_then_hits():
    X, z, _rs = _problem(seed=4)
    ds = lgb.Dataset(X, label=(z > 0).astype(float)).construct()
    c0 = _counts()
    for _ in range(4):
        lgb.train(dict(PARAMS), ds, num_boost_round=2)
    d = _delta(c0)
    assert d["label", "miss"] == 1 and d["label", "hit"] == 7, d
    assert d["stats", "miss"] == 3 and d["stats", "hit"] == 9, d


def test_a_later_booster_makes_no_padded_copy(monkeypatch):
    """On a resident Dataset a new Booster builds no label-sized host
    array to push: `padded()` is not called at all."""
    from lightgbm_tpu.dataset import BinnedDataset

    X, z, rs = _problem(seed=5)
    y = (z > 0).astype(np.float64)
    ds = lgb.Dataset(X, label=y, weight=0.5 + rs.rand(N)).construct()
    vs = lgb.Dataset(X[:200], label=y[:200], reference=ds).construct()
    kw = dict(valid_sets=[vs], valid_names=["v"], num_boost_round=2)
    lgb.train(dict(PARAMS), ds, **kw)

    calls = []
    real_padded = BinnedDataset.padded
    monkeypatch.setattr(
        BinnedDataset, "padded",
        lambda self, *a, **k: calls.append(a) or real_padded(self, *a, **k))
    lgb.train(dict(PARAMS), ds, **kw)
    assert calls == []
    ds._binned.invalidate_device_cache()
    lgb.train(dict(PARAMS), ds, **kw)
    assert len(calls) == 2  # the train label and weight, once each


# ---- every route that replaces what the cache was computed from -------
def _set_label(ds, y1, w1):
    ds.set_label(y1)
    return y1, None


def _set_label_same_memory(ds, y1, w1):
    # the Dataset holds a view of the caller's float32 array: rewritten
    # in place and set again it is the same MEMORY with new values,
    # which a device copy aliasing it (CPU backend) would have followed
    y = ds.get_label()
    assert np.shares_memory(y, ds._binned.metadata.label)
    y[:] = y1
    ds.set_label(y)
    assert np.shares_memory(y, ds._binned.metadata.label)
    return y1, None


def _set_weight(ds, y1, w1):
    ds.set_weight(w1)
    return None, w1


def _set_field(ds, y1, w1):
    ds.set_field("label", y1)
    return y1, None


def _row_padding(ds, y1, w1):
    ds._binned.ensure_row_block(3 * ds._binned.row_block)
    return None, None


def _invalidate(ds, y1, w1):
    ds._binned.invalidate_device_cache()
    return None, None


ROUTES = [_set_label, _set_label_same_memory, _set_weight, _set_field,
          _row_padding, _invalidate]


@pytest.mark.parametrize("route", ROUTES, ids=lambda f: f.__name__.lstrip("_"))
def test_invalidation_route_misses_and_trains_on_the_new_arrays(route):
    X, z, rs = _problem(seed=7)
    y0 = (z > 0).astype(np.float32)
    y1 = (z > 0.5).astype(np.float32)
    w1 = 0.25 + rs.rand(N)
    ds = lgb.Dataset(X, label=y0.copy(), free_raw_data=False).construct()
    before = _text(PARAMS, ds)
    old_label = ds._binned.device_label()

    new_y, new_w = route(ds, y1, w1)
    c0 = _counts()
    got = _text(PARAMS, ds)
    d = _delta(c0)
    if new_w is not None:
        # only what was replaced is pushed again
        assert (d["weight", "miss"], d["label", "miss"]) == (1, 0), d
        assert ds._binned.device_label() is old_label
    else:
        assert d["label", "miss"] == 1, d
        assert ds._binned.device_label() is not old_label
    if new_y is not None or new_w is not None:
        # the statistics are of the label / weight arrays: gone with them
        assert d["stats", "miss"] == 3, d
        assert got != before

    fresh = lgb.Dataset(X, label=y0 if new_y is None else new_y,
                        weight=new_w, free_raw_data=False).construct()
    fresh._binned.ensure_row_block(ds._binned.row_block)
    assert got == _text(PARAMS, fresh)
    # what the first Booster was handed is untouched (never written in
    # place, never donated): the old labels, padded with zeros
    assert np.array_equal(np.asarray(old_label)[:N], y0)


def test_a_data_parallel_booster_leaves_the_one_chip_copies_resident(mesh4):
    """A tree_learner=data Booster used to re-shard the Dataset's arrays
    and throw the unsharded ones away; since PR 32 it takes the Dataset's
    own mesh copies and drops nothing: a one-chip Booster afterwards
    hits every cache and trains the same model."""
    X, z, _rs = _problem(seed=7)
    y = (z > 0).astype(np.float32)
    ds = lgb.Dataset(X, label=y, free_raw_data=False).construct()
    before = _text(PARAMS, ds)
    one_chip = ds._binned.device_arrays()
    old_label = ds._binned.device_label()
    bst = lgb.train(dict(PARAMS, tree_learner="data"), ds, num_boost_round=2)
    g = bst._gbdt
    assert g.tree_learner_resolved == "data"
    assert g._mesh.devices.size == 4
    c0 = _counts()
    assert _text(PARAMS, ds) == before
    d = _delta(c0)
    assert d["label", "miss"] == 0 and d["stats", "miss"] == 0, d
    assert ds._binned.device_arrays() is one_chip
    assert ds._binned.device_label() is old_label
    # the mesh copies sit beside them, and go when the cache is dropped
    assert g.dev is ds._binned.device_arrays(g._mesh)
    assert g._label_dev is ds._binned.device_label(g._mesh)
    ds._binned.invalidate_device_cache()
    assert ds._binned.device_arrays(g._mesh) is not g.dev


DP = dict(PARAMS, tree_learner="data", tpu_growth_mode="rounds",
          tpu_hist_dtype="int16", metric="auc")


def _push_bytes():
    c = default_registry().counter(
        "lgbmtpu_dataset_push_bytes_total", labels=("kind",))
    return {k: c.value(kind=k) for k in ("bins", "rows")}


def test_mesh_copy_is_sharded_from_the_host_and_resident(mesh4):
    """The bins of a data-parallel Booster sit on four distinct devices,
    each shard a quarter of the padded rows; the Dataset's one-chip copy
    is never built; a second lgb.train on the Dataset pushes no bin and
    no label byte, and traces, lowers, compiles and loads nothing."""
    from lightgbm_tpu.analysis.retrace import (compile_counters,
                                                retrace_guard)

    n = 4096
    rs = np.random.RandomState(11)
    X = rs.randn(n, F)
    y = (X @ rs.randn(F) + 0.3 * rs.randn(n) > 0).astype(np.float64)
    ds = lgb.Dataset(X, label=y, free_raw_data=False).construct()
    vs = lgb.Dataset(X[:1000], label=y[:1000], reference=ds).construct()
    kw = dict(num_boost_round=8, valid_sets=[vs], valid_names=["v"])
    # the padding a TPU run takes (whole Pallas row blocks a chip): the
    # padded row count must stay a Python int through it
    from lightgbm_tpu.learner.histogram import HIST_BLK

    ds._binned.ensure_row_block(4 * HIST_BLK)
    assert type(ds._binned.num_rows_padded()) is int

    p0 = _push_bytes()
    b1 = lgb.train(dict(DP), ds, **kw)
    g = b1._gbdt
    binned = ds._binned
    assert binned._device is None and vs._binned._device is None
    assert "label" not in binned._rows_dev  # nor a one-chip label
    bins = g.dev["bins"]
    npad = binned.num_rows_padded()
    shards = bins.addressable_shards
    assert {s.device for s in shards} == set(mesh4)
    assert all(s.data.shape == (F, npad // 4) for s in shards)
    assert np.array_equal(np.asarray(bins)[:, :n], binned.bins)
    assert not np.asarray(bins)[:, n:].any()
    # label and valid mask ride the rows; a valid set is replicated
    assert g._label_dev.sharding == g.dev["valid"].sharding
    assert g.objective.label is g._label_dev
    vdev = g._dev_of(vs._binned)
    assert vdev["bins"].sharding.is_fully_replicated
    assert g.train.score.sharding.spec == (None, "data")
    p1 = _push_bytes()
    vpad = vs._binned.num_rows_padded()
    # int32 bins: the train rows once, the valid rows once a device
    assert p1["bins"] - p0["bins"] == F * 4 * (npad + 4 * vpad)
    assert p1["rows"] - p0["rows"] == 4 * (npad + 4 * vpad)

    c0 = compile_counters()
    with retrace_guard(max_retraces=0, what="second data-parallel train"):
        b2 = lgb.train(dict(DP), ds, **kw)
    c1 = compile_counters()
    for k in ("jaxpr_traces", "backend_compiles", "lower_s", "cache_load_s"):
        assert c1[k] == c0[k], (k, c0[k], c1[k])
    assert _push_bytes() == p1
    assert b2._gbdt.dev is g.dev
    assert b2._gbdt._dp is g._dp  # one grower per (mesh, spec)
    assert b2._gbdt._f_program is g._f_program
    # one program per chunk length: a job's first dispatch (fresh state)
    # and its later ones (a chunk's output state) find the state's
    # leaves under the same shardings
    assert {n: f._cache_size() for n, f in g._f_program.chunks.items()} \
        == {4: 1}
    assert b2._gbdt.train.score is not g.train.score
    assert b2.model_to_string() == b1.model_to_string()


def test_a_subset_is_its_own_dataset():
    X, z, _rs = _problem(seed=8)
    ds = lgb.Dataset(X, label=(z > 0).astype(float),
                     free_raw_data=False).construct()
    _text(PARAMS, ds)
    sub = ds.subset(np.arange(0, N, 2)).construct()
    c0 = _counts()
    _text(PARAMS, sub)
    d = _delta(c0)
    assert d["label", "miss"] == 1 and d["stats", "miss"] == 3, d
    assert sub._binned.device_label() is not ds._binned.device_label()


# ---- the init score is the float the per-Booster formulas gave --------
def _weighted_percentile(values, weights, alpha):
    order = np.argsort(values)
    v, w = values[order], weights[order]
    cw = np.cumsum(w)
    idx = int(np.searchsorted(cw, alpha * cw[-1]))
    return float(v[min(idx, len(v) - 1)])


def _clamped_logit(p, sigmoid=1.0):
    p = min(max(p, 1e-15), 1.0 - 1e-15)
    return float(np.log(p / (1.0 - p)) / sigmoid)


def _ref_binary(lab, w, cfg, k):
    cnt_pos, cnt_neg = float(np.sum(lab == 1)), float(np.sum(lab == 0))
    pos_w, neg_w = float(cfg.scale_pos_weight), 1.0
    if cfg.is_unbalance and cnt_pos > 0 and cnt_neg > 0:
        pos_w, neg_w = ((1.0, cnt_pos / cnt_neg) if cnt_pos > cnt_neg
                        else (cnt_neg / cnt_pos, 1.0))
    ww = np.ones_like(lab) if w is None else w
    lw = np.where(lab > 0, pos_w, neg_w) * ww
    pavg = float(np.sum(lab * lw) / max(np.sum(lw), 1e-20))
    return _clamped_logit(pavg, cfg.sigmoid)


def _ref_mean(lab, w, cfg, k):
    return float(np.average(lab, weights=w))


def _ref_percentile(alpha):
    def ref(lab, w, cfg, k):
        if w is None:
            return float(np.percentile(lab, alpha * 100))
        return _weighted_percentile(lab, w, alpha)
    return ref


def _ref_log_mean(lab, w, cfg, k):
    return float(np.log(max(np.average(lab, weights=w), 1e-20)))


def _ref_mape(lab, w, cfg, k):
    lw = 1.0 / np.maximum(1.0, np.abs(lab))
    if w is not None:
        lw = lw * w
    return _weighted_percentile(lab, lw.astype(np.float32), 0.5)


def _ref_ova(lab, w, cfg, k):
    return _clamped_logit(float(np.mean(lab == k)), cfg.sigmoid)


def _ref_xent(lab, w, cfg, k):
    return _clamped_logit(float(np.average(lab, weights=w)))


def _ref_xent_lambda(lab, w, cfg, k):
    havg = float(np.average(lab, weights=w))
    return float(np.log(max(np.expm1(havg), 1e-15)))


# (id, params, label kind, weighted, the parent's formula in NumPy)
INIT_SCORE_CASES = [
    ("binary", {"objective": "binary"}, "01", False, _ref_binary),
    ("binary-sigmoid", {"objective": "binary", "sigmoid": 1.7}, "01", False,
     _ref_binary),
    ("binary-is_unbalance", {"objective": "binary", "is_unbalance": True},
     "01", False, _ref_binary),
    ("binary-scale_pos_weight",
     {"objective": "binary", "scale_pos_weight": 2.3}, "01", False,
     _ref_binary),
    ("binary-weighted", {"objective": "binary"}, "01", True, _ref_binary),
    ("binary-weighted-is_unbalance",
     {"objective": "binary", "is_unbalance": True}, "01", True, _ref_binary),
    ("regression", {"objective": "regression"}, "real", False, _ref_mean),
    ("regression-weighted", {"objective": "regression"}, "real", True,
     _ref_mean),
    ("l1", {"objective": "regression_l1"}, "real", False,
     _ref_percentile(0.5)),
    ("l1-weighted", {"objective": "regression_l1"}, "real", True,
     _ref_percentile(0.5)),
    ("huber", {"objective": "huber"}, "real", False, _ref_mean),
    ("quantile", {"objective": "quantile", "alpha": 0.3}, "real", False,
     _ref_percentile(0.3)),
    ("quantile-weighted", {"objective": "quantile", "alpha": 0.3}, "real",
     True, _ref_percentile(0.3)),
    ("poisson", {"objective": "poisson"}, "pos", False, _ref_log_mean),
    ("gamma", {"objective": "gamma"}, "pos", True, _ref_log_mean),
    ("tweedie", {"objective": "tweedie"}, "pos", False, _ref_log_mean),
    ("mape", {"objective": "mape"}, "real", False, _ref_mape),
    ("mape-weighted", {"objective": "mape"}, "real", True, _ref_mape),
    ("multiclass", {"objective": "multiclass", "num_class": 4}, "class",
     False, lambda lab, w, cfg, k: 0.0),
    ("multiclassova", {"objective": "multiclassova", "num_class": 4,
                       "sigmoid": 1.3}, "class", True, _ref_ova),
    ("cross_entropy", {"objective": "cross_entropy"}, "prob", False,
     _ref_xent),
    ("cross_entropy-weighted", {"objective": "cross_entropy"}, "prob", True,
     _ref_xent),
    ("cross_entropy_lambda", {"objective": "cross_entropy_lambda"}, "prob",
     False, _ref_xent_lambda),
    ("cross_entropy_lambda-weighted", {"objective": "cross_entropy_lambda"},
     "prob", True, _ref_xent_lambda),
]


@pytest.mark.parametrize(
    "params,kind,weighted,ref",
    [c[1:] for c in INIT_SCORE_CASES], ids=[c[0] for c in INIT_SCORE_CASES])
def test_init_score_is_the_parents_float(params, kind, weighted, ref):
    X, z, rs = _problem(seed=11)
    y = {
        "01": (z > 0.4).astype(np.float64),
        "real": 3.0 * z + 1.0,
        "pos": np.exp(z / 3.0),
        "class": np.digitize(z, [-1.0, 0.0, 1.0]).astype(np.float64),
        "prob": 1.0 / (1.0 + np.exp(-z)),
    }[kind]
    w = 0.5 + rs.rand(N) if weighted else None
    ds = lgb.Dataset(X, label=y, weight=w, free_raw_data=False).construct()
    cfg = Config(dict(params, verbose=-1))
    # what the device holds and the old code pulled back: float32
    lab32 = y.astype(np.float32)
    w32 = None if w is None else w.astype(np.float32)

    for attempt in range(2):  # computed, then read from the Dataset
        obj = create_objective(cfg)
        obj.init(ds._binned)
        c0 = _counts()
        got = [obj.boost_from_score(k) for k in range(obj.num_class)]
        d = _delta(c0)
        assert d["stats", "hit" if attempt else "miss"] == obj.num_class, d
        assert d["stats", "miss" if attempt else "hit"] == 0, d
        want = [ref(lab32, w32, cfg, k) for k in range(obj.num_class)]
        assert all(type(v) is float for v in got)
        # bit for bit: the init score is in every leaf of tree 1 and in
        # the model text
        assert [v.hex() for v in got] == [v.hex() for v in want]
    assert d["label", "miss"] == d["weight", "miss"] == 0, d


def test_init_score_key_holds_the_config_values_it_reads():
    """One Dataset, two Boosters whose init scores differ only through
    the config: the second must not read the first's."""
    X, z, _rs = _problem(seed=12)
    y = (z > 0.6).astype(np.float64)
    ds = lgb.Dataset(X, label=y, free_raw_data=False).construct()
    got = {}
    for name, extra in (("plain", {}), ("unb", {"is_unbalance": True}),
                        ("spw", {"scale_pos_weight": 3.0}),
                        ("sig", {"sigmoid": 2.0})):
        obj = create_objective(Config(dict(PARAMS, **extra)))
        obj.init(ds._binned)
        got[name] = obj.boost_from_score(0)
    assert len(set(got.values())) == 4, got
    y32 = y.astype(np.float32)
    assert got["plain"].hex() == _clamped_logit(
        float(np.sum(y32 == 1) / N)).hex()


def test_reg_sqrt_label_is_the_objectives_own():
    """An objective derives from the shared label into its OWN array."""
    X, z, _rs = _problem(seed=13)
    y = 3.0 * z + 1.0
    ds = lgb.Dataset(X, label=y, free_raw_data=False).construct()
    plain = _text({**PARAMS, "objective": "regression"}, ds)
    obj = create_objective(Config({"objective": "regression",
                                   "reg_sqrt": True}))
    obj.init(ds._binned)
    shared = ds._binned.device_label()
    assert obj.label is not shared
    y32 = y.astype(np.float32)
    assert np.array_equal(np.asarray(shared)[:N], y32)
    want = float(np.average(np.asarray(obj.label)[:N]))
    assert obj.boost_from_score(0).hex() == want.hex()
    # and a reg_sqrt Booster between two plain ones changes nothing
    _text({**PARAMS, "objective": "regression", "reg_sqrt": True}, ds)
    assert _text({**PARAMS, "objective": "regression"}, ds) == plain
