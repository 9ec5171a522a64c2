"""forcedbins_filename: user-forced bin boundaries (reference
src/io/dataset_loader.cpp GetForcedBins + bin.cpp forced-bounds path)
must actually change bin-edge construction — the key was accepted but
unwired before this test existed."""

import json

import numpy as np
import pytest

import lightgbm_tpu as lgb
from lightgbm_tpu.binning import (
    BinMapper,
    find_bin_bounds_forced,
    load_forced_bins,
)
from lightgbm_tpu.config import Config
from lightgbm_tpu.dataset import BinnedDataset
from lightgbm_tpu.log import LightGBMError


def _write(tmp_path, entries):
    p = tmp_path / "forced.json"
    p.write_text(json.dumps(entries))
    return str(p)


def test_forced_bounds_change_bin_edges(tmp_path, rng):
    X = rng.randn(3000, 3)
    path = _write(tmp_path, [
        {"feature": 0, "bin_upper_bound": [-0.5, 0.0, 0.5]},
    ])
    plain = BinnedDataset.from_numpy(X, Config({"max_bin": 16}))
    forced = BinnedDataset.from_numpy(
        X, Config({"max_bin": 16, "forcedbins_filename": path})
    )
    ub = forced.mappers[0].upper_bounds
    for b in (-0.5, 0.0, 0.5):
        assert np.any(np.isclose(ub, b)), (b, ub)
    assert not np.array_equal(plain.mappers[0].upper_bounds, ub)
    # untouched features bin identically
    np.testing.assert_array_equal(
        plain.mappers[1].upper_bounds, forced.mappers[1].upper_bounds
    )
    # the forced edge really partitions: values either side of 0.5 land
    # in different bins
    m = forced.mappers[0]
    lo, hi = m.values_to_bins(np.asarray([0.499])), \
        m.values_to_bins(np.asarray([0.501]))
    assert lo[0] != hi[0]


def test_forced_bounds_respect_max_bin(rng):
    vals = rng.randn(5000)
    bounds = find_bin_bounds_forced(vals, 5000, 8, 3,
                                    [-1.0, -0.5, 0.0, 0.5, 1.0])
    assert len(bounds) <= 8
    assert np.isposinf(bounds[-1])
    for b in (-1.0, -0.5, 0.0, 0.5, 1.0):
        assert any(np.isclose(bounds, b)), bounds
    assert bounds == sorted(bounds)


def test_forced_bins_with_nan_missing(rng):
    vals = rng.randn(2000)
    vals[rng.rand(2000) < 0.1] = np.nan
    m = BinMapper.from_sample(vals, 2000, max_bin=16, forced_bounds=[0.0])
    assert any(np.isclose(m.upper_bounds, 0.0))
    # NaN bin still reserved on top
    assert m.nan_bin == m.num_bin - 1


def test_forced_bins_end_to_end_training(tmp_path, rng):
    X = rng.randn(2000, 3)
    y = (X[:, 0] > 0.25).astype(float)
    path = _write(tmp_path, [{"feature": 0, "bin_upper_bound": [0.25]}])
    bst = lgb.train(
        {"objective": "binary", "num_leaves": 7, "verbosity": -1,
         "forcedbins_filename": path, "max_bin": 8},
        lgb.Dataset(X, label=y, free_raw_data=False),
        num_boost_round=5,
    )
    # with the true decision boundary forced as a bin edge, the first
    # split threshold can sit exactly on it
    thresholds = np.concatenate(
        [t.threshold[t.decision_type == 0] for t in bst._gbdt.models]
    )
    assert np.any(np.isclose(thresholds, 0.25, atol=1e-12)), thresholds
    from sklearn.metrics import roc_auc_score

    assert roc_auc_score(y, bst.predict(X)) > 0.95


def test_forced_bins_file_errors(tmp_path):
    with pytest.raises(LightGBMError):
        load_forced_bins(str(tmp_path / "nope.json"))
    bad = tmp_path / "bad.json"
    bad.write_text("{not json")
    with pytest.raises(LightGBMError):
        load_forced_bins(str(bad))
    ok = tmp_path / "ok.json"
    ok.write_text(json.dumps([
        {"feature": 0, "bin_upper_bound": [1.0]},
        {"feature": 99, "bin_upper_bound": [1.0]},  # out of range: skip
        {"bin_upper_bound": [1.0]},  # malformed: skip
    ]))
    out = load_forced_bins(str(ok), num_total_features=3)
    assert out == {0: [1.0]}


def test_unwired_params_warn():
    """The accepted-but-unwired sweep: params
    with no effect in this build must WARN when set away from their
    inactive value, and every _UNIMPLEMENTED entry must really be
    unreferenced outside config.py."""
    import os
    import re

    from lightgbm_tpu import log
    from lightgbm_tpu.config import _UNIMPLEMENTED, warn_unimplemented

    msgs = []

    class _Cap:
        @staticmethod
        def info(m):
            msgs.append(m)

        warning = info

    log.register_logger(_Cap)
    try:
        warn_unimplemented(Config({"force_col_wise": True, "num_gpu": 4}))
    finally:
        log._logger = None  # restore the default print logger
    assert any("force_col_wise" in m for m in msgs)
    assert any("num_gpu" in m for m in msgs)

    # the sweep itself: no _UNIMPLEMENTED key is referenced in package
    # code outside config.py (if one becomes wired, drop it there)
    import lightgbm_tpu

    pkg = os.path.dirname(lightgbm_tpu.__file__)
    sources = []
    for root, _dirs, files in os.walk(pkg):
        for f in files:
            if f.endswith(".py") and f != "config.py":
                sources.append(open(os.path.join(root, f)).read())
    blob = "\n".join(sources)
    for name, _inactive, _why in _UNIMPLEMENTED:
        assert not re.search(rf"\b(cfg|config|c)\.{name}\b", blob), (
            f"{name} is referenced in package code but listed as "
            "unimplemented"
        )


def test_forced_bins_sparse_implicit_zero_mass(rng):
    """The sparse path samples only EXPLICIT values; the implicit-zero
    mass (total_sample_cnt - len(values)) must still count toward
    forced-segment budgets — and toward the greedy packer's totals —
    or a 90%-zero feature bins from 10% of its data."""
    import scipy.sparse as sp

    from lightgbm_tpu.binning import find_bin_bounds_forced

    explicit = rng.uniform(1.0, 5.0, 100)
    bounds = find_bin_bounds_forced(explicit, 1000, 16, 3, [0.5])
    # the zero-containing segment (-inf, 0.5] holds 900 of 1000 samples
    # even though `values` has none: it must still get real budget, and
    # 0.5 stays a bin edge
    assert any(np.isclose(bounds, 0.5))
    # the nonzero segment cannot eat nearly the whole ladder: its share
    # is ~100/1000 of the remaining budget
    above = [b for b in bounds if b > 0.5 and np.isfinite(b)]
    assert len(above) <= 4, bounds

    # end to end through the CSR constructor
    import json
    import tempfile

    X = sp.random(2000, 3, density=0.1, random_state=1,
                  data_rvs=lambda n: rng.uniform(1, 5, n)).tocsr()
    fb = tempfile.NamedTemporaryFile("w", suffix=".json", delete=False)
    json.dump([{"feature": 0, "bin_upper_bound": [0.5]}], fb)
    fb.close()
    ds = lgb.Dataset(X, label=rng.randn(2000), free_raw_data=False,
                     params={"forcedbins_filename": fb.name,
                             "max_bin": 16})
    ds.construct()
    assert any(np.isclose(ds._binned.mappers[0].upper_bounds, 0.5))


def test_forced_bins_non_list_json_is_fatal(tmp_path):
    bad = tmp_path / "obj.json"
    bad.write_text(json.dumps({"feature": 0, "bin_upper_bound": [1.0]}))
    with pytest.raises(LightGBMError):
        load_forced_bins(str(bad))
