"""Continued training (init_model) + snapshot_freq
(reference boosting.h:311 input_model, gbdt.cpp:258-262 snapshots)."""

from __future__ import annotations

import numpy as np
import pytest

import lightgbm_tpu as lgb


def _problem(n=2000, seed=0):
    rs = np.random.RandomState(seed)
    X = rs.randn(n, 6)
    w = rs.randn(6)
    y = ((X @ w + 0.4 * rs.randn(n)) > 0).astype(float)
    return X, y


PARAMS = {
    "objective": "binary",
    "num_leaves": 15,
    "learning_rate": 0.2,
    "verbosity": -1,
}


def test_split_training_equals_one_shot():
    """5 + 5 rounds via init_model == 10 rounds straight: score seeding
    through binned traversal is exact for our own models."""
    X, y = _problem()
    ds = lgb.Dataset(X, label=y, free_raw_data=False)
    full = lgb.train(dict(PARAMS), ds, num_boost_round=10)

    ds1 = lgb.Dataset(X, label=y, free_raw_data=False)
    first = lgb.train(dict(PARAMS), ds1, num_boost_round=5)
    ds2 = lgb.Dataset(X, label=y, free_raw_data=False)
    second = lgb.train(dict(PARAMS), ds2, num_boost_round=5, init_model=first)

    assert second.num_trees() == 10
    np.testing.assert_allclose(
        second.predict(X[:300]), full.predict(X[:300]), rtol=1e-5, atol=1e-6
    )


def test_init_model_from_file(tmp_path):
    X, y = _problem(seed=3)
    ds = lgb.Dataset(X, label=y, free_raw_data=False)
    first = lgb.train(dict(PARAMS), ds, num_boost_round=4)
    path = tmp_path / "m.txt"
    first.save_model(path)

    ds2 = lgb.Dataset(X, label=y, free_raw_data=False)
    second = lgb.train(dict(PARAMS), ds2, num_boost_round=3,
                       init_model=str(path))
    assert second.num_trees() == 7
    # logloss should not get worse by continuing
    from sklearn.metrics import log_loss

    l1 = log_loss(y, first.predict(X))
    l2 = log_loss(y, second.predict(X))
    assert l2 <= l1 + 1e-6


def test_model_text_with_removed_options_loads_and_continues(tmp_path):
    """A model text saved before tpu_chunk_scan / tpu_growth_rounds went
    carries them in its parameter block: it loads, predicts the same,
    and continues training to the same model as the text without them."""
    X, y = _problem(seed=11)
    ds = lgb.Dataset(X, label=y, free_raw_data=False)
    first = lgb.train(dict(PARAMS), ds, num_boost_round=4)
    text = first.model_to_string()
    old = text.replace(
        "parameters:\n",
        "parameters:\n[tpu_chunk_scan: auto]\n[tpu_growth_rounds: 0]\n")
    assert old != text and "[tpu_chunk_scan: auto]" in old
    path = tmp_path / "old.txt"
    path.write_text(old)

    for loaded in (lgb.Booster(model_str=old),
                   lgb.Booster(model_file=str(path))):
        assert loaded.num_trees() == 4
        np.testing.assert_array_equal(loaded.predict(X[:300]),
                                      first.predict(X[:300]))

    def continued(init):
        ds2 = lgb.Dataset(X, label=y, free_raw_data=False)
        return lgb.train(dict(PARAMS), ds2, num_boost_round=3,
                         init_model=init)

    from_old, from_new = continued(str(path)), continued(first)
    assert from_old.num_trees() == 7
    assert from_old.model_to_string() == from_new.model_to_string()


def test_continued_training_with_valid_and_early_stop():
    X, y = _problem(seed=5)
    ds = lgb.Dataset(X, label=y, free_raw_data=False)
    first = lgb.train(dict(PARAMS), ds, num_boost_round=3)
    ds2 = lgb.Dataset(X, label=y, free_raw_data=False)
    vs = lgb.Dataset(X[:400], label=y[:400], reference=ds2, free_raw_data=False)
    second = lgb.train(
        {**PARAMS, "metric": "binary_logloss"}, ds2, num_boost_round=5,
        valid_sets=[vs], valid_names=["v"], init_model=first,
    )
    assert second.num_trees() == 8
    assert np.isfinite(second.predict(X[:10])).all()


def test_snapshot_freq(tmp_path):
    X, y = _problem(seed=7)
    ds = lgb.Dataset(X, label=y, free_raw_data=False)
    out = tmp_path / "snap_model.txt"
    lgb.train(
        {**PARAMS, "snapshot_freq": 3, "output_model": str(out)},
        ds, num_boost_round=7,
    )
    s3 = lgb.Booster(model_file=f"{out}.snapshot_iter_3")
    s6 = lgb.Booster(model_file=f"{out}.snapshot_iter_6")
    assert s3.num_trees() == 3
    assert s6.num_trees() == 6


def test_cli_continued_training(tmp_path):
    import os

    from lightgbm_tpu.cli import main as cli_main

    X, y = _problem(seed=9)
    np.savetxt(tmp_path / "train.tsv", np.column_stack([y, X]),
               delimiter="\t", fmt="%.6f")
    cwd = os.getcwd()
    os.chdir(tmp_path)
    try:
        assert cli_main(["task=train", "objective=binary", "data=train.tsv",
                         "num_trees=4", "num_leaves=7", "verbosity=-1",
                         "output_model=m1.txt"]) == 0
        assert cli_main(["task=train", "objective=binary", "data=train.tsv",
                         "num_trees=3", "num_leaves=7", "verbosity=-1",
                         "input_model=m1.txt", "output_model=m2.txt"]) == 0
    finally:
        os.chdir(cwd)
    m2 = lgb.Booster(model_file=tmp_path / "m2.txt")
    assert m2.num_trees() == 7
