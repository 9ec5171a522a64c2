"""Whole models through lgb.train, on the XLA formulation (no Pallas
off the chip), with the last round's routing-only shortcut
(rounds.spends_budget) and with it monkeypatched off: the model text
must not change, and lgbmtpu_grower_rounds_total{width="route"} reads
one per tree that ends on its leaf budget. The routing kernel and the
fused growers: test_route_round.py."""

import json

import numpy as np
import pytest

import lightgbm_tpu as lgb
from lightgbm_tpu.learner import rounds as rounds_mod

from test_route_round import _no_shortcut, _routed, fresh_traces  # noqa: F401


# ------------------------- (b), (c) whole models, the XLA formulation
def _xy(rows=3000, columns=6, seed=5):
    rs = np.random.RandomState(seed)
    X = rs.randn(rows, columns)
    y = (1.2 * X[:, 0] + X[:, 1] ** 2 - 0.7 * X[:, 2] * X[:, 3]
         + 0.3 * rs.randn(rows))
    return X, y


def _model(params, X, y, rounds=3):
    ds = lgb.Dataset(X, label=y, free_raw_data=False)
    before = _routed()
    bst = lgb.train({"objective": "regression", "verbosity": -1,
                     "min_data_in_leaf": 5, "tpu_growth_mode": "rounds",
                     **params}, ds, num_boost_round=rounds)
    return bst.model_to_string(), bst, _routed() - before


def _forced(tmp_path):
    p = tmp_path / "forced.json"
    p.write_text(json.dumps({"feature": 0, "threshold": 0.0,
                             "left": {"feature": 1, "threshold": 0.5}}))
    return {"forcedsplits_filename": str(p)}


_MONO = {"monotone_constraints": [1, -1, 0, 0, 0, 0]}
# params, routing-only rounds per tree
_MODEL_CASES = {
    "leaves63": ({"num_leaves": 63}, 1),
    "leaves255": ({"num_leaves": 255, "min_data_in_leaf": 2}, 1),
    "forced": (_forced, 1),
    # a tree of forced splits alone: its last round is a forced one
    "forced_only": (lambda tmp: {**_forced(tmp), "num_leaves": 3}, 1),
    "mono_basic": ({**_MONO, "monotone_constraints_method": "basic",
                    "num_leaves": 31}, 1),
    # the conflict guard may defer a candidate, so these keep a
    # histogram in every round
    "mono_intermediate": ({**_MONO, "num_leaves": 31,
                           "monotone_constraints_method": "intermediate"},
                          0),
    "mono_advanced": ({**_MONO, "num_leaves": 31,
                       "monotone_constraints_method": "advanced"}, 0),
    "categorical": ({"num_leaves": 31, "categorical_feature": "4",
                     "max_cat_to_onehot": 2}, 1),
    "multiclass": ({"objective": "multiclass", "num_class": 3,
                    "num_leaves": 15}, 3),
    # (c) no gain is positive long before the budget is spent: such a
    # tree has no last round anybody could know of
    "stops_on_gain": ({"num_leaves": 63, "min_gain_to_split": 40.0}, 0),
}


# without tail_exact (a round may spend all the budget at once, as at
# the benchmark's sizes) only where the shortcut is taken at all
_WIDE = ("leaves63", "leaves255", "forced", "mono_basic", "categorical",
         "multiclass")


@pytest.mark.parametrize("case,tail", [
    *((c, "tail_exact") for c in _MODEL_CASES),
    *((c, "wide_tail") for c in _WIDE)])
def test_models_equal_the_all_histogram_formulation(
        monkeypatch, fresh_traces, tmp_path, case, tail):
    """lgb.train on the XLA formulation (no Pallas off the chip): the
    model text with the shortcut equals the one without, and the
    counter reads one routing-only round per tree that ends on its
    leaf budget, none for a tree that stops on gain or grows under the
    monotone conflict guard."""
    if tail == "wide_tail":
        monkeypatch.setattr(rounds_mod, "TAIL_EXACT_ROWS", 0)
    params, per_tree = _MODEL_CASES[case]
    if callable(params):
        params = params(tmp_path)
    X, y = _xy()
    if case == "categorical":
        X[:, 4] = np.random.RandomState(2).randint(0, 9, len(X))
        y = y + (X[:, 4] % 3 == 0)
    if case == "multiclass":
        y = np.digitize(y, np.quantile(y, [1 / 3, 2 / 3]))
    got, bst, routed = _model(params, X, y)
    spec = bst._gbdt.spec
    assert spec.rounds_slots > 0 and bool(spec.mono_mode) == (
        case in ("mono_intermediate", "mono_advanced"))
    assert routed == 3 * per_tree
    n_leaves = [t.num_leaves for t in bst._gbdt.models]
    if case == "stops_on_gain":
        assert 1 < max(n_leaves) < 63
    else:
        assert set(n_leaves) == {spec.num_leaves}
    _no_shortcut(monkeypatch)
    want, _, routed = _model(params, X, y)
    assert routed == 0
    assert got == want
