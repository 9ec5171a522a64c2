"""path_smooth and monotone-constraint interval propagation
(feature_histogram.hpp CalculateSplittedLeafOutput USE_SMOOTHING branch,
monotone_constraints.hpp:489 BasicLeafConstraints::Update)."""

from __future__ import annotations

import numpy as np
import pytest

import lightgbm_tpu as lgb


def _problem(n=3000, seed=0):
    rs = np.random.RandomState(seed)
    X = rs.randn(n, 5)
    y = (
        2.0 * X[:, 0]
        + np.sin(3 * X[:, 1])
        + 0.5 * X[:, 2] * X[:, 3]
        + 0.3 * rs.randn(n)
    )
    return X, y


def test_path_smooth_changes_and_regularizes():
    X, y = _problem()
    base = {"objective": "regression", "num_leaves": 31, "verbosity": -1,
            "learning_rate": 0.2, "min_data_in_leaf": 5}

    def leaves(ps):
        ds = lgb.Dataset(X, label=y, free_raw_data=False)
        bst = lgb.train({**base, "path_smooth": ps}, ds, num_boost_round=5)
        d = bst.dump_model()
        vals = []

        def walk(node):
            if "leaf_value" in node:
                vals.append(node["leaf_value"])
            else:
                walk(node["left_child"])
                walk(node["right_child"])

        for t in d["tree_info"]:
            walk(t["tree_structure"])
        return np.asarray(vals), bst.predict(X)

    v0, p0 = leaves(0.0)
    v10, p10 = leaves(10.0)
    vbig, pbig = leaves(1e6)
    assert not np.allclose(p0, p10)
    # smoothing pulls leaf outputs toward their parents: the spread of
    # leaf values shrinks monotonically with the smoothing strength
    assert np.std(v10) < np.std(v0)
    assert np.std(vbig) < 0.1 * np.std(v0)


def test_path_smooth_quality_parity_with_reference():
    """Smoothed training still learns (sanity against over-shrinkage)."""
    X, y = _problem(seed=2)
    ds = lgb.Dataset(X, label=y, free_raw_data=False)
    bst = lgb.train(
        {"objective": "regression", "num_leaves": 31, "verbosity": -1,
         "path_smooth": 1.0, "learning_rate": 0.1},
        ds, num_boost_round=40,
    )
    mse = float(np.mean((bst.predict(X) - y) ** 2))
    assert mse < 0.5 * float(np.var(y)), mse


def _check_monotone(bst, X, feat, direction, n_checks=40, n_grid=25):
    rs = np.random.RandomState(1)
    rows = X[rs.choice(len(X), n_checks, replace=False)]
    grid = np.linspace(X[:, feat].min(), X[:, feat].max(), n_grid)
    for r in rows:
        tiled = np.tile(r, (n_grid, 1))
        tiled[:, feat] = grid
        pred = bst.predict(tiled)
        diffs = np.diff(pred) * direction
        assert (diffs >= -1e-9).all(), (
            f"monotone violation on feature {feat}: {diffs.min()}"
        )


@pytest.mark.parametrize("direction", [1, -1])
def test_monotone_constraints_hold_globally(direction):
    """Deep trees must respect the constraint through INHERITED intervals
    — candidate-level ordering alone (round-2 implementation) fails
    this for descendants of a constrained split."""
    rs = np.random.RandomState(3)
    n = 4000
    X = rs.randn(n, 4)
    # strong non-monotone dependence on x0 tempts violations
    y = direction * (1.5 * X[:, 0] + 0.8 * np.sin(4 * X[:, 0])) + X[:, 1] + 0.2 * rs.randn(n)
    mono = [direction, 0, 0, 0]
    ds = lgb.Dataset(X, label=y, free_raw_data=False)
    bst = lgb.train(
        {"objective": "regression", "num_leaves": 63, "verbosity": -1,
         "monotone_constraints": mono, "learning_rate": 0.2,
         "min_data_in_leaf": 3},
        ds, num_boost_round=15,
    )
    _check_monotone(bst, X, 0, direction)


def test_monotone_constraint_reference_cli_agrees(tmp_path):
    """Same constrained config through the reference CLI: both must hold
    the constraint; quality within tolerance."""
    import subprocess
    from pathlib import Path

    CLI = Path(__file__).resolve().parent.parent / ".refbuild" / "lightgbm"
    if not CLI.exists():
        pytest.skip("reference CLI not built")
    rs = np.random.RandomState(5)
    n = 3000
    X = rs.randn(n, 3)
    y = 1.2 * X[:, 0] + np.sin(3 * X[:, 0]) + X[:, 1] + 0.2 * rs.randn(n)
    np.savetxt(tmp_path / "tr.tsv", np.column_stack([y, X]),
               delimiter="\t", fmt="%.6f")
    r = subprocess.run(
        [str(CLI), "task=train", "objective=regression", "data=tr.tsv",
         "num_trees=10", "num_leaves=31", "monotone_constraints=1,0,0",
         "output_model=ref.txt", "verbosity=-1"],
        cwd=tmp_path, capture_output=True, text=True, timeout=300,
    )
    assert r.returncode == 0, r.stdout + r.stderr
    ref = lgb.Booster(model_file=tmp_path / "ref.txt")

    ds = lgb.Dataset(X, label=y, free_raw_data=False)
    ours = lgb.train(
        {"objective": "regression", "num_leaves": 31, "verbosity": -1,
         "monotone_constraints": [1, 0, 0]},
        ds, num_boost_round=10,
    )
    _check_monotone(ours, X, 0, 1, n_checks=20)
    mse_ref = float(np.mean((ref.predict(X) - y) ** 2))
    mse_ours = float(np.mean((ours.predict(X) - y) ** 2))
    assert mse_ours <= mse_ref * 1.2, (mse_ours, mse_ref)


def test_unimplemented_params_warn(capsys):
    """Honest params: anything accepted-but-inert must warn. The list
    has shrunk as features landed (linear_tree / extra_trees /
    interaction_constraints / cegb_* / position bias are implemented
    now); forced splits remain pending."""
    X, y = _problem(n=500, seed=7)
    ds = lgb.Dataset(X, label=y, free_raw_data=False)
    lgb.train(
        {"objective": "regression", "num_leaves": 7, "verbosity": 0,
         "forcedsplits_filename": "splits.json"},
        ds, num_boost_round=1,
    )
    text = capsys.readouterr().err
    assert "forcedsplits_filename" in text

    # implemented params must NOT warn
    ds2 = lgb.Dataset(X, label=y, free_raw_data=False)
    lgb.train(
        {"objective": "regression", "num_leaves": 7, "verbosity": 0,
         "extra_trees": True, "interaction_constraints": "[0,1],[2,3]",
         "cegb_penalty_split": 0.1},
        ds2, num_boost_round=1,
    )
    text2 = capsys.readouterr().err
    assert "has no effect" not in text2


@pytest.mark.parametrize("method", ["basic", "intermediate", "advanced"])
@pytest.mark.parametrize("direction", [1, -1])
def test_monotone_methods_violation_scan(method, direction):
    """Deep-tree violation scan for all three constraint methods
    (monotone_constraints.hpp basic:489, intermediate:516,
    advanced:858). On this default (exact-oracle) path advanced
    downgrades to the intermediate formulation with a warning — the
    true advanced refinement rides the rounds grower
    (test_monotone_rounds_mode_violation_scan)."""
    rs = np.random.RandomState(5)
    n = 4000
    X = rs.randn(n, 4)
    y = direction * (1.5 * X[:, 0] + 0.8 * np.sin(4 * X[:, 0])) \
        + X[:, 1] + 0.2 * rs.randn(n)
    mono = [direction, 0, 0, 0]
    ds = lgb.Dataset(X, label=y, free_raw_data=False)
    bst = lgb.train(
        {"objective": "regression", "num_leaves": 63, "verbosity": -1,
         "monotone_constraints": mono, "learning_rate": 0.2,
         "min_data_in_leaf": 3, "monotone_constraints_method": method},
        ds, num_boost_round=10,
    )
    _check_monotone(bst, X, 0, direction)


@pytest.mark.parametrize("method", ["basic", "intermediate", "advanced"])
@pytest.mark.parametrize("direction", [1, -1])
def test_monotone_rounds_mode_violation_scan(method, direction):
    """Monotone constraints on the TPU fast path: the round-batched
    grower enforces basic via inherited
    intervals, intermediate via the per-round ancestry-bounds recompute
    with the same-round opposite-subtree conflict guard, and advanced
    via the per-leaf bin-range overlap refinement of the
    opposite-subtree extrema (monotone_constraints.hpp:858) — deep
    trees grown in rounds mode must hold the constraint globally under
    all three."""
    rs = np.random.RandomState(5)
    n = 4000
    X = rs.randn(n, 4)
    y = direction * (1.5 * X[:, 0] + 0.8 * np.sin(4 * X[:, 0])) \
        + X[:, 1] + 0.2 * rs.randn(n)
    mono = [direction, 0, 0, 0]
    ds = lgb.Dataset(X, label=y, free_raw_data=False)
    bst = lgb.train(
        {"objective": "regression", "num_leaves": 63, "verbosity": -1,
         "monotone_constraints": mono, "learning_rate": 0.2,
         "min_data_in_leaf": 3, "monotone_constraints_method": method,
         "tpu_growth_mode": "rounds"},
        ds, num_boost_round=10,
    )
    _check_monotone(bst, X, 0, direction)


def test_monotone_advanced_mode_resolution():
    """method=advanced resolves to mono_mode=2 on the rounds path and
    downgrades to the intermediate formulation (mono_mode=1, with a
    warning) on the exact oracle, which only implements intermediate."""
    rs = np.random.RandomState(3)
    X = rs.randn(1500, 3)
    y = 1.1 * X[:, 0] + 0.5 * X[:, 1] + 0.2 * rs.randn(1500)
    modes = {}
    for mode in ("rounds", "exact"):
        ds = lgb.Dataset(X, label=y, free_raw_data=False)
        bst = lgb.train(
            {"objective": "regression", "num_leaves": 15, "verbosity": -1,
             "monotone_constraints": [1, 0, 0],
             "monotone_constraints_method": "advanced",
             "tpu_growth_mode": mode},
            ds, num_boost_round=2,
        )
        modes[mode] = int(bst._gbdt.spec.mono_mode)
    assert modes == {"rounds": 2, "exact": 1}


def test_monotone_rounds_quality_close_to_exact():
    """Rounds-mode constrained training must stay within tolerance of
    the sequential exact grower's quality (same config, both methods)."""
    rs = np.random.RandomState(9)
    n = 4000
    X = rs.randn(n, 4)
    y = 1.2 * X[:, 0] + 0.6 * np.sin(3 * X[:, 0]) + 0.8 * X[:, 1] \
        + 0.2 * rs.randn(n)
    for method in ("basic", "intermediate"):
        mse = {}
        for mode in ("exact", "rounds"):
            ds = lgb.Dataset(X, label=y, free_raw_data=False)
            bst = lgb.train(
                {"objective": "regression", "num_leaves": 31,
                 "verbosity": -1, "monotone_constraints": [1, 0, 0, 0],
                 "learning_rate": 0.15, "min_data_in_leaf": 5,
                 "monotone_constraints_method": method,
                 "tpu_growth_mode": mode},
                ds, num_boost_round=15,
            )
            mse[mode] = float(np.mean((bst.predict(X) - y) ** 2))
        assert mse["rounds"] <= mse["exact"] * 1.15, (method, mse)


def test_monotone_intermediate_quality_at_least_basic():
    """The intermediate method bounds children by the opposite
    subtree's ACTUAL extrema instead of the frozen split midpoint —
    strictly weaker constraints, so training loss must not regress
    (reference docs: intermediate 'may slow the library very slightly'
    but 'should improve the results')."""
    rs = np.random.RandomState(8)
    n = 5000
    X = rs.randn(n, 4)
    y = 1.2 * X[:, 0] + 0.6 * np.sin(3 * X[:, 0]) + 0.8 * X[:, 1] \
        + 0.2 * rs.randn(n)
    mse = {}
    for method in ("basic", "intermediate"):
        ds = lgb.Dataset(X, label=y, free_raw_data=False)
        bst = lgb.train(
            {"objective": "regression", "num_leaves": 31, "verbosity": -1,
             "monotone_constraints": [1, 0, 0, 0], "learning_rate": 0.15,
             "min_data_in_leaf": 5,
             "monotone_constraints_method": method},
            ds, num_boost_round=20,
        )
        mse[method] = float(np.mean((bst.predict(X) - y) ** 2))
    assert mse["intermediate"] <= mse["basic"] * 1.02, mse
