"""Chunk-scan fused boosting (ISSUE 18): `fused_dispatch` runs rounds
as C-round `lax.scan` chunks — one executable launch per chunk — and
chunking and the masked tail must change NO bit: the same training
under a chunk ladder of (1,) (every round its own dispatch, every
round live, no masked tail; same code path) gives the same model text,
eval records, early-stop truncation, and no-splittable-leaf stop. The
chunk ladder bounds distinct scan executables at
len(DEFAULT_CHUNK_LADDER) for any round count (retrace-guard contract).
The independent reference (the eager sync loop) is tests/test_fused_loop.py."""

import numpy as np
import pytest

import lightgbm_tpu as lgb
import lightgbm_tpu.callback as cbm
import lightgbm_tpu.config as cfg
from lightgbm_tpu.boosting import _FUSED_STEP_CACHE, _pick_chunk
from lightgbm_tpu.config import DEFAULT_CHUNK_LADDER


def _expected_dispatches(n: int) -> int:
    d, left = 0, n
    while left > 0:
        left -= min(_pick_chunk(left, DEFAULT_CHUNK_LADDER), left)
        d += 1
    return d


def _train(params, X, y, rounds, ladder, Xv=None, yv=None):
    """Train under chunk ladder `ladder` (fused_dispatch reads
    config.DEFAULT_CHUNK_LADDER at call time)."""
    ds = lgb.Dataset(X, label=y, free_raw_data=False)
    valid_sets = valid_names = None
    if Xv is not None:
        valid_sets = [lgb.Dataset(Xv, label=yv, reference=ds,
                                  free_raw_data=False)]
        valid_names = ["va"]
    res = {}
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(cfg, "DEFAULT_CHUNK_LADDER", ladder)
        bst = lgb.train(dict(params), ds,
                        num_boost_round=rounds, valid_sets=valid_sets,
                        valid_names=valid_names,
                        callbacks=[cbm.record_evaluation(res)])
    return bst, res


def _assert_bit_identical(params, X, y, rounds, Xv=None, yv=None):
    bc, rc = _train(params, X, y, rounds, DEFAULT_CHUNK_LADDER, Xv, yv)
    bp, rp = _train(params, X, y, rounds, (1,), Xv, yv)
    assert bc.model_to_string() == bp.model_to_string()
    assert rc == rp  # eval records, exact float equality
    assert bp._gbdt.fused_dispatch_count == bp.num_trees() \
        // bp._gbdt.num_class == rounds
    return bc, bp


def test_chunk_vs_per_round_regression_bit_identical():
    rs = np.random.RandomState(7)
    X = rs.randn(800, 6)
    y = X @ rs.randn(6) + 0.3 * rs.randn(800)
    bc, bp = _assert_bit_identical(
        {"objective": "regression", "num_leaves": 7, "metric": "l2",
         "verbosity": -1},
        X[:600], y[:600], 8, X[600:], y[600:],
    )
    # dispatch-count probe: one launch per CHUNK under the default
    # ladder, one per round under ladder (1,)
    assert bc._gbdt.fused_dispatch_count == _expected_dispatches(8)
    assert bc._gbdt.fused_dispatch_count < 8
    assert bp._gbdt.fused_dispatch_count == 8


def test_chunk_vs_per_round_binary_sampled_bit_identical():
    """Bagging + feature_fraction exercise the fold_in(seed, it*K+k)
    RNG keying: frozen-`it` masked tail rounds must not consume the
    streams the next chunk replays."""
    rs = np.random.RandomState(13)
    X = rs.randn(900, 8)
    y = ((X @ rs.randn(8) + 0.3 * rs.randn(900)) > 0).astype(float)
    _assert_bit_identical(
        {"objective": "binary", "num_leaves": 7, "metric": "auc",
         "bagging_fraction": 0.6, "bagging_freq": 2,
         "feature_fraction": 0.7, "verbosity": -1},
        X[:700], y[:700], 7, X[700:], y[700:],
    )


@pytest.mark.parametrize("n", [1, 5])
def test_remainder_shorter_than_smallest_rung(n):
    """n rounds with n % 4 != 0 end in a dispatch of the smallest rung
    whose tail is masked on the device: the tail's trees and eval rows
    are sliced off on the host and no bit of what is kept differs from
    the run that never had a tail."""
    rs = np.random.RandomState(21)
    X = rs.randn(700, 6)
    y = ((X @ rs.randn(6) + 0.3 * rs.randn(700)) > 0).astype(float)
    bc, bp = _assert_bit_identical(
        {"objective": "binary", "num_leaves": 7, "metric": "auc",
         "bagging_fraction": 0.7, "bagging_freq": 1, "verbosity": -1},
        X[:500], y[:500], n, X[500:], y[500:],
    )
    g = bc._gbdt
    assert g.fused_dispatch_count == _expected_dispatches(n) == -(-n // 4)
    # the memoized program is shared with the ladder-(1,) run
    assert set(g._f_program.chunks) - {1} == {min(DEFAULT_CHUNK_LADDER)}
    assert bc.num_trees() == len(g.device_trees) == g.iter_ == n


def test_chunk_vs_per_round_multiclass_bit_identical():
    rs = np.random.RandomState(9)
    X = rs.randn(600, 6)
    y = np.argmax(X[:, :3] + 0.5 * rs.randn(600, 3), axis=1).astype(float)
    bc, _bp = _assert_bit_identical(
        {"objective": "multiclass", "num_class": 3, "num_leaves": 7,
         "metric": "multi_logloss", "verbosity": -1},
        X[:450], y[:450], 6, X[450:], y[450:],
    )
    assert bc._gbdt.fused_dispatch_count == _expected_dispatches(6)


@pytest.mark.slow  # 40-round pair of trainings — over the fast-tier budget
def test_early_stop_mid_chunk_truncates_bit_exactly():
    """Early stop fires inside a dispatched chunk: fused_truncate must
    leave model text, round count, and best_iteration identical to the
    unscanned loop (reference stop-timing semantics)."""
    rs = np.random.RandomState(5)
    X = rs.randn(900, 5)
    y = (X[:, 0] + 0.5 * rs.randn(900) > 0).astype(float)
    params = {"objective": "binary", "num_leaves": 7, "metric": "auc",
              "verbosity": -1, "early_stopping_round": 3}
    bc, rc = _train(params, X[:600], y[:600], 40, DEFAULT_CHUNK_LADDER,
                    X[600:], y[600:])
    bp, rp = _train(params, X[:600], y[:600], 40, (1,),
                    X[600:], y[600:])
    assert bc.best_iteration == bp.best_iteration >= 1
    assert bc.num_trees() == bp.num_trees() == bc.best_iteration + 3
    assert bc.num_trees() < 40  # actually stopped mid-chunk
    assert bc.model_to_string() == bp.model_to_string()
    assert rc == rp


def test_no_splittable_leaf_stop_matches():
    """The device `stopped` mask must reproduce the host loop's
    no-splittable-leaf stop (gbdt.cpp:429-452): post-stop rounds are
    algebraic no-ops and the model truncates at the stop round."""
    rs = np.random.RandomState(1)
    X = rs.randn(200, 4)
    y = X[:, 0] + 0.1 * rs.randn(200)
    params = {"objective": "regression", "num_leaves": 7,
              "verbosity": -1, "min_data_in_leaf": 120}
    bc, _ = _train(params, X, y, 8, DEFAULT_CHUNK_LADDER)
    bp, _ = _train(params, X, y, 8, (1,))
    assert bc.num_trees() == bp.num_trees() == 1  # the kept bias tree
    assert bp._gbdt.fused_dispatch_count == 8
    assert bc.model_to_string() == bp.model_to_string()


@pytest.mark.slow  # 100/13/64-round trainings warm the whole ladder
def test_retrace_guard_mixed_chunk_sizes(retrace_guard):
    """13, 64, and 100 rounds force mixed ladder rungs plus masked-tail
    chunks; across all of it at most len(DEFAULT_CHUNK_LADDER) scan
    executables exist and repeat trainings never retrace them."""
    rs = np.random.RandomState(2)
    X = rs.randn(1000, 5)
    y = X @ rs.randn(5) + 0.2 * rs.randn(1000)
    params = {"objective": "regression", "num_leaves": 4,
              "verbosity": -1, "min_data_in_leaf": 2}

    def train(n):
        ds = lgb.Dataset(X, label=y, free_raw_data=False)
        return lgb.train(dict(params), ds, num_boost_round=n)

    _FUSED_STEP_CACHE.clear()
    b100 = train(100)
    assert b100.num_trees() == 100
    assert len(_FUSED_STEP_CACHE) == 1
    prog = next(iter(_FUSED_STEP_CACHE.values()))
    rungs = set(prog.chunks)
    assert rungs <= set(DEFAULT_CHUNK_LADDER)
    assert len(rungs) <= len(DEFAULT_CHUNK_LADDER)
    chunk_fns = list(prog.chunks.values())
    with retrace_guard(entry_points=chunk_fns, max_retraces=0,
                       what="mixed chunk sizes over a warm ladder"):
        assert train(13).num_trees() == 13
        assert train(64).num_trees() == 64
    # repeat trainings introduced no rungs beyond the ladder either
    assert set(prog.chunks) == rungs
    assert b100._gbdt.fused_dispatch_count == _expected_dispatches(64) \
        + _expected_dispatches(36)  # driver chunks at _check_every=64
