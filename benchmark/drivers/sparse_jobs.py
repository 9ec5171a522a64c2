"""Closed-loop training jobs on a SPARSE one-hot table, one client: the
``train_jobs`` loop, run unchanged, after a capability check.

Who sends it: ads, insurance and retail teams that train on one-hot or
sparse indicator columns passed as a scipy matrix, re-training or
sweeping hyper-parameters on a resident table. What they need from the
program, and what the driver therefore checks FIRST, at a small size and
before any costly set-up: that the program states two facts of its EFB
path, the gauges
- ``lgbmtpu_split_bundle_layout`` = 1: the rounds grower's split search
  read the bundle histograms in their own layout (one round of a
  8,192-row draw of the cell's generator), and
- ``lgbmtpu_dataset_bundle_rows`` = the bin-construction sample: the
  grouping counted its conflicts on the sample, not on every row (the
  construction of a draw 50,000 rows past the sample: 250,000 rows at
  the default 200,000).

A program without them groups the full table's 12M rows in an hour or
more: it exits non-zero here with the reason, in seconds, with no result
line. The draws of the check are the cell's own generator: the first
rows of its one training table."""

from __future__ import annotations

import time

from benchmark.harness import cellrun
from benchmark.harness.manifest import load_plugin
from benchmark.harness.spans import counter_totals

BUNDLE_S = "lgbmtpu_dataset_bundle_seconds_total"
BUNDLED = "lgbmtpu_tree_bundled_splits_total"
SPLITS = "lgbmtpu_tree_splits_total"

TINY_ROWS = 8_192
PAST_SAMPLE = 50_000
CHECK_SEED = 20110101
# the cell's parameters at a size that compiles in seconds
TINY_PARAMS = {"num_leaves": 4, "max_bin": 15, "min_data_in_leaf": 1,
               "min_sum_hessian_in_leaf": 1e-3, "verbosity": -1}


class CannotRunCell(SystemExit):
    """The program lacks what the cell measures: a non-zero exit with no
    result line, before any costly set-up."""


def _gauge(name: str):
    from lightgbm_tpu.obs.metrics import default_registry

    values = list(default_registry().snapshot().get(name, {}).values())
    return values[0] if values else None


def _refuse(why: str, t0: float) -> CannotRunCell:
    return CannotRunCell(
        f"[benchmark] this program cannot run the cell: {why}. Found in "
        f"{time.perf_counter() - t0:.1f}s, before any set-up")


def require_bundle_program(lgb, gen, features: int, params) -> float:
    """The two facts above; returns the seconds the check took, raises
    ``CannotRunCell`` otherwise."""
    from lightgbm_tpu.config import Config

    t0 = time.perf_counter()
    X, y, _, _ = gen.make(CHECK_SEED, TINY_ROWS, 16, features)
    tiny = dict(params, **TINY_PARAMS)
    ds = lgb.Dataset(X, label=y, params=dict(tiny), free_raw_data=False)
    lgb.train(tiny, ds, num_boost_round=1)
    layout = _gauge("lgbmtpu_split_bundle_layout")
    if layout != 1:
        raise _refuse(
            "one round on a sparse one-hot table did not report a split "
            "search in the bundle layout (gauge "
            f"lgbmtpu_split_bundle_layout = {layout})", t0)
    want = Config(dict(params)).bin_construct_sample_cnt
    X, y, _, _ = gen.make(CHECK_SEED, want + PAST_SAMPLE, 16, features)
    lgb.Dataset(X, label=y, params=dict(params)).construct()
    rows = _gauge("lgbmtpu_dataset_bundle_rows")
    if rows != want:
        raise _refuse(
            f"the grouping of a {want + PAST_SAMPLE}-row table did not report "
            f"counting its conflicts on the {want}-row sample (gauge "
            f"lgbmtpu_dataset_bundle_rows = {rows})", t0)
    return time.perf_counter() - t0


def run(cell, args: cellrun.RunArgs, rec) -> cellrun.Outcome:
    import lightgbm_tpu as lgb

    data = cell.config["dataset"]
    gen = load_plugin(cell.root, "datasets", data["generator"])
    with rec.span("capability"):
        took = require_bundle_program(lgb, gen, int(data["features"]),
                                      cell.config["params"])
    cellrun.log(f"capability check passed in {took:.1f}s")
    # what the check added to the program's counters, for the readers
    # of the run's own share (layer_metrics/dataset.bundle_s.py,
    # learner.bundled_split_share.py)
    totals = counter_totals((BUNDLE_S, BUNDLED, SPLITS))
    rec.observe(bundle_s_before_run=totals[BUNDLE_S],
                splits_before_run={BUNDLED: totals[BUNDLED],
                                   SPLITS: totals[SPLITS]})
    out = load_plugin(cell.root, "drivers", "train_jobs").run(
        cell, args, rec)
    out.facts["capability_s"] = took
    return out
