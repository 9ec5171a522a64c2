"""The categorical cell (PR 36): ``expo-cat`` resolves and agrees with
BENCHMARK.json and the reader files; a 20k-row cut runs through
``train_jobs`` on the CPU (the chip's default program, kernels
interpreted) and ``cat_audit`` calls it correct; the audit refuses three
planted faults; the two new readers against hand numbers."""

import json
import re
import shutil
import time
from types import SimpleNamespace

import numpy as np
import pytest

from benchmark.harness import cellrun
from benchmark.harness.manifest import load_plugin, repo_root, resolve_cell

ROOT = repo_root()
MANIFEST = json.loads((ROOT / "BENCHMARK.json").read_text())
CELL = "expo-cat.train"
NEW = ("learner.cat_split_share", "dataset.cat_other_row_share")


def test_the_configuration_resolves_at_the_sources_shape():
    cell = resolve_cell(ROOT, CELL)
    cfg = cell.config
    assert cell.chips == 1 and cell.traffic_name == "train-jobs-8"
    assert cfg["dataset"] == dict(
        generator="synthetic_expo", rows=33 * 2 ** 20, valid_rows=2 ** 20,
        features=8, rows_published=10_000_000,
        valid_rows_published=1_000_000)
    p = cfg["params"]
    assert p["categorical_feature"] == "0,1,2,4,5,6"
    assert (p["num_leaves"], p["max_bin"], p["learning_rate"]) == (
        255, 255, 0.1)
    assert (p["min_data_in_leaf"], p["min_sum_hessian_in_leaf"]) == (0, 100)
    assert (p["max_cat_to_onehot"], p["max_cat_threshold"], p["cat_smooth"],
            p["cat_l2"], p["min_data_per_group"]) == (4, 32, 10, 10, 100)
    assert cfg["expect"] == {"grower": "rounds", "hist_dtype": "int16",
                             "devices": 1}
    assert cfg["reference"] == "cat_audit" and cfg["reduced"] == [
        "num_boost_round"]
    q = cfg["quality"]
    for k in ("root_gain_share", "tree2_gain_share", "auc_band"):
        assert len(q[f"{k}_why"]) > 80  # every floor with its reason
    assert len(q["ref_auc"]) >= 2


def test_benchmark_json_lists_the_cell_and_its_two_metrics():
    by = {p["name"]: p for p in MANIFEST["per_layer"]}
    for name in NEW:
        r = load_plugin(ROOT, "layer_metrics", name)
        assert by[name] == {
            "name": name, "unit": r.UNIT, "better": r.BETTER,
            "source": r.SOURCE, "layer": r.LAYER, "moves": r.MOVES,
            "workloads": [CELL]}
    listed = {p["name"] for p in resolve_cell(ROOT, CELL).per_layer}
    assert {"learner.split_search_ms_per_tree", "learner.route_ms_per_tree",
            "metrics.valid_eval_ms_per_tree", "learner.hist_ms_per_tree",
            "dataset.bins_push_gb", *NEW} <= listed
    assert not {n for n in listed if n.startswith(
        ("collective.", "parallel.", "objective.rank", "metrics.rank",
         "learner.hist_blocked"))}


@pytest.fixture
def expo_root(tmp_path):
    """The benchmark with the tiny cut of the configuration added, as
    ``conftest.bench_root`` adds its own."""
    shutil.copytree(ROOT / "benchmark", tmp_path / "benchmark",
                    ignore=shutil.ignore_patterns("tests", "__pycache__"))
    addons = ROOT / "benchmark" / "tests" / "addons"
    for f in addons.rglob("*"):
        if f.is_file():
            shutil.copy(f, tmp_path / "benchmark" / f.relative_to(addons))
    m = json.loads(json.dumps(MANIFEST))
    m["configs"].append({"name": "tiny-expo", "source": "benchmark/tests",
                         "file": "benchmark/configs/tiny-expo.json",
                         "reduced": [], "why": "CPU rehearsal"})
    m["workloads"].append({"name": "tiny-expo.train", "config": "tiny-expo",
                           "traffic": "tiny-jobs", "chips": 1,
                           "why": "CPU rehearsal"})
    for e in m["end_to_end"] + m["per_layer"]:
        if CELL in e.get("workloads", ()):
            e["workloads"].append("tiny-expo.train")
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(m))
    return tmp_path


@pytest.mark.parametrize("trace", [False, True], ids=["untraced", "traced"])
def test_tiny_cell_runs_and_the_audit_accepts_it(expo_root, trace):
    r = cellrun.run_cell(
        expo_root, "tiny-expo.train",
        cellrun.RunArgs(seed=1, seconds=1.0, trace=trace,
                        t_process=time.perf_counter()), device=None)
    assert r["correct"] and r["failed"] == 0 and r["attempted"] >= 8
    m = r["metrics"]
    if not trace:
        assert m["train_trees_per_s"]["value"] > 0 and "setup_s" in m
        return
    assert m["compile.in_window"]["value"] == 0
    assert m["engine.cache_loads_per_job"]["value"] == 0
    assert 0 < m["learner.cat_split_share"]["value"] <= 100
    # 305 Zipf codes over 20,480 rows: the rare tail of two columns
    assert 0 < m["dataset.cat_other_row_share"]["value"] < 5
    from lightgbm_tpu.obs.metrics import default_registry

    g = default_registry().snapshot()["lgbmtpu_split_search_directions"]
    assert [int(g['{kind="%s"}' % k]) for k in (
        "default_right", "default_left", "categorical", "cat_subset",
        "monotone_test")] == [1, 1, 1, 1, 0]


# ------------------------------------------------------- planted faults
@pytest.fixture(scope="module")
def trained():
    import lightgbm_tpu as lgb

    cfg = json.loads((ROOT / "benchmark/tests/addons/configs/tiny-expo.json"
                      ).read_text())
    gen = load_plugin(ROOT, "datasets", "synthetic_expo")
    X, y, Xv, yv = gen.make(2, 20480, 4096, 8)

    def train(params):
        ds = lgb.Dataset(X, label=y, params=dict(params),
                         free_raw_data=False).construct()
        vs = lgb.Dataset(Xv, label=yv, reference=ds).construct()
        ev = {}
        bst = lgb.train(dict(params), ds, num_boost_round=4,
                        valid_sets=[vs], valid_names=["valid"],
                        callbacks=[lgb.record_evaluation(ev)])
        return bst.model_to_string(), ds._binned.bins, ev["valid"]["auc"]

    audit = load_plugin(ROOT, "references", "cat_audit")

    def verdict(text, bins, aucs, params=cfg["params"]):
        return audit.audit(text, X, y, Xv, yv, bins, aucs, params,
                           cfg["quality"], 2)

    return SimpleNamespace(cfg=cfg, train=train, verdict=verdict,
                           audit=audit, honest=train(cfg["params"]))


def _edit_tree(text, k, key, edit):
    """The model text with field ``key`` of tree ``k`` rewritten."""
    head, *blocks = text.split("\nTree=")
    lines = blocks[k].split("\n")
    i = next(i for i, ln in enumerate(lines) if ln.startswith(key + "="))
    vals = lines[i].split("=", 1)[1].split(" ")
    lines[i] = key + "=" + " ".join(edit(vals))
    blocks[k] = "\n".join(lines)
    return "\nTree=".join([head] + blocks)


def test_the_audit_accepts_the_honest_model(trained):
    v = trained.verdict(*trained.honest)
    assert v["problems"] == []
    f = v["facts"]
    assert f["categorical_columns"] == [0, 1, 2, 4, 5, 6]
    assert f["splits_by_kind_trees_1_2"]["cat_subset"] > 0
    assert f["nan_type_numerical_splits_trees_1_2"] > 0
    assert f["tree1_leaf_count_mismatches"] == 0
    assert f["tree2_leaf_count_mismatches"] == 0


def test_one_flipped_bit_of_a_category_set_is_refused(trained):
    text, bins, aucs = trained.honest
    _, trees = trained.audit.parse(text)
    # a category of the root's column that many rows hold
    def flip(vals):
        vals[0] = str(int(vals[0]) ^ 1)  # category 0 changes sides
        return vals

    v = trained.verdict(_edit_tree(text, 0, "cat_threshold", flip), bins,
                        aucs)
    assert any("leaf counts differ" in p for p in v["problems"])
    assert trees[0].cat_threshold.size  # there was a set to flip


def test_a_flipped_default_direction_on_the_nan_column_is_refused(trained):
    text, bins, aucs = trained.honest
    _, trees = trained.audit.parse(text)
    k, node = next((k, int(i)) for k, t in enumerate(trees[:2])
                   for i in np.flatnonzero(
                       (t.split_feature == 3) & ((t.decision_type & 1) == 0)))

    def flip(vals):
        vals[node] = str(int(vals[node]) ^ 2)
        return vals

    v = trained.verdict(_edit_tree(text, k, "decision_type", flip), bins,
                        aucs)
    assert any("leaf counts differ" in p for p in v["problems"])


def test_a_model_trained_with_the_parameter_ignored_is_refused(trained):
    """What the parent of PR 36 does with this configuration: the
    Dataset does not read ``categorical_feature`` from its parameters and
    bins every column as numerical."""
    ignored = {k: v for k, v in trained.cfg["params"].items()
               if k != "categorical_feature"}
    v = trained.verdict(*trained.train(ignored))
    said = " | ".join(v["problems"])
    assert "binned as numerical" in said
    assert "no sorted-subset split" in said
    assert v["facts"]["categorical_columns"] == []


# ------------------------------------------------------------- readers
def test_the_two_readers_against_hand_numbers(monkeypatch):
    from lightgbm_tpu.obs import metrics

    fresh = metrics.MetricsRegistry(enabled=True)
    monkeypatch.setattr(metrics, "_default", fresh)
    share = load_plugin(ROOT, "layer_metrics", NEW[0])
    other = load_plugin(ROOT, "layer_metrics", NEW[1])
    inp = SimpleNamespace(rec=SimpleNamespace(obs={"rows": 1000}))
    # a program without the counters (the parent): nothing, no error
    assert share.read(inp) is None and other.read(inp) is None
    c = fresh.counter("lgbmtpu_tree_splits_total", "", labels=("kind",))
    for kind, n in (("numerical", 30), ("default_left", 10),
                    ("cat_onehot", 4), ("cat_subset", 36)):
        c.inc(n, kind=kind)
    assert share.read(inp) == pytest.approx(100 * 40 / 80)
    fresh.gauge("lgbmtpu_dataset_cat_other_rows").set(87)
    g = fresh.gauge("lgbmtpu_dataset_columns", "", labels=("kind",))
    g.set(2, kind="numerical")
    g.set(0, kind="categorical")
    assert other.read(inp) is None  # no categorical column
    g.set(6, kind="categorical")
    assert other.read(inp) == pytest.approx(100 * 87 / 6000)
    assert other.read(SimpleNamespace(rec=SimpleNamespace(obs={}))) is None
