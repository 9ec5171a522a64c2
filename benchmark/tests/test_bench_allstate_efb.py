"""The sparse one-hot cell (PR 38): ``allstate-efb`` resolves at the
source's shape and agrees with BENCHMARK.json and the reader files; a
20k-row cut runs through ``sparse_jobs`` on the CPU (the chip's default
program, kernels interpreted) and ``efb_audit`` calls it correct; the
capability check refuses a program without the two gauges; the audit
refuses planted faults."""

import json
import shutil
import time
from types import SimpleNamespace

import numpy as np
import pytest

from benchmark.harness import cellrun
from benchmark.harness.manifest import load_plugin, repo_root, resolve_cell

ROOT = repo_root()
MANIFEST = json.loads((ROOT / "BENCHMARK.json").read_text())
CELL = "allstate-efb.train"
NEW = ("learner.unbundle_ms_per_tree", "learner.bundled_split_share",
       "dataset.bundle_s")
TINY = "benchmark/tests/addons/configs/tiny-allstate.json"


def test_the_configuration_resolves_at_the_sources_shape():
    cell = resolve_cell(ROOT, CELL)
    cfg = cell.config
    assert cell.chips == 1 and cell.traffic_name == "sparse-jobs-8"
    assert cell.traffic["driver"] == "sparse_jobs"
    assert {k: cell.traffic[k] for k in (
        "rounds_per_job", "warmup_jobs", "min_jobs", "trace_jobs")} == {
        "rounds_per_job": 8, "warmup_jobs": 1, "min_jobs": 2,
        "trace_jobs": 2}
    d = cfg["dataset"]
    assert (d["generator"], d["features"], d["valid_rows"]) == (
        "synthetic_allstate", 4228, 2 ** 20)
    assert d["rows"] >= 12_184_290
    p = cfg["params"]
    assert (p["num_leaves"], p["max_bin"], p["learning_rate"]) == (
        255, 255, 0.1)
    assert (p["min_data_in_leaf"], p["min_sum_hessian_in_leaf"]) == (0, 100)
    assert p["enable_bundle"] is True and p["objective"] == "binary"
    assert cfg["expect"] == {"grower": "rounds", "hist_dtype": "int16",
                             "devices": 1}
    assert cfg["reference"] == "efb_audit" and cfg["reduced"] == [
        "num_boost_round"]
    gen = load_plugin(ROOT, "datasets", "synthetic_allstate")
    assert gen.FEATURES == 4228 and gen.NNZ_PER_ROW == 31


def test_benchmark_json_lists_the_cell_and_its_three_metrics():
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
            "dataset.construct_s", "dataset.bins_push_gb",
            "compile.in_window", "engine.cache_loads_per_job", *NEW
            } <= listed
    # the 2-D shape reader counts `features` where the kernels see the
    # bundle columns (PERF.md section 7)
    assert not {n for n in listed if n.startswith(
        ("collective.", "parallel.", "objective.rank", "metrics.rank",
         "learner.hist_blocked", "learner.hist_round_", "learner.cat_",
         "dataset.cat_"))}
    e2e = {e["name"]: e for e in MANIFEST["end_to_end"]}
    assert CELL in e2e["train_trees_per_s"]["workloads"]


def test_generator_is_one_hot_and_seeded():
    gen = load_plugin(ROOT, "datasets", "synthetic_allstate")
    X, y, Xv, yv = gen.make(2 ** 31 + 11, 3000, 500, 4228)
    X2, y2, Xv2, _ = gen.make(2 ** 31 + 11, 3000, 500, 4228)
    assert X.shape == (3000, 4228) and Xv.shape == (500, 4228)
    assert X.nnz == 31 * 3000 and (X != X2).nnz == 0 and (Xv != Xv2).nnz == 0
    # one training table whatever the seed (the same work in every run),
    # the seed's own valid rows
    X3, y3, Xv3, _ = gen.make(2 ** 31 + 12, 3000, 500, 4228)
    assert (X != X3).nnz == 0 and np.array_equal(y, y3)
    assert (Xv != Xv3).nnz > 0
    onehot = load_plugin(ROOT, "references", "efb_audit").onehot_columns(
        X.tocsc())
    assert onehot.sum() == 4216  # the 12 numerical columns are not
    assert 0.002 < y.mean() < 0.05


@pytest.fixture
def efb_root(tmp_path):
    """The benchmark with the tiny cut of the configuration added, as
    ``conftest.bench_root`` adds its own."""
    shutil.copytree(ROOT / "benchmark", tmp_path / "benchmark",
                    ignore=shutil.ignore_patterns("tests", "__pycache__"))
    addons = ROOT / "benchmark" / "tests" / "addons"
    for f in addons.rglob("*"):
        if f.is_file():
            shutil.copy(f, tmp_path / "benchmark" / f.relative_to(addons))
    m = json.loads(json.dumps(MANIFEST))
    m["configs"].append({"name": "tiny-allstate",
                         "source": "benchmark/tests",
                         "file": "benchmark/configs/tiny-allstate.json",
                         "reduced": [], "why": "CPU rehearsal"})
    m["workloads"].append({"name": "tiny-allstate.train",
                           "config": "tiny-allstate",
                           "traffic": "tiny-sparse-jobs", "chips": 1,
                           "why": "CPU rehearsal"})
    for e in m["end_to_end"] + m["per_layer"]:
        if CELL in e.get("workloads", ()):
            e["workloads"].append("tiny-allstate.train")
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(m))
    return tmp_path


def _with_ref_auc(root):
    """The tiny configuration with a reference AUC: the audit's band
    needs one, and a CPU fixture cannot have a chip's."""
    p = root / "benchmark" / "configs" / "tiny-allstate.json"
    cfg = json.loads(p.read_text())
    cfg["quality"]["ref_auc"] = {"1": [0.5, 0.5, 0.5, 0.5]}
    cfg["quality"]["auc_band"] = 0.5
    p.write_text(json.dumps(cfg))


@pytest.mark.parametrize("trace", [False, True], ids=["untraced", "traced"])
def test_tiny_cell_runs_and_the_audit_accepts_it(efb_root, trace):
    _with_ref_auc(efb_root)
    r = cellrun.run_cell(
        efb_root, "tiny-allstate.train",
        cellrun.RunArgs(seed=1, seconds=1.0, trace=trace,
                        t_process=time.perf_counter()), device=None)
    assert r["correct"] and r["failed"] == 0 and r["attempted"] >= 8
    m = r["metrics"]
    if not trace:
        assert m["train_trees_per_s"]["value"] > 0 and "setup_s" in m
        return
    assert m["compile.in_window"]["value"] == 0
    assert m["engine.cache_loads_per_job"]["value"] == 0
    assert 0 < m["learner.bundled_split_share"]["value"] <= 100
    assert 0 < m["dataset.bundle_s"]["value"] < 60
    from lightgbm_tpu.obs.metrics import default_registry

    snap = default_registry().snapshot()
    assert snap["lgbmtpu_split_bundle_layout"][""] == 1
    assert snap["lgbmtpu_dataset_bundle_rows"][""] == 16384
    cols = snap["lgbmtpu_dataset_columns"]
    assert cols['{kind="merged_feature"}'] > 3000
    assert 0 < cols['{kind="bundle"}'] <= 64


def test_the_check_refuses_a_program_without_the_two_gauges(monkeypatch):
    """A program that states neither fact (the parent of PR 38) ends in
    the check with a non-zero exit; so does one that groups on every
    row."""
    import lightgbm_tpu as lgb
    from lightgbm_tpu.obs import metrics

    drv = load_plugin(ROOT, "drivers", "sparse_jobs")
    gen = load_plugin(ROOT, "datasets", "synthetic_allstate")
    p = dict(json.loads((ROOT / TINY).read_text())["params"])
    real_search = metrics.record_split_search
    monkeypatch.setattr(metrics, "record_split_search",
                        lambda dirs, bundle_layout=False: real_search(dirs))
    metrics.default_registry().reset()
    with pytest.raises(drv.CannotRunCell, match="bundle layout"):
        drv.require_bundle_program(lgb, gen, 4228, p)
    monkeypatch.setattr(metrics, "record_split_search", real_search)
    monkeypatch.setattr(metrics, "record_dataset_bundle",
                        lambda seconds, rows=None: None)
    metrics.default_registry().reset()
    with pytest.raises(drv.CannotRunCell, match="sample"):
        drv.require_bundle_program(lgb, gen, 4228, p)


# ------------------------------------------------------- planted faults
@pytest.fixture(scope="module")
def trained():
    import lightgbm_tpu as lgb

    cfg = json.loads((ROOT / TINY).read_text())
    gen = load_plugin(ROOT, "datasets", "synthetic_allstate")
    # at 20k rows the valid AUC over 4 rounds is noise (about 70
    # positives): seed 1's valid rows are ones on which it rises
    X, y, Xv, yv = gen.make(1, 20480, 4096, 4228)

    def train(params):
        ds = lgb.Dataset(X, label=y, params=dict(params),
                         free_raw_data=False).construct()
        vs = lgb.Dataset(Xv, label=yv, reference=ds).construct()
        ev = {}
        bst = lgb.train(dict(params), ds, num_boost_round=4,
                        valid_sets=[vs], valid_names=["valid"],
                        callbacks=[lgb.record_evaluation(ev)])
        return bst.model_to_string(), ds._binned.bins, ev["valid"]["auc"]

    audit = load_plugin(ROOT, "references", "efb_audit")
    quality = dict(cfg["quality"], ref_auc={"1": [0.5] * 4}, auc_band=0.5)

    def verdict(text, bins, aucs):
        return audit.audit(text, X, y, Xv, yv, bins, aucs, cfg["params"],
                           quality, 1)

    return SimpleNamespace(train=train, verdict=verdict, X=X,
                           honest=train(cfg["params"]))


def _edit_tree(text, k, key, edit):
    """The model text with field ``key`` of tree ``k`` rewritten."""
    head, *blocks = text.split("\nTree=")
    lines = blocks[k].split("\n")
    i = next(i for i, ln in enumerate(lines) if ln.startswith(key + "="))
    vals = lines[i].split("=", 1)[1].split(" ")
    lines[i] = key + "=" + " ".join(edit(vals))
    blocks[k] = "\n".join(lines)
    return "\nTree=".join([head] + blocks)


def test_the_honest_model_passes(trained):
    v = trained.verdict(*trained.honest)
    assert not v["problems"], v["problems"]
    f = v["facts"]
    assert f["merged_features"] > 3000 and f["device_columns"] <= 64
    assert f["trees12_splits_on_merged_members"] > 0
    assert f["tree1_leaf_count_mismatches"] == 0
    assert f["tree2_leaf_count_mismatches"] == 0


@pytest.mark.parametrize("fault", ["leaf_count", "threshold", "bins"])
def test_the_audit_refuses_a_planted_fault(trained, fault):
    text, bins, aucs = trained.honest
    if fault == "leaf_count":
        text = _edit_tree(text, 1, "leaf_count",
                          lambda v: [str(int(v[0]) + 1)] + v[1:])
        want = "leaf counts differ"
    elif fault == "threshold":
        # send every row of tree 1's root one way: the counts break
        text = _edit_tree(text, 0, "threshold",
                          lambda v: ["1e30"] + v[1:])
        want = "leaf counts differ"
    else:
        # a split one-hot column loses 20 of its rows in its bundle with
        # no member written after it there
        audit = load_plugin(ROOT, "references", "efb_audit")
        bins = bins.copy()
        _, trees = __import__("benchmark.harness.modeltext",
                              fromlist=["parse"]).parse(text)
        f = next(int(f) for f, t in zip(trees[0].split_feature,
                                         trees[0].threshold) if t < 1e-30)
        rows = trained.X.tocsc()[:, f].indices
        g, _ = audit.locate(rows, bins, audit.value_counts(bins))
        bins[g, rows[:20]] = 0
        want = "bundle rule"
    v = trained.verdict(text, bins, aucs)
    assert any(__import__("re").search(want, p) for p in v["problems"]), \
        v["problems"]
