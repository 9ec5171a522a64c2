"""The four-chip cell (PR 32): ``higgs-dp4`` resolves and agrees with
BENCHMARK.json and the reader files; on four virtual devices the tiny
data-parallel configuration runs through ``train_jobs`` with the step
memo holding and ONE copy of the bins pushed; the new reader returns a
number there and nothing from a program without the counter."""

import json
import time

from benchmark.harness import cellrun
from benchmark.harness.manifest import load_plugin, repo_root, resolve_cell

ROOT = repo_root()
MANIFEST = json.loads((ROOT / "BENCHMARK.json").read_text())
CELL = "higgs-dp4.train"
PUSH = "lgbmtpu_dataset_push_bytes_total"


def args(trace=False, seed=3):
    return cellrun.RunArgs(seed=seed, seconds=3.0, trace=trace,
                           t_process=time.perf_counter())


def test_the_configuration_resolves_at_its_published_widths():
    cell = resolve_cell(ROOT, CELL)
    higgs = resolve_cell(ROOT, "higgs.train").config
    cfg = cell.config
    assert cell.chips == 4 and cell.traffic_name == "train-jobs-8"
    assert cfg["params"] == dict(higgs["params"], tree_learner="data")
    assert cfg["expect"] == {"grower": "rounds", "hist_dtype": "int16",
                             "devices": 4}
    assert cfg["dataset"]["features"] == 28
    assert cfg["dataset"]["valid_rows"] == 1048576
    # whole Pallas row blocks a chip, and the sizing rule's grain
    rows = cfg["dataset"]["rows"]
    assert rows % (4 * 2 ** 19) == 0 and rows // 4 == 13 * 2 ** 20
    for k in ("root_gain_share_min", "tree2_gain_share_min", "auc_band"):
        assert cfg["quality"][k] == higgs["quality"][k]
    assert cfg["reference"] == "tree_audit" and cfg["reduced"] == [
        "num_boost_round"]
    # the f32-channel reference AUC is recorded for whole 8-round jobs
    assert cfg["quality"]["ref_auc"] and all(
        len(v) >= 8 for v in cfg["quality"]["ref_auc"].values())


def test_the_manifest_and_the_reader_files_agree():
    four_chip = [w["name"] for w in MANIFEST["workloads"]
                 if w["chips"] == 4]
    assert four_chip == [CELL]  # 1 of 5: inside the 25% rule
    listed = {p["name"]: p for p in MANIFEST["per_layer"]
              if CELL in p.get("workloads", ())}
    for name in ("collective.ms_per_tree", "collective.exposed_share",
                 "collective.wire_mb_per_tree", "dataset.bins_push_gb"):
        r = load_plugin(ROOT, "layer_metrics", name)
        spec = listed[name]
        assert (spec["unit"], spec["better"], spec["source"],
                spec["layer"], spec["moves"]) == (
            r.UNIT, r.BETTER, r.SOURCE, r.LAYER, r.MOVES), name
    assert listed["collective.ms_per_tree"]["workloads"] == [CELL]
    # the metrics every accepted cell shares, and the two shares of the
    # kernel that does the work, are reported here too
    for name in ("compile.in_window", "engine.cache_loads_per_job",
                 "engine.dispatches_per_tree", "boosting.device_ms_per_tree",
                 "learner.hist_ms_per_tree", "learner.hist_round_mxu_share",
                 "learner.hist_round_roofline"):
        assert name in listed, name
    e2e = {e["name"]: e for e in MANIFEST["end_to_end"]}
    assert CELL in e2e["train_trees_per_s"]["workloads"]


def _with_tiny_dp4(bench_root):
    m = json.loads((bench_root / "BENCHMARK.json").read_text())
    m["configs"].append({"name": "tiny-dp4", "source": "benchmark/tests",
                         "file": "benchmark/configs/tiny-dp4.json",
                         "reduced": [], "why": "CPU rehearsal"})
    m["workloads"].append({"name": "tiny-dp4.train", "config": "tiny-dp4",
                           "traffic": "tiny-jobs", "chips": 4,
                           "why": "CPU rehearsal"})
    for e in m["end_to_end"] + m["per_layer"]:
        if CELL in e.get("workloads", ()):
            e["workloads"].append("tiny-dp4.train")
    (bench_root / "BENCHMARK.json").write_text(json.dumps(m))


def test_four_device_rehearsal_memoizes_and_pushes_one_copy(bench_root):
    from lightgbm_tpu.obs.metrics import default_registry

    def pushed():
        return default_registry().snapshot().get(PUSH, {}).get(
            '{kind="bins"}', 0.0)

    _with_tiny_dp4(bench_root)
    before = pushed()
    r = cellrun.run_cell(bench_root, "tiny-dp4.train", args(trace=True),
                         None)
    assert r["correct"], "bin shards must sit on four distinct devices"
    m = r["metrics"]
    assert m["compile.in_window"]["value"] == 0
    # the data-parallel step is memoized: a window job traces, lowers
    # and loads nothing
    assert m["engine.cache_loads_per_job"]["value"] == 0
    assert m["engine.dispatches_per_tree"]["value"] == 0.25
    assert m["collective.wire_mb_per_tree"]["value"] > 0
    # one sharded copy of the 28 x 32,768 train bins and one replicated
    # copy (x 4 devices) of the 28 x 4,096 valid bins, int32, whatever
    # the number of jobs
    one_copy = 28 * 4 * (32768 + 4 * 4096)
    assert pushed() - before == one_copy
    assert m["dataset.bins_push_gb"]["value"] * 1e9 >= one_copy


def test_the_reader_returns_nothing_without_the_counter(monkeypatch):
    from lightgbm_tpu.obs import metrics

    reader = load_plugin(ROOT, "layer_metrics", "dataset.bins_push_gb")
    # a program that never counted a push (the parent commit): no value,
    # no exception
    monkeypatch.setattr(metrics, "_default", metrics.MetricsRegistry())
    assert reader.read(None) is None
    metrics.record_dataset_push("rows", 4096)
    assert reader.read(None) == 0.0
    metrics.record_dataset_push("bins", 2_500_000_000)
    assert reader.read(None) == 2.5
