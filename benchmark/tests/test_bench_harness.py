"""The harness is driven by data: a configuration, traffic mixes and
per-layer metrics added purely as new files (benchmark/tests/addons)
run through it; BENCHMARK.json keeps to its contract and agrees with
the files it names."""

import json
import re
import time

import pytest

from benchmark.harness import cellrun
from benchmark.harness.manifest import (ManifestError, load_plugin,
                                        repo_root, resolve_cell)

MANIFEST = json.loads((repo_root() / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9][A-Za-z0-9_.-]{0,63}$")


def args(trace=False, seconds=3.0, seed=1):
    return cellrun.RunArgs(seed=seed, seconds=seconds, trace=trace,
                           t_process=time.perf_counter())


def test_added_train_cell_runs_and_is_correct(bench_root):
    r = cellrun.run_cell(bench_root, "tiny.train", args(), None)
    assert r["correct"] and r["failed"] == 0
    assert r["attempted"] >= 8 and r["attempted"] % 4 == 0
    assert set(r["metrics"]) == {"train_trees_per_s", "setup_s"}
    assert r["metrics"]["train_trees_per_s"]["unit"] == "trees/s"
    assert r["metrics"]["setup_s"]["value"] > 0


def test_added_layer_metric_is_read_and_an_empty_reader_left_out(
        bench_root):
    r = cellrun.run_cell(bench_root, "tiny.train", args(trace=True), None)
    assert r["correct"]
    m = r["metrics"]
    assert m["dummy.jobs"] == {"value": 2.0, "unit": "jobs"}
    assert "dummy.absent" not in m
    assert m["compile.in_window"]["value"] == 0
    assert m["engine.cache_loads_per_job"]["value"] == 0  # the step memo
    assert m["engine.dispatches_per_tree"]["value"] == 0.25
    assert m["dataset.construct_s"]["value"] > 0
    # a CPU trace has no device plane: no device metric is printed
    assert not any(k.startswith(("learner.", "boosting.")) for k in m)
    assert "busy_s" not in r["device"]


def test_four_device_rehearsal_of_the_data_parallel_path(bench_root):
    m = json.loads((bench_root / "BENCHMARK.json").read_text())
    m["configs"].append({"name": "tiny-dp4", "source": "benchmark/tests",
                         "file": "benchmark/configs/tiny-dp4.json",
                         "reduced": [], "why": "CPU rehearsal"})
    m["workloads"].append({"name": "tiny-dp4.train", "config": "tiny-dp4",
                           "traffic": "tiny-jobs", "chips": 4,
                           "why": "CPU rehearsal"})
    for e in m["end_to_end"] + m["per_layer"]:
        if "tiny.train" in e.get("workloads", ()):
            e["workloads"].append("tiny-dp4.train")
    (bench_root / "BENCHMARK.json").write_text(json.dumps(m))
    r = cellrun.run_cell(bench_root, "tiny-dp4.train", args(seed=3), None)
    assert r["correct"], "bin shards must sit on four distinct devices"
    r = cellrun.run_cell(bench_root, "tiny-dp4.train",
                         args(seed=3, trace=True), None)
    assert r["correct"]
    assert r["metrics"]["collective.wire_mb_per_tree"]["value"] > 0
    # a data-parallel Booster never memoizes its fused step: every job
    # traces it again and loads the executable from the persistent cache
    # (a cache LOAD, which is not a compile: the run stays correct)
    assert r["metrics"]["compile.in_window"]["value"] == 0
    assert r["metrics"]["engine.cache_loads_per_job"]["value"] >= 1


def test_unknown_names_are_errors(bench_root):
    with pytest.raises(ManifestError):
        resolve_cell(bench_root, "no.such.cell")
    with pytest.raises(ManifestError):
        load_plugin(bench_root, "layer_metrics", "no.such.metric")


# ---------------------------------------------------------- the contract
def test_manifest_keys_names_and_limits():
    assert set(MANIFEST) == {"command", "paths", "run_seconds", "configs",
                             "workloads", "end_to_end", "per_layer"}
    assert MANIFEST["paths"] == ["benchmark"]
    assert MANIFEST["command"] == ["python3", "benchmark/run.py"]
    assert 1 <= MANIFEST["run_seconds"] <= 51
    names = [x["name"] for k in ("configs", "workloads", "end_to_end",
                                 "per_layer") for x in MANIFEST[k]]
    assert len(names) == len(set(names))
    assert all(NAME.match(n) for n in names)
    assert all(len(x["why"]) <= 200
               for k in ("configs", "workloads") for x in MANIFEST[k])
    assert (repo_root() / "BENCHMARK.json").stat().st_size <= 64 * 1024
    four = [w for w in MANIFEST["workloads"] if w["chips"] == 4]
    assert len(four) <= max(1, len(MANIFEST["workloads"]) // 4)
    pairs = [(w["config"], w["traffic"]) for w in MANIFEST["workloads"]]
    assert len(pairs) == len(set(pairs))


def test_end_to_end_metrics_keep_to_the_contract():
    e2e = {e["name"]: e for e in MANIFEST["end_to_end"]}
    assert e2e["setup_s"]["bound"] == 0.1
    for e in e2e.values():
        assert 0.01 <= e["bound"] <= 0.1
        assert e["source"] in ("host_clock", "device_trace")
    for w in MANIFEST["workloads"]:
        cell = resolve_cell(repo_root(), w["name"])
        got = {e["name"] for e in cell.end_to_end}
        assert "setup_s" in got and len(got) >= 2
        assert cell.per_layer
        # a per-layer metric is reported only where the metric it moves is
        assert all(p["moves"] in got for p in cell.per_layer)


def test_every_named_file_exists_and_layer_files_agree():
    root = repo_root()
    for c in MANIFEST["configs"]:
        cfg = json.loads((root / c["file"]).read_text())
        assert set(c["reduced"]) == set(cfg["reduced"])
        load_plugin(root, "datasets", cfg["dataset"]["generator"])
        load_plugin(root, "references", cfg["reference"])
    for w in MANIFEST["workloads"]:
        cell = resolve_cell(root, w["name"])
        load_plugin(root, "drivers", cell.traffic["driver"])
    for p in MANIFEST["per_layer"]:
        mod = load_plugin(root, "layer_metrics", p["name"])
        assert (mod.LAYER, mod.MOVES, mod.SOURCE, mod.UNIT, mod.BETTER) \
            == (p["layer"], p["moves"], p["source"], p["unit"],
                p["better"]), p["name"]
        assert mod.__doc__ and callable(mod.read)
    on_disk = {f.stem for f in (root / "benchmark" / "layer_metrics"
                                ).glob("*.py")}
    # the collective readers wait on disk for the four-chip cell
    assert {p["name"] for p in MANIFEST["per_layer"]} <= on_disk
