"""benchmark/tests run on the CPU (not part of tier-1 ``tests/``):

    python -m pytest benchmark/tests -q

Pallas kernels run under the interpreter and four virtual devices stand
in for the four-chip host; nothing here reports a device metric."""

import json
import os
import shutil
import sys
from pathlib import Path

os.environ.setdefault("JAX_PLATFORMS", "cpu")
os.environ.setdefault("LGBM_TPU_PALLAS_INTERPRET", "1")
os.environ.setdefault(
    "XLA_FLAGS", "--xla_force_host_platform_device_count=4")

REPO = Path(__file__).resolve().parents[2]
if str(REPO) not in sys.path:
    sys.path.insert(0, str(REPO))

import pytest  # noqa: E402

ADDONS = Path(__file__).with_name("addons")


@pytest.fixture
def bench_root(tmp_path):
    """A copy of the benchmark with the add-on files of
    ``benchmark/tests/addons`` dropped in as NEW files and their names
    ADDED to a copy of BENCHMARK.json: what a later PR does to bring a
    configuration, a traffic mix and per-layer metrics, with no edit to
    a file that is there."""
    shutil.copytree(REPO / "benchmark", tmp_path / "benchmark",
                    ignore=shutil.ignore_patterns("tests", "__pycache__"))
    for f in ADDONS.rglob("*"):
        if f.is_file():
            dst = tmp_path / "benchmark" / f.relative_to(ADDONS)
            assert not dst.exists(), f"{dst} would overwrite a file"
            shutil.copy(f, dst)
    m = json.loads((REPO / "BENCHMARK.json").read_text())
    m["configs"].append({"name": "tiny", "source": "benchmark/tests",
                         "file": "benchmark/configs/tiny.json",
                         "reduced": [], "why": "CPU rehearsal"})
    m["workloads"].append({"name": "tiny.train", "config": "tiny",
                           "traffic": "tiny-jobs", "chips": 1,
                           "why": "CPU rehearsal"})
    for e in m["end_to_end"] + m["per_layer"]:
        if "higgs.train" in e.get("workloads", ()):
            e["workloads"].append("tiny.train")
    # readers on disk that BENCHMARK.json does not list (the add-ons, and
    # the collective readers that wait for the four-chip cell) are named
    # here, again as data
    from benchmark.harness.manifest import load_plugin

    listed = {p["name"] for p in m["per_layer"]}
    for f in sorted((tmp_path / "benchmark" / "layer_metrics").glob("*.py")):
        if f.stem in listed:
            continue
        r = load_plugin(tmp_path, "layer_metrics", f.stem)
        m["per_layer"].append({
            "name": f.stem, "unit": r.UNIT, "better": r.BETTER,
            "source": r.SOURCE, "layer": r.LAYER, "moves": r.MOVES,
            "workloads": ["tiny.train"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(m))
    return tmp_path
