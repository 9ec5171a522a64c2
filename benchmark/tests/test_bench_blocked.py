"""The readers a wide table brought (PR 30): issued multiply-adds from
the program's own gauges and counters (rooflines/hist_blocked.py)
against hand numbers, the four per-layer readers on a recorded and on a
synthetic trace, and a configuration whose 600 columns span two feature
blocks run end to end by ``train_jobs`` under the interpreter."""

import json
import time

import pytest

from benchmark.harness import cellrun, device
from benchmark.harness.manifest import load_plugin, repo_root
from benchmark.harness.trace import DevicePlane, Event, TraceView

blocked = load_plugin(repo_root(), "rooflines", "hist_blocked")
roof = load_plugin(repo_root(), "rooflines", "hist_round")
V5E = device.peaks_for("TPU v5 lite")

# the registry of a process that trained 24 trees of the wide cell's
# schedule (1, 2, 4, 8 candidates at 8 slots, 16, 32, 3 x 48, then the
# routing-only round), six feature blocks of 352 columns
WIDE = {
    "lgbmtpu_hist_feature_blocks": {
        '{kernel="hist_nat_tpu"}': 6, '{kernel="hist_round_tpu"}': 0,
        '{kernel="route_round_tpu"}': 1},
    "lgbmtpu_hist_block_columns": {
        '{kernel="hist_nat_tpu"}': 352, '{kernel="hist_round_tpu"}': 0,
        '{kernel="route_round_tpu"}': 48},
    "lgbmtpu_hist_calls_per_pass": {
        '{width="root"}': 1, '{width="8"}': 1, '{width="16"}': 1,
        '{width="32"}': 1, '{width="48"}': 1},
    "lgbmtpu_grower_rounds_total": {
        '{width="8"}': 96, '{width="16"}': 24, '{width="32"}': 24,
        '{width="48"}': 72, '{width="route"}': 24},
    "lgbmtpu_train_trees_total": {"": 24},
}


def test_issued_flops_of_one_wide_tree_by_hand():
    s = blocked.program_schedule(WIDE)
    assert s["columns"]["hist_nat_tpu"] == 2112  # what the kernel multiplies
    assert s["rounds_per_tree"] == {"8": 4, "16": 1, "32": 1, "48": 3,
                                    "route": 1}
    # 1 + 4 x 8 + 16 + 32 + 3 x 48 slot-passes of 2,112 columns
    assert blocked.slot_columns_per_tree(s) == 225 * 2112
    flops = blocked.issued_flops_per_tree(s, 400_000, 63, 3)
    assert flops == 2 * 225 * 3 * 400_000 * 2112 * 63
    assert flops == pytest.approx(7.185e13, rel=1e-3)
    assert flops / V5E["bf16_flops"] == pytest.approx(0.365, rel=5e-3)
    # one stream: 400,000 rows x (2,000 x 4 + 12) B at 819 GB/s
    floor, bound = roof.floor_seconds(400_000, 2000, V5E, "int16")
    assert bound == "hbm" and floor == pytest.approx(3.913e-3, rel=1e-3)


def test_chunked_whole_table_program_by_hand():
    """The rank cell's shape: 137 columns in 5 loop groups of 28 (140
    multiplied), the fused kernel, its 32- and 48-slot passes in two
    calls of 16 and of 24 slots."""
    snap = json.loads(json.dumps(WIDE))
    snap["lgbmtpu_hist_feature_blocks"] = {
        '{kernel="hist_nat_tpu"}': 1, '{kernel="hist_round_tpu"}': 1,
        '{kernel="route_round_tpu"}': 1}
    snap["lgbmtpu_hist_block_columns"] = {
        '{kernel="hist_nat_tpu"}': 140, '{kernel="hist_round_tpu"}': 140,
        '{kernel="route_round_tpu"}': 140}
    snap["lgbmtpu_hist_calls_per_pass"]['{width="32"}'] = 2
    snap["lgbmtpu_hist_calls_per_pass"]['{width="48"}'] = 2
    s = blocked.program_schedule(snap)
    assert blocked.slot_columns_per_tree(s) == (
        1 + 4 * 8 + 16 + 2 * 16 + 3 * 2 * 24) * 140


def test_a_program_without_the_gauges_gives_nothing():
    """The parent commit exports none of the schedule gauges: the
    readers return nothing and do not raise."""
    old = {k: v for k, v in WIDE.items()
           if k in ("lgbmtpu_grower_rounds_total",
                    "lgbmtpu_train_trees_total")}
    assert blocked.program_schedule(old) is None
    assert blocked.program_schedule({}) is None
    untrained = dict(WIDE, lgbmtpu_train_trees_total={})
    assert blocked.program_schedule(untrained) is None
    assert blocked.channels_of("int16") == blocked.channels_of("int8") == 3
    assert blocked.channels_of("bf16x2") == 5


class _Rec:
    def __init__(self, **obs):
        self.obs = obs


def _wide_trace(trees=2):
    """Two trees' worth of device ops: per tree a root pass and nine
    blocked passes of 50 ms, ten routing passes of 1 ms (one per round,
    the last round's alone) and one 20 ms fusion."""
    ops, t = [], 0.0

    def op(name, ms):
        nonlocal t
        ops.append(Event(name, t, t + ms * 1e6))
        t += ms * 1e6 + 1e5  # 0.1 ms of idle after every op

    for _ in range(trees):
        op("%hist_nat_tpu.1 = f32[66,3,2016]{2,1,0} custom-call(", 50)
        for i in range(9):
            op(f"%hist_nat_tpu.{i + 2} = f32[66,144,2016]{{2,1,0}} "
               "custom-call(", 50)
            op(f"%route_round_tpu.{i} = (s32[1,401408], s32[1,401408]) "
               "custom-call(", 1)
        op("%route_round_tpu.9 = s32[1,401408]{1,0} custom-call(", 1)
        op("%fusion.7 = f32[96,2000] fusion(", 20)
    return TraceView({0: DevicePlane(0, ops=ops)}, [("window", 0.0, t)])


def _inp(trace, snapshot, monkeypatch, **obs):
    from lightgbm_tpu.obs import metrics

    class _Reg:
        def snapshot(self):
            return snapshot

    monkeypatch.setattr(metrics, "default_registry", lambda: _Reg())
    cell = cellrun.resolve_cell(repo_root(), "epsilon-wide.train")
    base = dict(trees=2, rows=400_000, features=2000, bins=63, chips=1,
                hist_dtype="int16")
    return cellrun.LayerInput(cell=cell, rec=_Rec(**{**base, **obs}),
                              trace=trace, peaks=V5E)


def _read(name, inp):
    return load_plugin(repo_root(), "layer_metrics", name).read(inp)


def test_the_four_readers_on_a_synthetic_wide_trace(monkeypatch):
    inp = _inp(_wide_trace(), WIDE, monkeypatch)
    assert _read("learner.hist_streams_per_tree", inp) == 10
    # busy 2 x (10 x 50 + 10 x 1 + 20) ms less the Pallas calls
    assert _read("learner.non_hist_ms_per_tree", inp) == pytest.approx(20.0)
    # 7.185e13 FLOP a tree over 197 TF/s over 0.5 s of kernels
    assert _read("learner.hist_blocked_mxu_share", inp) == pytest.approx(
        100 * 7.185e13 / 197e12 / 0.5, rel=1e-3)
    # ten 3.913 ms reads over 0.5 s
    assert _read("learner.hist_blocked_roofline", inp) == pytest.approx(
        100 * 10 * 3.913e-3 / 0.5, rel=1e-3)


def test_the_readers_give_nothing_without_their_sources(monkeypatch):
    names = ("learner.hist_streams_per_tree", "learner.non_hist_ms_per_tree",
             "learner.hist_blocked_mxu_share",
             "learner.hist_blocked_roofline")
    no_trace = _inp(None, WIDE, monkeypatch)
    assert all(_read(n, no_trace) is None for n in names)
    # the parent's program: the trace is there, the gauges are not
    parent = _inp(_wide_trace(), {}, monkeypatch)
    assert _read("learner.hist_blocked_mxu_share", parent) is None
    assert _read("learner.hist_blocked_roofline", parent) is None
    assert _read("learner.hist_streams_per_tree", parent) == 10
    empty = TraceView({0: DevicePlane(0, ops=[
        Event("%fusion.1 = f32[8] fusion(", 0.0, 1e6)])},
        [("window", 0.0, 1e6)])
    quiet = _inp(empty, WIDE, monkeypatch)
    assert all(_read(n, quiet) is None for n in names)


def test_a_two_block_configuration_runs_end_to_end(bench_root):
    """tiny-wide (4,096 x 600 x 15 bins, 15 leaves): two feature blocks
    of 320 columns, the routed round, through the harness with the
    wide cell's per-layer list; a CPU trace has no device plane, so the
    device readers are silent and the program's gauges are what shows
    that the blocked path ran."""
    from lightgbm_tpu.obs.metrics import default_registry

    # the schedule is read from process-lifetime gauges and counters:
    # drop what earlier tests of this process left in them
    default_registry().reset()
    m = json.loads((bench_root / "BENCHMARK.json").read_text())
    m["configs"].append({"name": "tiny-wide", "source": "benchmark/tests",
                         "file": "benchmark/configs/tiny-wide.json",
                         "reduced": [], "why": "CPU rehearsal"})
    m["workloads"].append({"name": "tiny-wide.train", "config": "tiny-wide",
                           "traffic": "tiny-jobs", "chips": 1,
                           "why": "CPU rehearsal"})
    for e in m["end_to_end"] + m["per_layer"]:
        if "epsilon-wide.train" in e.get("workloads", ()):
            e["workloads"].append("tiny-wide.train")
    (bench_root / "BENCHMARK.json").write_text(json.dumps(m))

    def args(trace):
        return cellrun.RunArgs(seed=1, seconds=3.0, trace=trace,
                               t_process=time.perf_counter())

    r = cellrun.run_cell(bench_root, "tiny-wide.train", args(False), None)
    assert r["correct"] and r["failed"] == 0 and r["attempted"] >= 8
    assert set(r["metrics"]) == {"train_trees_per_s", "setup_s"}
    r = cellrun.run_cell(bench_root, "tiny-wide.train", args(True), None)
    assert r["correct"]
    got = r["metrics"]
    assert got["compile.in_window"]["value"] == 0
    assert got["engine.dispatches_per_tree"]["value"] == 0.25
    assert got["engine.cache_loads_per_job"]["value"] == 0
    assert not any(k.startswith("learner.") for k in got)
    schedule = blocked.read_schedule()
    assert schedule["columns"]["hist_nat_tpu"] == 2 * 320
    assert schedule["columns"]["hist_round_tpu"] == 0
    assert schedule["columns"]["route_round_tpu"] == 14
    assert schedule["calls"] == {"root": 1, "8": 1, "14": 1}
    # every tree ends on its leaf budget: one routing-only round each
    assert schedule["rounds_per_tree"]["route"] == pytest.approx(1.0)
