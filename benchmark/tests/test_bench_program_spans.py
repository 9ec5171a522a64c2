"""The program's own spans in the trace (harness/program_spans.py): the
split of the jobs' idle time on hand-made intervals, the five readers
against ``engine.host_ms_per_tree``, a trace without program spans (what
a parent commit leaves), and the CPU rehearsal of the tiny cell."""

import gzip
import time
from pathlib import Path

import pytest

from benchmark.harness import cellrun, program_spans
from benchmark.harness.manifest import load_plugin, repo_root, resolve_cell
from benchmark.harness.spans import Recorder
from benchmark.harness.trace import DevicePlane, Event, TraceView

DATA = Path(__file__).with_name("data")
MS = 1e6  # ns
IDLE_READERS = {
    "engine.idle_booster_init_ms_per_tree": "booster_init",
    "boosting.idle_fused_start_ms_per_tree": "fused_start",
    "boosting.idle_dispatch_ms_per_tree": "dispatch",
    "boosting.idle_collect_ms_per_tree": "collect",
    "engine.idle_other_ms_per_tree": "other",
}
COMPILE_READERS = ("compile.trace_lower_s", "compile.cache_load_s",
                   "compile.backend_compile_s")

# one job 0..100 ms; chip 0 runs 30..70, chip 1 runs 34..74
JOBS = [(0 * MS, 100 * MS)]
BUSY = [[(30 * MS, 70 * MS)], [(34 * MS, 74 * MS)]]
SPANS = [(n, s * MS, e * MS) for n, s, e in [
    ("engine.train", 2, 98),
    ("engine.booster_init", 4, 20),
    ("boosting.objective_init", 5, 12),  # nested: counts as booster_init
    ("boosting.fused_start", 20, 28),
    ("objective.boost_from_score", 21, 26),
    ("fused dispatch", 28, 32),  # the gap 28..30|34 straddles two spans
    ("round: fused step", 29, 31),
    ("fused collect (readback)", 32, 90),
    ("materialize host trees (readback)", 80, 88),
    ("engine.finish", 92, 97),
    ("materialize host trees (readback)", 93, 95),  # under no part's span
]]


def test_idle_is_split_by_part_and_the_parts_sum_to_wall_less_busy():
    split = program_spans.attribute(JOBS, SPANS, BUSY)
    assert split.jobs == 1
    assert split.job_idle_s == pytest.approx(0.100 - 0.040)
    assert sum(split.idle_s.values()) == pytest.approx(split.job_idle_s,
                                                       rel=1e-12)
    assert split.idle_s == {
        "booster_init": pytest.approx(0.016),  # 4..20, children included
        "fused_start": pytest.approx(0.008),  # 20..28
        # 28..32, idle until 30 on chip 0 and throughout on chip 1
        "dispatch": pytest.approx((0.002 + 0.004) / 2),
        # 32..90: chip 0 idle 70..90, chip 1 idle 32..34 and 74..90;
        # and the materialize inside engine.finish, 93..95
        "collect": pytest.approx((0.020 + 0.018) / 2 + 0.002),
        # 0..4, 90..93, 95..100: engine.train's and engine.finish's own
        # time and what no span covers
        "other": pytest.approx(0.004 + 0.003 + 0.005),
    }
    rows = {r.name: r for r in split.rows}
    assert split.rows[-1].name == program_spans.NO_SPAN
    assert split.uncovered_idle_s == pytest.approx(0.004)  # 0..2, 98..100
    assert rows["engine.booster_init"].wall_s == pytest.approx(0.016)
    assert rows["engine.booster_init"].self_s == pytest.approx(0.009)
    assert rows["boosting.objective_init"].idle_s == pytest.approx(0.007)
    assert rows["materialize host trees (readback)"].calls == 2
    assert rows["round: fused step"].idle_s == pytest.approx(
        (0.001 + 0.002) / 2)  # 29..31: chip 0 idle until 30
    assert sum(r.idle_s for r in split.rows) == pytest.approx(
        split.job_idle_s)
    assert sum(r.self_s for r in split.rows) == pytest.approx(0.100)
    idle = [r.idle_s for r in split.rows[:-1]]
    assert idle == sorted(idle, reverse=True)
    text = "\n".join(program_spans.table(split))
    assert "objective.boost_from_score" in text and " 0 bytes" in text


def test_spans_are_cut_to_the_jobs_and_one_chip_needs_no_average():
    # two jobs; the span that runs over a job's end is cut to it
    jobs = [(0, 10 * MS), (20 * MS, 30 * MS)]
    spans = [("engine.train", 0, 10 * MS), ("fused dispatch", 8 * MS, 12 * MS),
             ("engine.train", 20 * MS, 30 * MS),
             ("engine.booster_init", 21 * MS, 25 * MS)]
    split = program_spans.attribute(jobs, spans, [[(5 * MS, 9 * MS),
                                                   (26 * MS, 40 * MS)]])
    assert split.job_idle_s == pytest.approx(0.006 + 0.006)
    assert split.idle_s["dispatch"] == pytest.approx(0.001)  # 9..10
    assert split.idle_s["booster_init"] == pytest.approx(0.004)
    assert split.idle_s["other"] == pytest.approx(0.005 + 0.002)
    assert split.uncovered_idle_s == pytest.approx(0.0)


def _layer_input(cell, view, trees=8):
    rec = Recorder()
    rec.observe(trees=trees)
    return cellrun.LayerInput(cell=cell, rec=rec, trace=view, peaks=None)


def test_the_five_readers_sum_to_engine_host_ms_per_tree(
        bench_root, monkeypatch, capsys):
    cell = resolve_cell(bench_root, "tiny.train")
    devices = {i: DevicePlane(i, ops=[Event("op", s, e) for s, e in b])
               for i, b in enumerate(BUSY)}
    view = TraceView(devices, [("window", 0, 100 * MS),
                               ("job", *JOBS[0])])
    pb = bench_root / "fake.xplane.pb"
    pb.write_bytes(b"x" * 7)
    monkeypatch.setattr(program_spans, "newest_trace", lambda d: pb)
    monkeypatch.setattr(program_spans, "program_spans",
                        lambda path: tuple(SPANS))
    inp = _layer_input(cell, view)
    got = {name: load_plugin(repo_root(), "layer_metrics", name).read(inp)
           for name in IDLE_READERS}
    whole = load_plugin(repo_root(), "layer_metrics",
                        "engine.host_ms_per_tree").read(inp)
    assert whole == pytest.approx(60.0 / 8)
    assert sum(got.values()) == pytest.approx(whole, rel=1e-9)
    assert got["boosting.idle_fused_start_ms_per_tree"] == pytest.approx(1.0)
    err = capsys.readouterr().err  # the `other` reader logs the table
    assert err.count("idle inside the 1 job spans") == 1
    assert "trace file 7 bytes" in err
    # no device trace (a CPU rehearsal), or no trees: nothing
    for bad in (_layer_input(cell, None), _layer_input(cell, view, 0)):
        assert all(load_plugin(repo_root(), "layer_metrics", name).read(bad)
                   is None for name in IDLE_READERS)


def test_a_trace_without_program_spans_yields_nothing(bench_root):
    """The trace PR 22 recorded on the chip holds ``bench:job`` and no
    ``lgbm:`` event, as a parent commit's does: the five readers leave
    their metric out and do not raise."""
    cell = resolve_cell(bench_root, "tiny.train")
    d = cellrun.trace_dir(cell) / "plugins" / "profile" / "recorded"
    d.mkdir(parents=True)
    pb = d / "tiny_train.xplane.pb"
    pb.write_bytes(gzip.decompress(
        (DATA / "tiny_train.xplane.pb.gz").read_bytes()))
    assert program_spans.newest_trace(cellrun.trace_dir(cell)) == pb
    assert program_spans.program_spans(pb) == ()
    inp = _layer_input(cell, TraceView.from_file(pb), trees=4)
    assert load_plugin(repo_root(), "layer_metrics",
                       "engine.host_ms_per_tree").read(inp) > 0
    for name in IDLE_READERS:
        assert load_plugin(repo_root(), "layer_metrics",
                           name).read(inp) is None, name


def test_compile_readers_yield_nothing_without_the_duration_counters(
        bench_root, monkeypatch):
    from lightgbm_tpu.analysis import retrace

    cell = resolve_cell(bench_root, "tiny.train")
    inp = _layer_input(cell, None)
    monkeypatch.setattr(retrace, "compile_counters", lambda: {
        "jaxpr_traces": 3, "backend_compiles": 2, "listener_installed": 1})
    for name in COMPILE_READERS:
        assert load_plugin(repo_root(), "layer_metrics",
                           name).read(inp) is None, name
    monkeypatch.setattr(retrace, "compile_counters", lambda: {
        "trace_s": 2.0, "lower_s": 1.5, "backend_compile_s": 9.0,
        "cache_load_s": 0.25})
    got = [load_plugin(repo_root(), "layer_metrics", name).read(inp)
           for name in COMPILE_READERS]
    assert got == [3.5, 0.25, 8.75]


def test_cpu_rehearsal_finds_the_program_spans_in_its_trace(bench_root):
    from lightgbm_tpu import timer

    if not hasattr(timer, "TRACE_PREFIX"):
        pytest.skip("this program opens no lgbm: spans (a parent commit "
                    "with the benchmark's files laid over it)")
    assert timer.TRACE_PREFIX == program_spans.PROGRAM_PREFIX
    r = cellrun.run_cell(
        bench_root, "tiny.train",
        cellrun.RunArgs(seed=1, seconds=3.0, trace=True,
                        t_process=time.perf_counter()), None)
    assert r["correct"]
    m = r["metrics"]
    # a CPU trace has no device plane: none of the five is printed; the
    # three counters are, and a compile was paid somewhere in set-up
    assert not set(IDLE_READERS) & set(m)
    assert set(COMPILE_READERS) <= set(m)
    assert m["compile.trace_lower_s"]["value"] > 0
    assert m["compile.cache_load_s"]["value"] >= 0
    assert m["compile.backend_compile_s"]["value"] >= 0
    cell = resolve_cell(bench_root, "tiny.train")
    spans = program_spans.program_spans(
        program_spans.newest_trace(cellrun.trace_dir(cell)))
    names = [n for n, _, _ in spans]
    jobs = cell.traffic["trace_jobs"]
    for one in ("engine.train", "engine.booster_init",
                "boosting.fused_start", "objective.boost_from_score",
                "fused dispatch", "fused collect (readback)",
                "engine.finish"):
        assert names.count(one) == jobs, one
    assert len(names) <= 40 * jobs
    trains = [(s, e) for n, s, e in spans if n == "engine.train"]
    assert all(any(ts <= s and e <= te for ts, te in trains)
               for _, s, e in spans)
