"""The trace -> metrics reduction: interval arithmetic on constructed
traces, and the whole reduction on a small trace recorded on the chip
(benchmark/tests/data/, one traced job of the tiny rehearsal cell)."""

from pathlib import Path

import pytest

from benchmark.harness import trace as T
from benchmark.harness.trace import DevicePlane, Event, TraceView

DATA = Path(__file__).with_name("data")
MS = 1e6  # ns


def ev(name, start_ms, end_ms):
    return Event(name, start_ms * MS, end_ms * MS)


def view(ops, spans, ndev=1):
    devs = {i: DevicePlane(i, ops=list(ops)) for i in range(ndev)}
    return TraceView(devs, [(n, s * MS, e * MS) for n, s, e in spans])


def test_interval_arithmetic():
    assert T.union([(5, 7), (0, 2), (1, 3), (7, 8), (4, 4)]) == [
        (0, 3), (5, 8)]
    assert T.total([(0, 3), (5, 8)]) == 6
    assert T.clip([(0, 3), (5, 8)], 2, 6) == [(2, 3), (5, 6)]
    assert T.complement([(0, 3), (5, 8)], -1, 10) == [
        (-1, 0), (3, 5), (8, 10)]
    assert T.subtract([(0, 10), (20, 30)], [(2, 3), (8, 22), (29, 40)]) \
        == [(0, 2), (3, 8), (22, 29)]


def test_busy_union_idle_share_and_window_clip():
    # a while op enclosing two fusions, then a kernel; window 0..100 ms
    ops = [ev("while.1", 10, 50), ev("fusion.1", 10, 20),
           ev("fusion.2", 30, 50), ev("custom-call.7", 60, 90),
           ev("fusion.9", 95, 120)]  # runs past the window's end
    v = view(ops, [("window", 0, 100)])
    assert v.window_s == pytest.approx(0.1)
    assert v.busy_s() == pytest.approx(0.075)  # 40 + 30 + 5 ms
    assert 1 - v.busy_s() / v.window_s == pytest.approx(0.25)
    assert v.busy_in(0, 55 * MS) == pytest.approx(0.040)


def test_self_time_does_not_count_a_loop_body_twice():
    ops = [ev("while.1", 10, 50), ev("fusion.1", 10, 20),
           ev("fusion.2", 30, 50), ev("fusion.1", 60, 65)]
    st = T.self_times(ops)
    assert st["while.1"] == pytest.approx(10 * MS)  # 40 less 10 less 20
    assert st["fusion.1"] == pytest.approx(15 * MS)
    assert [e.name for e in T.leaf_events(ops)] == [
        "fusion.1", "fusion.2", "fusion.1"]
    v = view(ops, [("window", 0, 100)])
    assert v.top_ops(2) == [["fusion.2", pytest.approx(0.020)],
                            ["fusion.1", pytest.approx(0.015)]]


def test_kernel_sums_match_names_and_average_over_chips():
    ops = [ev("%hist_round_tpu.8 = (f32[144,7140]{1,0}) custom-call(", 0, 30),
           ev("%seg_sum_tpu.10 = f32[2,255] custom-call(", 30, 40),
           ev("%fusion.3 = f32[8] fusion(", 40, 45)]
    v = view(ops, [("window", 0, 50)], ndev=2)
    assert v.op_seconds(r"^%?(hist_round_tpu|hist_nat_tpu)\b") == (
        pytest.approx(0.030), 1)
    assert v.op_seconds(r"^%fusion") == (pytest.approx(0.005), 1)
    assert v.op_seconds(r"nothing") == (0.0, 0)


def test_idle_gaps_are_labelled_by_the_span_and_where_in_it():
    # two jobs; device works 10..40 and 60..90; window 0..100
    ops = [ev("a", 10, 20), ev("b", 25, 40), ev("a", 60, 90)]
    spans = [("window", 0, 100), ("job", 5, 45), ("job", 50, 95)]
    gaps = dict(view(ops, spans).idle_gaps())
    assert gaps == {
        "job: before first device op": pytest.approx(0.015),  # 5-10, 50-60
        "job: between device ops": pytest.approx(0.005),  # 20-25
        "job: after last device op": pytest.approx(0.010),  # 40-45, 90-95
        "no benchmark span": pytest.approx(0.015),  # 0-5, 45-50, 95-100
    }
    # the innermost span wins where spans nest
    spans.append(("readback", 41, 44))
    gaps = dict(view(ops, spans).idle_gaps())
    assert gaps["readback: no device op in span"] == pytest.approx(0.003)
    assert gaps["job: after last device op"] == pytest.approx(0.007)


def test_exposed_collective_time():
    # an async pair around compute (the wait in -done is exposed), a
    # collective that another op overlaps from 25 on, one inside a loop
    ops = [ev("all-reduce-start.1", 0, 1), ev("fusion.1", 1, 6),
           ev("all-reduce-done.1", 6, 9),
           ev("all-reduce.2", 10, 30), ev("fusion.2", 25, 40),
           ev("while.3", 50, 60), ev("all-gather.4", 50, 52),
           ev("fusion.5", 52, 60)]
    v = view(ops, [("window", 0, 100)], ndev=2)
    assert v.collective_s() == pytest.approx(0.026)
    # 0-1, 6-9, 10-25, 50-52
    assert v.exposed_collective_s() == pytest.approx(0.021)
    # a transfer on the async line counts as in flight, and is hidden
    # where a compute op covers it
    for d in v.devices.values():
        d.async_ops.append(ev("%all-reduce-start.1", 1, 6))
    v = TraceView(v.devices, v.host_spans)
    assert v.collective_s() == pytest.approx(0.031)
    assert v.exposed_collective_s() == pytest.approx(0.021)


# ------------------------------------------- a trace recorded on the chip
@pytest.fixture(scope="module")
def recorded(tmp_path_factory):
    import gzip

    out = tmp_path_factory.mktemp("trace") / "tiny_train.xplane.pb"
    out.write_bytes(gzip.decompress(
        (DATA / "tiny_train.xplane.pb.gz").read_bytes()))
    return TraceView.from_file(out)


def test_recorded_trace_planes_spans_and_window(recorded):
    assert list(recorded.devices) == [0]
    names = [n for n, _, _ in recorded.host_spans]
    assert names.count("window") == 1 and names.count("job") == 1
    assert recorded.window_s == pytest.approx(0.059670853, rel=1e-6)
    d = recorded.devices[0]
    assert len(d.ops) > 5000 and d.async_ops
    assert [m.name.split("(")[0] for m in d.modules
            if m.name.startswith("jit_chunk")] == ["jit_chunk"]


def test_recorded_trace_busy_idle_and_gap_attribution(recorded):
    assert recorded.busy_s() == pytest.approx(0.017287152, rel=1e-6)
    gaps = dict(recorded.idle_gaps())
    assert gaps == {
        "job: after last device op": pytest.approx(0.021897339, rel=1e-6),
        "job: before first device op": pytest.approx(0.011018705,
                                                      rel=1e-6),
        "job: between device ops": pytest.approx(0.009409027, rel=1e-6),
        "no benchmark span": pytest.approx(5.863e-05, rel=1e-3),
    }
    assert sum(gaps.values()) + recorded.busy_s() == pytest.approx(
        recorded.window_s, rel=1e-9)
    (job,) = recorded.spans_named("job")
    assert recorded.busy_in(*job) == pytest.approx(recorded.busy_s())


def test_recorded_trace_kernel_sums_and_widths(recorded):
    from benchmark.harness.manifest import load_plugin, repo_root

    roof = load_plugin(repo_root(), "rooflines", "hist_round")
    passes = roof.kernel_passes(recorded, 28, 255)
    seconds, events = recorded.op_seconds(roof.KERNEL_PATTERN)
    assert events == len(passes) > 0
    assert seconds == pytest.approx(sum(d for _, d in passes))
    # 15 leaves: every round fits the narrowest rung (8 slots); the root
    # histogram is one slot; 4 trees
    assert {s for s, _ in passes} == {1, 8}
    assert sum(1 for s, _ in passes if s == 1) == 4
    top = recorded.top_ops(3)
    assert top[0][0].startswith("%hist_round_tpu.4 = (f32[24,7140], ")
    assert top[0][1] == pytest.approx(0.002636626, rel=1e-6)
    assert all(len(name) <= 160 and "{" not in name for name, _ in top)
