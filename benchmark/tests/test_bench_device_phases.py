"""Device time by program phase (harness/xmeta.py, device_phases.py):
the wire-format reader on the trace PR 22 recorded on the chip, the
whole rule (a) to (i) on a hand-made module and hand-made events, the
identity the thirteen readers keep with the metrics that are there, a
trace without phases (what a parent commit leaves), the CPU rehearsal,
and the trace recorded on the chip WITH the phases (data/tiny_phases)."""

import gzip
import time
from pathlib import Path

import pytest

from benchmark.harness import cellrun, device_phases, program_spans, xmeta
from benchmark.harness.manifest import load_plugin, repo_root, resolve_cell
from benchmark.harness.spans import Recorder
from benchmark.harness.trace import DevicePlane, Event, TraceView

DATA = Path(__file__).with_name("data")
MS = 1e6  # ns
KERNELS = load_plugin(repo_root(), "rooflines", "hist_round").KERNEL_PATTERN
CHUNK = "jit_chunk(1933382835734451975)"  # the PR 22 fixture's step
PHASE_READERS = {
    "objective.grad_ms_per_tree": "objective.gradients",
    "learner.quantize_ms_per_tree": "learner.quantize",
    "learner.select_ms_per_tree": "learner.select",
    "learner.route_ms_per_tree": "learner.route",
    "learner.hist_glue_ms_per_tree": "learner.hist",
    "learner.subtract_ms_per_tree": "learner.subtract",
    "learner.split_search_ms_per_tree": "learner.split_search",
    "learner.pool_write_ms_per_tree": "learner.pool_write",
    "boosting.renew_ms_per_tree": "boosting.renew",
    "boosting.score_update_ms_per_tree": "boosting.score_update",
    "metrics.valid_eval_ms_per_tree": "metrics.valid_eval",
    "parallel.reduce_ms_per_tree": "parallel.reduce",
    "boosting.unscoped_ms_per_tree": device_phases.UNSCOPED,
}


def _unzipped(tmp_path, name):
    pb = tmp_path / name
    pb.write_bytes(gzip.decompress((DATA / f"{name}.gz").read_bytes()))
    return pb


# ------------------------------------------------ the reader, PR 22's trace
def test_xmeta_reads_the_modules_the_recorded_trace_embeds(tmp_path):
    mods = xmeta.read_modules(_unzipped(tmp_path, "tiny_train.xplane.pb"))
    assert len(mods) == 9 and CHUNK in mods
    chunk = mods[CHUNK]
    assert len(chunk.computations) == len(chunk.roots) == 567
    assert len(chunk.instructions) == 9556 == sum(
        len(v) for v in chunk.computations.values())
    f = chunk.instructions["fusion.170"]
    assert f.opcode == "fusion" and len(f.called) == 1
    assert f.op_name.startswith("jit(chunk)/while/body/closed_call/"
                                "jit(grow_tree_rounds)/while/body/cond/")
    assert f.op_name.endswith("vmap(jit(take_along_axis))/gather")
    assert f.operands == ("copy.286", "reshape.2987")
    assert chunk.computations[f.computation].count("fusion.170") == 1
    # every operand and every root is an instruction of the module
    assert all(o in chunk.instructions for i in chunk.instructions.values()
               for o in i.operands)
    assert set(chunk.roots.values()) <= set(chunk.instructions)


def test_xmeta_agrees_with_the_generated_protobuf_classes(tmp_path):
    pytest.importorskip("tensorflow")
    from tensorflow.compiler.xla.service import hlo_pb2
    from tensorflow.tsl.profiler.protobuf import xplane_pb2

    pb = _unzipped(tmp_path, "tiny_train.xplane.pb")
    space = xplane_pb2.XSpace()
    space.ParseFromString(pb.read_bytes())
    (plane,) = [p for p in space.planes if p.name == xmeta.METADATA_PLANE]
    mine = xmeta.read_modules(pb)
    assert len(plane.event_metadata) == len(mine)
    for meta in plane.event_metadata.values():
        (stat,) = meta.stats
        proto = hlo_pb2.HloProto()
        proto.ParseFromString(stat.bytes_value)
        mod = mine[meta.name]
        for comp in proto.hlo_module.computations:
            by_id = {i.id: i.name for i in comp.instructions}
            assert mod.computations[comp.id] == list(by_id.values())
            assert mod.roots[comp.id] == by_id[comp.root_id]
            for i in comp.instructions:
                got = mod.instructions[i.name]
                assert (got.opcode, got.op_name, got.called) == (
                    i.opcode, i.metadata.op_name,
                    tuple(i.called_computation_ids))
                assert got.operands == tuple(
                    by_id[o] for o in i.operand_ids)


def test_join_coverage_of_the_recorded_trace(tmp_path):
    """Numbers of PR 34's session, re-derived here: of 17.287 ms of op
    self time, 17.286 ms joins to an instruction of the embedded
    modules; 3.763 ms of that carries no op_name (a compiler-made
    reduce-window, copies)."""
    pb = _unzipped(tmp_path, "tiny_train.xplane.pb")
    view = TraceView.from_file(pb)
    table = device_phases.table_of(pb, view, KERNELS)
    assert table.chips == 1 and not table.has_tokens
    assert table.self_ns == pytest.approx(view.busy_s() * 1e9, rel=1e-12)
    assert table.self_ns / MS == pytest.approx(17.287152, abs=1e-6)
    assert table.joined_ns / MS == pytest.approx(17.286122, abs=1e-6)
    assert table.no_op_name_ns / MS == pytest.approx(3.762608, abs=1e-6)
    assert table.kernels_ns / MS == pytest.approx(3.031133, abs=1e-6)
    # no phase anywhere: everything but the kernels is unscoped
    assert set(table.rows) == {device_phases.UNSCOPED}
    assert table.ns(device_phases.UNSCOPED) + table.kernels_ns == \
        pytest.approx(table.self_ns, rel=1e-12)
    assert xmeta.bytes_accessed(pb.read_bytes())  # the LOG line's estimate


def test_a_trace_without_phases_yields_nothing(bench_root):
    """PR 22's trace holds no ``lgbm.`` token, as a parent commit's
    does: every reader leaves its metric out and does not raise."""
    cell = resolve_cell(bench_root, "tiny.train")
    d = cellrun.trace_dir(cell) / "plugins" / "profile" / "recorded"
    d.mkdir(parents=True)
    pb = _unzipped(d, "tiny_train.xplane.pb")
    inp = _layer_input(cell, TraceView.from_file(pb), trees=4)
    assert load_plugin(repo_root(), "layer_metrics",
                       "boosting.device_ms_per_tree").read(inp) > 0
    for name in PHASE_READERS:
        assert load_plugin(repo_root(), "layer_metrics",
                           name).read(inp) is None, name


# --------------------------------------------- the rule, a hand-made module
def _varint(n):
    out = bytearray()
    while True:
        out.append((n & 0x7F) | (0x80 if n > 0x7F else 0))
        n >>= 7
        if not n:
            return bytes(out)


def _bytes(number, payload):
    if isinstance(payload, str):
        payload = payload.encode()
    return _varint(number << 3 | 2) + _varint(len(payload)) + payload


def _int(number, value):
    return _varint(number << 3) + _varint(value)


def _inst(ident, name, opcode, op_name="", called=(), operands=(),
          packed=False):
    out = _bytes(1, name) + _bytes(2, opcode) + _int(35, ident)
    if op_name:
        out += _bytes(7, _bytes(2, op_name))
    for number, ids in ((36, operands), (38, called)):
        if packed and ids:
            out += _bytes(number, b"".join(_varint(i) for i in ids))
        else:
            out += b"".join(_int(number, i) for i in ids)
    return out


def _computation(ident, root, instructions):
    return (b"".join(_bytes(2, i) for i in instructions)
            + _int(5, ident) + _int(6, root))


def _space(modules):
    """An XSpace whose /host:metadata plane embeds ``modules``:
    {name: [computation bytes]}; a device plane's name comes first, as
    in a recorded trace."""
    entries = b""
    for key, (name, comps) in enumerate(modules.items(), 1):
        proto = _bytes(1, _bytes(1, name)
                       + b"".join(_bytes(3, c) for c in comps))
        meta = (_int(1, key) + _bytes(2, name)
                + _bytes(5, _int(1, 7) + _bytes(6, proto)))
        entries += _bytes(4, _int(1, key) + _bytes(2, meta))
    return (_bytes(1, _int(1, 1) + _bytes(2, "/device:TPU:0"))
            + _bytes(1, _int(1, 2) + _bytes(2, xmeta.METADATA_PLANE)
                     + entries))


STEP = "jit_step(1)"
S = "jit(step)/"
HAND = {STEP: [
    # fusion.a: two phases inside -> its ROOT's, and booked as mixed
    _computation(1, 13, [
        _inst(11, "p.0", "parameter"),
        _inst(12, "sub.1", "subtract", S + "lgbm.learner.subtract/sub",
              operands=(11,)),
        _inst(13, "add.1", "add",
              S + "vmap(lgbm.learner.split_search)/add", operands=(12,)),
    ]),
    # fusion.outer -> fusion.inner -> one phase, through the nesting
    _computation(2, 21, [
        _inst(21, "fusion.inner", "fusion", called=(3,), packed=True)]),
    _computation(3, 31, [
        _inst(31, "top_k.1", "sort", S + "lgbm.learner.select/top_k")]),
    # the loop: body and condition
    _computation(20, 42, [
        _inst(41, "gte.5", "get-tuple-element"),
        _inst(42, "fusion.b", "fusion",
              S + "while/body/lgbm.learner.pool_write/scatter",
              called=(1,), operands=(41,)),
    ]),
    _computation(21, 51, [
        _inst(51, "compare.1", "compare", S + "while/cond/lt")]),
    # a loop the compiler made of ONE traced gather: the loop keeps the
    # gather's op_name, its body has none
    _computation(30, 72, [
        _inst(71, "gte.9", "get-tuple-element"),
        _inst(72, "slice.7", "dynamic-slice", operands=(71,)),
    ]),
    _computation(31, 73, [_inst(73, "compare.2", "compare")]),
    # the entry
    _computation(10, 68, [
        _inst(60, "arg.0", "parameter"),
        _inst(61, "fusion.a", "fusion", called=(1,), operands=(60,)),
        _inst(62, "fusion.outer", "fusion", called=(2,), operands=(60,)),
        _inst(63, "while.1", "while", S + "while", called=(20, 21),
              operands=(61,), packed=True),
        _inst(64, "copy.1", "copy", operands=(61,)),  # one producer
        _inst(65, "copy.2", "copy", operands=(61, 62), packed=True),
        _inst(66, "hist_round_tpu.3", "custom-call",
              S + "lgbm.learner.route/lgbm.learner.hist/pallas_call"),
        _inst(67, "gather.1", "gather",
              S + "lgbm.learner.route/lgbm.learner.hist/jit(take)/gather"),
        _inst(69, "while.2", "while",
              S + "lgbm.objective.gradients/jit(take)/gather",
              called=(30, 31), operands=(60,)),
        _inst(68, "tuple.1", "tuple", operands=(63, 64, 65, 66, 67, 69)),
    ]),
]}


def _hand_events(shift=0.0):
    """One launch of the step, 0..100 ms (+ shift): name, start, end."""
    def ev(name, a, b):
        return Event(name, (a + shift) * MS, (b + shift) * MS)

    ops = [
        ev("%fusion.a = f32[4]{0} fusion(f32[4]{0} %arg.0)", 2, 12),
        ev("%fusion.outer = f32[4]{0} fusion(f32[4]{0} %arg.0)", 12, 15),
        ev("%while.1 = (f32[4]{0}) while(%fusion.a)", 20, 50),
        ev("%fusion.b = f32[4]{0} fusion(%gte.5)", 22, 30),  # in the loop
        ev("%fusion.b = f32[4]{0} fusion(%gte.5)", 34, 48),
        ev("%copy.1 = f32[4]{0} copy(%fusion.a)", 50, 54),
        ev("%copy.2 = f32[4]{0} copy(%fusion.a, %fusion.outer)", 54, 55),
        ev("%hist_round_tpu.3 = f32[4]{0} custom-call()", 60, 80),
        ev("%gather.1 = f32[4]{0} gather(%arg.0)", 80, 86),
        ev("%while.2 = (f32[4]{0}) while(%arg.0)", 86, 90),
        ev("%slice.7 = f32[1]{0} dynamic-slice(%gte.9)", 87, 89),
        ev("%mystery.9 = f32[4]{0} fusion()", 90, 91),  # in no module
        ev("%fusion.a = f32[4]{0} fusion(f32[4]{0} %arg.0)", 140, 150),
    ]
    return ops, [ev(STEP, 1, 99), ev("jit_other(2)", 139, 151)]


def test_hand_made_module_partitions_the_busy_time_exactly(tmp_path):
    pb = tmp_path / "hand.xplane.pb"
    pb.write_bytes(_space(HAND))
    mods = xmeta.read_modules(pb)
    assert list(mods) == [STEP]
    assert mods[STEP].instructions["copy.2"].operands == (
        "fusion.a", "fusion.outer")  # packed ids
    assert mods[STEP].instructions["while.1"].called == (20, 21)
    # chip 1 runs the same launch 3 ms later; the window cuts 120 ms on
    devices = {}
    for i, shift in enumerate((0.0, 3.0)):
        ops, launches = _hand_events(shift)
        devices[i] = DevicePlane(i, ops=ops, modules=launches)
    view = TraceView(devices, [("window", 0, 120 * MS)])
    table = device_phases.table_of(pb, view, KERNELS)
    ms = {p: r.ns / MS for p, r in table.rows.items()}
    assert ms == {
        # fusion.a 10 (its root's phase) + copy.1 4 (inherited)
        "learner.split_search": pytest.approx(14.0),
        "learner.select": pytest.approx(3.0),  # fusion.outer, nested
        "learner.pool_write": pytest.approx(22.0),  # fusion.b 8 + 14
        "learner.hist": pytest.approx(6.0),  # gather.1: the INNERMOST
        # while.2's own 2 (its op_name) + slice.7 2 (its loop's, rule f)
        "objective.gradients": pytest.approx(4.0),
        # while.1's own 8 + copy.2 1 (producers disagree) + mystery 1;
        # the launch of jit_other lies outside the window
        device_phases.UNSCOPED: pytest.approx(10.0),
    }
    assert table.rows["learner.split_search"].inherited_ns == \
        pytest.approx(4 * MS)
    assert table.rows["objective.gradients"].inherited_ns == \
        pytest.approx(2 * MS)
    assert sum(r.inherited_ns for r in table.rows.values()) == \
        pytest.approx(6 * MS)
    assert table.kernels_ns == pytest.approx(20 * MS)
    assert table.mixed == {
        ("learner.split_search", "learner.subtract"):
            pytest.approx((10 + 22) * MS)}  # fusion.a and fusion.b
    assert table.rows["learner.pool_write"].events == 2
    assert table.chips == 2 and table.has_tokens
    assert sum(ms.values()) + 20.0 == pytest.approx(view.busy_s() * 1e3)
    assert table.joined_ns == pytest.approx(78 * MS)
    text = "\n".join(device_phases.lines(table, trees=2))
    assert "mixed learner.split_search + learner.subtract: 16.000" in text
    assert "%fusion.b = f32[4] fusion" in text
    # an event no launch encloses joins nothing
    ops, launches = _hand_events()
    one = TraceView({0: DevicePlane(0, ops=ops, modules=launches[:1])},
                    [("window", 0, 200 * MS)])
    late = device_phases.table_of(pb, one, KERNELS)
    assert late.ns(device_phases.UNSCOPED) == pytest.approx(20 * MS)
    assert late.ns("learner.split_search") == pytest.approx(14 * MS)


def test_a_scope_name_is_found_wherever_the_stack_prints_it():
    assert device_phases.phase_of(
        "jit(f)/while/body/vmap(lgbm.learner.split_search)/jit(x)/mul"
    ) == "learner.split_search"
    assert device_phases.phase_of(
        "jit(f)/lgbm.learner.route/lgbm.learner.hist/dot") == "learner.hist"
    assert device_phases.phase_of(
        "jit(f)/transpose(lgbm.metrics.valid_eval)") == "metrics.valid_eval"
    assert device_phases.phase_of("jit(f)/lgbm:host span/add") is None
    assert device_phases.phase_of("") is None


# ------------------------------- the trace recorded WITH the phases (PR 34)
def test_phase_table_of_the_trace_recorded_on_the_chip(tmp_path):
    """data/tiny_phases: one traced 4-round job of the tiny cell on a
    v5e, the program's scopes in the embedded module. The table as the
    reader gave it on the day it was recorded (ns in the window)."""
    pb = _unzipped(tmp_path, "tiny_phases.xplane.pb")
    assert (DATA / "tiny_phases.xplane.pb.gz").stat().st_size <= 600_000
    mods = xmeta.read_modules(pb)
    (chunk,) = [m for n, m in mods.items() if n.startswith("jit_chunk(")]
    assert len(mods) == 10 and len(chunk.instructions) == 11288
    assert len(chunk.computations) == 676
    view = TraceView.from_file(pb)
    table = device_phases.table_of(pb, view, KERNELS)
    assert table.has_tokens and table.chips == 1
    assert {p: (round(r.ns), r.events, round(r.inherited_ns))
            for p, r in table.rows.items()} == {
        "learner.split_search": (9847097, 2693, 3094345),
        device_phases.UNSCOPED: (690474, 4861, 0),
        "learner.select": (604834, 3332, 20342),
        "learner.pool_write": (415673, 80, 0),
        "learner.subtract": (344660, 280, 0),
        "metrics.valid_eval": (303464, 1143, 17812),
        "learner.route": (85722, 416, 0),
        "boosting.renew": (66119, 16, 0),
        "learner.hist": (54720, 84, 0),
        "learner.quantize": (38555, 48, 0),
        "boosting.score_update": (12804, 8, 0),
    }
    # the binary gradient is fused into its consumers: no event of its own
    assert "objective.gradients" not in table.rows
    assert table.mixed[("boosting.score_update", "learner.quantize",
                        "objective.gradients")] == pytest.approx(21296)
    assert max(table.mixed, key=table.mixed.get) == (
        "learner.pool_write", "learner.split_search", "learner.subtract")
    assert table.kernels_ns == 2598101
    assert table.self_ns == 15062223 == round(view.busy_s() * 1e9)
    assert table.joined_ns == 15061953 and table.no_op_name_ns == 3207199
    assert sum(r.ns for r in table.rows.values()) + table.kernels_ns == \
        pytest.approx(table.self_ns, rel=1e-12)
    text = "\n".join(device_phases.lines(table, trees=4))
    assert "busy 3.766 = histogram kernels 0.650 + phases 2.943 " \
        "+ unscoped 0.173" in text


# ------------------------------------------------------------- the readers
def _layer_input(cell, view, trees=8):
    rec = Recorder()
    rec.observe(trees=trees)
    return cellrun.LayerInput(cell=cell, rec=rec, trace=view, peaks=None)


def test_the_phase_readers_and_the_kernels_sum_to_device_ms_per_tree(
        bench_root, capsys):
    cell = resolve_cell(bench_root, "tiny.train")
    d = cellrun.trace_dir(cell) / "plugins" / "profile" / "hand"
    d.mkdir(parents=True)
    (d / "hand.xplane.pb").write_bytes(_space(HAND))
    devices = {}
    for i, shift in enumerate((0.0, 3.0)):
        ops, launches = _hand_events(shift)
        devices[i] = DevicePlane(i, ops=ops, modules=launches)
    view = TraceView(devices, [("window", 0, 120 * MS)])
    inp = _layer_input(cell, view, trees=2)

    def read(name):
        return load_plugin(repo_root(), "layer_metrics", name).read(inp)

    got = {name: read(name) for name in PHASE_READERS}
    assert got["learner.split_search_ms_per_tree"] == pytest.approx(7.0)
    assert got["learner.hist_glue_ms_per_tree"] == pytest.approx(3.0)
    assert got["boosting.unscoped_ms_per_tree"] == pytest.approx(5.0)
    # a phase the program opens and this trace has no event of: 0, not None
    assert got["boosting.renew_ms_per_tree"] == 0.0
    assert got["parallel.reduce_ms_per_tree"] == 0.0
    assert read("learner.hist_ms_per_tree") == pytest.approx(10.0)
    assert sum(got.values()) + read("learner.hist_ms_per_tree") == \
        pytest.approx(read("boosting.device_ms_per_tree"), rel=1e-12)
    err = capsys.readouterr().err  # the unscoped reader logs the table
    assert err.count("device time by program phase") == 1
    assert "histogram kernels 10.000" in err
    for bad in (_layer_input(cell, None), _layer_input(cell, view, 0)):
        assert all(load_plugin(repo_root(), "layer_metrics", name).read(bad)
                   is None for name in PHASE_READERS)


def test_every_phase_reader_is_listed_and_reads_a_phase_of_the_program():
    import json

    listed = {p["name"]: p for p in json.loads(
        (repo_root() / "BENCHMARK.json").read_text())["per_layer"]}
    cells = listed["boosting.device_ms_per_tree"]["workloads"]
    for name in PHASE_READERS:
        reader = load_plugin(repo_root(), "layer_metrics", name)
        spec = listed[name]
        assert (spec["layer"], spec["moves"], spec["source"], spec["unit"],
                spec["better"]) == (reader.LAYER, reader.MOVES,
                                    reader.SOURCE, reader.UNIT,
                                    reader.BETTER), name
        assert spec["workloads"] == (
            ["higgs-dp4.train"] if name.startswith("parallel.") else cells)
    from lightgbm_tpu import timer

    if not hasattr(timer, "DEVICE_PHASES"):
        pytest.skip("this program opens no device phase (a parent commit "
                    "with the benchmark's files laid over it)")
    assert device_phases.PHASE_TOKEN.pattern.startswith(
        timer.DEVICE_PREFIX.replace(".", r"\."))
    assert set(PHASE_READERS.values()) - {device_phases.UNSCOPED} == set(
        timer.DEVICE_PHASES)


def test_cpu_rehearsal_embeds_the_phases_in_its_trace(bench_root):
    """A CPU trace has no device plane, so no phase metric is printed;
    but the executable it embeds is this program's, and its op_names
    carry the vocabulary and nothing outside it."""
    from lightgbm_tpu import timer

    if not hasattr(timer, "DEVICE_PHASES"):
        pytest.skip("this program opens no device phase")
    import jax
    from jax.experimental.compilation_cache import compilation_cache

    # an executable that an older program left in the compile cache
    # carries that program's op_names (the key strips debug info): this
    # run compiles its own
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        r = cellrun.run_cell(
            bench_root, "tiny.train",
            cellrun.RunArgs(seed=1, seconds=3.0, trace=True,
                            t_process=time.perf_counter()), None)
    finally:
        jax.config.update("jax_enable_compilation_cache", True)
        compilation_cache.reset_cache()
    assert r["correct"]
    assert not set(PHASE_READERS) & set(r["metrics"])
    cell = resolve_cell(bench_root, "tiny.train")
    mods = xmeta.read_modules(
        program_spans.newest_trace(cellrun.trace_dir(cell)))
    (chunk,) = [m for n, m in mods.items() if n.startswith("jit_chunk(")]
    found = {device_phases.phase_of(i.op_name)
             for i in chunk.instructions.values()} - {None}
    assert found <= set(timer.DEVICE_PHASES)
    assert found >= set(timer.DEVICE_PHASES) - {"parallel.reduce"}
