"""A table too wide for one bins tile (histogram.hist_plan): the
histogram kernels take the feature axis as a grid dimension
(pallas_hist.hist_nat_tpu, feat_block) and a round is the routing pass
over its split columns followed by a blocked slot-keyed pass
(rounds.hist_schedule, use_routed). Held here, off hardware, under the
Pallas interpreter: the blocked kernel against the XLA formulation, the
routed round against the fused whole-table kernel, whole trees and whole
models against the single-block formulation (the tile limit
monkeypatched down so that small tables block), the VMEM plan against
hand numbers, and the program's gauges and span. The kernels at the
wide cell's real shapes for a described v5e: test_aot_v5e.py."""

import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import lightgbm_tpu as lgb
from lightgbm_tpu.learner import rounds as rounds_mod
from lightgbm_tpu.learner.histogram import (
    HIST_BLK,
    HistPlan,
    _hist_nat_fallback,
    _round_s_max,
    _slot_chunks,
    build_gh8,
    build_gh8_quant,
    hist_nat_slots,
    hist_plan,
    hist_round,
    route_round,
)
from lightgbm_tpu.obs import default_registry

from test_pallas_kernels import _grow_case, interp  # noqa: F401
from test_route_round import _drop_traces, _round_inputs, _routed

# learner/__init__ re-exports the histogram FUNCTION, shadowing the
# submodule on attribute import
hist_mod = sys.modules["lightgbm_tpu.learner.histogram"]


@pytest.fixture
def narrow_tile(monkeypatch):
    """One bins tile holds 32 columns, not 512: a 70-column table runs
    by three feature blocks of one loop group each, the last ragged."""
    monkeypatch.setattr(hist_mod, "_TILE_COLS", 32)
    _drop_traces()
    yield
    _drop_traces()


# ---------------------------------------- (a) the blocked kernel itself
@pytest.mark.parametrize("bins", [15, 63])
@pytest.mark.parametrize("layout", ["int16", "int8", "bf16x2"])
def test_blocked_hist_nat_equals_the_xla_formulation(interp, layout, bins):
    """300 columns by blocks of 64 (five, the last of 44 columns) and of
    128 (three), six slots in two chunks: exact integer sums on the
    int-packed layouts, f32 rounding on the bf16x2 split."""
    rs = np.random.RandomState(bins)
    F, N, S = 300, 2 * HIST_BLK, 6
    table = jnp.asarray(rs.randint(0, bins, (F, N)).astype(np.int32))
    slot = jnp.asarray(rs.randint(0, S + 1, N).astype(np.int32))
    quant = layout != "bf16x2"
    if quant:
        gh8 = build_gh8_quant(
            jnp.asarray(rs.randint(-100, 100, N).astype(np.float32)),
            jnp.asarray(rs.randint(0, 100, N).astype(np.float32)),
            jnp.ones(N, jnp.float32))
    else:
        gh8 = build_gh8(jnp.asarray(rs.randn(N).astype(np.float32)),
                        jnp.asarray(rs.rand(N).astype(np.float32)),
                        jnp.ones(N, jnp.float32))
    want = np.asarray(_hist_nat_fallback(table, gh8, slot, S, bins,
                                         quant=quant))
    for block in (64, 128):
        plan = HistPlan(4, block, F)
        assert plan.blocks == -(-F // block) and F % block
        got = np.asarray(hist_nat_slots(
            table, gh8, slot, S, bins, quant=quant, int8=layout == "int8",
            plan=plan))
        if quant:
            np.testing.assert_array_equal(got, want)
        else:
            np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-3)


def _int_valued(build, rs, N):
    """Gradient channels whose sums are exact in every layout (small
    integers: exact in bf16, so the bf16x2 split's low halves are 0)."""
    g = jnp.asarray(rs.randint(-100, 100, N).astype(np.float32))
    h = jnp.asarray(rs.randint(0, 100, N).astype(np.float32))
    return build(g, h, jnp.ones(N, jnp.float32))


def _pallas_out_shapes(fn, *args):
    """Output shapes of the pallas_call equations `fn` traces to."""
    shapes = []

    def walk(jaxpr):
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "pallas_call":
                shapes.extend(v.aval.shape for v in eqn.outvars)
            for sub in jax.core.jaxprs_in_params(eqn.params):
                walk(sub)

    walk(jax.make_jaxpr(fn)(*args).jaxpr)
    return shapes


@pytest.mark.parametrize("bins", [33, 63, 64])
@pytest.mark.parametrize("layout", ["int16", "int8", "bf16x2"])
def test_paired_kernels_equal_the_xla_formulation(interp, layout, bins):
    """33..64 bins: two columns per one-hot tile, 64 lanes a column in
    the output block. 75 columns (odd): whole (three loop groups of 26,
    the last pairing column 74 with itself), by blocks of 64 (the
    second ragged: 11 columns) and of 32 (one-group blocks); 7 columns:
    the loop unrolled whole into a 2-D block, the single-leaf kernel
    too. Integer-valued channels, so every layout is bit for bit."""
    from lightgbm_tpu.learner.histogram import _hist_fallback, histogram
    from lightgbm_tpu.learner.pallas_hist import hist_nat_tpu

    rs = np.random.RandomState(bins)
    N, S = 2 * HIST_BLK, 6
    quant = layout != "bf16x2"
    kw = dict(quant=quant, int8=layout == "int8")
    slot = jnp.asarray(rs.randint(0, S + 1, N).astype(np.int32))
    gh8 = _int_valued(build_gh8 if layout == "bf16x2" else build_gh8_quant,
                      rs, N)
    for F, plans in ((75, (None, HistPlan(4, 64, 75), HistPlan(6, 32, 75))),
                     (7, (None,))):
        table = jnp.asarray(rs.randint(0, bins, (F, N)).astype(np.int32))
        want = np.asarray(_hist_nat_fallback(table, gh8, slot, S, bins,
                                             quant=quant))
        for plan in plans:
            got = hist_nat_slots(table, gh8, slot, S, bins, plan=plan, **kw)
            np.testing.assert_array_equal(np.asarray(got), want)
    one = _int_valued(build_gh8, rs, N)  # the single-leaf kernel's
    np.testing.assert_array_equal(
        np.asarray(histogram(table, one, bins)),
        np.asarray(_hist_fallback(table, one, bins)))
    # the blocks themselves: 128 lanes a pair, whole groups of 32
    nat = lambda F, fb: _pallas_out_shapes(  # noqa: E731
        lambda b, g, s: hist_nat_tpu(b, g, s, S, bins, nat_ch=3,
                                     interpret=True, feat_block=fb),
        jnp.zeros((F, N), jnp.int32), gh8, slot)
    assert nat(75, 0) == [(3, 18, 26 * 64)]
    assert nat(75, 32) == [(3, 18, 32 * 64)]
    assert nat(7, 0) == [(18, 8 * 64)]


@pytest.mark.parametrize("bins", [15, 32, 65, 255])
def test_other_bin_counts_keep_one_column_per_matmul(bins):
    """<= 32 and > 64 bins: a column's stride in the output block is
    its bins and the blocks have the shapes they had."""
    from lightgbm_tpu.learner import pallas_hist as ph

    assert ph.columns_per_matmul(bins) == 1
    assert ph.column_stride(bins) == bins
    assert ph.feature_groups(33, bins) == (2, 17)
    assert ph.hist_out_block(24, 28, bins) == (24, 28 * bins)
    assert ph.hist_out_block(24, 137, bins) == (5, 24, 28 * bins)
    assert ph.hist_out_block(8, 137, bins, whole=True) == (8, 137 * bins)
    assert ph._oh_iota_shape(bins, HIST_BLK, False) == (bins, HIST_BLK)
    assert ph._oh_iota_shape(bins, HIST_BLK, True) == (-(-bins // 4),
                                                       HIST_BLK)
    N = HIST_BLK
    shapes = _pallas_out_shapes(
        lambda b, g, s: ph.hist_nat_tpu(b, g, s, 4, bins, nat_ch=3,
                                        interpret=True, feat_block=32),
        jnp.zeros((70, N), jnp.int32), jnp.zeros((8, N), jnp.float32),
        jnp.zeros(N, jnp.int32))
    assert shapes == [(3, 12, 32 * bins)]


@pytest.mark.parametrize("bins", [33, 63, 64])
def test_the_pair_rule_and_hist_out_flat_of_a_stride_64_block(bins):
    from lightgbm_tpu.learner import pallas_hist as ph

    assert ph.columns_per_matmul(bins) == 2 and ph.column_stride(bins) == 64
    # groups of whole pairs; an odd table's last pair runs past it
    assert ph.feature_groups(33, bins) == (2, 18)
    assert ph.feature_groups(137, bins) == (5, 28)
    assert ph.hist_out_block(24, 7, bins) == (24, 8 * 64)
    assert ph.hist_out_block(24, 137, bins) == (5, 24, 28 * 64)
    assert ph.hist_out_block(8, 137, bins, whole=True) == (8, 138 * 64)
    assert ph._oh_iota_shape(bins, HIST_BLK, False) == (128, HIST_BLK)
    assert ph._oh_iota_shape(bins, HIST_BLK, True) == (32, HIST_BLK)
    # a (2 groups, 3 rows, 4 columns x 64 lanes) block of a 7-column
    # table: cell (row, column, bin) holds its own index, pad lanes and
    # the eighth column -1
    G, rows, Fg, F = 2, 3, 4, 7
    block = -np.ones((G, rows, Fg, 64), np.float32)
    want = np.arange(rows * F * bins, dtype=np.float32).reshape(rows, F, bins)
    for f in range(F):
        block[f // Fg, :, f % Fg, :bins] = want[:, f]
    got = ph.hist_out_flat(jnp.asarray(block.reshape(G, rows, Fg * 64)),
                           F, bins)
    np.testing.assert_array_equal(np.asarray(got),
                                  want.reshape(rows, F * bins))
    got = ph.hist_out_flat(
        jnp.asarray(block.transpose(1, 0, 2, 3).reshape(rows, G * Fg * 64)),
        F, bins)
    np.testing.assert_array_equal(np.asarray(got),
                                  want.reshape(rows, F * bins))


# --------------------------------------------- (b) the round at width
@pytest.mark.parametrize("B", [32, 63])
@pytest.mark.parametrize("variant", ["plain", "efb", "cat"])
@pytest.mark.parametrize("layout", ["bf16x2", "int16", "int8"])
def test_routed_round_is_the_fused_round(interp, layout, variant, B):
    """Routing over the round's split columns alone (a table of S rows,
    the identity for column one-hot), then the blocked pass keyed by the
    slots it returns, against the fused kernel holding all 100 columns:
    the same row->leaf vector and the same histograms, also where the
    split columns (EFB-encoded, categorical) lie past the first block,
    one column per matmul (32 bins) and two (63)."""
    S, F = 8, 100
    table, gh8, pleaf, params, coh, cat_mask = _round_inputs(
        layout, F, variant, S, B)
    quant = layout != "bf16x2"
    kw = dict(efb=variant == "efb", cat_mask=cat_mask)
    want_h, want_leaf = hist_round(
        table, gh8, pleaf, params, coh, S, B, quant=quant,
        int8=layout == "int8", **kw)
    col = np.asarray(params[:, 1])
    assert (col >= 32).sum() >= 3  # split columns past the first block
    leaf, slot = route_round(table[col], pleaf, params,
                             jnp.eye(S, dtype=jnp.float32), S, B,
                             with_slot=True, **kw)
    np.testing.assert_array_equal(np.asarray(leaf), np.asarray(want_leaf))
    slot = np.asarray(slot)
    assert slot.min() >= 0 and slot.max() == S and (slot < S).mean() > 0.05
    got_h = hist_nat_slots(table, gh8, jnp.asarray(slot), S, B, quant=quant,
                           int8=layout == "int8", plan=HistPlan(S, 32, F))
    if quant:
        np.testing.assert_array_equal(np.asarray(got_h), np.asarray(want_h))
    else:
        np.testing.assert_allclose(np.asarray(got_h), np.asarray(want_h),
                                   rtol=2e-5, atol=2e-3)


# ------------------------------------------------ (c) whole trees
@pytest.mark.parametrize("leaves", [31, 63])
@pytest.mark.parametrize("layout", ["int16", "int8", "bf16x2"])
def test_trees_at_width_equal_the_single_block_formulation(
        interp, monkeypatch, layout, leaves):
    """70 columns: as one block (the fused kernel) and as three (routing
    pass + blocked pass, the root pass blocked too): every array of the
    tree and every row's leaf bit for bit, the same rounds at the same
    widths, and the round that spends the budget still routes only."""
    quant = layout != "bf16x2"
    kw = dict(rounds_slots=48 if quant else 25, has_cat=False,
              num_leaves=leaves, quant=quant, quant_int8=layout == "int8",
              quant_levels=4 if quant else 0)

    def grow():
        _drop_traces()
        return _grow_case(kw, quant=quant, columns=70, rows=HIST_BLK,
                          smooth=True, with_stats=True)

    whole = grow()
    monkeypatch.setattr(hist_mod, "_TILE_COLS", 32)
    wide = grow()
    _drop_traces()
    for a, b in zip(jax.tree.leaves(whole[4]), jax.tree.leaves(wide[4])):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    np.testing.assert_array_equal(whole[1], wide[1])
    counts = [int(n) for n in wide[5]["rounds"]]
    assert counts == [int(n) for n in whole[5]["rounds"]]
    assert counts[-2] == 1 and int(wide[4].num_nodes) == leaves - 1


# ------------------------------------------------ (d) whole models
def _wide_xy(rows=HIST_BLK, seed=9):
    """80 columns: 50 categorical, 56..75 sparse and nearly exclusive
    (EFB bundles them), both past the first 32-column block."""
    rs = np.random.RandomState(seed)
    X = rs.randn(rows, 80).astype(np.float32)
    X[:, 50] = rs.randint(0, 9, rows)
    for f in range(56, 76):
        X[:, f] = np.where(rs.randint(0, 20, rows) == f - 56,
                           rs.randn(rows), 0.0)
    y = (X[:, 0] + 0.8 * X[:, 40] + (X[:, 50] % 3 == 0)
         + 2.0 * X[:, 60] - 1.5 * X[:, 70] + 0.3 * rs.randn(rows))
    return X, y


@pytest.mark.parametrize("hist_dtype", ["int16", "int8"])
def test_models_at_width_equal_the_single_block_formulation(
        interp, monkeypatch, hist_dtype):
    X, y = _wide_xy()
    params = {"objective": "regression", "verbosity": -1, "num_leaves": 31,
              "max_bin": 63, "min_data_in_leaf": 5,
              "max_cat_to_onehot": 4,
              "tpu_growth_mode": "rounds", "tpu_hist_dtype": hist_dtype}
    if hist_dtype == "int8":
        params.update(use_quantized_grad=True, num_grad_quant_bins=4)

    def model():
        _drop_traces()
        ds = lgb.Dataset(X, label=y, categorical_feature=[50],
                         free_raw_data=False)
        before = _routed()
        bst = lgb.train(dict(params), ds, num_boost_round=3)
        g = bst._gbdt
        assert g.hist_dtype == hist_dtype and not g._force_sync
        assert g.spec.efb and g.spec.has_cat
        sched = rounds_mod.hist_schedule(g.spec, *g.dev["bins"].shape[::-1])
        return bst.model_to_string(), sched, _routed() - before

    whole, sched, routed = model()
    assert sched.fused and not sched.routed and sched.plan.blocks == 1
    monkeypatch.setattr(hist_mod, "_TILE_COLS", 32)
    wide, sched, routed_wide = model()
    _drop_traces()
    assert sched.routed and not sched.fused and sched.plan.blocks >= 2
    assert wide == whole
    assert routed_wide == routed == 3


# ------------------------------------------------ (e) the plan, by hand
def test_hist_plan_hand_numbers():
    """The slot-budget functions at the benchmark's shapes. Budget: a
    fifth of the 64 MiB scoped limit, 13,421,772 B, less the one-hot
    iota scratch (the rows of one matmul's one-hot tile x 2048 x 4 B on
    the compare path: a column's bins, or 128 rows at 33..64 bins, where
    two columns share the tile)."""
    budget = 13_421_772 - 128 * 2048 * 4
    assert budget == 12_373_196
    # 2,000 x 64, 3 channels. Whole table: one slot's block is 63
    # groups x 32 columns x 64 lanes x 3 x 4 B = 1,548,288 B -> 7 slots
    # a call, 4 chunks = 28 < 48, and the bins tile is 16.4 MB: blocked.
    assert _round_s_max(2000, 64, True, False) == budget // 1_548_288 == 7
    # One 32-column group of one slot: 3 x 32 x 64 x 4 = 24,576 B; 48
    # slots x 10 groups = 11,796,480 B fit, 11 do not: 63 groups go in
    # 7 equal blocks of 9 groups = 288 columns (10.6 MB resident)
    assert budget // (48 * 24_576) == 10
    assert hist_plan(48, 2000, 64, True) == HistPlan(48, 288, 2000)
    assert hist_plan(48, 2000, 64, True).blocks == 7
    # 63 bins (what max_bin=63 gives) and 33: the same plan, because a
    # column takes 64 lanes of the block at any of 33..64 bins; 7 x 288
    # = 2,016 columns multiplied
    for bins in (33, 63):
        assert _round_s_max(2000, bins, True, False) == 7
        assert hist_plan(48, 2000, bins, True) == HistPlan(48, 288, 2000)
    # the int8 path's iota scratch is a quarter (32 packed rows,
    # 262,144 B): 13,159,628 // (48 x 24,576) = 11 groups fit, 6 blocks
    # of 11 = 352 columns
    assert (13_421_772 - 32 * 2048 * 4) // (48 * 24_576) == 11
    assert hist_plan(48, 2000, 63, True, True) == HistPlan(48, 352, 2000)
    # 32 bins, one column per matmul: 12,288 B a group and slot, 22
    # groups would fit, the bins tile holds 16: 4 blocks of 512 columns
    assert hist_plan(48, 2000, 32, True) == HistPlan(48, 512, 2000)
    # the bf16x2 split (5 channels): 32 slots a call at most, so 48
    # slots are 24 + 24 over blocks sized for 32: 12,373,196 // (32 x
    # 40,960) = 9 groups
    p = hist_plan(48, 2000, 64, False)
    assert p == HistPlan(32, 288, 2000)
    assert _slot_chunks(48, p.s_max) == [(0, 24), (24, 24)]
    # 255 bins: 97,920 B a group and slot, budget 11,332,812 B: two
    # groups, 32 blocks of 64 columns
    assert (13_421_772 - 255 * 2048 * 4) // (48 * 97_920) == 2
    assert hist_plan(48, 2000, 255, True) == HistPlan(48, 64, 2000)
    # a few slots of a wide table: the bins tile bounds the block (512
    # columns, 8 MiB double-buffered), not the output
    assert hist_plan(1, 2000, 64, True) == HistPlan(1, 512, 2000)
    # the cells the benchmark had: the whole table, one feature block,
    # the slot chunks they always ran
    p = hist_plan(48, 137, 255, True)
    assert p.blocks == 1 and p.feat_block == 137 and 24 <= p.s_max < 32
    assert _slot_chunks(48, p.s_max) == [(0, 24), (24, 24)]
    assert _slot_chunks(16, p.s_max) == [(0, 16)]
    for int8 in (False, True):
        assert hist_plan(48, 28, 255, True, int8) == HistPlan(64, 28, 28)
    # more slot chunks than the fused kernel takes, and nothing to block
    # (one loop group): the whole table in chunks, as before
    assert hist_plan(10_000, 28, 255, True) == HistPlan(64, 28, 28)


def _spec(**kw):
    from lightgbm_tpu.learner import GrowerSpec

    base = dict(num_leaves=255, num_bins=63, max_depth=-1, rounds_slots=48,
                has_cat=False, quant=True, quant_levels=256)
    return GrowerSpec(**{**base, **kw})


def test_hist_schedule_of_the_cells(interp):
    """What rounds.hist_schedule resolves the benchmark's four shapes
    to: one feature block and the fused kernel at 28 and 137 columns,
    the routed round by seven blocks at 2,000 x 63, one kernel call a
    pass everywhere but the rank cell's 32- and 48-slot passes."""
    wide = rounds_mod.hist_schedule(_spec(), 196 * HIST_BLK, 2000)
    assert wide.routed and not wide.fused and wide.num_bins == 63
    assert wide.plan == HistPlan(48, 288, 2000) and wide.plan.blocks == 7
    assert wide.calls == (("root", 1), ("8", 1), ("16", 1), ("32", 1),
                          ("48", 1))
    higgs = rounds_mod.hist_schedule(_spec(num_bins=255), 512 * HIST_BLK, 28)
    assert higgs.fused and not higgs.routed and higgs.plan.blocks == 1
    assert higgs.calls == wide.calls
    rank = rounds_mod.hist_schedule(_spec(num_bins=255), 512 * HIST_BLK, 137)
    assert rank.fused and rank.plan.blocks == 1
    assert rank.calls == (("root", 1), ("8", 1), ("16", 1), ("32", 2),
                          ("48", 2))


def test_no_kernel_no_calls(monkeypatch):
    """Off the Pallas backends the schedule names no kernel call."""
    monkeypatch.delenv("LGBM_TPU_PALLAS_INTERPRET", raising=False)
    sched = rounds_mod.hist_schedule(_spec(), 196 * HIST_BLK, 2000)
    assert not sched.fused and not sched.routed and sched.calls == ()


# ------------------------------------- (f) gauges, span, warning text
@pytest.mark.parametrize("max_bin,per_matmul", [(15, 1), (63, 2)])
def test_gauges_and_span_of_a_wide_program(interp, narrow_tile, max_bin,
                                           per_matmul):
    from lightgbm_tpu import timer

    seen = []
    sink = lambda name, t0, dt: seen.append(name)  # noqa: E731
    timer.add_trace_sink(sink)
    try:
        rs = np.random.RandomState(2)
        X = rs.randn(HIST_BLK, 70).astype(np.float32)
        ds = lgb.Dataset(X, label=X[:, 3] + X[:, 66], free_raw_data=False)
        lgb.train({"objective": "regression", "verbosity": -1,
                   "num_leaves": 15, "max_bin": max_bin,
                   "min_data_in_leaf": 5,
                   "tpu_growth_mode": "rounds", "tpu_hist_dtype": "int16"},
                  ds, num_boost_round=2)
    finally:
        timer.remove_trace_sink(sink)
    assert "learner.hist_plan" in seen
    snap = default_registry().snapshot()
    blocks = snap["lgbmtpu_hist_feature_blocks"]
    assert blocks['{kernel="hist_nat_tpu"}'] == 3
    assert blocks['{kernel="route_round_tpu"}'] == 1
    assert blocks['{kernel="hist_round_tpu"}'] == 0
    cols = snap["lgbmtpu_hist_block_columns"]
    assert cols['{kernel="hist_nat_tpu"}'] == 32
    assert cols['{kernel="route_round_tpu"}'] == 14  # its split columns
    calls = snap["lgbmtpu_hist_calls_per_pass"]
    assert calls['{width="root"}'] == calls['{width="8"}'] \
        == calls['{width="14"}'] == 1
    # two columns per one-hot tile at 33..64 bins; the routing pass
    # builds no histogram
    per = snap["lgbmtpu_hist_columns_per_matmul"]
    assert per['{kernel="hist_nat_tpu"}'] == per_matmul
    assert per['{kernel="hist_round_tpu"}'] == 0
    assert per['{kernel="route_round_tpu"}'] == 0


def test_gate_warning_names_the_formulation_that_runs(monkeypatch):
    from lightgbm_tpu import log

    monkeypatch.setattr(hist_mod, "_gate_warned", set())
    seen = []
    monkeypatch.setattr(log, "warning", seen.append)
    monkeypatch.setenv("LGBM_TPU_PALLAS_INTERPRET", "1")
    assert not hist_mod._pallas_ok("take_small_tpu", HIST_BLK + 1)
    assert not hist_mod.can_hist_round(HIST_BLK, 10_000, 28, 255, True)
    assert "running the XLA formulation instead" in seen[0]
    assert "hist_round_tpu" in seen[1] and "hist_nat_tpu passes" in seen[1] \
        and "the XLA formulation instead" not in seen[1]


# --------------------------------------------- (g) Dataset.construct
def test_construct_by_slabs_equals_the_per_column_loop():
    """Bin mappers from blocked transposes on threads, float32 left as
    it is: the bounds and every bin equal the straightforward loop over
    strided float64 columns, with NaNs, a categorical, a constant and
    sparse columns that EFB bundles (the groups too)."""
    from lightgbm_tpu.binning import BinMapper, BinType
    from lightgbm_tpu.config import Config
    from lightgbm_tpu.dataset import BinnedDataset

    rs = np.random.RandomState(3)
    N, F = 9000, 70
    X = rs.randn(N, F).astype(np.float32)
    X[rs.rand(N, F) < 0.02] = np.nan
    X[:, 5] = rs.randint(0, 12, N)
    X[:, 33] = 1.0
    for f in range(40, 60):
        X[:, f] = np.where(rs.randint(0, 25, N) == f - 40, rs.randn(N), 0.0)
    cfg = Config({"max_bin": 63, "enable_bundle": False})
    got = BinnedDataset.from_numpy(X, cfg, categorical_feature=[5])
    X64 = X.astype(np.float64)
    assert len(got.mappers) == F
    for i, f in enumerate(got.used_features):
        want = BinMapper.from_sample(
            X64[:, f], total_sample_cnt=N, max_bin=63,
            min_data_in_bin=cfg.min_data_in_bin,
            bin_type=BinType.CATEGORICAL if f == 5 else BinType.NUMERICAL,
            max_cat_threshold=cfg.max_cat_threshold)
        np.testing.assert_array_equal(got.mappers[f].upper_bounds,
                                      want.upper_bounds)
        np.testing.assert_array_equal(got.bins[i],
                                      want.values_to_bins(X64[:, f]))
    both = [BinnedDataset.from_numpy(a, Config({"max_bin": 63}),
                                     categorical_feature=[5])
            for a in (X, X64)]
    assert both[0].bundle_layout is not None
    assert both[0].bundle_layout.groups == both[1].bundle_layout.groups
    np.testing.assert_array_equal(both[0].bins, both[1].bins)


def test_find_groups_bound_changes_no_group():
    """The inclusion-exclusion bound only skips intersections that
    would have failed the budget: the groups equal those of the loop
    that counts every one."""
    from lightgbm_tpu import bundling

    rs = np.random.RandomState(1)
    N, F = 20000, 60
    bins = np.zeros((F, N), np.uint8)
    for f in range(F):
        if f % 3 == 0:  # dense
            bins[f] = rs.randint(0, 16, N)
        else:  # sparse, some overlapping
            hit = rs.rand(N) < (0.004 if f % 3 == 1 else 0.3)
            bins[f] = np.where(hit, rs.randint(1, 16, N), 0)
    nb, mf, cat = [16] * F, [0] * F, [False] * F
    got = bundling.find_groups(bins, nb, mf, cat, 256)
    counted = []
    real_sum = np.sum

    def counting_sum(a, *args, **kw):
        counted.append(1)
        return real_sum(a, *args, **kw)

    # the reference: same loop with the bound made vacuous (N -> inf)
    import unittest.mock as mock

    with mock.patch.object(bundling.np, "sum", counting_sum):
        bundling.find_groups(bins, nb, mf, cat, 256)
    skipped = len(counted)
    lengths = sorted(len(g) for g in got)
    assert lengths[-1] > 1 and lengths[0] == 1  # some bundled, some not
    # brute force: every candidate intersection counted
    want = _find_groups_plain(bins, nb, mf, 256)
    assert got == want
    assert skipped < _find_groups_plain.counted


def _find_groups_plain(bins, num_bins, most_freq, max_group_bins):
    """bundling.find_groups as it was before the bound (numeric
    features only)."""
    from lightgbm_tpu.bundling import MAX_SEARCH_GROUP

    F, N = bins.shape
    budget = N // 10000
    masks = [bins[f] != most_freq[f] for f in range(F)]
    cnts = np.array([int(m.sum()) for m in masks])
    groups, gmask, gbins, gconf = [], [], [], []
    _find_groups_plain.counted = 0
    for f in (int(f) for f in np.argsort(-cnts, kind="stable")):
        width, placed = int(num_bins[f]) - 1, False
        if cnts[f] < N:
            searched = 0
            for gid in range(len(groups)):
                if searched >= MAX_SEARCH_GROUP:
                    break
                if gbins[gid] + width > max_group_bins:
                    continue
                rest = budget - gconf[gid]
                if rest < 0:
                    continue
                searched += 1
                _find_groups_plain.counted += 1
                cnt = int(np.sum(gmask[gid] & masks[f]))
                if cnt <= rest and cnt <= cnts[f] // 2:
                    groups[gid].append(f)
                    gmask[gid] |= masks[f]
                    gbins[gid] += width
                    gconf[gid] += cnt
                    placed = True
                    break
        if not placed:
            groups.append([f])
            gmask.append(masks[f].copy())
            gbins.append(1 + width)
            gconf.append(0)
    return groups
