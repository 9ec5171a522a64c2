"""Compile the main path's kernels at the ranking cell's real widths
for a DESCRIBED v5e, in this process, without a chip (the
on-chip-measurement guide's third rehearsal): what Mosaic refuses
here — scoped VMEM, tiling, a dynamic slice it cannot lower — costs no
chip time. Nothing runs, so this says nothing about results or times.

137 columns is past FEATURE_UNROLL: the kernels loop over 5 groups of
28 columns into a 3-D output block, with dynamic sublane reads of the
bins tile. Unrolled whole, the 8-slot kernel alone took 63-75 s to
compile here and the 32-slot one did not finish in 11 minutes (PR 26)."""

import os

import pytest

import jax
import jax.numpy as jnp

ROWS, FEATURES, BINS = 1 << 20, 137, 255


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no compiler here, or another process holds it
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.sharding import SingleDeviceSharding

    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def no_compile_cache():
    """A compile for a described device is written to the persistent
    cache but cannot be read back without a chip."""
    from jax.experimental.compilation_cache import compilation_cache as cc

    before = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", before)
    cc.reset_cache()


def _arg(one_chip, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)


def test_slot_chunks_at_137_columns_are_two_equal_calls():
    from lightgbm_tpu.learner.histogram import _round_s_max, _slot_chunks

    s_max = _round_s_max(FEATURES, BINS, True, False)
    assert 24 <= s_max < 32
    assert [sc for _, sc in _slot_chunks(32, s_max)] == [16, 16]
    assert [sc for _, sc in _slot_chunks(48, s_max)] == [24, 24]
    # the 16-slot rung is one call at 137 columns too
    assert _slot_chunks(16, s_max) == [(0, 16)]
    # 28 columns: every rung of the ladder is one call, as before
    s28 = _round_s_max(28, BINS, True, False)
    assert [_slot_chunks(s, s28) for s in (8, 16, 32, 48)] == [
        [(0, 8)], [(0, 16)], [(0, 32)], [(0, 48)]]


@pytest.mark.parametrize("features,slots,int8,bins", [
    (FEATURES, 8, False, BINS), (FEATURES, 16, False, BINS),
    (FEATURES, 24, False, BINS), (FEATURES, 24, True, BINS),
    # the Higgs cells' width: the ladder's 16-slot rung, both MXU types
    (28, 16, False, BINS), (28, 16, True, BINS),
    # max_bin=63 under one bins tile: the fused round with two columns
    # per one-hot tile (PR 31), unrolled whole and by loop groups
    (28, 48, False, 63), (28, 48, True, 63), (FEATURES, 48, False, 63),
])
def test_round_kernel_compiles(one_chip, no_compile_cache,
                               features, slots, int8, bins):
    from lightgbm_tpu.learner.pallas_hist import hist_round_tpu

    fn = jax.jit(lambda b, g, p, pr, oh: hist_round_tpu(
        b, g, p, pr, oh, slots, bins, 3, int8=int8, oh_shift=0))
    compiled = fn.lower(
        _arg(one_chip, (features, ROWS), jnp.int32),
        _arg(one_chip, (8, ROWS), jnp.float32),
        _arg(one_chip, (ROWS,), jnp.int32),
        _arg(one_chip, (slots, 16), jnp.int32),
        _arg(one_chip, (slots, features), jnp.float32)).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text and "hist_round_tpu" in text


@pytest.mark.parametrize("features,cat", [
    (28, False), (FEATURES, False), (28, True), (FEATURES, True)],
    ids=["28", "137", "28-cat", "137-cat"])
def test_route_kernel_compiles_in_one_call(one_chip, no_compile_cache,
                                           features, cat):
    """The routing-only pass of the round that spends the leaf budget,
    at the cells' full 48 slots, under VMEM_LIMIT_BYTES: ONE call also
    at 137 columns, where the histogram pass it replaces is two
    (_slot_chunks bounds the histogram block, which this pass lacks),
    and under a name the histogram kernels' trace readers
    (benchmark/rooflines/hist_round.KERNEL_PATTERN) do not match."""
    import re

    from lightgbm_tpu.learner.histogram import (
        _round_s_max, _slot_chunks, route_round)

    slots = 48
    chunks = _slot_chunks(slots, _round_s_max(features, BINS, True, False))
    assert len(chunks) == (2 if features == FEATURES else 1)
    args = [_arg(one_chip, (features, ROWS), jnp.int32),
            _arg(one_chip, (ROWS,), jnp.int32),
            _arg(one_chip, (slots, 16), jnp.int32),
            _arg(one_chip, (slots, features), jnp.float32)]
    if cat:
        args.append(_arg(one_chip, (slots, BINS), jnp.int8))
    fn = jax.jit(lambda b, p, pr, oh, cm=None: route_round(
        b, p, pr, oh, slots, BINS, cat_mask=cm))
    text = fn.lower(*args).compile().as_text()
    assert text.count("custom_call_target=\"tpu_custom_call\"") == 1
    assert "%route_round_tpu" in text
    assert not re.search(r"%(hist_round_tpu|hist_nat_tpu)\b", text)


# higgs-dp4.train (PR 32): one shard of 54,525,952 rows over the four
# chips of a v5e:2x2, 28 columns. Only the root pass is compiled here
# (3 s): the fused round at 28 x 255 takes 11 s at 8 slots and 73 s at
# 48, over this suite's clock (ROADMAP D13), and is left to
# `benchmark/tools/aot_kernels.py --config higgs-dp4` by hand.
DP4_SHARD_ROWS = 13 * (1 << 20)


@pytest.mark.parametrize("features,rows", [
    (FEATURES, ROWS), (28, DP4_SHARD_ROWS)], ids=["137", "dp4-shard"])
def test_root_kernel_compiles_at_137_columns(one_chip, no_compile_cache,
                                             features, rows):
    from lightgbm_tpu.learner.pallas_hist import hist_nat_tpu

    fn = jax.jit(lambda b, g, s: hist_nat_tpu(b, g, s, 1, BINS, nat_ch=3))
    compiled = fn.lower(
        _arg(one_chip, (features, rows), jnp.int32),
        _arg(one_chip, (8, rows), jnp.float32),
        _arg(one_chip, (rows,), jnp.int32)).compile()
    assert "hist_nat_tpu" in compiled.as_text()


# ------------------------------------------------ the wide cell (PR 30)
# epsilon-wide.train: 400,000 rows (196 row blocks) x 2,000 columns x 63
# bins. No call holds the table's (2000, 2048) bins tile: the histogram
# kernel runs by 7 feature blocks of 288 columns (9 loop groups of 16
# pairs: two 63-bin columns per one-hot tile, PR 31; the int8 operands'
# smaller scratch leaves room for 6 blocks of 352), the routing pass
# over the round's 48 split columns.
WIDE_ROWS, WIDE_FEATURES, WIDE_BINS = 196 * 2048, 2000, 63


@pytest.mark.parametrize("slots,int8", [
    (8, False), (48, False), (8, True), (48, True)])
def test_blocked_kernel_compiles_at_2000_columns(one_chip, no_compile_cache,
                                                 slots, int8):
    """The 48-slot bf16 call is the long one (5.5 s here; 43-50 s
    before PR 31, when every accumulate landed at a multiple of 63
    lanes and not on a whole 128-lane slab): its resident block is 48 x
    3 x 288 x 64 x 4 B = 10.6 MB of the 64 MiB scoped limit."""
    from lightgbm_tpu.learner.histogram import HistPlan, hist_plan
    from lightgbm_tpu.learner.pallas_hist import hist_nat_tpu

    plan = hist_plan(48, WIDE_FEATURES, WIDE_BINS, True, int8)
    block = 352 if int8 else 288
    assert plan == HistPlan(48, block, WIDE_FEATURES)
    assert plan.blocks == -(-WIDE_FEATURES // block)
    fn = jax.jit(lambda b, g, s: hist_nat_tpu(
        b, g, s, slots, WIDE_BINS, nat_ch=3, int8=int8,
        feat_block=plan.feat_block))
    text = fn.lower(
        _arg(one_chip, (WIDE_FEATURES, WIDE_ROWS), jnp.int32),
        _arg(one_chip, (8, WIDE_ROWS), jnp.float32),
        _arg(one_chip, (WIDE_ROWS,), jnp.int32)).compile().as_text()
    assert text.count("custom_call_target=\"tpu_custom_call\"") == 1
    assert "%hist_nat_tpu" in text
    # (blocks x groups, slots x channels, 32 columns x 64 lanes)
    kind = "s32" if int8 else "f32"
    groups = plan.blocks * block // 32
    assert groups == (66 if int8 else 63)
    assert f"{kind}[{groups},{slots * 3},2048]" in text


@pytest.mark.parametrize("cat", [False, True], ids=["plain", "cat"])
def test_routing_pass_compiles_over_the_split_columns(
        one_chip, no_compile_cache, cat):
    """A round at width: route_round_tpu over a (48, rows) table of the
    round's split columns, returning the rows' new leaves AND their
    histogram slots, in one call."""
    import re

    from lightgbm_tpu.learner.histogram import route_round

    slots = 48
    args = [_arg(one_chip, (slots, WIDE_ROWS), jnp.int32),
            _arg(one_chip, (WIDE_ROWS,), jnp.int32),
            _arg(one_chip, (slots, 16), jnp.int32),
            _arg(one_chip, (slots, slots), jnp.float32)]
    if cat:
        args.append(_arg(one_chip, (slots, WIDE_BINS), jnp.int8))
    fn = jax.jit(lambda b, p, pr, oh, cm=None: route_round(
        b, p, pr, oh, slots, WIDE_BINS, cat_mask=cm, with_slot=True))
    text = fn.lower(*args).compile().as_text()
    assert text.count("custom_call_target=\"tpu_custom_call\"") == 1
    assert "%route_round_tpu" in text
    assert not re.search(r"%(hist_round_tpu|hist_nat_tpu)\b", text)


def test_rank_marks_keep_their_names_in_the_compiled_program(
        one_chip, no_compile_cache, monkeypatch):
    """The device trace finds the LambdaRank gradient by the names of
    the two Pallas calls around it."""
    from lightgbm_tpu.learner import rank_marks

    monkeypatch.setattr(rank_marks, "_use_pallas", lambda: True)

    def f(score):
        score, = rank_marks.mark("rank_grad_begin", (score,))
        g = jnp.sort(score) * 2.0
        return rank_marks.mark("rank_grad_end", (g, g + 1.0))

    text = jax.jit(f).lower(
        _arg(one_chip, (4096,), jnp.float32)).compile().as_text()
    assert "%rank_grad_begin_tpu" in text and "%rank_grad_end_tpu" in text


# ------------------------------------- the whole grower (PR 33): how a
# round writes the histogram pool. The first whole-program compile for
# a described chip in the repo: the rounds grower at a narrow ROUTED
# shape (576 columns x 63 bins = 2 feature blocks of 288, 8 row blocks,
# 255 leaves, 48 slots, int16 channels), 20-30 s here; the wide cell's
# own shape (2,000 x 63 x 196 row blocks) takes 45-55 s and shows the
# same structure (PERF.md section 6, PR 33, has the recipe).
POOL_ROWS, POOL_FEATURES, POOL_LEAVES = 8 * 2048, 576, 255


_SHAPE = r"(\w+)\[([\d,]*)\]"


def _while_body_instructions(text, fused=True):
    """(opcode, result elements, line) of every instruction in the
    computations an optimised HLO module's `while` bodies reach; with
    `fused=False` only of those that run as instructions of their own
    (a fusion's inside is registers, not arrays)."""
    import math
    import re

    comps, cur = {}, None
    for line in text.splitlines():
        head = re.match(r"(?:ENTRY )?%?([\w.\-]+) \(.*\{\s*$", line)
        if head and not line.startswith(" "):
            cur = comps.setdefault(head.group(1), [])
        elif line.startswith("}"):
            cur = None
        elif cur is not None:
            cur.append(line)
    calls = re.compile(
        r"(?:calls|to_apply|body|condition|true_computation|"
        r"false_computation)=%?([\w.\-]+)|branch_computations=\{([^}]*)\}")
    todo = [m.group(1) for lines in comps.values() for ln in lines
            if " while(" in ln
            for m in [re.search(r"body=%?([\w.\-]+)", ln)]]
    assert todo, "no while loop in the compiled grower"
    seen = set()
    while todo:
        c = todo.pop()
        if c in seen or c not in comps:
            continue
        seen.add(c)
        for ln in comps[c]:
            if not fused and " fusion(" in ln:
                continue
            for one, many in calls.findall(ln):
                todo += [one] if one else [
                    n.strip().lstrip("%") for n in many.split(",")]
    instr = re.compile(r"\s*(?:ROOT )?%?[\w.\-]+ = \w+\[([\d,]*)\]"
                       r"(?:\{[^}]*\})? ([\w\-]+)\(")
    out = []
    for c in sorted(seen):
        for ln in comps[c]:
            m = instr.match(ln)
            if m and m.group(1):
                n = math.prod(int(d) for d in m.group(1).split(","))
                out.append((m.group(2), n, ln.strip()))
    return out


@pytest.fixture(scope="module")
def grower_text(one_chip, no_compile_cache):
    """The optimised HLO of the whole one-chip grower at the routed
    576 x 63 shape, a plain numerical table (no categorical column, no
    NaN bin, no monotone constraint): 25-30 s, compiled once."""
    import sys

    from lightgbm_tpu.config import Config
    from lightgbm_tpu.learner import GrowerSpec, make_split_params
    from lightgbm_tpu.learner.rounds import grow_tree_rounds, hist_schedule

    F, N, L, B = POOL_FEATURES, POOL_ROWS, POOL_LEAVES, WIDE_BINS
    spec = GrowerSpec(num_leaves=L, num_bins=B, max_depth=-1,
                      rounds_slots=48, quant=True, quant_levels=256,
                      has_cat=False, has_nan=False, has_mono=False)
    params = jax.tree.map(lambda x: _arg(one_chip, x.shape, x.dtype),
                          make_split_params(Config({})))
    cols = [_arg(one_chip, (F,), jnp.int32)] * 3 + [
        _arg(one_chip, (F,), jnp.bool_)]
    rows = [_arg(one_chip, (N,), jnp.float32)] * 3
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(sys.modules["lightgbm_tpu.learner.histogram"],
                   "_use_pallas", lambda: True)
        sched = hist_schedule(spec, N, F)
        assert sched.routed and sched.plan.blocks == 2
        text = jax.jit(
            lambda bins, nan, nb, mono, cat, g, h, m, fm, p, sc:
            grow_tree_rounds.__wrapped__(bins, nan, nb, mono, cat, g, h, m,
                                         fm, p, spec, gh_scale=sc)
        ).lower(_arg(one_chip, (F, N), jnp.int32), *cols, *rows,
                _arg(one_chip, (F,), jnp.bool_), params,
                _arg(one_chip, (2,), jnp.float32)).compile().as_text()
    assert "%route_round_tpu" in text and "%hist_nat_tpu" in text
    return text


def test_no_pool_sized_copy_in_a_round_of_the_compiled_grower(grower_text):
    """Inside the grower's while body nothing of the histogram pool's
    size is copied or transposed: a round reads its parents' rows,
    and writes its children's rows into the loop's carry in place. The
    parent of PR 33 had SEVEN such ops here (the pool turned to the
    scatters' layout before the ladder's switch, turned back in every
    rung, copied once more in the 32- and 48-slot rungs): 41 ms of the
    wide cell's 791 ms a tree."""
    body = _while_body_instructions(grower_text)
    pool = POOL_LEAVES * 3 * POOL_FEATURES * WIDE_BINS
    assert any(n == pool for _, n, _ in body)  # the carry is in there
    moved = [ln for op, n, ln in body
             if n == pool and op in ("copy", "transpose")]
    assert not moved, "\n".join(ln[:200] for ln in moved)


def test_the_child_search_moves_no_candidates_in_the_compiled_grower(
        grower_text):
    """The children's split search finds its winner by reductions over
    the candidates where they lie: no gather over a round's children x
    columns x bins cells (the parent took four (columns, bins) arrays a
    child along the bin axis for the tie-break), no array of them with
    the directions as a 3-wide minor dimension (the parent stacked
    three), no mask of them written out as an array (the parent: four a
    round), and XLA's estimated cycles under `lgbm.learner.split_search`
    at most a third of the parent's 6,327,147 at this shape: this tree
    reads 1,035,884 (PR 35; a prototype of the issue read 1,009,138)."""
    import re

    cells = {2 * s * POOL_FEATURES * WIDE_BINS * k
             for s in (8, 16, 32, 48) for k in (1, 3)}
    shapes = {name: dims for name, _, dims in re.findall(
        r"%?([\w.\-]+) = " + _SHAPE, grower_text)}

    def elements(dims):
        n = 1
        for d in filter(None, dims.split(",")):
            n *= int(d)
        return n

    gathers = []
    for op, n, ln in _while_body_instructions(grower_text):
        if op != "gather":
            continue
        args = re.search(r" gather\(([^)]*)\)", ln).group(1)
        sizes = [n] + [elements(shapes.get(a.strip().lstrip("%"), ""))
                       for a in args.split(",")]
        if cells & set(sizes):
            gathers.append(ln)
    assert not gathers, "\n".join(ln[:200] for ln in gathers)
    arrays = _while_body_instructions(grower_text, fused=False)
    assert any(n in cells for _, n, _ in arrays)  # the candidates are there
    stacked = [ln for _, n, ln in arrays if n in cells
               and re.search(r"= \w+\[[\d,]*,3\]", ln)]
    assert not stacked, "\n".join(ln[:200] for ln in stacked)
    masks = [ln for _, n, ln in arrays
             if n in cells and re.search(r"= pred\[", ln)]
    assert not masks, "\n".join(ln[:200] for ln in masks)
    cycles = 0
    for ln in grower_text.splitlines():
        cyc = re.search(r'estimated_cycles":"(\d+)', ln)
        name = re.search(r'op_name="([^"]*)"', ln)
        if cyc and name and re.findall(
                r"lgbm\.([a-z_.]+)", name.group(1))[-1:] == [
                    "learner.split_search"]:
            cycles += int(cyc.group(1))
    assert 0 < cycles <= 6_327_147 // 3, cycles


def test_categorical_grower_compiles_at_the_expo_shape(one_chip,
                                                       no_compile_cache):
    """The whole one-chip grower with every fact a table of categorical
    columns and missing values switches on (`has_cat`, `has_nan`,
    `cat_subset`: the round kernels' category test at 48 slots x 255
    bins, the routing-only round with its masks, the sorted-subset
    scan) at the `expo-cat` cell's 8 columns, for a described v5e (PR
    36: 21 s; Mosaic refuses nothing). The fused kernel holds the whole
    table: no routed rounds, one call a pass."""
    import sys

    from lightgbm_tpu.config import Config
    from lightgbm_tpu.learner import GrowerSpec, make_split_params
    from lightgbm_tpu.learner.rounds import grow_tree_rounds, hist_schedule

    F, N, L, B = 8, 1 << 20, 255, 255
    spec = GrowerSpec(num_leaves=L, num_bins=B, max_depth=-1,
                      rounds_slots=48, quant=True, quant_levels=256,
                      has_cat=True, has_nan=True, cat_subset=True,
                      has_mono=False)
    assert spec.search == (True, True, True, False)
    params = jax.tree.map(lambda x: _arg(one_chip, x.shape, x.dtype),
                          make_split_params(Config({})))
    cols = [_arg(one_chip, (F,), jnp.int32)] * 3 + [
        _arg(one_chip, (F,), jnp.bool_)]
    rows = [_arg(one_chip, (N,), jnp.float32)] * 3
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(sys.modules["lightgbm_tpu.learner.histogram"],
                   "_use_pallas", lambda: True)
        sched = hist_schedule(spec, N, F)
        assert sched.fused and not sched.routed
        assert all(n == 1 for _, n in sched.calls)
        text = jax.jit(
            lambda bins, nan, nb, mono, cat, g, h, m, fm, p, sc:
            grow_tree_rounds.__wrapped__(bins, nan, nb, mono, cat, g, h, m,
                                         fm, p, spec, gh_scale=sc)
        ).lower(_arg(one_chip, (F, N), jnp.int32), *cols, *rows,
                _arg(one_chip, (F,), jnp.bool_), params,
                _arg(one_chip, (2,), jnp.float32)).compile().as_text()
    # four ladder rungs' fused kernels with their (slots, 255) s8 masks,
    # and the routing-only round
    assert "%hist_round_tpu" in text and "%route_round_tpu" in text
    assert "s8[48,255]" in text


@pytest.mark.parametrize("has_cat,table_rows", [(True, 8 + 16), (False, 8)],
                         ids=["cat", "plain"])
def test_valid_traversal_compiles_at_the_expo_shape(one_chip,
                                                    no_compile_cache,
                                                    has_cat, table_rows):
    """`tree.traverse_tree_bins` over the `expo-cat` cell's 2^20 valid
    rows x 8 columns for a described v5e: with categorical columns the
    ONE `take_small_tpu` a level returns the node's 16 category words
    under its 8 parameters (PR 37), and the compiled walk holds no
    gather; a numerical table keeps the 8-row call."""
    import sys

    from lightgbm_tpu.learner import GrowerSpec
    from lightgbm_tpu.parallel.data_parallel import _tree_arrays_structure
    from lightgbm_tpu.tree import traverse_tree_bins

    G, N, L, B = 8, 1 << 20, 255, 255
    arrays = jax.tree.map(
        lambda x: _arg(one_chip, x.shape, x.dtype),
        _tree_arrays_structure(
            GrowerSpec(num_leaves=L, num_bins=B, max_depth=-1)))
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(sys.modules["lightgbm_tpu.learner.histogram"],
                   "_use_pallas", lambda: True)
        text = jax.jit(
            lambda a, b, n: traverse_tree_bins(a, b, n, has_cat=has_cat)
        ).lower(arrays, _arg(one_chip, (G, N), jnp.int32),
                _arg(one_chip, (G,), jnp.int32)).compile().as_text()
    calls = [ln for ln in text.splitlines()
             if "custom-call(" in ln and "take_small_tpu" in ln]
    assert calls and all(f"f32[{table_rows},{N}]" in ln for ln in calls)
    assert " gather(" not in text
