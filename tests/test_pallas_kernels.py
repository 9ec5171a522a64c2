"""Pallas TPU kernel coverage OFF hardware.

`LGBM_TPU_PALLAS_INTERPRET=1` makes histogram.py dispatch to the real
pallas kernels under `pallas_call(interpret=True)` on CPU, so the MXU
one-hot formulation and the slot-packed natural-order kernel are
exercised by CI and compared against the
XLA einsum fallback — kernel drift fails the suite instead of waiting
for a live chip (the reference analog: running CUDA learner logic
through the CPU build's tests, test_consistency.py)."""

import numpy as np
import pytest

import jax.numpy as jnp

from lightgbm_tpu.learner.histogram import (
    HIST_BLK,
    build_gh8,
    _hist_fallback,
    _hist_nat_fallback,
)


@pytest.fixture
def interp(monkeypatch):
    """Force the interpreted-pallas dispatch AND clear jit caches at
    both ends: the growers' jit cache keys on (spec, shapes), not the
    env, so a cached fallback trace from a neighboring test would be
    silently reused under interp=1 (and vice versa), making the
    interpret-vs-fallback comparisons vacuous."""
    import jax

    jax.clear_caches()
    monkeypatch.setenv("LGBM_TPU_PALLAS_INTERPRET", "1")
    yield
    jax.clear_caches()


@pytest.fixture(scope="module")
def data():
    rs = np.random.RandomState(3)
    N, F, B = 2 * HIST_BLK, 5, 64
    bins = jnp.asarray(rs.randint(0, B, (F, N)).astype(np.int32))
    gh8 = build_gh8(
        jnp.asarray(rs.randn(N).astype(np.float32)),
        jnp.asarray((rs.rand(N) + 0.5).astype(np.float32)),
        jnp.ones(N, jnp.float32),
    )
    return N, F, B, bins, gh8


def test_hist_tpu_interpret_matches_fallback(interp, data):
    N, F, B, bins, gh8 = data
    from lightgbm_tpu.learner.histogram import histogram

    out = histogram(bins, gh8, B)  # dispatches to interpreted hist_tpu
    ref = _hist_fallback(bins, gh8, B)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-3, rtol=1e-4)


def test_hist_nat_tpu_interpret_matches_fallback(interp, data):
    N, F, B, bins, gh8 = data
    from lightgbm_tpu.learner.histogram import hist_nat_slots

    rs = np.random.RandomState(4)
    S = 7
    slot = jnp.asarray(rs.randint(0, S + 1, N).astype(np.int32))
    out = hist_nat_slots(bins, gh8, slot, S, B)
    ref = _hist_nat_fallback(bins, gh8, slot, S, B)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-3, rtol=1e-4)


@pytest.mark.parametrize("oh_shift", [0, 4, 7])
def test_hist_nat_int8_interpret_exact(interp, data, oh_shift):
    """Quantized int8 mode: s8 x s8 -> s32 sums are EXACT integers and
    must equal the f32 fallback bit-for-bit (integer levels within
    +/-127 sum exactly in both paths at this size). Every SWAR one-hot
    scale (byte values 128/8/1, histogram.int8_oh_shift policy) must
    rescale back to identical sums."""
    N, F, B, bins, _ = data
    from lightgbm_tpu.learner.histogram import (
        build_gh8_quant,
        hist_nat_slots,
    )

    rs = np.random.RandomState(5)
    gq = jnp.asarray(rs.randint(-2, 3, N).astype(np.float32))
    hq = jnp.asarray(rs.randint(0, 5, N).astype(np.float32))
    gh8q = build_gh8_quant(gq, hq, jnp.ones(N, jnp.float32))
    S = 6
    slot = jnp.asarray(rs.randint(0, S + 1, N).astype(np.int32))
    out = hist_nat_slots(bins, gh8q, slot, S, B, quant=True, int8=True,
                         oh_shift=oh_shift)
    ref = _hist_nat_fallback(bins, gh8q, slot, S, B, quant=True)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))


def test_take_segsum_large_table_falls_back(interp, data):
    """ADVICE r4 medium: the take/seg_sum kernels materialize an
    (L, blk) one-hot in VMEM — at num_leaves-scale L (config allows up
    to 131072) that tile alone exceeds the scoped budget. Above
    _TAKE_L_CAP both must route to the XLA path and stay correct."""
    N, F, B, bins, _ = data
    from lightgbm_tpu.learner.histogram import (
        _TAKE_L_CAP,
        seg_sum,
        take_cols,
    )

    rs = np.random.RandomState(8)
    L = _TAKE_L_CAP + 100
    tab = jnp.asarray(rs.randn(2, L).astype(np.float32))
    idx = jnp.asarray(rs.randint(-1, L, N).astype(np.int32))
    out = np.asarray(take_cols(tab, idx))  # must not hit the kernel
    ii = np.asarray(idx)
    ref = np.where(ii[None, :] >= 0,
                   np.asarray(tab)[:, np.clip(ii, 0, L - 1)], 0.0)
    np.testing.assert_allclose(out, ref, rtol=1e-6)

    vals = jnp.asarray(rs.randn(2, N).astype(np.float32))
    s = np.asarray(seg_sum(vals, idx, L))
    assert s.shape == (2, L)
    nz = np.unique(ii[ii >= 0])[:20]
    for l in nz:
        np.testing.assert_allclose(
            s[:, l], np.asarray(vals)[:, ii == l].sum(axis=1),
            atol=1e-3, rtol=1e-5)


def test_int8_oh_shift_policy():
    from lightgbm_tpu.learner.histogram import int8_oh_shift

    assert int8_oh_shift(10 ** 6, 4) == 0  # bench shape: full speed
    assert int8_oh_shift(10 ** 6, 127) == 4  # 1M x 127 x 8 < 2^31
    assert int8_oh_shift(18 * 10 ** 6, 127) is None  # ADVICE r4 wrap
    assert int8_oh_shift(16 * 10 ** 6, 127) == 7


def _grow_case(spec_kw, quant=False, columns=6, rows=HIST_BLK,
               smooth=False, with_stats=False):
    """Grow one tree on a synthetic set; returns (leaf_values, row_leaf,
    node_feature, node_bin), and with_stats=True the whole TreeArrays
    and the grower's stats after them. smooth=True makes the gradients
    a smooth function of the columns plus noise, not noise alone: the
    splits then halve their leaves, so every early leaf can split
    again."""
    from lightgbm_tpu.config import Config
    from lightgbm_tpu.dataset import BinnedDataset
    from lightgbm_tpu.learner import GrowerSpec, grow_tree, make_split_params

    rs = np.random.RandomState(11)
    X = rs.randn(rows, columns).astype(np.float32)
    cfg = Config({"max_bin": 63, "min_data_in_leaf": 5})
    ds = BinnedDataset.from_numpy(X, cfg)
    d = ds.device_arrays()
    N = ds.num_rows_padded()
    F = ds.num_used_features
    if quant:
        grad = rs.randint(-2, 3, N).astype(np.float32)
        hess = rs.randint(1, 4, N).astype(np.float32)
        gh_scale = jnp.asarray(np.float32([0.125, 0.25]))
    else:
        grad = rs.randn(N).astype(np.float32)
        hess = np.full(N, 0.25, np.float32)
        gh_scale = None
    if smooth:
        z = np.zeros(N, np.float32)
        z[:rows] = np.tanh(X @ rs.randn(columns).astype(np.float32) / 2)
        grad = (np.clip(np.rint(2 * z + 0.4 * grad), -2, 2) if quant
                else z + 0.05 * grad)
    grad = jnp.asarray(grad) * d["valid"]
    hess = jnp.asarray(hess) * d["valid"]
    spec_kw = dict(spec_kw)  # callers reuse their dict across runs
    n_leaves = spec_kw.pop("num_leaves", 15)
    params = make_split_params(Config({"num_leaves": n_leaves, "max_bin": 63,
                                       "min_data_in_leaf": 5}))
    spec = GrowerSpec(num_leaves=n_leaves, num_bins=ds.max_num_bin,
                      max_depth=-1, **spec_kw)
    tree, rl, *stats = grow_tree(
        d["bins"], d["nan_bin"], d["num_bins"], d["mono"], d["is_cat"],
        grad, hess, d["valid"], jnp.ones(F, bool), params, spec,
        valid=d["valid"], gh_scale=gh_scale, with_stats=with_stats,
    )
    out = (np.asarray(tree.leaf_value), np.asarray(rl),
           np.asarray(tree.node_feature), np.asarray(tree.node_bin))
    return out + (tree, *stats) if with_stats else out


def _ladder_rounds(widths, leaves, rows):
    """Rounds per ladder width, then the routing-only rounds, of a tree
    whose every leaf can split: candidates double until the slot count
    or the leaf budget binds; at a small row count a round takes at
    most half the budget left (rounds.py tail_exact); the round that
    spends the last of the budget builds no histogram."""
    from lightgbm_tpu.learner.rounds import TAIL_EXACT_ROWS

    counts, splits, live = [0] * (len(widths) + 1), 0, 1
    while splits < leaves - 1:
        budget = leaves - 1 - splits
        n = min(budget, live)
        if rows <= TAIL_EXACT_ROWS:
            n = min(n, max((budget + 1) // 2, 1))
        if budget <= min(n, widths[-1]):
            counts[-1] += 1
        else:
            counts[sum(n > w for w in widths[:-1])] += 1
        n = min(n, widths[-1])
        splits, live = splits + n, live + n
    return counts


@pytest.mark.parametrize("layout,leaves,rows", [
    ("bf16x2", 63, HIST_BLK), ("int16", 63, HIST_BLK),
    ("int8", 63, HIST_BLK), ("int16", 255, 4 * HIST_BLK),
])
def test_fused_round_ladder_matches_fallback(interp, monkeypatch, layout,
                                             leaves, rows):
    """The slot ladder (rounds.LADDER_RUNGS below the slot count, then
    it: 8/16/32/48 for the 3-channel layouts, 8/16/25 for bf16x2): a
    grow passes through the 16-candidate round, runs as many rounds at
    each width as its leaf budget implies, equals the single-width
    tree bit for bit, and reproduces the XLA path's tree."""
    import jax

    from lightgbm_tpu.learner import rounds as rounds_mod

    quant = layout != "bf16x2"
    slots = 48 if quant else 25
    kw = dict(rounds_slots=slots, has_cat=False, num_leaves=leaves,
              quant=quant, quant_int8=layout == "int8",
              quant_levels=4 if quant else 0)
    fused = _grow_case(kw, quant=quant, rows=rows, smooth=True,
                       with_stats=True)
    widths = tuple(int(w) for w in fused[5]["widths"])
    assert widths == ((8, 16, 32, 48) if quant else (8, 16, 25))
    counts = [int(n) for n in fused[5]["rounds"]]
    assert counts[:-1] == _ladder_rounds(widths, leaves, rows)
    assert counts[-1] == sum(counts[:-1]) and counts[1] > 0
    assert counts[-2] == 1  # the last round routed rows only
    assert int(fused[4].num_nodes) == leaves - 1

    with monkeypatch.context() as m:
        m.setattr(rounds_mod, "LADDER_RUNGS", ())
        jax.clear_caches()  # the ladder is read when the grower is traced
        single = _grow_case(kw, quant=quant, rows=rows, smooth=True,
                            with_stats=True)
    assert tuple(int(w) for w in single[5]["widths"]) == (slots,)
    for a, b in zip(jax.tree.leaves(fused[4]), jax.tree.leaves(single[4])):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    np.testing.assert_array_equal(fused[1], single[1])
    monkeypatch.setenv("LGBM_TPU_PALLAS_INTERPRET", "0")
    jax.clear_caches()
    fb = _grow_case(kw, quant=quant, rows=rows, smooth=True)
    np.testing.assert_allclose(fused[0], fb[0], atol=5e-4)
    np.testing.assert_array_equal(fused[2], fb[2])
    np.testing.assert_array_equal(fused[3], fb[3])


def test_fused_round_efb_matches_fallback(interp):
    """The fused kernel's in-kernel EFB decode (params cols 7-9) must
    match decode_feature_bins on a genuinely bundled dataset."""
    import os

    import jax

    import lightgbm_tpu as lgb

    rs = np.random.RandomState(21)
    n = HIST_BLK
    blocks = []
    for b in range(3):
        z = np.zeros((n, 6))
        idx = rs.randint(0, 6, n)
        z[np.arange(n), idx] = rs.rand(n) + 0.5
        on = rs.rand(n) < 0.3
        z[~on] = 0.0
        blocks.append(z)
    X = np.hstack([rs.randn(n, 2)] + blocks)
    w = rs.randn(X.shape[1])
    y = (X @ w + 0.3 * rs.randn(n) > 0).astype(np.float64)
    params = {"objective": "binary", "num_leaves": 15, "max_bin": 63,
              "min_data_in_leaf": 5, "verbosity": -1,
              "tpu_growth_mode": "rounds", "tpu_round_slots": 8}

    def run():
        ds = lgb.Dataset(X, label=y, free_raw_data=False)
        bst = lgb.train(dict(params), ds, num_boost_round=3)
        assert ds._binned.bundle_layout is not None  # bundling engaged
        return bst.predict(X)

    p_fused = run()
    os.environ["LGBM_TPU_PALLAS_INTERPRET"] = "0"
    jax.clear_caches()
    p_fb = run()
    np.testing.assert_allclose(p_fused, p_fb, atol=1e-5, rtol=1e-5)


def test_fused_round_categorical_matches_fallback(interp):
    """Categorical splits inside the fused kernel (per-slot category
    masks contracted against the row's own-bin one-hot) must reproduce
    the XLA path's trees through the train API."""
    import os

    import jax

    import lightgbm_tpu as lgb

    rs = np.random.RandomState(31)
    n = HIST_BLK
    Xc = rs.randint(0, 12, (n, 2)).astype(np.float64)
    Xn = rs.randn(n, 4)
    X = np.column_stack([Xc, Xn])
    y = ((Xc[:, 0] % 3 == 0).astype(float) * 2 + Xn[:, 0]
         + 0.3 * rs.randn(n) > 1).astype(np.float64)
    params = {"objective": "binary", "num_leaves": 15, "max_bin": 63,
              "min_data_in_leaf": 5, "verbosity": -1,
              "categorical_feature": "0,1",
              "tpu_growth_mode": "rounds", "tpu_round_slots": 8}

    def run():
        ds = lgb.Dataset(X, label=y, free_raw_data=False)
        bst = lgb.train(dict(params), ds, num_boost_round=3)
        return bst.predict(X)

    p_fused = run()
    os.environ["LGBM_TPU_PALLAS_INTERPRET"] = "0"
    jax.clear_caches()
    p_fb = run()
    np.testing.assert_allclose(p_fused, p_fb, atol=1e-5, rtol=1e-5)


@pytest.mark.parametrize("quant,int8", [(False, False), (True, False),
                                        (True, True)])
def test_fused_round_grower_matches_fallback(interp, quant, int8):
    """The fused partition+histogram kernel (has_cat=False dispatches
    rounds.py onto pallas_hist._round_kernel) must reproduce the XLA
    path's tree EXACTLY: same splits, same partition, same leaves."""
    import os

    import jax

    kw = dict(rounds_slots=8, has_cat=False, quant=quant,
              quant_int8=int8, quant_levels=4 if quant else 0)
    fused = _grow_case(kw, quant=quant)
    os.environ["LGBM_TPU_PALLAS_INTERPRET"] = "0"
    jax.clear_caches()  # the grower jit baked the interpreted dispatch
    fb = _grow_case(kw, quant=quant)
    np.testing.assert_allclose(fused[0], fb[0], atol=5e-4)
    assert (fused[1] == fb[1]).mean() > 0.999
    np.testing.assert_array_equal(fused[2], fb[2])
    np.testing.assert_array_equal(fused[3], fb[3])


def test_take_and_segsum_interpret(interp, data):
    """take_cols / seg_sum one-hot contraction paths vs plain XLA."""
    N, F, B, bins, _ = data
    from lightgbm_tpu.learner.histogram import seg_sum, take_cols

    rs = np.random.RandomState(6)
    L = 31
    tab = jnp.asarray(rs.randn(3, L).astype(np.float32))
    idx = jnp.asarray(rs.randint(-1, L, N).astype(np.int32))  # -1 = dead
    out = np.asarray(take_cols(tab, idx))
    ref = np.where(np.asarray(idx)[None, :] >= 0,
                   np.asarray(tab)[:, np.clip(np.asarray(idx), 0, L - 1)],
                   0.0)
    np.testing.assert_allclose(out, ref, rtol=1e-6)

    vals = jnp.asarray(rs.randn(2, N).astype(np.float32))
    s = np.asarray(seg_sum(vals, idx, L))
    refsum = np.zeros((2, L), np.float32)
    ii = np.asarray(idx)
    for l in range(L):
        refsum[:, l] = np.asarray(vals)[:, ii == l].sum(axis=1)
    np.testing.assert_allclose(s, refsum, atol=1e-3, rtol=1e-5)


def test_nat_grower_with_interpreted_kernel(interp):
    """End-to-end: the natural-order rounds grower with the interpreted
    slot-packed kernel matches the einsum-fallback grower exactly."""
    from lightgbm_tpu.config import Config
    from lightgbm_tpu.dataset import BinnedDataset
    from lightgbm_tpu.learner import GrowerSpec, grow_tree, make_split_params

    rs = np.random.RandomState(9)
    X = rs.randn(HIST_BLK, 6).astype(np.float32)
    cfg = Config({"max_bin": 63, "min_data_in_leaf": 5})
    ds = BinnedDataset.from_numpy(X, cfg)
    d = ds.device_arrays()
    N = ds.num_rows_padded()
    F = ds.num_used_features
    grad = jnp.asarray(rs.randn(N).astype(np.float32)) * d["valid"]
    hess = jnp.ones(N, jnp.float32) * 0.25 * d["valid"]
    params = make_split_params(Config({"num_leaves": 15, "max_bin": 63,
                                       "min_data_in_leaf": 5}))
    spec = GrowerSpec(num_leaves=15, num_bins=ds.max_num_bin, max_depth=-1,
                      rounds_slots=8)

    def run():
        tree, rl = grow_tree(
            d["bins"], d["nan_bin"], d["num_bins"], d["mono"], d["is_cat"],
            grad, hess, d["valid"], jnp.ones(F, bool), params, spec,
            valid=d["valid"],
        )
        return np.asarray(tree.leaf_value), np.asarray(rl)

    lv_interp, rl_interp = run()
    import os

    import jax

    os.environ["LGBM_TPU_PALLAS_INTERPRET"] = "0"
    jax.clear_caches()  # the grower jit baked the interpreted dispatch
    lv_fb, rl_fb = run()
    np.testing.assert_allclose(lv_interp, lv_fb, atol=5e-4)
    assert (rl_interp == rl_fb).mean() > 0.999


# ------------------------------------------- int8 SWAR one-hot (ISSUE 12)
@pytest.mark.parametrize("B", [18, 63, 255])
def test_hist_nat_int8_swar_interpret_exact(interp, data, B):
    """Byte-SWAR one-hot (4 bins per i32 lane): integer sums must equal
    the f32 fallback bit-for-bit at bin counts that are not multiples
    of 4 (the packed-row padding), the cells' own 255 among them, for
    every marker shift the growers pass."""
    N, F, _, _, _ = data
    from lightgbm_tpu.learner.histogram import (
        build_gh8_quant,
        hist_nat_slots,
    )

    rs = np.random.RandomState(12)
    bins = jnp.asarray(rs.randint(0, B, (F, N)).astype(np.int32))
    gq = jnp.asarray(rs.randint(-8, 9, N).astype(np.float32))
    hq = jnp.asarray(rs.randint(0, 17, N).astype(np.float32))
    gh8q = build_gh8_quant(gq, hq, jnp.ones(N, jnp.float32))
    S = 6
    slot = jnp.asarray(rs.randint(0, S + 1, N).astype(np.int32))
    ref = np.asarray(_hist_nat_fallback(bins, gh8q, slot, S, B, quant=True))
    for oh_shift in (0, 4, 7):
        out = hist_nat_slots(bins, gh8q, slot, S, B, quant=True, int8=True,
                             oh_shift=oh_shift)
        np.testing.assert_array_equal(np.asarray(out), ref)


def test_swar_onehot_unpack_ordering():
    """The byte unpack (i32 -> 4 x s8 bitcast onto sublanes) must place
    packed row j's byte m at bin 4*j + m — a swapped order would score
    every bin into a neighbor — and slice the padding rows off when the
    bin count is not a multiple of 4. pltpu.bitcast only evaluates
    inside a kernel, so the helper runs under an interpreted
    pallas_call."""
    import jax
    from jax.experimental import pallas as pl

    from lightgbm_tpu.learner.pallas_hist import _swar_onehot

    B, blk = 18, 256
    rs = np.random.RandomState(13)
    bins_row = jnp.asarray(rs.randint(0, B, (1, blk)).astype(np.int32))
    hit = (np.arange(B)[:, None]
           == np.asarray(bins_row)[0][None, :]).astype(np.int8)
    for oh_shift, marker in ((0, -128), (4, 8), (7, 1)):
        def kernel(bins_ref, out_ref, oh_shift=oh_shift):
            out_ref[...] = _swar_onehot((bins_ref[...],), B, blk, oh_shift)

        oh = pl.pallas_call(
            kernel,
            out_shape=jax.ShapeDtypeStruct((B, blk), jnp.int8),
            interpret=True,
        )(bins_row)
        np.testing.assert_array_equal(np.asarray(oh), hit * marker)


# -------------------------------------- chunked fused round (ISSUE 12)
def test_fused_round_chunked_matches_fallback(interp, monkeypatch):
    """When S exceeds the one-chunk VMEM schedule, hist_round re-streams
    the slot axis and composes disjoint per-chunk partition deltas; the
    chunked kernel must reproduce the XLA path's tree exactly. Forced
    by shrinking _round_s_max to 3 (rounds_slots=8 -> 3 chunks)."""
    import os
    import sys

    import jax

    # learner/__init__ re-exports the histogram FUNCTION, shadowing the
    # submodule on attribute import — go through sys.modules
    hist_mod = sys.modules["lightgbm_tpu.learner.histogram"]
    monkeypatch.setattr(hist_mod, "_round_s_max",
                        lambda *a, **k: 3)
    kw = dict(rounds_slots=8, has_cat=False, quant=True,
              quant_levels=4)
    fused = _grow_case(kw, quant=True)
    os.environ["LGBM_TPU_PALLAS_INTERPRET"] = "0"
    jax.clear_caches()
    fb = _grow_case(kw, quant=True)
    np.testing.assert_allclose(fused[0], fb[0], atol=5e-4)
    assert (fused[1] == fb[1]).mean() > 0.999
    np.testing.assert_array_equal(fused[2], fb[2])
    np.testing.assert_array_equal(fused[3], fb[3])


def test_row_mesh_wraps_kernels_per_shard(interp):
    """On a data mesh the per-row kernels the boosting step calls
    OUTSIDE the grower's shard_map must wrap themselves per shard —
    Mosaic kernels cannot be partitioned by GSPMD (the four-chip
    bring-up failure, PR 21). Same numbers as the unwrapped call, every
    pallas_call inside a shard_map, and the replicated route for row
    counts that do not split into HIST_BLK-aligned shards."""
    import jax

    from lightgbm_tpu.learner.histogram import (
        row_mesh, seg_sum, take_cols)
    from lightgbm_tpu.parallel.data_parallel import make_mesh

    mesh = make_mesh()
    n_dev = int(mesh.devices.size)
    rs = np.random.RandomState(4)
    L = 15
    tab = jnp.asarray(rs.randn(3, L).astype(np.float32))

    def both(idx, vals):
        return take_cols(tab, idx), seg_sum(vals, idx, L)

    for N in (n_dev * HIST_BLK, HIST_BLK):  # sharded / replicated
        idx = jnp.asarray(rs.randint(-1, L + 1, N).astype(np.int32))
        vals = jnp.asarray(rs.randint(-4, 5, (2, N)).astype(np.float32))
        plain = both(idx, vals)

        def on_mesh(idx, vals):
            with row_mesh(mesh):
                return both(idx, vals)

        got = jax.jit(on_mesh)(idx, vals)
        for a, b in zip(got, plain):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        assert _pallas_calls(jax.make_jaxpr(both)(idx, vals)) == \
            {False: 2}
        assert _pallas_calls(jax.make_jaxpr(on_mesh)(idx, vals)) == \
            {True: 2}


def _pallas_calls(closed) -> dict:
    """{inside a shard_map?: count} over every pallas_call of a jaxpr."""
    from jax.extend.core import ClosedJaxpr, Jaxpr

    counts: dict = {}

    def walk(jaxpr, inside):
        for e in jaxpr.eqns:
            name = e.primitive.name
            if name == "pallas_call":
                counts[inside] = counts.get(inside, 0) + 1
                continue
            for v in e.params.values():
                for sub in (v if isinstance(v, (list, tuple)) else [v]):
                    if isinstance(sub, ClosedJaxpr):
                        sub = sub.jaxpr
                    if isinstance(sub, Jaxpr):
                        walk(sub, inside or name == "shard_map")

    walk(closed.jaxpr, False)
    return counts


# ------------------- the feature loop past FEATURE_UNROLL columns (PR 26)
@pytest.fixture(scope="module")
def wide():
    """37 columns at 64 bins: two loop groups of 20 (whole pairs), three
    columns past the end."""
    rs = np.random.RandomState(7)
    N, F, B = 2 * HIST_BLK, 37, 64
    bins = jnp.asarray(rs.randint(0, B, (F, N)).astype(np.int32))
    return N, F, B, bins, rs


def test_feature_groups_and_slot_chunks():
    from lightgbm_tpu.learner.histogram import _slot_chunks
    from lightgbm_tpu.learner.pallas_hist import (feature_groups,
                                                  hist_out_block)

    assert feature_groups(28, 255) == (1, 28)
    assert feature_groups(32, 255) == (1, 32)
    assert feature_groups(33, 255) == (2, 17)
    assert feature_groups(137, 255) == (5, 28)
    assert hist_out_block(24, 28, 255) == (24, 28 * 255)
    assert hist_out_block(24, 137, 255) == (5, 24, 28 * 255)
    # one call while the slots fit (every cell before the 137-column one)
    assert _slot_chunks(48, 64) == [(0, 48)] and _slot_chunks(8, 26) == [(0, 8)]
    assert _slot_chunks(32, 26) == [(0, 16), (16, 16)]
    assert _slot_chunks(48, 26) == [(0, 24), (24, 24)]
    assert _slot_chunks(8, 3) == [(0, 3), (3, 3), (6, 2)]


def test_hist_nat_wide_interpret_matches_fallback(interp, wide):
    N, F, B, bins, rs = wide
    from lightgbm_tpu.learner.histogram import hist_nat_slots

    gh8 = build_gh8(
        jnp.asarray(rs.randn(N).astype(np.float32)),
        jnp.asarray((rs.rand(N) + 0.5).astype(np.float32)),
        jnp.ones(N, jnp.float32),
    )
    S = 5
    slot = jnp.asarray(rs.randint(0, S + 1, N).astype(np.int32))
    out = hist_nat_slots(bins, gh8, slot, S, B)
    ref = _hist_nat_fallback(bins, gh8, slot, S, B)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-3, rtol=1e-4)


def test_hist_nat_wide_int8_interpret_exact(interp, wide):
    N, F, B, bins, rs = wide
    from lightgbm_tpu.learner.histogram import (
        build_gh8_quant,
        hist_nat_slots,
    )

    gq = jnp.asarray(rs.randint(-2, 3, N).astype(np.float32))
    hq = jnp.asarray(rs.randint(0, 5, N).astype(np.float32))
    gh8q = build_gh8_quant(gq, hq, jnp.ones(N, jnp.float32))
    S = 6
    slot = jnp.asarray(rs.randint(0, S + 1, N).astype(np.int32))
    out = hist_nat_slots(bins, gh8q, slot, S, B, quant=True, int8=True,
                         oh_shift=4)
    ref = _hist_nat_fallback(bins, gh8q, slot, S, B, quant=True)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))


@pytest.mark.parametrize("quant,int8", [(False, False), (True, True)])
def test_fused_round_wide_grower_matches_fallback(interp, quant, int8):
    """40 columns: the round kernel loops over two groups of 20 and its
    3-D output block comes back as the (slots, channels, columns, bins)
    histograms the XLA path builds."""
    import os

    import jax

    kw = dict(rounds_slots=8, has_cat=False, quant=quant,
              quant_int8=int8, quant_levels=4 if quant else 0)
    fused = _grow_case(kw, quant=quant, columns=40)
    os.environ["LGBM_TPU_PALLAS_INTERPRET"] = "0"
    jax.clear_caches()
    fb = _grow_case(kw, quant=quant, columns=40)
    np.testing.assert_allclose(fused[0], fb[0], atol=5e-4)
    np.testing.assert_array_equal(fused[2], fb[2])
    np.testing.assert_array_equal(fused[3], fb[3])
