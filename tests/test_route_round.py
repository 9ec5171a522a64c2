"""The round that spends the last of a tree's leaf budget routes rows
only (rounds.spends_budget -> round_step(route_only=True) ->
histogram.route_round -> pallas_hist.route_round_tpu): no histogram
pass and no split search for children that can never split. The trees
cannot change: selection, routing and every leaf value are the same
arithmetic on the same inputs. Held here, off hardware: the routing
call against the fused kernel's own partition output, and whole trees
against the all-histogram formulation (the shortcut monkeypatched
off). Whole models through lgb.train: test_route_round_models.py."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from lightgbm_tpu.learner import rounds as rounds_mod
from lightgbm_tpu.learner.histogram import (
    HIST_BLK,
    build_gh8,
    build_gh8_quant,
    hist_round,
    route_round,
)
from lightgbm_tpu.obs import default_registry

from test_pallas_kernels import _grow_case, _ladder_rounds, interp  # noqa: F401


def _drop_traces():
    """The growers read rounds.py's module constants when they are
    traced, and neither jit's cache nor the fused step's memo keys on
    them."""
    from lightgbm_tpu.boosting import _FUSED_STEP_CACHE

    _FUSED_STEP_CACHE.clear()
    jax.clear_caches()


@pytest.fixture
def fresh_traces():
    _drop_traces()
    yield
    _drop_traces()


def _no_shortcut(monkeypatch):
    """Every round builds its histograms and searches its children, as
    before the shortcut."""
    monkeypatch.setattr(rounds_mod, "spends_budget",
                        lambda n_cand, budget, slots: jnp.bool_(False))
    _drop_traces()


def _routed():
    return default_registry().counter(
        "lgbmtpu_grower_rounds_total", labels=("width",)
    ).value(width=rounds_mod.ROUTE_LABEL)


# ------------------------------------------------ (a) the routing call
def _round_inputs(layout, columns, variant, slots=8, bins=32):
    """One round-kernel call's operands: rows spread over 12 leaves,
    `slots` - 1 of them split (the last slot is a pad: leaf id L = 99),
    on random columns and thresholds with NaN bins and default
    directions; `variant` adds the EFB decode columns or turns three
    slots categorical."""
    rs = np.random.RandomState(17)
    N, S, B = 2 * HIST_BLK, slots, bins
    bins_fm = jnp.asarray(rs.randint(0, B, (columns, N)).astype(np.int32))
    if layout == "bf16x2":
        gh8 = build_gh8(jnp.asarray(rs.randn(N).astype(np.float32)),
                        jnp.asarray(rs.rand(N).astype(np.float32)),
                        jnp.ones(N, jnp.float32))
    else:
        gh8 = build_gh8_quant(
            jnp.asarray(rs.randint(-2, 3, N).astype(np.float32)),
            jnp.asarray(rs.randint(0, 4, N).astype(np.float32)),
            jnp.ones(N, jnp.float32))
    pleaf = jnp.asarray(rs.randint(0, 12, N).astype(np.int32))
    col = rs.randint(0, columns, S)
    p = np.zeros((S, 16), np.int32)
    p[:, 0] = rs.permutation(12)[:S]
    p[-1, 0] = 99
    p[:, 1] = col
    p[:, 2] = rs.randint(0, B, S)  # threshold bin
    p[:, 3] = rs.randint(0, 2, S)  # default left
    p[:, 4] = np.where(rs.rand(S) < 0.5, B - 1, -1)  # NaN bin
    p[:, 5] = rs.randint(0, 2, S)  # left is the smaller child
    p[:, 6] = 20 + np.arange(S)  # new leaf ids
    p[:, 8] = -1  # direct column unless EFB says otherwise
    cat_mask = None
    if variant == "efb":
        p[:, 7] = rs.randint(0, B // 2, S)  # off_lo
        p[:, 9] = rs.randint(2, B // 2, S)  # width
        p[:, 8] = np.where(rs.rand(S) < 0.7,
                           rs.randint(0, 2, S) * (p[:, 9] - 1), -1)  # mfb
    if variant == "cat":
        p[:3, 10] = 1
        cat_mask = jnp.asarray(rs.randint(0, 2, (S, B)).astype(np.int8))
    coh = (col[:, None] == np.arange(columns)[None, :]).astype(np.float32)
    return bins_fm, gh8, pleaf, jnp.asarray(p), jnp.asarray(coh), cat_mask


@pytest.mark.parametrize("variant", ["plain", "efb", "cat"])
@pytest.mark.parametrize("columns", [28, 40])  # 40: past FEATURE_UNROLL
@pytest.mark.parametrize("layout", ["bf16x2", "int16", "int8"])
def test_route_round_is_the_fused_kernels_partition(interp, layout, columns,
                                                    variant):
    """route_round returns hist_round's second output on the same
    operands, in every channel layout (the routing pass itself has no
    channels: its scratch differs by layout only through the
    categorical one-hot)."""
    S, B = 8, 32
    bins_fm, gh8, pleaf, params, coh, cat_mask = _round_inputs(
        layout, columns, variant, S, B)
    kw = dict(efb=variant == "efb", cat_mask=cat_mask)
    _, want = hist_round(bins_fm, gh8, pleaf, params, coh, S, B,
                         quant=layout != "bf16x2", int8=layout == "int8",
                         **kw)
    got = route_round(bins_fm, pleaf, params, coh, S, B, **kw)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    moved = np.asarray(got) != np.asarray(pleaf)
    assert 0.05 < moved.mean() < 0.6  # rows did move, to new leaves only
    assert set(np.unique(np.asarray(got)[moved])) <= set(range(20, 20 + S))


def test_route_round_is_one_call_where_hist_round_chunks(interp,
                                                         monkeypatch):
    """Past the VMEM schedule hist_round runs its slot axis in chunks
    and composes their partition deltas; the routing pass has no
    histogram block, so it stays ONE call and returns the same rows."""
    import sys

    hist_mod = sys.modules["lightgbm_tpu.learner.histogram"]
    monkeypatch.setattr(hist_mod, "_round_s_max", lambda *a, **k: 3)
    S, B = 8, 32
    bins_fm, gh8, pleaf, params, coh, _ = _round_inputs("int16", 6, "plain",
                                                        S, B)
    args = (bins_fm, pleaf, params, coh, S, B)
    fused = jax.make_jaxpr(lambda *a: hist_round(
        a[0], gh8, *a[1:4], S, B, quant=True))(*args[:4])
    routed = jax.make_jaxpr(lambda *a: route_round(*a, S, B))(*args[:4])

    def calls(j):
        from lightgbm_tpu.analysis.jaxpr_audit import iter_eqns

        return sum(e.primitive.name == "pallas_call" for e in iter_eqns(j))

    assert (calls(fused), calls(routed)) == (3, 1)
    _, want = hist_round(bins_fm, gh8, pleaf, params, coh, S, B, quant=True)
    np.testing.assert_array_equal(
        np.asarray(route_round(*args)), np.asarray(want))


# --------------------------------------------- (b) whole trees, fused
@pytest.mark.parametrize("tail", ["tail_exact", "wide_tail"])
@pytest.mark.parametrize("layout,leaves,rows", [
    ("bf16x2", 63, HIST_BLK), ("int16", 63, HIST_BLK),
    ("int8", 63, HIST_BLK), ("bf16x2", 255, 4 * HIST_BLK),
    ("int16", 255, 4 * HIST_BLK), ("int8", 255, 4 * HIST_BLK),
])
def test_last_round_routing_keeps_the_tree(interp, monkeypatch, layout,
                                           leaves, rows, tail):
    """Through the fused kernels: exactly one round routes rows only
    (the one that spends the budget: one leaf under tail_exact, up to a
    full slot count without it, as in the benchmark's cells), and the
    tree and the rows' leaves equal, bit for bit, those of the grower
    that builds histograms in every round."""
    if tail == "wide_tail":
        monkeypatch.setattr(rounds_mod, "TAIL_EXACT_ROWS", 0)
    quant = layout != "bf16x2"
    kw = dict(rounds_slots=48 if quant else 25, has_cat=False,
              num_leaves=leaves, quant=quant, quant_int8=layout == "int8",
              quant_levels=4 if quant else 0)
    got = _grow_case(kw, quant=quant, rows=rows, smooth=True,
                     with_stats=True)
    widths = tuple(int(w) for w in got[5]["widths"])
    counts = [int(n) for n in got[5]["rounds"]]
    assert counts[-2] == 1 and int(got[4].num_nodes) == leaves - 1
    assert counts[-1] == sum(counts[:-1])
    if tail == "tail_exact":  # every leaf of these trees can split
        assert counts[:-1] == _ladder_rounds(widths, leaves, rows)

    _no_shortcut(monkeypatch)
    want = _grow_case(kw, quant=quant, rows=rows, smooth=True,
                      with_stats=True)
    plain = [int(n) for n in want[5]["rounds"]]
    assert plain[-2] == 0 and plain[-1] == counts[-1]
    # the same rounds but the last, which ran at some histogram width
    moved = [a - b for a, b in zip(plain[:-2], counts[:-2])]
    assert sorted(moved) == [0] * (len(widths) - 1) + [1]
    for a, b in zip(jax.tree.leaves(got[4]), jax.tree.leaves(want[4])):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    np.testing.assert_array_equal(got[1], want[1])
