"""The split search against its specification: the parent's
`_best_split_impl` (PR 34's tree, moved here verbatim as the oracle).
Its flat-index order over (column, reindexed bin, direction) IS the
specification of every tie: it differs from the reference's two scans
where a default-right candidate at a lower reindexed bin ties a
default-left one at a higher. The search (`split._best_split_impl`)
finds the same winner by reductions over keys made of iotas, traces only
the directions its static `SearchDirections` name, and lays the planes
out bins-major where the columns fill the lanes better
(`split.columns_on_lanes`): every case below holds it to the oracle bit
for bit wherever a candidate is valid, ties included."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from lightgbm_tpu.learner.split import (
    BIG,
    NEG_INF,
    SearchDirections,
    SplitRecord,
    _best_split_impl,
    _cat_subset_scan,
    columns_on_lanes,
    leaf_gain,
    leaf_gain_given_output,
    leaf_output,
)
from test_learner import _oracle_best_gain, _params


def _parent_best_split_impl(
    hist, sum_g, sum_h, sum_c, num_bins, nan_bin, mono, is_cat, params,
    feat_mask, cat_subset: bool, parent_output, cmin, cmax,
    penalty=None, rand_bin=None,
):
    _, F, B = hist.shape
    g = hist[0]
    h = hist[1]
    c = hist[2]
    bin_idx = jnp.arange(B, dtype=jnp.int32)[None, :]  # (1, B)

    has_nan = (nan_bin >= 0)[:, None]  # (F, 1)
    nan_g = jnp.where(has_nan[:, 0], jnp.take_along_axis(g, jnp.maximum(nan_bin, 0)[:, None], axis=1)[:, 0], 0.0)[:, None]
    nan_h = jnp.where(has_nan[:, 0], jnp.take_along_axis(h, jnp.maximum(nan_bin, 0)[:, None], axis=1)[:, 0], 0.0)[:, None]
    nan_c = jnp.where(has_nan[:, 0], jnp.take_along_axis(c, jnp.maximum(nan_bin, 0)[:, None], axis=1)[:, 0], 0.0)[:, None]

    # ---- numerical: cumulative left sums, threshold t keeps bins <= t left.
    cg = jnp.cumsum(g, axis=1)
    ch = jnp.cumsum(h, axis=1)
    cc = jnp.cumsum(c, axis=1)

    def eval_lr(lg, lh, lc):
        rg = sum_g - lg
        rh = sum_h - lh
        rc = sum_c - lc
        gains = leaf_gain(
            lg, lh, params, lc, parent_output, cmin, cmax
        ) + leaf_gain(rg, rh, params, rc, parent_output, cmin, cmax)
        ok = (
            (lc >= params.min_data_in_leaf)
            & (rc >= params.min_data_in_leaf)
            & (lh >= params.min_sum_hessian_in_leaf)
            & (rh >= params.min_sum_hessian_in_leaf)
        )
        # monotone basic: candidate-level output ordering
        lo = leaf_output(lg, lh, params, lc, parent_output, cmin, cmax)
        ro = leaf_output(rg, rh, params, rc, parent_output, cmin, cmax)
        m = mono[:, None]
        ok &= jnp.where(m > 0, lo <= ro, True)
        ok &= jnp.where(m < 0, lo >= ro, True)
        return gains, ok, (lg, lh, lc)

    # NaN bin (last bin) is never <= t for valid t, so cum excludes it.
    # default right: missing stays right.
    gain_dr, ok_dr, _ = eval_lr(cg, ch, cc)
    # default left: NaN bin mass joins the left side.
    gain_dl, ok_dl, _ = eval_lr(cg + nan_g, ch + nan_h, cc + nan_c)
    # only evaluate the default-left variant when the feature has a NaN bin
    ok_dl &= has_nan

    # threshold validity: t in [0, num_bin-2], excluding the NaN bin itself
    last_real = jnp.where(nan_bin[:, None] >= 0, num_bins[:, None] - 2, num_bins[:, None] - 1)
    t_ok = bin_idx < last_real
    num_mask = (~is_cat)[:, None] & t_ok
    # (PR 36: the forward scan's last threshold, missing alone right)
    ok_dr &= num_mask | ((~is_cat)[:, None] & has_nan
                         & (bin_idx == last_real))
    ok_dl &= num_mask

    # ---- categorical one-vs-rest: bin t alone goes left. With the
    # sorted-subset path enabled, one-hot applies only to features with
    # num_bin <= max_cat_to_onehot (feature_histogram.cpp:182 use_onehot);
    # without it (legacy callers) every categorical stays one-vs-rest.
    gain_cat, ok_cat, _ = eval_lr(g, h, c)
    ok_cat &= (
        is_cat[:, None]
        & (bin_idx < num_bins[:, None])
        & (bin_idx != nan_bin[:, None])
    )
    if cat_subset:
        ok_cat &= (num_bins <= params.max_cat_to_onehot)[:, None]

    if rand_bin is not None:
        # extra_trees: one random numerical threshold per feature per
        # node (col_sampler / feature_histogram extra-trees scan); the
        # categorical directions keep their full search. Applied in
        # ORIGINAL bin space, before the tie-break reindexing below.
        rb_ok = bin_idx == rand_bin[:, None]
        ok_dr &= rb_ok
        ok_dl &= rb_ok

    parent_gain_plain = leaf_gain(sum_g, sum_h, params)
    parent_gain = jnp.where(
        params.path_smooth > 0.0,
        leaf_gain_given_output(sum_g, sum_h, params, parent_output),
        parent_gain_plain,
    )
    shift = parent_gain + params.min_gain_to_split

    # ---- tie-breaking mirrors the reference scan order exactly
    # (feature_histogram.hpp:396-441 FindBestThresholdSequentially):
    # the REVERSE scan runs first (t descending -> on equal gain the
    # HIGHEST threshold wins, and it owns the default-left direction),
    # the forward scan second and replacing only on strictly greater
    # gain; missing-type-None features run ONLY the reverse scan. We
    # express this inside one argmax by reindexing the bin axis so the
    # preferred candidate of any tie has the lowest flat index: the
    # default-left direction is stored bin-flipped and stacked first,
    # and the default-right direction is bin-flipped for features with
    # no NaN bin (whose single reference scan is the reverse one).
    no_nan = ~has_nan  # (F, 1)
    bin_rev = jnp.clip(last_real - 1 - bin_idx, 0, B - 1)  # (F, B)

    def flipb(a):
        return jnp.take_along_axis(a, bin_rev, axis=1)

    gain_dl_s = flipb(gain_dl)
    ok_dl_s = flipb(ok_dl)
    gain_dr_s = jnp.where(no_nan, flipb(gain_dr), gain_dr)
    ok_dr_s = jnp.where(no_nan, flipb(ok_dr), ok_dr)

    # stack: dir axis LAST in flat order (F, B, D) so ties break on
    # feature, then (reindexed) bin, then
    # (dl, dr, cat[, cat_asc, cat_desc]). Categorical-subset deviation
    # from the reference on EXACT float ties only: it scans all
    # ascending subset prefixes before any descending one
    # (feature_histogram.cpp:276), while this order interleaves
    # directions per prefix length.
    dirs = [gain_dl_s, gain_dr_s, gain_cat]
    oks = [ok_dl_s, ok_dr_s, ok_cat]
    if cat_subset:
        big = is_cat & (num_bins > params.max_cat_to_onehot)
        cs_gain, cs_ok, cs_sums, inv_rank, valid_bin, cs_used = _cat_subset_scan(
            g, h, c, num_bins, nan_bin, big, sum_g, sum_h, sum_c, params,
            parent_output, cmin, cmax,
        )
        dirs += [cs_gain[:, :, 0], cs_gain[:, :, 1]]
        oks += [cs_ok[:, :, 0], cs_ok[:, :, 1]]
    D = len(dirs)
    gains = jnp.stack(dirs, axis=-1) - shift  # (F, B, D)
    ok = jnp.stack(oks, axis=-1)
    if feat_mask is not None:
        ok &= feat_mask[:, None, None]
    gains = jnp.where(ok, gains, NEG_INF)
    if penalty is not None:
        # CEGB DeltaGain (cost_effective_gradient_boosting.hpp:79):
        # per-feature acquisition cost subtracted from every candidate
        gains = gains - penalty[:, None, None]

    flat = gains.reshape(-1)
    idx = jnp.argmax(flat)
    best_gain = flat[idx]
    f = (idx // (B * D)).astype(jnp.int32)
    b = ((idx // D) % B).astype(jnp.int32)
    d = (idx % D).astype(jnp.int32)
    default_left = d == 0
    cat = d >= 2
    # undo the tie-break bin reindexing (numerical dirs only)
    lr_f = last_real[f, 0]
    was_flipped = (d == 0) | ((d == 1) & (nan_bin[f] < 0))
    b = jnp.where(
        was_flipped & ~cat, jnp.clip(lr_f - 1 - b, 0, B - 1), b
    ).astype(jnp.int32)

    lg_num = cg[f, b] + jnp.where(default_left, nan_g[f, 0], 0.0)
    lh_num = ch[f, b] + jnp.where(default_left, nan_h[f, 0], 0.0)
    lc_num = cc[f, b] + jnp.where(default_left, nan_c[f, 0], 0.0)
    lg = jnp.where(cat, g[f, b], lg_num)
    lh = jnp.where(cat, h[f, b], lh_num)
    lc = jnp.where(cat, c[f, b], lc_num)
    # one-hot left set: the single winning bin
    cat_mask = (jnp.arange(B, dtype=jnp.int32) == b) & cat

    if cat_subset:
        is_sub = d >= 3
        asc = d == 3
        lg = jnp.where(is_sub, cs_sums[0, f, b, d - 3], lg)
        lh = jnp.where(is_sub, cs_sums[1, f, b, d - 3], lh)
        lc = jnp.where(is_sub, cs_sums[2, f, b, d - 3], lc)
        rank_f = inv_rank[f]
        sub_mask = jnp.where(
            asc, rank_f <= b, rank_f >= cs_used[f] - 1 - b
        ) & valid_bin[f]
        cat_mask = jnp.where(is_sub, sub_mask, cat_mask)

    rec = SplitRecord(
        gain=best_gain,
        feature=f,
        bin=b,
        default_left=default_left,
        is_cat=cat,
        cat_mask=cat_mask,
        left_g=lg,
        left_h=lh,
        left_c=lc,
        right_g=sum_g - lg,
        right_h=sum_h - lh,
        right_c=sum_c - lc,
    )
    return rec, jnp.max(gains, axis=(1, 2))


# ---------------------------------------------------------------- cases
ROWS = 48  # few rows in many bins: empty bins, so equal gains abound
SHAPES = {16: 40, 63: 12, 255: 6}  # bins -> columns (16: columns on lanes)
FIELDS = ("feature", "bin", "default_left", "is_cat", "cat_mask", "left_g",
          "left_h", "left_c", "right_g", "right_h", "right_c")


def _tables(F, B, nan, cat, mono, rs):
    """Per-column facts: NaN bins (the last bin), categoricals, monotone
    signs; columns of several widths."""
    num_bins = rs.randint(max(3, B // 2), B + 1, size=F).astype(np.int32)
    num_bins[0] = B
    has_nan = {"none": np.zeros(F, bool), "all": np.ones(F, bool),
               "some": rs.rand(F) < 0.5}[nan]
    if nan == "some":
        has_nan[:2] = (True, False)
    is_cat = np.zeros(F, bool)
    if cat != "none":
        is_cat = rs.rand(F) < 0.4
        is_cat[1:3] = (True, False)
    m = np.zeros(F, np.int32)
    if mono == "some":
        m = rs.randint(-1, 2, size=F).astype(np.int32)
        m[:3] = (1, -1, 0)
    return num_bins, np.where(has_nan, num_bins - 1, -1).astype(np.int32), \
        m, is_cat


def _histogram(F, B, num_bins, nan_bin, rs, tie):
    """(3, F, B) sums of ROWS rows with small integer gradients, every
    column a partition of the same rows (equal column totals); `tie`
    constructs an exact tie on top of the empty bins' own."""
    bins = np.stack([rs.randint(0, nb, size=ROWS) for nb in num_bins])
    if tie == "columns":  # the same threshold of two columns
        bins[3] = bins[0] % num_bins[3]
        bins[2] = bins[0] % num_bins[2]
    if tie == "directions":  # an empty NaN bin: default-left = -right
        for f in np.nonzero(nan_bin >= 0)[0]:
            bins[f] = np.minimum(bins[f], nan_bin[f] - 1)
    grad = rs.randint(-3, 4, size=ROWS).astype(np.float32)
    hess = rs.randint(1, 3, size=ROWS).astype(np.float32)
    if tie == "thresholds":  # mirrored halves: two thresholds, one gain
        grad[ROWS // 2:] = grad[:ROWS // 2]
        hess[ROWS // 2:] = hess[:ROWS // 2]
        half = np.stack([rs.randint(0, nb // 2, size=ROWS // 2)
                         for nb in num_bins])
        bins = np.concatenate(
            [half, (num_bins[:, None] // 2) * 2 - 1 - half], axis=1)
    hist = np.zeros((3, F, B), np.float32)
    for f in range(F):
        for ch, v in enumerate((grad, hess, np.ones(ROWS, np.float32))):
            np.add.at(hist[ch, f], bins[f], v)
    return hist, bins, grad, hess


def _problem(F, B, nan, cat, mono, extras, seed, tie, tables_seed=None):
    rs = np.random.RandomState(seed)
    num_bins, nan_bin, m, is_cat = _tables(
        F, B, nan, cat, mono,
        rs if tables_seed is None else np.random.RandomState(tables_seed))
    hist, bins, grad, hess = _histogram(F, B, num_bins, nan_bin, rs, tie)
    kw = dict(min_data_in_leaf=float(rs.randint(1, 4)),
              lambda_l2=float(rs.randint(0, 2)),
              min_data_per_group=5.0, cat_smooth=1.0)
    if seed % 3 == 1:
        kw.update(lambda_l1=0.5, max_delta_step=0.7, path_smooth=2.0)
    bounds = (-BIG, BIG) if mono == "none" or seed % 2 else (-0.4, 0.6)
    args = dict(
        hist=hist, sum_g=grad.sum(), sum_h=hess.sum(), sum_c=float(ROWS),
        num_bins=num_bins, nan_bin=nan_bin, mono=m, is_cat=is_cat,
        params=_params(**kw), feat_mask=None, parent_output=0.1 * seed,
        cmin=bounds[0], cmax=bounds[1], penalty=None, rand_bin=None)
    if extras:
        args.update(
            feat_mask=rs.rand(F) < 0.8,
            penalty=(rs.randint(0, 3, size=F) / 4.0).astype(np.float32),
            rand_bin=rs.randint(0, np.maximum(num_bins - 2, 1)).astype(
                np.int32))
    return args, (bins, grad, hess)


def _run(impl, static):
    def run(a):
        return impl(
            a["hist"], a["sum_g"], a["sum_h"], a["sum_c"], a["num_bins"],
            a["nan_bin"], a["mono"], a["is_cat"], a["params"],
            a["feat_mask"], static, a["parent_output"], a["cmin"],
            a["cmax"], a["penalty"], a["rand_bin"])
    return run


def _call(impl, static):
    """Op by op: fused, this backend contracts a multiply-add in one
    direction's gains and not in another's, and an exact tie of two
    directions parts by a last digit in one formulation alone."""
    def call(a):
        with jax.disable_jit():
            return _run(impl, static)(jax.tree.map(
                lambda x: None if x is None else jnp.asarray(x), a,
                is_leaf=lambda x: x is None))
    return call


def _same(got, want, where, exact=True):
    """The choice, its sums and the gains bit for bit wherever a candidate
    is valid, else the gain alone (`exact=False`: the gains to a last
    digit, for two COMPILED programs of this backend)."""
    (rec, col), (orec, ocol) = got, want
    for a, b in ((rec.gain, orec.gain), (col, ocol)):
        if exact:
            np.testing.assert_array_equal(a, b, err_msg=where)
        else:  # the gain is a difference: less the parent's and the shift
            np.testing.assert_allclose(a, b, rtol=2e-6, atol=2e-5,
                                       err_msg=where)
    valid = np.asarray(orec.gain) > NEG_INF / 2
    for name in FIELDS:
        a, b = np.asarray(getattr(rec, name)), np.asarray(getattr(orec, name))
        ok = valid.reshape(valid.shape + (1,) * (a.ndim - valid.ndim))
        np.testing.assert_array_equal(
            np.where(ok, a, 0), np.where(ok, b, 0), err_msg=f"{where}: {name}")
    return int(valid.sum())


def _facts(nan, cat, mono, subset=False):
    return SearchDirections(default_left=nan != "none",
                            categorical=cat != "none", cat_subset=subset,
                            monotone_test=mono != "none")


TIES = (None, "columns", "directions", "thresholds", None, "columns")


@pytest.mark.parametrize("bins", sorted(SHAPES))
@pytest.mark.parametrize("extras", [False, True], ids=["plain", "extras"])
@pytest.mark.parametrize("mono", ["none", "some"])
@pytest.mark.parametrize("cat", ["none", "some"])
@pytest.mark.parametrize("nan", ["none", "some", "all"])
def test_search_equals_the_parents_formulation(nan, cat, mono, extras, bins):
    """Only the directions the table can have are traced, and the winner
    and its sums are the all-directions flat argmax's, ties included."""
    F, B = SHAPES[bins], bins
    search = _call(_best_split_impl, _facts(nan, cat, mono))
    oracle = _call(_parent_best_split_impl, False)
    n_valid = 0
    for seed, tie in enumerate(TIES):
        args, (rows, grad, hess) = _problem(F, B, nan, cat, mono, extras,
                                            seed, tie)
        got = search(args)
        n_valid += _same(got, oracle(args), f"seed {seed}, tie {tie}")
        if (nan, cat, mono, extras, seed % 3) == ("none",) * 3 + (False, 0):
            # the plain case: the gain an exhaustive NumPy search finds
            p = args["params"]
            want = _oracle_best_gain(
                np.minimum(rows, args["num_bins"][:, None] - 1), grad, hess,
                B, l2=float(p.lambda_l2), min_data=float(p.min_data_in_leaf))
            assert float(got[0].gain) == pytest.approx(want, rel=1e-4,
                                                       abs=1e-5)
    assert n_valid >= len(TIES) - 2  # the cases do search something


@pytest.mark.parametrize("bins", [16, 63])
@pytest.mark.parametrize("extras", [False, True], ids=["plain", "extras"])
@pytest.mark.parametrize("nan", ["none", "some"])
def test_sorted_subset_directions_equal_the_parents(nan, extras, bins):
    F, B = SHAPES[bins], bins
    search = _call(_best_split_impl, _facts(nan, "some", "none", True))
    oracle = _call(_parent_best_split_impl, True)
    for seed, tie in enumerate(TIES):
        args, _ = _problem(F, B, nan, "some", "none", extras, seed, tie)
        _same(search(args), oracle(args), f"seed {seed}, tie {tie}")


@pytest.mark.parametrize("F,B", [(130, 63), (12, 63), (40, 16), (6, 255)])
def test_general_directions_on_a_plain_table_in_either_layout(F, B):
    """A hand-built spec's defaults trace every direction: the same
    winner on a table that has none of them, bins-major or bins-minor."""
    assert columns_on_lanes(F, B) == (F in (130, 40))
    assert columns_on_lanes(2000, 63) and not columns_on_lanes(28, 255) \
        and not columns_on_lanes(137, 255)
    general = _call(_best_split_impl, SearchDirections())
    exact = _call(_best_split_impl, _facts("none", "none", "none"))
    oracle = _call(_parent_best_split_impl, False)
    for seed, tie in enumerate(TIES):
        args, _ = _problem(F, B, "none", "none", "none", False, seed, tie)
        want = oracle(args)
        _same(general(args), want, f"general, seed {seed}")
        _same(exact(args), want, f"exact, seed {seed}")


@pytest.mark.parametrize("nan,cat,bins", [
    ("none", "none", 16), ("some", "some", 16), ("none", "none", 63),
    ("all", "some", 63)])
def test_sixteen_children_at_once_equal_sixteen_calls(nan, cat, bins):
    """A round's batch (vmap over its children) is the single search."""
    F, B = SHAPES[bins], bins
    facts = _facts(nan, cat, "none")
    per_child = ("hist", "sum_g", "sum_h", "parent_output")  # thresholds:
    # a tie of two DIFFERENT sums, which a batch may round another way
    problems = [_problem(F, B, nan, cat, "none", False, 3 * k, TIES[k % 3],
                         tables_seed=7)[0] for k in range(16)]
    for a in problems:  # the parameters are the round's, as the tables
        a["params"] = problems[0]["params"]
    single = _call(_best_split_impl, facts)
    batch = {n: (np.stack([np.asarray(a[n], np.float32) for a in problems])
                 if n in per_child else problems[0][n])
             for n in problems[0]}
    axes = ({n: 0 if n in per_child else None for n in batch},)
    got = jax.jit(jax.vmap(_run(_best_split_impl, facts), in_axes=axes))(
        batch)
    singles = [single(a) for a in problems]
    assert _same(got, jax.tree.map(lambda *x: np.stack(x), *singles),
                 "batched", exact=False) >= 14
