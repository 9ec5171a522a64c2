"""Vectorized best-split search over (feature, threshold, missing-direction).

Reimplements the split-gain math of the reference threshold scan
(src/treelearner/feature_histogram.hpp:832 FindBestThresholdSequentially,
CUDA analog src/treelearner/cuda/cuda_best_split_finder.cu) as cumulative
sums over the bin axis plus masked reductions — no sequential per-bin loop:

- L1/L2 regularization via ThresholdL1 soft-thresholding
  (feature_histogram.hpp GetLeafGain/CalculateSplittedLeafOutput),
- missing-value handling: NaN bin is the last bin of a feature; both
  default directions are evaluated (the reference's double scan; the
  default-right one reaches "every value left, missing alone right"),
- categorical features use one-vs-rest splits (bin == t goes left);
  the sorted-subset search (feature_histogram.hpp:449) is a later
  milestone,
- min_data_in_leaf / min_sum_hessian_in_leaf / min_gain_to_split masks,
- monotone-constraint candidate masking (basic method),
- tie-break: every candidate carries a key made of iotas (its place in
  the reference's scan order within its column); reductions keep the
  higher gain and, on equal gains, the lower key, then the first column:
  the reference's first-feature-wins strictly-greater update order,
- only the directions the Dataset can have are traced
  (SearchDirections), and the planes lie bins-major where the columns
  fill the chip's lanes better than the bins (columns_on_lanes).

Gains are stored shifted by (parent_gain + min_gain_to_split) so that
"> 0" means a valid improving split, as in the reference SplitInfo.
"""

from __future__ import annotations

from typing import Dict, NamedTuple, Optional

import jax
import jax.numpy as jnp
from jax import lax

# plain float (NOT jnp.float32): a module-level device constant would
# initialize the jax backend at import time — taking the chip before
# the CLI can steer the run onto another platform
NEG_INF = -1e30
K_EPSILON = 1e-15  # reference kEpsilon (meta.h)


class SplitParams(NamedTuple):
    """Dynamic (traced) split hyper-parameters."""

    lambda_l1: jax.Array
    lambda_l2: jax.Array
    min_data_in_leaf: jax.Array
    min_sum_hessian_in_leaf: jax.Array
    min_gain_to_split: jax.Array
    max_delta_step: jax.Array
    path_smooth: jax.Array
    # categorical sorted-subset params (feature_histogram.hpp:449+)
    cat_smooth: jax.Array
    cat_l2: jax.Array
    max_cat_threshold: jax.Array  # int32
    max_cat_to_onehot: jax.Array  # int32
    min_data_per_group: jax.Array
    # CEGB (cost_effective_gradient_boosting.hpp:79 DeltaGain)
    cegb_tradeoff: jax.Array
    cegb_penalty_split: jax.Array
    # per-node feature sampling rate (ColSampler feature_fraction_bynode)
    feature_fraction_bynode: jax.Array


class SplitRecord(NamedTuple):
    """Best split for one leaf (reference split_info.hpp:22 SplitInfo)."""

    gain: jax.Array  # f32, shifted; <=0 means no valid split
    feature: jax.Array  # int32, used-feature index
    bin: jax.Array  # int32 threshold bin (or category bin for 1-vs-rest)
    default_left: jax.Array  # bool
    is_cat: jax.Array  # bool
    cat_mask: jax.Array  # (B,) bool — cat bins going LEFT (subset splits)
    left_g: jax.Array
    left_h: jax.Array
    left_c: jax.Array
    right_g: jax.Array
    right_h: jax.Array
    right_c: jax.Array


def threshold_l1(s: jax.Array, l1: jax.Array) -> jax.Array:
    """reference feature_histogram.hpp ThresholdL1."""
    return jnp.sign(s) * jnp.maximum(jnp.abs(s) - l1, 0.0)


BIG = 1e29  # constraint sentinel (comfortably inside f32)


def leaf_output(
    g: jax.Array,
    h: jax.Array,
    p: SplitParams,
    count: Optional[jax.Array] = None,
    parent_output: Optional[jax.Array] = None,
    cmin: Optional[jax.Array] = None,
    cmax: Optional[jax.Array] = None,
) -> jax.Array:
    """CalculateSplittedLeafOutput (feature_histogram.hpp): -T(G)/(H+l2),
    clipped by max_delta_step, then path smoothing
    out*n/(n+ps) + parent*ps/(n+ps) when count/parent are given, then
    clamped to the leaf's monotone-constraint interval [cmin, cmax]."""
    out = -threshold_l1(g, p.lambda_l1) / (h + p.lambda_l2 + K_EPSILON)
    out = jnp.where(
        p.max_delta_step > 0.0,
        jnp.clip(out, -p.max_delta_step, p.max_delta_step),
        out,
    )
    if count is not None and parent_output is not None:
        denom = count + p.path_smooth
        sm = (out * count + parent_output * p.path_smooth) / jnp.maximum(
            denom, K_EPSILON
        )
        out = jnp.where(p.path_smooth > 0.0, sm, out)
    if cmin is not None:
        out = jnp.clip(out, cmin, cmax)
    return out


def leaf_gain_given_output(g, h, p: SplitParams, output) -> jax.Array:
    """GetLeafGainGivenOutput: -(2 T(G) o + (H+l2) o^2)."""
    t = threshold_l1(g, p.lambda_l1)
    return -(2.0 * t * output + (h + p.lambda_l2) * output * output)


def leaf_gain(
    g: jax.Array,
    h: jax.Array,
    p: SplitParams,
    count: Optional[jax.Array] = None,
    parent_output: Optional[jax.Array] = None,
    cmin: Optional[jax.Array] = None,
    cmax: Optional[jax.Array] = None,
) -> jax.Array:
    """GetLeafGain: the closed form T(G)^2/(H+l2) when no output
    modifier is active; otherwise GetLeafGainGivenOutput at the
    clipped/smoothed/clamped output (the reference's USE_MAX_OUTPUT /
    USE_SMOOTHING / constraint template branches)."""
    t = threshold_l1(g, p.lambda_l1)
    free = t * t / (h + p.lambda_l2 + K_EPSILON)
    o = leaf_output(g, h, p, count, parent_output, cmin, cmax)
    given = leaf_gain_given_output(g, h, p, o)
    active = p.max_delta_step > 0.0
    if count is not None and parent_output is not None:
        active = active | (p.path_smooth > 0.0)
    if cmin is not None:
        active = active | (cmin > -BIG) | (cmax < BIG)
    return jnp.where(active, given, free)


def _cat_subset_scan(g, h, c, num_bins, nan_bin, is_cat, sum_g, sum_h, sum_c,
                     params, parent_output, cmin, cmax):
    """Sorted-subset categorical split search (feature_histogram.cpp:246+
    FindBestThresholdCategoricalInner, non-onehot branch), vectorized over
    features with the per-bin scan expressed as cumulative sums:

    - valid bins: count >= cat_smooth (the reference compares the
      hessian-estimated count; we have exact counts),
    - stable sort by g/(h + cat_smooth) ascending,
    - two scans (ascending / descending prefixes), prefix length capped
      at max_num_cat = min(max_cat_threshold, (used+1)/2),
    - l2 + cat_l2 regularization,
    - min_data_per_group batching: gain is only evaluated when at least
      min_data_per_group rows accumulated since the last evaluation
      (sequential reset -> lax.scan over the bin axis),
    - break conditions (right side too small) are monotone in the prefix
      length, so they become masks.

    Returns (gains (F, B, 2), ok (F, B, 2), sums (3, F, B, 2),
    inv_rank (F, B), valid_bin (F, B)); direction 0 = ascending prefix,
    1 = descending. The left set for candidate (f, i, dir) is
    {b : valid_bin[f,b] and (inv_rank[f,b] <= i if dir==0 else
    inv_rank[f,b] >= used[f]-1-i)}.
    """
    F, B = g.shape
    bidx = jnp.arange(B)[None, :]
    valid_bin = (
        (c >= params.cat_smooth)
        & is_cat[:, None]
        & (bidx < num_bins[:, None])
        # the NaN bin is not a category: prediction (host Tree / device
        # traversal via the same mask) always routes missing right
        & (bidx != nan_bin[:, None])
    )
    ratio = jnp.where(valid_bin, g / (h + params.cat_smooth), jnp.inf)
    order = jnp.argsort(ratio, axis=1, stable=True)  # (F, B) invalid last
    inv_rank = jnp.argsort(order, axis=1)  # rank of each bin in the sort
    used = jnp.sum(valid_bin, axis=1).astype(jnp.int32)  # (F,)

    vf = jnp.take_along_axis(valid_bin, order, axis=1)
    sg = jnp.where(vf, jnp.take_along_axis(g, order, axis=1), 0.0)
    sh = jnp.where(vf, jnp.take_along_axis(h, order, axis=1), 0.0)
    sc = jnp.where(vf, jnp.take_along_axis(c, order, axis=1), 0.0)

    # direction 0: ascending prefixes; direction 1: descending prefixes
    sg2 = jnp.stack([sg, sg[:, ::-1]], axis=-1)  # (F, B, 2)
    sh2 = jnp.stack([sh, sh[:, ::-1]], axis=-1)
    sc2 = jnp.stack([sc, sc[:, ::-1]], axis=-1)
    # descending prefixes start from the END of the VALID region: roll the
    # reversed arrays so sorted-last valid bins come first
    shift = (B - used)[:, None, None]
    idx = (jnp.arange(B)[None, :, None] + shift) % B
    sg2 = sg2.at[:, :, 1].set(jnp.take_along_axis(sg2[:, :, 1:2], idx, axis=1)[:, :, 0])
    sh2 = sh2.at[:, :, 1].set(jnp.take_along_axis(sh2[:, :, 1:2], idx, axis=1)[:, :, 0])
    sc2 = sc2.at[:, :, 1].set(jnp.take_along_axis(sc2[:, :, 1:2], idx, axis=1)[:, :, 0])

    lg = jnp.cumsum(sg2, axis=1)
    lh = jnp.cumsum(sh2, axis=1) + K_EPSILON
    lc = jnp.cumsum(sc2, axis=1)
    rg = sum_g - lg
    rh = sum_h - lh
    rc = sum_c - lc

    i_idx = jnp.arange(B, dtype=jnp.int32)[None, :, None]
    max_num_cat = jnp.minimum(params.max_cat_threshold, (used[:, None, None] + 1) // 2)
    pos_ok = (i_idx < max_num_cat) & (i_idx < used[:, None, None])

    # continue conditions (skip eval, keep accumulating group)
    c2 = (lc < params.min_data_in_leaf) | (lh < params.min_sum_hessian_in_leaf)
    # break conditions (monotone in i): stop this direction entirely
    brk = (
        (rc < params.min_data_in_leaf)
        | (rc < params.min_data_per_group)
        | (rh < params.min_sum_hessian_in_leaf)
    )
    brk = jnp.cumsum(brk.astype(jnp.int32), axis=1) > 0

    # min_data_per_group batching: sequential reset per (feature, dir)
    def step(grp, x):
        sc_i, skip_i, brk_i = x
        grp = grp + sc_i
        do_eval = (~skip_i) & (~brk_i) & (grp >= params.min_data_per_group)
        return jnp.where(do_eval, 0.0, grp), do_eval

    xs = (
        jnp.moveaxis(sc2, 1, 0),  # (B, F, 2)
        jnp.moveaxis(c2, 1, 0),
        jnp.moveaxis(brk, 1, 0),
    )
    _, do_eval = lax.scan(step, jnp.zeros((F, 2)), xs)
    do_eval = jnp.moveaxis(do_eval, 0, 1)  # (F, B, 2)

    cat_params = params._replace(lambda_l2=params.lambda_l2 + params.cat_l2)
    gains = leaf_gain(
        lg, lh, cat_params, lc, parent_output, cmin, cmax
    ) + leaf_gain(rg, rh, cat_params, rc, parent_output, cmin, cmax)
    ok = do_eval & pos_ok
    return gains, ok, jnp.stack([lg, lh, lc]), inv_rank, valid_bin, used


class SearchDirections(NamedTuple):
    """What a split can be on this Dataset: the search traces only
    these (default-right always). Static facts of the INPUT, filled
    into GrowerSpec by the host; the defaults are the general case."""

    default_left: bool = True  # some used column has a NaN bin
    categorical: bool = True  # some used column is categorical
    cat_subset: bool = False  # ... wider than max_cat_to_onehot
    monotone_test: bool = True  # some column has a monotone constraint


def best_split(
    hist: jax.Array,  # (3, F, B) f32 — (grad, hess, count) channels
    sum_g: jax.Array,
    sum_h: jax.Array,
    sum_c: jax.Array,
    num_bins: jax.Array,  # (F,) int32
    nan_bin: jax.Array,  # (F,) int32, -1 if feature has no NaN bin
    mono: jax.Array,  # (F,) int32 in {-1, 0, 1}
    is_cat: jax.Array,  # (F,) bool
    params: SplitParams,
    feat_mask: Optional[jax.Array] = None,  # (F,) bool — ColSampler feature_fraction
    dirs: SearchDirections = SearchDirections(),  # static
    parent_output: jax.Array = 0.0,  # the leaf's current output (smoothing)
    cmin: jax.Array = -BIG,  # monotone-constraint interval of the leaf
    cmax: jax.Array = BIG,
    penalty: Optional[jax.Array] = None,  # (F,) — CEGB DeltaGain subtraction
    rand_bin: Optional[jax.Array] = None,  # (F,) — extra_trees: the single
    # numerical threshold candidate per feature (random per node)
) -> SplitRecord:
    """Find the best split of a leaf with given histogram and totals."""
    return _best_split_impl(
        hist, sum_g, sum_h, sum_c, num_bins, nan_bin, mono, is_cat, params,
        feat_mask, dirs, parent_output, cmin, cmax, penalty, rand_bin,
    )[0]


def feature_best_gains(
    hist, sum_g, sum_h, sum_c, num_bins, nan_bin, mono, is_cat, params,
    feat_mask=None, dirs: SearchDirections = SearchDirections(),
    parent_output=0.0, cmin=-BIG, cmax=BIG,
):
    """Per-feature best (shifted) gain: max over thresholds/directions.

    The local-gain vote of the voting-parallel learner
    (voting_parallel_tree_learner.cpp:353 local top-k proposals) —
    computed on the LOCAL (un-reduced) histogram."""
    return _best_split_impl(
        hist, sum_g, sum_h, sum_c, num_bins, nan_bin, mono, is_cat, params,
        feat_mask, dirs, parent_output, cmin, cmax,
    )[1]


def columns_on_lanes(F: int, B: int) -> bool:
    """The search's layout, from the table's shape where the program is
    built: the (column, bin) planes lie bins-major with the COLUMNS on
    the chip's 128 lanes where the columns fill whole lane tiles better
    than the bins do (2,000 x 63: 98% against 49%; 28 x 255: 22% against
    99.6%). Bins-major, the cumulative sums and the per-column
    reductions run across vector registers and only the last reduction
    crosses lanes."""

    def fill(n):
        return n / (-(-n // 128) * 128)

    return fill(F) > fill(B)


def _prefer(x, y):
    """Of two candidates (gain, key, *left sums) the higher gain; on
    equal gains the lower key."""
    first = (x[0] > y[0]) | ((x[0] == y[0]) & (x[1] < y[1]))
    return tuple(jnp.where(first, a, b) for a, b in zip(x, y))


def _best_split_impl(
    hist, sum_g, sum_h, sum_c, num_bins, nan_bin, mono, is_cat, params,
    feat_mask, dirs: SearchDirections, parent_output, cmin, cmax,
    penalty=None, rand_bin=None,
):
    _, F, B = hist.shape
    hist_fb = hist
    # `ax`: the bin axis of a (column, bin) plane (columns_on_lanes)
    ax = 0 if columns_on_lanes(F, B) else 1
    if ax == 0:
        hist = jnp.swapaxes(hist, 1, 2)  # (3, B, F)
    if not dirs.monotone_test:
        # no constrained column: every leaf's interval is (-BIG, BIG)
        cmin = cmax = None
    bin_idx = jnp.expand_dims(jnp.arange(B, dtype=jnp.int32), 1 - ax)
    nan_b = jnp.expand_dims(nan_bin, ax)
    nb = jnp.expand_dims(num_bins, ax)
    has_nan = nan_b >= 0
    # thresholds are t in [0, last - 1]: never the NaN bin (the last one)
    last_col = jnp.where(nan_bin >= 0, num_bins - 2, num_bins - 1)  # (F,)

    # ---- tie-breaking mirrors the reference scan order exactly
    # (feature_histogram.hpp:396-441 FindBestThresholdSequentially):
    # the REVERSE scan runs first (t descending -> on equal gain the
    # HIGHEST threshold wins, and it owns the default-left direction),
    # the forward scan second and replacing only on strictly greater
    # gain; missing-type-None features run ONLY the reverse scan. A
    # candidate's KEY is its place in that order within its column,
    # `reindexed bin * D + direction`: the bin axis counted from the top
    # for the default-left direction and, in columns with no NaN bin
    # (whose single reference scan is the reverse one), for the
    # default-right one; directions in the order dl, dr, cat[, subset
    # asc, desc]. First column, then lowest key, wins a tie.
    # Categorical-subset deviation from the reference on EXACT float
    # ties only: it scans all ascending subset prefixes before any
    # descending one (feature_histogram.cpp:276), while this order
    # interleaves directions per prefix length.
    order = [name for name, on in (
        ("dl", dirs.default_left), ("dr", True), ("cat", dirs.categorical),
        ("asc", dirs.cat_subset), ("desc", dirs.cat_subset)) if on]
    D = len(order)

    parent_gain = jnp.where(
        params.path_smooth > 0.0,
        leaf_gain_given_output(sum_g, sum_h, params, parent_output),
        leaf_gain(sum_g, sum_h, params),
    )
    shift = parent_gain + params.min_gain_to_split

    def column_best(name, gains, ok, pos, left, axis):
        """One direction's best candidate per column (gain, key, left
        sums): masked, shifted, then ONE reduction over the bin axis."""
        if feat_mask is not None:
            ok = ok & jnp.expand_dims(feat_mask, axis)
        gains = jnp.where(ok, gains - shift, NEG_INF)
        if penalty is not None:
            # CEGB DeltaGain (cost_effective_gradient_boosting.hpp:79):
            # per-feature acquisition cost subtracted from every candidate
            gains = gains - jnp.expand_dims(penalty, axis)
        keys = jnp.broadcast_to(pos * D + order.index(name), gains.shape)
        return lax.reduce(
            (gains, keys, *left),
            (jnp.float32(-jnp.inf), jnp.int32(2 ** 31 - 1))
            + (jnp.float32(0.0),) * 3, _prefer, (axis,))

    def direction(name, left, ok, pos):
        """A left / right direction on the planes: gain and validity of
        every candidate from its left sums, then the columns' best."""
        lg, lh, lc = left
        rg = sum_g - lg
        rh = sum_h - lh
        rc = sum_c - lc
        gains = leaf_gain(
            lg, lh, params, lc, parent_output, cmin, cmax
        ) + leaf_gain(rg, rh, params, rc, parent_output, cmin, cmax)
        ok = (
            ok
            & (lc >= params.min_data_in_leaf)
            & (rc >= params.min_data_in_leaf)
            & (lh >= params.min_sum_hessian_in_leaf)
            & (rh >= params.min_sum_hessian_in_leaf)
        )
        if dirs.monotone_test:
            # monotone basic: candidate-level output ordering
            lo = leaf_output(lg, lh, params, lc, parent_output, cmin, cmax)
            ro = leaf_output(rg, rh, params, rc, parent_output, cmin, cmax)
            m = jnp.expand_dims(mono, ax)
            ok &= jnp.where(m > 0, lo <= ro, True)
            ok &= jnp.where(m < 0, lo >= ro, True)
        return column_best(name, gains, ok, pos, left, ax)

    # ---- numerical: cumulative left sums, threshold t keeps bins <= t
    # left (the NaN bin is never <= t for valid t, so they exclude it).
    # ONE sum of the three channels where the bins are major (adds across
    # vector registers); a channel at a time where they are minor: past
    # 128 bins XLA scans in two levels, and per channel that is the order
    # of additions the recorded model texts have
    cum = jnp.cumsum(hist, axis=1) if ax == 0 else jnp.stack(
        [jnp.cumsum(plane, axis=1) for plane in hist])
    last_real = jnp.expand_dims(last_col, ax)
    num_ok = jnp.expand_dims(~is_cat, ax) & (bin_idx < last_real)
    if rand_bin is not None:
        # extra_trees: one random numerical threshold per feature per
        # node (col_sampler / feature_histogram extra-trees scan); the
        # categorical directions keep their full search
        num_ok &= bin_idx == jnp.expand_dims(rand_bin, ax)
    from_top = last_real - 1 - bin_idx
    # default right: missing stays right
    dr_ok = num_ok
    if dirs.default_left:
        # a column with a NaN bin has one threshold more, as in the
        # reference's forward scan: every value left, missing alone right
        dr_ok = num_ok | (jnp.expand_dims(~is_cat, ax) & has_nan
                          & (bin_idx == last_real))
        if rand_bin is not None:
            dr_ok &= bin_idx == jnp.expand_dims(rand_bin, ax)
    best = direction("dr", cum, dr_ok,
                     jnp.where(has_nan, bin_idx, from_top))
    if dirs.default_left:
        # default left: the NaN bin's mass joins the left side, in the
        # columns that have one
        nan3 = jnp.sum(jnp.where(bin_idx == nan_b, hist, 0.0), axis=1 + ax,
                       keepdims=True)
        best = _prefer(best, direction(
            "dl", cum + nan3, num_ok & has_nan, from_top))
    if dirs.categorical:
        # categorical one-vs-rest: bin t alone goes left. With the
        # sorted-subset path enabled, one-hot applies only to features
        # with num_bin <= max_cat_to_onehot (feature_histogram.cpp:182
        # use_onehot); without it every categorical stays one-vs-rest.
        ok = jnp.expand_dims(is_cat, ax) & (bin_idx < nb) & (bin_idx != nan_b)
        if dirs.cat_subset:
            ok &= jnp.expand_dims(num_bins <= params.max_cat_to_onehot, ax)
        best = _prefer(best, direction("cat", hist, ok, bin_idx))
    if dirs.cat_subset:
        big = is_cat & (num_bins > params.max_cat_to_onehot)
        cs_gain, cs_ok, cs_sums, inv_rank, valid_bin, cs_used = _cat_subset_scan(
            hist_fb[0], hist_fb[1], hist_fb[2], num_bins, nan_bin, big,
            sum_g, sum_h, sum_c, params, parent_output, cmin, cmax,
        )
        prefix = jnp.arange(B, dtype=jnp.int32)[None, :]
        for k, name in enumerate(("asc", "desc")):
            best = _prefer(best, column_best(
                name, cs_gain[:, :, k], cs_ok[:, :, k], prefix,
                cs_sums[:, :, :, k], 1))

    # ---- the winner: the first column that attains the best gain
    col_gain, col_key = best[:2]  # (F,)
    f = jnp.argmax(col_gain).astype(jnp.int32)
    d = col_key[f] % D
    default_left = (d == order.index("dl")) if dirs.default_left else \
        jnp.bool_(False)
    cat = ~default_left & (d != order.index("dr"))
    # undo the tie-break bin reindexing (numerical dirs only)
    b = col_key[f] // D
    b = jnp.clip(
        jnp.where(default_left | (~cat & (nan_bin[f] < 0)),
                  last_col[f] - 1 - b, b),
        0, B - 1).astype(jnp.int32)
    # one-hot left set: the single winning bin
    cat_mask = (jnp.arange(B, dtype=jnp.int32) == b) & cat
    if dirs.cat_subset:
        rank_f = inv_rank[f]
        sub_mask = jnp.where(
            d == order.index("asc"), rank_f <= b, rank_f >= cs_used[f] - 1 - b
        ) & valid_bin[f]
        cat_mask = jnp.where(d >= order.index("asc"), sub_mask, cat_mask)

    lg, lh, lc = (side[f] for side in best[2:])
    rec = SplitRecord(
        gain=col_gain[f],
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
    if dirs.categorical:
        # the record of a categorical search is MATERIALISED here: fused
        # with its consumers in the rounds grower, XLA:TPU (libtpu
        # 0.0.34) builds a category mask that is not the set the left
        # sums were taken over (my chip runs, PR 36: 29 of 39 leaf
        # counts of one tree off at 40,960 rows, the masks subsets of
        # the searched sets, none off with this barrier, with a host
        # callback on the record, on the CPU, or in the search alone;
        # PERF.md section 6). Numerical tables trace no barrier.
        rec = lax.optimization_barrier(rec)
    return rec, col_gain
