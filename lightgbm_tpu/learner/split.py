"""Vectorized best-split search over (feature, threshold, missing-direction).

Reimplements the split-gain math of the reference threshold scan
(src/treelearner/feature_histogram.hpp:832 FindBestThresholdSequentially,
CUDA analog src/treelearner/cuda/cuda_best_split_finder.cu) as cumulative
sums over the bin axis plus a masked argmax — no sequential per-bin loop:

- L1/L2 regularization via ThresholdL1 soft-thresholding
  (feature_histogram.hpp GetLeafGain/CalculateSplittedLeafOutput),
- missing-value handling: NaN bin is the last bin of a feature; both
  default directions are evaluated (the reference's double scan),
- categorical features use one-vs-rest splits (bin == t goes left);
  the sorted-subset search (feature_histogram.hpp:449) is a later
  milestone,
- min_data_in_leaf / min_sum_hessian_in_leaf / min_gain_to_split masks,
- monotone-constraint candidate masking (basic method),
- tie-break: argmax over arrays laid out (dir, F, B) flattened picks the
  lowest flat index, matching the reference's first-feature-wins
  strictly-greater update order.

Gains are stored shifted by (parent_gain + min_gain_to_split) so that
"> 0" means a valid improving split, as in the reference SplitInfo.
"""

from __future__ import annotations

from typing import Dict, NamedTuple, Optional

import jax
import jax.numpy as jnp

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
    from jax import lax

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
    cat_subset: bool = False,  # static: dataset has large-cardinality cats
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
        feat_mask, cat_subset, parent_output, cmin, cmax, penalty, rand_bin,
    )[0]


def feature_best_gains(
    hist, sum_g, sum_h, sum_c, num_bins, nan_bin, mono, is_cat, params,
    feat_mask=None, cat_subset: bool = False, parent_output=0.0,
    cmin=-BIG, cmax=BIG,
):
    """Per-feature best (shifted) gain: max over thresholds/directions.

    The local-gain vote of the voting-parallel learner
    (voting_parallel_tree_learner.cpp:353 local top-k proposals) —
    computed on the LOCAL (un-reduced) histogram."""
    return _best_split_impl(
        hist, sum_g, sum_h, sum_c, num_bins, nan_bin, mono, is_cat, params,
        feat_mask, cat_subset, parent_output, cmin, cmax,
    )[1]


def _best_split_impl(
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
    ok_dr &= num_mask
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
