"""Device-side EFB bundle support for the growers.

The bin matrix on device holds one column per BUNDLE (bundling.py);
split finding and partitioning still speak per-feature. Three traced
helpers bridge the gap:

- `best_split_bundled` (the rounds grower): the split search over the
  bundle histogram (3, G, Bc) IN ITS OWN LAYOUT. A candidate is a cell
  (column, merged bin) — one threshold of one feature, from the static
  tables `bundling.CellTables` — and its left sums come from cumulative
  sums that restart at each member's range: below the member's
  most-frequent bin the prefix, at or past it the leaf's totals less the
  suffix (the most-frequent bin is not stored: its mass is the leaf
  total less the member's stored sum, the reference FixHistogram,
  include/LightGBM/dataset.h:768). The winner is PR 35's (gain, key)
  reduction with the key in the reference's scan order (feature, then
  threshold). No (3, F, Bf) expansion is ever built.
- `expand_hist` (the sequential growers, grower.py / permuted.py):
  bundle histogram (3, G, Bc) -> per-feature histogram (3, F, Bf) by
  gather, the same FixHistogram arithmetic.
- `decode_feature_bins`: bundle column values -> original bins of one
  feature (used by the partition step in place of a direct column read).
"""

from __future__ import annotations

from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
from jax import lax

from ..timer import device_phase
from .split import (
    BIG,
    NEG_INF,
    SearchDirections,
    SplitParams,
    SplitRecord,
    _best_split_impl,
    _prefer,
    leaf_gain,
    leaf_gain_given_output,
    leaf_output,
)


class BundleCells(NamedTuple):
    """bundling.CellTables on the device: (G, Bc) each, col_feat (G,)."""

    feat: jax.Array
    thr: jax.Array
    seg_start: jax.Array
    seg_end: jax.Array
    past: jax.Array
    nan_last: jax.Array
    nan_mfb: jax.Array
    cand: jax.Array
    extra: jax.Array
    pos_dr: jax.Array
    pos_dl: jax.Array
    col_feat: jax.Array
    direct: jax.Array


class BundleInfo(NamedTuple):
    """Traced bundle arrays (built host-side in dataset.py)."""

    bundle_of: jax.Array  # (F,) int32 — device column per feature
    off_lo: jax.Array  # (F,) int32 — merged-range start (0 for direct)
    mfb: jax.Array  # (F,) int32 — excluded most-freq bin; -1 = direct
    expand_idx: jax.Array  # (F, Bf) int32 — flat (G*Bc) index or -1
    width: jax.Array  # (F,) int32 — merged-range length (num_bin - 1)
    cells: Optional[BundleCells] = None  # the search's cell tables


def expand_hist(hist_g: jax.Array, g: jax.Array, h: jax.Array, c: jax.Array,
                binfo: BundleInfo) -> jax.Array:
    """(3, G, Bc) bundle histogram -> (3, F, Bf) per-feature histogram.

    g/h/c are the leaf totals used to recover the most-frequent slot:
    hist[f, mfb] = total - sum(stored bins of f).
    """
    F, Bf = binfo.expand_idx.shape
    flat = hist_g.reshape(3, -1)
    safe = jnp.clip(binfo.expand_idx, 0, flat.shape[1] - 1)
    out = jnp.take(flat, safe.reshape(-1), axis=1).reshape(3, F, Bf)
    out = jnp.where(binfo.expand_idx[None] >= 0, out, 0.0)
    has_mfb = binfo.mfb >= 0
    totals = jnp.stack([g, h, c]).astype(jnp.float32)  # (3,)
    missing = totals[:, None] - jnp.sum(out, axis=2)  # (3, F)
    onehot = (
        (jnp.arange(Bf, dtype=jnp.int32)[None, :] == binfo.mfb[:, None])
        & has_mfb[:, None]
    )  # (F, Bf)
    return out + onehot[None].astype(jnp.float32) * missing[:, :, None]


def decode_feature_bins(bcol: jax.Array, f: jax.Array,
                        binfo: BundleInfo) -> jax.Array:
    """Bundle-column values -> feature f's original bins.

    Direct columns (mfb == -1) pass through unchanged; merged features
    map their range [off_lo, off_lo + width) back (re-inserting the
    skipped most-frequent slot) and everything else to mfb. `f` may be
    a scalar or a per-row vector matching bcol (all ops elementwise) —
    the single home of this decode; keep traversal/partition callers on
    it."""
    return decode_bins(bcol, binfo.mfb[f], binfo.off_lo[f], binfo.width[f])


def decode_bins(bcol: jax.Array, m: jax.Array, lo: jax.Array,
                width: jax.Array) -> jax.Array:
    """`decode_feature_bins` from the feature's (mfb, off_lo, width)
    themselves: a caller that has them per row already (the valid
    traversal's per-node table) gathers nothing."""
    t = bcol - lo
    in_range = (t >= 0) & (t < width)
    decoded = jnp.where(in_range, t + (t >= m), m)
    return jnp.where(m >= 0, decoded, bcol)


def _seg_scan(x: jax.Array, first: jax.Array, reverse: bool = False
              ) -> jax.Array:
    """Inclusive sums along the last axis that restart at every cell
    where `first` is set (a segment's first cell in the scan's
    direction: its start forward, its end in reverse)."""

    def op(a, b):
        return a[0] | b[0], jnp.where(b[0], b[1], a[1] + b[1])

    flags = jnp.broadcast_to(first, x.shape)
    return lax.associative_scan(op, (flags, x), reverse=reverse,
                                axis=x.ndim - 1)[1]


def _pad_take(table: jax.Array, idx: jax.Array, fill) -> jax.Array:
    """table[idx] where idx == len(table) reads `fill` (no feature)."""
    return jnp.concatenate(
        [table, jnp.full((1,), fill, table.dtype)])[idx]


def best_split_bundled(
    hist: jax.Array,  # (3, G, Bc) f32 bundle histogram
    sum_g: jax.Array,
    sum_h: jax.Array,
    sum_c: jax.Array,
    num_bins: jax.Array,  # (F,) int32
    nan_bin: jax.Array,  # (F,) int32
    mono: jax.Array,  # (F,) int32
    is_cat: jax.Array,  # (F,) bool
    params: SplitParams,
    cells: BundleCells,
    num_bins_max: int,  # static: the SplitRecord's category-mask width
    feat_mask: Optional[jax.Array] = None,  # (F,) bool
    dirs: SearchDirections = SearchDirections(),  # static
    parent_output: jax.Array = 0.0,
    cmin: jax.Array = -BIG,
    cmax: jax.Array = BIG,
    penalty: Optional[jax.Array] = None,  # (F,)
    rand_bin: Optional[jax.Array] = None,  # (F,)
):
    """The best split of a leaf from its bundle histogram, read in the
    bundle layout (module docstring). Returns (SplitRecord, (G,) best
    shifted gain per device column: the election unit of voting).

    The same candidates, rules and tie order as `split.best_split` over
    `expand_hist`'s per-feature histogram: numerical default-right (with
    "every value left, missing alone right" where the feature has a NaN
    bin) and default-left, the directions a merged member can have;
    categorical features are single-feature columns and keep the
    column-wise one-vs-rest / sorted-subset search. A key is
    (feature, place in the reference's scan order, direction), so the
    lowest feature wins a tie, then the reference's order within it.
    Left sums are formed in another order of additions than the
    expansion's cumulative sum: gains agree to rounding, not to the bit."""
    _, G, Bc = hist.shape
    F = num_bins.shape[0]
    if not dirs.monotone_test:
        cmin = cmax = None
    order = [name for name, on in (
        ("dl", dirs.default_left), ("dr", True), ("cat", dirs.categorical),
        ("asc", dirs.cat_subset), ("desc", dirs.cat_subset)) if on]
    D = len(order)
    assert F * Bc * D < 2 ** 31, "bundle search keys overflow int32"
    parent_gain = jnp.where(
        params.path_smooth > 0.0,
        leaf_gain_given_output(sum_g, sum_h, params, parent_output),
        leaf_gain(sum_g, sum_h, params),
    )
    shift = parent_gain + params.min_gain_to_split
    feat = cells.feat

    with device_phase("learner.unbundle"):
        # what turns the bundle histogram into per-feature candidates:
        # the segment sums, the most-frequent-bin mass, the cell tables
        T = jnp.stack([sum_g, sum_h, sum_c]).astype(jnp.float32)[:, None,
                                                                   None]
        # a single-feature column is one segment from its bin 0: there
        # the plain cumulative sum, a channel at a time, the very sums
        # split._best_split_impl takes over the expansion's rows
        pre = jnp.where(cells.direct, jnp.stack(
            [jnp.cumsum(plane, axis=1) for plane in hist]),
            _seg_scan(hist, cells.seg_start))
        suf = _seg_scan(hist, cells.seg_end, reverse=True)
        left = jnp.where(cells.past, T - suf, pre)
        if dirs.default_left:
            # a member's NaN mass beside each of its cells: its stored
            # NaN bin (the segment's last cell) read back down the
            # segment, or, where the NaN bin is the most-frequent one,
            # the member's missing mass
            nan_v = _seg_scan(jnp.where(cells.nan_last, hist, 0.0),
                              cells.seg_end, reverse=True)
            nan_v = jnp.where(cells.nan_mfb, T - (pre + suf - hist), nan_v)
        ok = ~_pad_take(is_cat, feat, True)
        if feat_mask is not None:
            ok &= _pad_take(feat_mask, feat, False)
        if rand_bin is not None:
            ok &= cells.thr == _pad_take(rand_bin, feat, -1)
        m_cell = _pad_take(mono, feat, 0) if dirs.monotone_test else None
        pen = None if penalty is None else _pad_take(penalty, feat, 0.0)

    def direction(name, lft, ok_d, pos):
        """A direction's best candidate per column: (gain, key, left
        sums) by one reduction over the merged bins."""
        lg, lh, lc = lft
        rg, rh, rc = sum_g - lg, sum_h - lh, sum_c - lc
        gains = leaf_gain(
            lg, lh, params, lc, parent_output, cmin, cmax
        ) + leaf_gain(rg, rh, params, rc, parent_output, cmin, cmax)
        ok_d = (
            ok_d
            & (lc >= params.min_data_in_leaf)
            & (rc >= params.min_data_in_leaf)
            & (lh >= params.min_sum_hessian_in_leaf)
            & (rh >= params.min_sum_hessian_in_leaf)
        )
        if m_cell is not None:
            lo = leaf_output(lg, lh, params, lc, parent_output, cmin, cmax)
            ro = leaf_output(rg, rh, params, rc, parent_output, cmin, cmax)
            ok_d &= jnp.where(m_cell > 0, lo <= ro, True)
            ok_d &= jnp.where(m_cell < 0, lo >= ro, True)
        gains = jnp.where(ok_d, gains - shift, NEG_INF)
        if pen is not None:
            gains = gains - pen
        keys = (feat * Bc + pos) * D + order.index(name)
        return lax.reduce(
            (gains, keys, lg, lh, lc),
            (jnp.float32(-jnp.inf), jnp.int32(2 ** 31 - 1))
            + (jnp.float32(0.0),) * 3, _prefer, (1,))

    dr_ok = ok & (cells.cand | cells.extra) if dirs.default_left \
        else ok & cells.cand
    best = direction("dr", left, dr_ok, cells.pos_dr)
    if dirs.default_left:
        has_nan = _pad_take(nan_bin, feat, -1) >= 0
        best = _prefer(best, direction("dl", left + nan_v,
                                       ok & cells.cand & has_nan,
                                       cells.pos_dl))
    col_gain = best[0]  # (G,)
    gain, key, lg, lh, lc = lax.reduce(
        best, (jnp.float32(-jnp.inf), jnp.int32(2 ** 31 - 1))
        + (jnp.float32(0.0),) * 3, _prefer, (0,))
    f = jnp.minimum(key // (D * Bc), F - 1).astype(jnp.int32)
    d = key % D
    pos = (key // D) % Bc
    default_left = (d == order.index("dl")) if dirs.default_left else \
        jnp.bool_(False)
    last = jnp.where(nan_bin[f] >= 0, num_bins[f] - 2, num_bins[f] - 1)
    b = jnp.where(default_left | (nan_bin[f] < 0), last - 1 - pos, pos)
    rec = SplitRecord(
        gain=gain,
        feature=f,
        bin=jnp.clip(b, 0, num_bins_max - 1).astype(jnp.int32),
        default_left=default_left,
        is_cat=jnp.bool_(False),
        cat_mask=jnp.zeros(num_bins_max, bool),
        left_g=lg, left_h=lh, left_c=lc,
        right_g=sum_g - lg, right_h=sum_h - lh, right_c=sum_c - lc,
    )
    if dirs.categorical:
        # categorical features never merge: their columns are searched
        # column-wise (split._best_split_impl over the (G, Bc) planes,
        # every other column without a bin), then mapped back
        cf = cells.col_feat
        col_cat = _pad_take(is_cat, cf, False)
        cdirs = SearchDirections(default_left=False, categorical=True,
                                 cat_subset=dirs.cat_subset,
                                 monotone_test=dirs.monotone_test)
        crec, ccol = _best_split_impl(
            hist, sum_g, sum_h, sum_c,
            jnp.where(col_cat, _pad_take(num_bins, cf, 0), 0),
            jnp.where(col_cat, _pad_take(nan_bin, cf, -1), -1),
            _pad_take(mono, cf, 0), col_cat, params,
            None if feat_mask is None else _pad_take(feat_mask, cf, False),
            cdirs, parent_output,
            -BIG if cmin is None else cmin, BIG if cmax is None else cmax,
            None if penalty is None else _pad_take(penalty, cf, 0.0),
        )
        cfeat = cf[crec.feature]
        use_cat = (crec.gain > rec.gain) | (
            (crec.gain == rec.gain) & (cfeat < rec.feature))
        crec = crec._replace(feature=cfeat,
                             cat_mask=crec.cat_mask[:num_bins_max])
        rec = jax.tree.map(lambda c, n: jnp.where(use_cat, c, n), crec, rec)
        col_gain = jnp.maximum(col_gain, ccol)
    return rec, col_gain
