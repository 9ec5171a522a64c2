"""Exclusive Feature Bundling (EFB) — host-side preprocessing.

Re-creates the behavior of the reference's bundling pass
(src/io/dataset.cpp:111 FindGroups, :250 FastFeatureBundling): sparse,
(nearly) mutually-exclusive features are merged into one bin column so
the per-column histogram cost drops from O(#features) to O(#bundles).

TPU formulation: the device bin matrix stays ONE dense feature-major
int matrix — bundling just shrinks its leading axis. Each bundle
column stores, per row, the offset-shifted bin of whichever member
feature is away from its most-frequent bin (0 = "every member at its
most-frequent bin"). Split finding still runs per ORIGINAL feature:
bundle histograms are expanded back to per-feature layout with a
gather, and each feature's most-frequent-bin slot is recovered from the
leaf totals minus the stored bins — exactly the reference's
FixHistogram trick (include/LightGBM/dataset.h:768), which exists for
the same reason (the most-frequent bin is not stored).

Grouping mirrors FindGroups' greedy pass: features ordered by
non-default count descending (dense first), each placed in the first
group where the conflict count stays within the global budget and half
the feature's own non-default count, with a per-group merged-width cap
so the uniform device bin axis does not grow.

Conflicts are counted on the rows of the BIN-CONSTRUCTION SAMPLE (the
rows the mappers were built from; all rows when a Dataset has no more
than ``bin_construct_sample_cnt``), with a budget of sample rows //
10000: the reference's FindGroups / FastFeatureBundling count on
``sample_indices`` with ``single_val_max_conflict_cnt = total_sample_cnt
/ 10000`` (recalled from the reference's source, which is not on this
machine). The encode writes every row, so a pair of members that never
met in the sample may meet in the full table: such a row keeps the last
member written, as the reference's push order does. Departures from the
reference, each deliberate: categorical features never merge (the
sorted-subset scan needs a dedicated column); a feature whose implicit
zeros are not its most-frequent bin never merges on the sparse path;
the merged width of a group is capped at max(max_bin + 1, 256) bins;
no group-level "is sparse" / multi-value decision (one dense device
matrix holds every column).
"""

from __future__ import annotations

from typing import List, NamedTuple, Optional, Sequence, Tuple

import numpy as np

from .binning import BinMapper, BinType

# reference dataset.cpp FindGroups constants
MAX_SEARCH_GROUP = 100


class BundleLayout(NamedTuple):
    """Host description of the feature -> bundle-column mapping.

    All per-feature arrays are indexed by USED-feature position (the
    grower's feature axis). Singleton columns store original bins
    directly (mfb == -1, off_lo == 0).
    """

    groups: List[List[int]]  # used-feature positions per bundle column
    bundle_of: np.ndarray  # (F,) int32 — device column of each feature
    off_lo: np.ndarray  # (F,) int32 — merged-range start within the column
    mfb: np.ndarray  # (F,) int32 — excluded most-freq bin; -1 = stored direct
    col_bins: int  # uniform device bin-axis size B' (max column width)

    @property
    def num_columns(self) -> int:
        return len(self.groups)

    def is_trivial(self) -> bool:
        """True when every group is a singleton (no merging happened)."""
        return all(len(g) == 1 for g in self.groups)


def find_groups(
    bins: np.ndarray,  # (F, S) binned rows of the sample (used features)
    num_bins: Sequence[int],
    most_freq: Sequence[int],
    is_cat: Sequence[bool],
    max_group_bins: int,
) -> List[List[int]]:
    """Greedy conflict-bounded grouping (reference FindGroups semantics)
    over the sample's rows (module docstring): N below is the sample's.

    Categorical features never merge (their bin identity is a category;
    the sorted-subset scan assumes a dedicated column).
    """
    F, N = bins.shape
    budget = N // 10000  # single_val_max_conflict_cnt
    nd_masks = [bins[f] != most_freq[f] for f in range(F)]
    nd_cnt = np.array([int(m.sum()) for m in nd_masks])
    # dense first, like FastFeatureBundling's sort by non-zero count
    order = np.argsort(-nd_cnt, kind="stable")

    groups: List[List[int]] = []
    group_mask: List[np.ndarray] = []
    group_cnt: List[int] = []  # rows set in group_mask
    group_bins: List[int] = []
    group_conflict: List[int] = []
    group_has_cat: List[bool] = []
    for f in order:
        f = int(f)
        width = int(num_bins[f]) - 1  # mfb slot excluded once merged
        placed = False
        if not is_cat[f] and nd_cnt[f] < N:  # fully-dense features never merge
            # cap the candidate-group search like the reference
            # (max_search_group, dataset.cpp:117) — without it, wide
            # sparse data pays O(F x G x N) host preprocessing
            searched = 0
            for gid in range(len(groups)):
                if searched >= MAX_SEARCH_GROUP:
                    break
                # a group founded by a categorical feature stays a
                # dedicated column both ways: the categorical never
                # merges INTO a group, and no numeric feature merges
                # into ITS group (build_layout would offset-encode the
                # categorical column, breaking the bin==category
                # identity the sorted-subset scan relies on)
                if group_has_cat[gid]:
                    continue
                if group_bins[gid] + width > max_group_bins:
                    continue
                rest = budget - group_conflict[gid]
                if rest < 0:
                    continue
                searched += 1
                # two sets of a and b of N rows share at least a + b - N:
                # where that alone is past the budget (any two dense
                # columns) the O(N) intersection need not be counted
                if group_cnt[gid] + int(nd_cnt[f]) - N > rest:
                    continue
                cnt = int(np.sum(group_mask[gid] & nd_masks[f]))
                if cnt <= rest and cnt <= nd_cnt[f] // 2:
                    groups[gid].append(f)
                    group_mask[gid] |= nd_masks[f]
                    group_cnt[gid] += int(nd_cnt[f]) - cnt
                    group_bins[gid] += width
                    group_conflict[gid] += cnt
                    placed = True
                    break
        if not placed:
            groups.append([f])
            group_mask.append(nd_masks[f].copy())
            group_cnt.append(int(nd_cnt[f]))
            # a solo feature keeps its full bin range (incl. mfb)
            group_bins.append(1 + width)
            group_conflict.append(0)
            group_has_cat.append(bool(is_cat[f]))
    return groups


class _RowBits:
    """A set of sample rows as packed bits: conflict counts and unions
    in O(member rows), never a sort of the group's rows."""

    # a member's rows are tested a chunk at a time: two dense columns
    # meet in the first chunk already, and a count past the budget
    # rejects the group without reading the rest
    CHUNK = 4096

    def __init__(self, n_rows: int, rows: np.ndarray) -> None:
        self.bits = np.zeros((n_rows + 7) >> 3, np.uint8)
        self.add(rows)

    def add(self, rows: np.ndarray) -> None:
        np.bitwise_or.at(self.bits, rows >> 3,
                         (1 << (rows & 7)).astype(np.uint8))

    def count(self, rows: np.ndarray, stop: int) -> int:
        """Rows of `rows` in the set, or some count past `stop` once the
        count is known to exceed it."""
        cnt = 0
        for lo in range(0, len(rows), self.CHUNK):
            r = rows[lo:lo + self.CHUNK]
            cnt += int(np.count_nonzero((self.bits[r >> 3] >> (r & 7)) & 1))
            if cnt > stop:
                break
        return cnt


def find_groups_sparse(
    nd_rows: List[Optional[np.ndarray]],  # sorted non-default row ids of
    # the SAMPLE, or None when the feature must stay a dedicated column
    num_bins: Sequence[int],
    n_rows: int,  # rows of the sample
    max_group_bins: int,
) -> List[List[int]]:
    """find_groups over SPARSE features: a group's rows are a bitset of
    the sample's rows and a conflict count reads the member's rows alone
    — no (F, N) boolean masks and no sort (the CSR ingestion path;
    dataset.cpp:111 FindGroups semantics otherwise, module docstring).
    Features whose nd_rows is None (categorical, dense, most-freq bin !=
    zero bin) found singleton groups that accept no members."""
    F = len(nd_rows)
    budget = n_rows // 10000
    cnts = np.array(
        [n_rows if r is None else len(r) for r in nd_rows], np.int64
    )
    order = np.argsort(-cnts, kind="stable")

    groups: List[List[int]] = []
    group_rows: List[Optional[_RowBits]] = []
    group_cnt: List[int] = []  # rows set in group_rows
    group_bins: List[int] = []
    group_conflict: List[int] = []
    for f in order:
        f = int(f)
        width = int(num_bins[f]) - 1
        placed = False
        if nd_rows[f] is not None and cnts[f] < n_rows:
            searched = 0
            for gid in range(len(groups)):
                if searched >= MAX_SEARCH_GROUP:
                    break
                if group_rows[gid] is None:
                    continue  # founded by an unmergeable feature
                if group_bins[gid] + width > max_group_bins:
                    continue
                rest = budget - group_conflict[gid]
                if rest < 0:
                    continue
                searched += 1
                # two sets of a and b of N rows share at least a + b - N
                # (find_groups' shortcut)
                if group_cnt[gid] + int(cnts[f]) - n_rows > rest:
                    continue
                cnt = group_rows[gid].count(
                    nd_rows[f], min(rest, int(cnts[f]) // 2))
                if cnt <= rest and cnt <= cnts[f] // 2:
                    groups[gid].append(f)
                    group_rows[gid].add(nd_rows[f])
                    group_cnt[gid] += int(cnts[f]) - cnt
                    group_bins[gid] += width
                    group_conflict[gid] += cnt
                    placed = True
                    break
        if not placed:
            groups.append([f])
            group_rows.append(
                _RowBits(n_rows, nd_rows[f]) if nd_rows[f] is not None
                else None
            )
            group_cnt.append(int(cnts[f]))
            group_bins.append(1 + width)
            group_conflict.append(0)
    return groups


def build_layout(
    groups: List[List[int]],
    num_bins: Sequence[int],
) -> BundleLayout:
    F = len(num_bins)
    bundle_of = np.zeros(F, np.int32)
    off_lo = np.zeros(F, np.int32)
    mfb = np.full(F, -1, np.int32)
    col_bins = 1
    for gid, feats in enumerate(groups):
        if len(feats) == 1:
            f = feats[0]
            bundle_of[f] = gid
            col_bins = max(col_bins, int(num_bins[f]))
            continue
        off = 1  # merged bin 0 = all members at their most-freq bin
        for f in feats:
            bundle_of[f] = gid
            off_lo[f] = off
            off += int(num_bins[f]) - 1
        col_bins = max(col_bins, off)
    return BundleLayout(
        groups=groups,
        bundle_of=bundle_of,
        off_lo=off_lo,
        mfb=np.full(F, -1, np.int32),  # filled by encode()
        col_bins=col_bins,
    )


def encode(
    bins: np.ndarray,  # (F, N) per-feature bins
    layout: BundleLayout,
    num_bins: Sequence[int],
    most_freq: Sequence[int],
    dtype=np.int32,
) -> Tuple[np.ndarray, BundleLayout]:
    """Merge per-feature bin columns into bundle columns.

    Conflicting rows (two members away from default — within the
    counted budget) resolve to the LAST member written, matching the
    reference's push-order overwrite.
    """
    F, N = bins.shape
    G = layout.num_columns
    out = np.zeros((G, N), dtype=dtype)
    mfb = np.full(F, -1, np.int32)
    for gid, feats in enumerate(layout.groups):
        if len(feats) == 1:
            out[gid] = bins[feats[0]]
            continue
        col = out[gid]
        for f in feats:
            m = int(most_freq[f])
            mfb[f] = m
            b = bins[f]
            nd = b != m
            shifted = b[nd].astype(np.int64) - (b[nd] > m)
            col[nd] = (layout.off_lo[f] + shifted).astype(dtype)
    return out, layout._replace(mfb=mfb)


def build_expand_idx(
    layout: BundleLayout, num_bins: Sequence[int], feat_bins: int
) -> np.ndarray:
    """(F, feat_bins) flat gather index into the (G * col_bins) bundle
    histogram for each (feature, bin); -1 marks the most-freq slot
    (recovered by subtraction) and out-of-range bins."""
    F = len(num_bins)
    Bc = layout.col_bins
    idx = np.full((F, feat_bins), -1, np.int32)
    for f in range(F):
        g = int(layout.bundle_of[f])
        nb = int(num_bins[f])
        m = int(layout.mfb[f])
        for b in range(nb):
            if m < 0:  # direct storage
                idx[f, b] = g * Bc + b
            elif b != m:
                idx[f, b] = g * Bc + int(layout.off_lo[f]) + b - (b > m)
    return idx


def bundle_features(
    bins: np.ndarray,
    mappers: List[BinMapper],
    max_bin: int,
    dtype=np.int32,
    sample_idx: Optional[np.ndarray] = None,
) -> Optional[Tuple[np.ndarray, BundleLayout, np.ndarray]]:
    """Full EFB pass over the binned (used-feature) matrix: groups from
    the rows `sample_idx` (the bin-construction sample; None = all
    rows), every row encoded.

    Returns (merged_bins (G, N), layout, expand_idx (F, Bf)) or None
    when no merging is possible (all groups singleton) — caller keeps
    the plain per-feature matrix with zero overhead.
    """
    num_bins = [m.num_bin for m in mappers]
    most_freq = [m.most_freq_bin for m in mappers]
    is_cat = [m.bin_type == BinType.CATEGORICAL for m in mappers]
    max_group_bins = max(max_bin + 1, 256)
    sample = bins if sample_idx is None else bins[:, sample_idx]
    groups = find_groups(sample, num_bins, most_freq, is_cat,
                         max_group_bins)
    if all(len(g) == 1 for g in groups):
        return None
    layout = build_layout(groups, num_bins)
    merged, layout = encode(bins, layout, num_bins, most_freq, dtype)
    feat_bins = max(num_bins)
    expand_idx = build_expand_idx(layout, num_bins, feat_bins)
    return merged, layout, expand_idx


class CellTables(NamedTuple):
    """Static per-cell tables of the bundle histogram's own (column,
    merged bin) layout, (G, Bc) each unless said: what the rounds
    grower's split search reads in place of a per-feature expansion
    (learner/bundle.best_split_bundled). A cell is one numerical
    threshold of one feature: cell (g, off_lo + t) of a merged member is
    its threshold t, cell (g, b) of a single-feature column its
    threshold b. Bin 0 of a merged column ("every member at its
    most-frequent bin") and the padding past a column's bins belong to
    no feature; categorical columns hold no numerical candidate and keep
    the column-wise search (`col_feat`)."""

    feat: np.ndarray  # int32 used-feature position, F where none
    thr: np.ndarray  # int32 threshold in the feature's own bins
    seg_start: np.ndarray  # bool first cell of a segment (a member's
    # range, a single-feature column's bins, every other cell alone)
    seg_end: np.ndarray  # bool last cell of a segment
    past: np.ndarray  # bool threshold at or past the member's most-
    # frequent bin: its left sums are the leaf's totals less the suffix
    nan_last: np.ndarray  # bool the cell stores its feature's NaN bin
    nan_mfb: np.ndarray  # bool the feature's NaN bin is its most-
    # frequent bin (not stored: its mass is the member's missing mass)
    cand: np.ndarray  # bool a threshold t <= last - 1 of a numerical
    # feature (`last` = its last non-NaN bin)
    extra: np.ndarray  # bool t == last in a feature with a NaN bin:
    # every value left, missing alone right (default-right only)
    pos_dr: np.ndarray  # int32 place in the reference's scan order,
    pos_dl: np.ndarray  # int32 per direction (split._best_split_impl)
    col_feat: np.ndarray  # (G,) int32 the feature of a single-feature
    # column, F for a bundle of several
    direct: np.ndarray  # (G, 1) bool a single-feature column


def cell_tables(layout: BundleLayout, num_bins: Sequence[int],
                nan_bin: Sequence[int], is_cat: Sequence[bool],
                col_bins: int) -> CellTables:
    """The CellTables of a Dataset's layout over `col_bins` cells a
    column (the device bin axis). Built once per Dataset, on the host."""
    G, F, Bc = layout.num_columns, len(num_bins), int(col_bins)
    feat = np.full((G, Bc), F, np.int32)
    thr = np.zeros((G, Bc), np.int32)
    seg_start = np.ones((G, Bc), bool)
    seg_end = np.ones((G, Bc), bool)
    past = np.zeros((G, Bc), bool)
    nan_last = np.zeros((G, Bc), bool)
    nan_mfb = np.zeros((G, Bc), bool)
    col_feat = np.full(G, F, np.int32)
    for g, members in enumerate(layout.groups):
        if len(members) == 1:
            f = members[0]
            col_feat[g] = f
            lo, w, m, direct = 0, int(num_bins[f]), -1, True
            spans = [(f, lo, w, m)]
        else:
            spans = [(f, int(layout.off_lo[f]), int(num_bins[f]) - 1,
                      int(layout.mfb[f])) for f in members]
            direct = False
        for f, lo, w, m in spans:
            seg_start[g, lo + 1:lo + w] = False
            seg_end[g, lo:lo + w - 1] = False
            if is_cat[f]:
                continue
            feat[g, lo:lo + w] = f
            thr[g, lo:lo + w] = np.arange(w)
            nb = int(nan_bin[f])
            if direct:
                if nb >= 0:
                    nan_last[g, nb] = True
                continue
            past[g, lo + max(m, 0):lo + w] = True
            if nb >= 0:
                if nb == m:
                    nan_mfb[g, lo:lo + w] = True
                else:
                    nan_last[g, lo + nb - (nb > m)] = True
    nbin = np.append(np.asarray(num_bins, np.int32), 0)[feat]
    has_nan = np.append(np.asarray(nan_bin, np.int32), -1)[feat] >= 0
    last = np.where(has_nan, nbin - 2, nbin - 1)
    real = feat < F
    cand = real & (thr <= last - 1)
    extra = real & has_nan & (thr == last)
    pos_dl = np.where(real, last - 1 - thr, 0).astype(np.int32)
    pos_dr = np.where(has_nan, thr, pos_dl).astype(np.int32)
    return CellTables(feat, thr, seg_start, seg_end, past, nan_last,
                      nan_mfb, cand, extra, pos_dr, pos_dl, col_feat,
                      (col_feat < F)[:, None])
