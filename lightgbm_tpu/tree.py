"""Tree model: host representation, prediction, and device traversal.

Mirrors the reference array-based Tree (include/LightGBM/tree.h:26,
src/io/tree.cpp): internal node arrays (split_feature, threshold,
decision_type, left/right children with <0 = ~leaf encoding) and leaf
arrays. decision_type bit layout (tree.h:20-21):

  bit 0: categorical (1) / numerical (0)
  bit 1: default_left
  bits 2-3: missing type (0 None, 1 Zero, 2 NaN)

Thresholds are stored as real values; numerical decisions are
`value <= threshold` -> left. Categorical decisions test membership of
int(value) in a bitset (cat_threshold) -> left.
"""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import TYPE_CHECKING, List, Optional, Sequence

import numpy as np

from .binning import BinType, K_ZERO_THRESHOLD, MissingType

if TYPE_CHECKING:
    from .dataset import BinnedDataset
    from .learner.grower import TreeArrays

_CAT_MASK = 1
_DEFAULT_LEFT_MASK = 2


def _missing_type_of(dt: int) -> int:
    return (int(dt) >> 2) & 3


@dataclass
class Tree:
    """Host-side decision tree in the reference model-file layout."""

    num_leaves: int
    shrinkage: float = 1.0
    # internal nodes (num_leaves - 1 entries; may be 0 for a stump)
    split_feature: np.ndarray = field(default_factory=lambda: np.zeros(0, np.int32))
    split_gain: np.ndarray = field(default_factory=lambda: np.zeros(0, np.float64))
    threshold: np.ndarray = field(default_factory=lambda: np.zeros(0, np.float64))
    decision_type: np.ndarray = field(default_factory=lambda: np.zeros(0, np.int32))
    left_child: np.ndarray = field(default_factory=lambda: np.zeros(0, np.int32))
    right_child: np.ndarray = field(default_factory=lambda: np.zeros(0, np.int32))
    internal_value: np.ndarray = field(default_factory=lambda: np.zeros(0, np.float64))
    internal_weight: np.ndarray = field(default_factory=lambda: np.zeros(0, np.float64))
    internal_count: np.ndarray = field(default_factory=lambda: np.zeros(0, np.int64))
    # leaves
    leaf_value: np.ndarray = field(default_factory=lambda: np.zeros(1, np.float64))
    leaf_weight: np.ndarray = field(default_factory=lambda: np.zeros(1, np.float64))
    leaf_count: np.ndarray = field(default_factory=lambda: np.zeros(1, np.int64))
    # categorical bitsets (tree.h cat_boundaries_/cat_threshold_)
    num_cat: int = 0
    cat_boundaries: np.ndarray = field(default_factory=lambda: np.zeros(1, np.int64))
    cat_threshold: np.ndarray = field(default_factory=lambda: np.zeros(0, np.uint32))
    is_linear: bool = False
    # linear leaves (tree.h leaf_const_/leaf_coeff_/leaf_features_):
    # output = leaf_const + sum(coeff * raw feature), falling back to
    # leaf_value when any leaf feature is NaN (tree.cpp:137-153)
    leaf_const: np.ndarray = field(default_factory=lambda: np.zeros(0, np.float64))
    leaf_features: List[List[int]] = field(default_factory=list)
    leaf_coeff: List[List[float]] = field(default_factory=list)

    # ------------------------------------------------------------------
    @staticmethod
    def from_arrays(arrays: "TreeArrays", dataset: "BinnedDataset", shrinkage: float) -> "Tree":
        """Convert device TreeArrays (used-feature indices, bin thresholds)
        to the host model (original feature indices, real thresholds)."""
        n_nodes = int(arrays.num_nodes)
        num_leaves = n_nodes + 1
        t = Tree(num_leaves=num_leaves, shrinkage=shrinkage)
        used = dataset.used_features
        mappers = dataset.mappers

        nf = np.asarray(arrays.node_feature[:n_nodes])
        nb = np.asarray(arrays.node_bin[:n_nodes])
        ndl = np.asarray(arrays.node_default_left[:n_nodes])
        ncat = np.asarray(arrays.node_cat[:n_nodes])
        ncat_mask = np.asarray(arrays.node_cat_mask[:n_nodes]) if ncat.any() else None

        t.split_feature = used[nf].astype(np.int32) if n_nodes else np.zeros(0, np.int32)
        t.split_gain = np.asarray(arrays.node_gain[:n_nodes], dtype=np.float64)
        t.left_child = np.asarray(arrays.node_left[:n_nodes], dtype=np.int32)
        t.right_child = np.asarray(arrays.node_right[:n_nodes], dtype=np.int32)
        t.internal_value = np.asarray(arrays.node_value[:n_nodes], dtype=np.float64)
        t.internal_weight = np.asarray(arrays.node_weight[:n_nodes], dtype=np.float64)
        t.internal_count = np.asarray(
            np.round(arrays.node_count[:n_nodes]), dtype=np.int64
        )
        t.leaf_value = np.asarray(arrays.leaf_value[:num_leaves], dtype=np.float64) * shrinkage
        t.leaf_weight = np.asarray(arrays.leaf_weight[:num_leaves], dtype=np.float64)
        t.leaf_count = np.asarray(np.round(arrays.leaf_count[:num_leaves]), dtype=np.int64)

        thresholds = np.zeros(n_nodes, np.float64)
        decision = np.zeros(n_nodes, np.int32)
        cat_boundaries = [0]
        cat_threshold: List[np.uint32] = []
        n_cat = 0
        for i in range(n_nodes):
            m = mappers[int(t.split_feature[i])]
            dt = 0
            if m.missing_type == MissingType.NAN:
                dt |= 2 << 2
            # NOTE: MissingType.ZERO is intentionally emitted as None: the
            # grower currently routes the zero bin numerically (by
            # threshold), so prediction must too; the reference's
            # zero-as-missing default-direction double scan is a pending
            # milestone (feature_histogram.hpp:832 NA_AS_MISSING path).
            if ncat[i]:
                dt |= _CAT_MASK
                # bitset over the left-going category VALUES (one for
                # one-vs-rest, several for sorted-subset splits —
                # tree.h cat_threshold_ layout)
                bins_left = np.nonzero(ncat_mask[i])[0]
                cat_vals = [
                    int(m.categories[bl])
                    for bl in bins_left
                    if bl < len(m.categories)
                ]
                # empty set degenerates to an all-right bitset (never a
                # valid split; kept loud-safe rather than guessing a bin)
                n_words = (max(cat_vals) // 32 + 1) if cat_vals else 1
                words = [0] * n_words
                for cv in cat_vals:
                    words[cv // 32] |= 1 << (cv % 32)
                thresholds[i] = float(n_cat)  # index into cat_boundaries
                cat_threshold.extend(np.uint32(w) for w in words)
                cat_boundaries.append(len(cat_threshold))
                n_cat += 1
            else:
                if ndl[i]:
                    dt |= _DEFAULT_LEFT_MASK
                thresholds[i] = m.bin_to_value(int(nb[i]))
                if m.nan_bin >= 0 and int(nb[i]) == m.nan_bin - 1:
                    # every value left, missing alone right: no value is
                    # above this threshold (the reference's AvoidInf)
                    thresholds[i] = 1e300
            decision[i] = dt
        t.threshold = thresholds
        t.decision_type = decision
        t.num_cat = n_cat
        t.cat_boundaries = np.asarray(cat_boundaries, dtype=np.int64)
        t.cat_threshold = np.asarray(cat_threshold, dtype=np.uint32)
        return t

    # ------------------------------------------------------------------
    def max_depth(self) -> int:
        if self.num_leaves <= 1:
            return 0
        depth = np.zeros(len(self.left_child), np.int32)
        md = 1
        for i in range(len(self.left_child)):
            for c in (self.left_child[i], self.right_child[i]):
                if c >= 0:
                    depth[c] = depth[i] + 1
                    md = max(md, depth[c] + 1)
                else:
                    md = max(md, depth[i] + 1)
        return int(md)

    def _cat_in_bitset(self, node: int, values: np.ndarray) -> np.ndarray:
        ci = int(self.threshold[node])
        lo, hi = self.cat_boundaries[ci], self.cat_boundaries[ci + 1]
        words = self.cat_threshold[lo:hi]
        iv = np.where(np.isnan(values), -1, values).astype(np.int64)
        ok = (iv >= 0) & (iv < 32 * len(words))
        ivc = np.clip(iv, 0, max(0, 32 * len(words) - 1))
        bits = (words[ivc // 32] >> (ivc % 32).astype(np.uint32)) & 1
        return ok & (bits == 1)

    def go_left(self, node: int, x: np.ndarray) -> bool:
        """Scalar decision for one row at one node — the single source of
        truth for decision semantics shared with the vectorized walk below
        (tree.h Decision/CategoricalDecision)."""
        v = x[self.split_feature[node]]
        dt = int(self.decision_type[node])
        if dt & _CAT_MASK:
            if np.isnan(v):
                return False
            return bool(self._cat_in_bitset(node, np.asarray([v]))[0])
        missing_type = (dt >> 2) & 3
        default_left = bool(dt & _DEFAULT_LEFT_MASK)
        isna = np.isnan(v)
        if missing_type == 2:  # NaN as missing
            if isna:
                return default_left
        else:
            if isna:
                v = 0.0
            if missing_type == 1 and abs(v) <= K_ZERO_THRESHOLD:  # Zero as missing
                return default_left
        return bool(v <= self.threshold[node])

    def predict_leaf(self, X: np.ndarray) -> np.ndarray:
        """Vectorized decision walk -> leaf index per row (Tree::Predict)."""
        n = X.shape[0]
        if self.num_leaves <= 1:
            return np.zeros(n, np.int64)
        cur = np.zeros(n, np.int64)  # node ids; leaves become ~leaf
        active = np.ones(n, bool)
        while np.any(active):
            nodes = cur[active]
            feat = self.split_feature[nodes]
            x = X[active, feat]
            dt = self.decision_type[nodes]
            is_cat = (dt & _CAT_MASK) != 0
            go_left = np.zeros(len(nodes), bool)
            # numerical
            num_idx = ~is_cat
            if np.any(num_idx):
                xv = x[num_idx].astype(np.float64)
                nn = nodes[num_idx]
                thr = self.threshold[nn]
                mt = (dt[num_idx] >> 2) & 3
                dl = (dt[num_idx] & _DEFAULT_LEFT_MASK) != 0
                isna = np.isnan(xv)
                # Zero missing: NaN and 0 treated as missing (tree.cpp Decision)
                miss = np.where(mt == 2, isna, np.where(mt == 1, isna | (np.abs(xv) <= K_ZERO_THRESHOLD), np.zeros_like(isna)))
                xv = np.where(isna & (mt != 2), 0.0, xv)
                gl = np.where(miss, dl, xv <= thr)
                go_left[num_idx] = gl
            if np.any(is_cat):
                cn = nodes[is_cat]
                xv = x[is_cat].astype(np.float64)
                gl = np.zeros(len(cn), bool)
                for u in np.unique(cn):
                    mask = cn == u
                    gl[mask] = self._cat_in_bitset(int(u), xv[mask])
                go_left[is_cat] = gl
            nxt = np.where(go_left, self.left_child[nodes], self.right_child[nodes])
            cur[active] = nxt
            active = cur >= 0
        return ~cur  # leaf index

    def predict(self, X: np.ndarray) -> np.ndarray:
        leaf = self.predict_leaf(X)
        if not self.is_linear:
            return self.leaf_value[leaf]
        return self.linear_leaf_outputs(X, leaf)

    def linear_leaf_outputs(self, X: np.ndarray, leaf: np.ndarray) -> np.ndarray:
        """Linear-leaf outputs per row (tree.cpp:137-153 PredictionFun
        with is_linear): const + coeffs . raw features, NaN -> leaf_value."""
        out = self.leaf_value[leaf].astype(np.float64).copy()
        for l in range(self.num_leaves):
            m = leaf == l
            if not np.any(m):
                continue
            feats = self.leaf_features[l] if l < len(self.leaf_features) else []
            const = self.leaf_const[l] if l < len(self.leaf_const) else 0.0
            if not feats:
                out[m] = const
                continue
            Xl = np.asarray(X, np.float64)[np.ix_(m, feats)]
            v = const + Xl @ np.asarray(self.leaf_coeff[l], np.float64)
            nanrow = np.isnan(Xl).any(axis=1)
            out[m] = np.where(nanrow, self.leaf_value[l], v)
        return out

    def fit_linear_leaves(self, row_leaf: np.ndarray, grad: np.ndarray,
                          hess: np.ndarray, raw: np.ndarray,
                          cat_features: set, linear_lambda: float,
                          shrinkage: float,
                          row_mask: "np.ndarray | None" = None) -> None:
        """Fit one ridge model per leaf on the leaf's PATH features
        (linear_tree_learner.cpp:255-358 CalculateLinear): accumulate
        X^T H X / X^T g over non-NaN leaf rows, solve
        coeffs = -(X^T H X + lambda I)^-1 X^T g, scale by shrinkage.
        Degenerate leaves (fewer usable rows than coefficients) keep the
        plain leaf_value as a constant."""
        L = self.num_leaves
        paths: List[List[int]] = [[] for _ in range(L)]

        def walk(node, feats):
            if node < 0:
                paths[~node] = feats
                return
            f = int(self.split_feature[node])
            nf = feats if (f in cat_features or f in feats) else feats + [f]
            walk(int(self.left_child[node]), nf)
            walk(int(self.right_child[node]), nf)

        if L > 1:
            walk(0, [])
        self.is_linear = True
        self.leaf_const = self.leaf_value.astype(np.float64).copy()
        self.leaf_features = [list(p) for p in paths]
        self.leaf_coeff = [[0.0] * len(p) for p in paths]
        raw = np.asarray(raw, np.float64)
        for leaf in range(L):
            feats = paths[leaf]
            k = len(feats)
            sel = row_leaf == leaf
            if row_mask is not None:  # in-bag rows only (bagging / GOSS)
                sel = sel & row_mask
            if k == 0 or not np.any(sel):
                continue
            Xl = raw[np.ix_(sel, feats)]
            ok = ~np.isnan(Xl).any(axis=1)
            if int(ok.sum()) < k + 1:
                continue
            Xa = np.concatenate(
                [Xl[ok], np.ones((int(ok.sum()), 1))], axis=1
            )
            g = np.asarray(grad, np.float64)[sel][ok]
            h = np.asarray(hess, np.float64)[sel][ok]
            A = (Xa.T * h) @ Xa
            A[np.arange(k), np.arange(k)] += linear_lambda
            b = Xa.T @ g
            try:
                coef = -np.linalg.solve(A, b)
            except np.linalg.LinAlgError:
                continue
            if not np.isfinite(coef).all():
                continue
            self.leaf_coeff[leaf] = [float(c) * shrinkage for c in coef[:k]]
            self.leaf_const[leaf] = float(coef[k]) * shrinkage

    def feature_importance_split(self, num_features: int) -> np.ndarray:
        imp = np.zeros(num_features)
        for i in range(len(self.split_feature)):
            if self.split_gain[i] > 0:
                imp[self.split_feature[i]] += 1
        return imp

    def feature_importance_gain(self, num_features: int) -> np.ndarray:
        imp = np.zeros(num_features)
        for i in range(len(self.split_feature)):
            if self.split_gain[i] > 0:
                imp[self.split_feature[i]] += self.split_gain[i]
        return imp


def tree_to_arrays(t: Tree, dataset: "BinnedDataset") -> "TreeArrays":
    """Inverse of Tree.from_arrays: host model tree -> device TreeArrays
    sized to the tree, for binned traversal (continued training seeds
    scores, DART drops of loaded trees).

    Thresholds map back through the dataset's bin boundaries; for models
    trained on THIS binning the round-trip is exact (thresholds are bin
    upper bounds), for foreign models the approximation is bounded by
    one bin width — the same resolution training itself sees.
    """
    import jax.numpy as jnp

    from .learner.grower import TreeArrays

    L = t.num_leaves
    n_nodes = L - 1
    B = dataset.max_num_bin
    used_of = {int(f): i for i, f in enumerate(dataset.used_features)}
    nf = np.zeros(max(n_nodes, 1), np.int32)
    nb = np.zeros(max(n_nodes, 1), np.int32)
    ndl = np.zeros(max(n_nodes, 1), bool)
    ncat = np.zeros(max(n_nodes, 1), bool)
    nmask = np.zeros((max(n_nodes, 1), B), bool)
    for i in range(n_nodes):
        f_orig = int(t.split_feature[i])
        m = dataset.mappers[f_orig]
        dt = int(t.decision_type[i])
        if f_orig not in used_of:
            # split feature is trivial (constant) in THIS dataset: every
            # row takes the same branch — resolve it host-side and encode
            # as an always-left / always-right numerical node on feature 0
            row = np.zeros(len(dataset.mappers))
            row[f_orig] = m.min_value
            go_l = bool(t.go_left(i, row))
            nf[i] = 0
            nb[i] = B + 1 if go_l else -1
            continue
        nf[i] = used_of[f_orig]
        if dt & _CAT_MASK:
            ncat[i] = True
            ci = int(t.threshold[i])
            lo, hi = int(t.cat_boundaries[ci]), int(t.cat_boundaries[ci + 1])
            words = t.cat_threshold[lo:hi]
            c2b = m._cat_to_bin or {}
            for cv, b in c2b.items():
                if cv // 32 < len(words) and (int(words[cv // 32]) >> (cv % 32)) & 1:
                    if b < B:
                        nmask[i, b] = True
        else:
            ndl[i] = bool(dt & _DEFAULT_LEFT_MASK)
            nb[i] = int(
                np.clip(
                    np.searchsorted(m.upper_bounds, t.threshold[i], side="left"),
                    0,
                    max(m.num_bin - 1, 0),
                )
            )
    z = np.zeros
    return TreeArrays(
        num_nodes=jnp.int32(n_nodes),
        node_feature=jnp.asarray(nf),
        node_bin=jnp.asarray(nb),
        node_gain=jnp.asarray(np.asarray(t.split_gain, np.float32) if n_nodes else z(1, np.float32)),
        node_default_left=jnp.asarray(ndl),
        node_cat=jnp.asarray(ncat),
        node_cat_mask=jnp.asarray(nmask),
        node_left=jnp.asarray(np.asarray(t.left_child, np.int32) if n_nodes else z(1, np.int32)),
        node_right=jnp.asarray(np.asarray(t.right_child, np.int32) if n_nodes else z(1, np.int32)),
        node_value=jnp.asarray(np.asarray(t.internal_value, np.float32) if n_nodes else z(1, np.float32)),
        node_weight=jnp.asarray(np.asarray(t.internal_weight, np.float32) if n_nodes else z(1, np.float32)),
        node_count=jnp.asarray(np.asarray(t.internal_count, np.float32) if n_nodes else z(1, np.float32)),
        leaf_value=jnp.asarray(np.asarray(t.leaf_value, np.float32)),
        leaf_weight=jnp.asarray(np.asarray(t.leaf_weight, np.float32)),
        leaf_count=jnp.asarray(np.asarray(t.leaf_count, np.float32)),
        leaf_depth=jnp.zeros(L, jnp.int32),
    )


# bins of a node's category set that one f32 row of the traversal's
# per-node table carries: take_cols contracts at Precision.HIGHEST
# against a 0/1 one-hot, so a non-negative integer below 2**24 comes
# back exact; a power of two makes a bin's word and bit a shift and a mask
_CAT_WORD_SHIFT = 4
CAT_WORD_BITS = 1 << _CAT_WORD_SHIFT


def num_cat_words(num_bins: int) -> int:
    """Rows the category sets add to the traversal's per-node table."""
    return -(-num_bins // CAT_WORD_BITS)


def cat_mask_words(node_cat_mask):
    """(max_nodes, B) bool sets of left-going bins -> (W, max_nodes) f32
    bit words, W = num_cat_words(B): bin b of a node is bit
    b % CAT_WORD_BITS of its word b // CAT_WORD_BITS, the last word
    padded with zeros. Once a tree (tiny)."""
    import jax.numpy as jnp

    max_nodes, B = node_cat_mask.shape
    W = num_cat_words(B)
    bits = jnp.pad(node_cat_mask, ((0, 0), (0, W * CAT_WORD_BITS - B)))
    weights = jnp.left_shift(1, jnp.arange(CAT_WORD_BITS, dtype=jnp.int32))
    words = jnp.sum(
        bits.reshape(max_nodes, W, CAT_WORD_BITS) * weights, axis=-1)
    return words.T.astype(jnp.float32)


def traverse_tree_bins(arrays: "TreeArrays", bins_fm, nan_bin, bundle=None,
                       has_cat: bool = True):
    """Device traversal of a grown tree over a BINNED matrix -> per-row leaf.

    Used to score validation sets each iteration (reference
    ScoreUpdater::AddScore via tree traversal). DEPTH-stepped: every row
    advances one level per pass, so the loop runs tree-depth times (not
    num_nodes times — 254 sequential passes at 255 leaves would dominate
    the fused iteration). Per pass, the rows' current-node parameters
    (feature column, threshold bin, default direction, children, NaN
    bin) come from ONE one-hot MXU contraction against a packed
    per-node table (take_cols — this chip has no vector gather: a (N,)
    element take from a small table read 4 ms per 1M rows on a v5e,
    250M elements a second, the contraction ~0.3 ms: PERF.md section
    6, PR 36 and PR 37), and each row's split-feature bin is a masked
    select over the column axis. A categorical node's set of left-going bins rides
    the same table as bit words (cat_mask_words), so the row's verdict
    is a word select and a bit test on the VPU, not a gather. With
    `bundle` (EFB datasets) the matrix columns are bundles: the node's
    feature's (mfb, off_lo, width) ride the table as three rows more and
    each row's bin is decoded from them (bundle.decode_bins; three
    per-row element gathers of the per-feature tables cost 260 of the
    sparse cell's 279 ms a tree of valid eval, PERF.md section 6, PR
    38). `has_cat=False` (all-numerical dataset) statically skips the
    category-set test and keeps the table at its 8 rows.
    """
    import jax.numpy as jnp
    from jax import lax

    from .learner.histogram import take_cols

    G, N = bins_fm.shape
    n_nodes = arrays.num_nodes
    max_nodes = arrays.node_feature.shape[0]

    # per-node derived columns (tiny (L-1,) gathers, once per tree)
    node_col = (arrays.node_feature if bundle is None
                else bundle.bundle_of[arrays.node_feature])
    node_nan = nan_bin[arrays.node_feature]
    pack = jnp.stack([
        node_col.astype(jnp.float32),  # 0: device bin column
        arrays.node_feature.astype(jnp.float32),  # 1: feature id (unread)
        arrays.node_bin.astype(jnp.float32),  # 2
        arrays.node_default_left.astype(jnp.float32),  # 3
        arrays.node_cat.astype(jnp.float32),  # 4
        arrays.node_left.astype(jnp.float32),  # 5 (negative = ~leaf)
        arrays.node_right.astype(jnp.float32),  # 6
        node_nan.astype(jnp.float32),  # 7 (-1 = none)
    ])  # (8, max_nodes)
    if bundle is not None:
        # 8..10: how the node's feature sits in its bundle column
        pack = jnp.concatenate([pack, jnp.stack([
            t[arrays.node_feature].astype(jnp.float32)
            for t in (bundle.mfb, bundle.off_lo, bundle.width)])])
    n_base = pack.shape[0]
    if has_cat:
        # then the node's category set, (W, max_nodes) bit words
        pack = jnp.concatenate([pack, cat_mask_words(arrays.node_cat_mask)])

    def cond(s):
        it, row_node = s
        return (it < max_nodes) & jnp.any(row_node >= 0)

    def body(s):
        it, row_node = s
        k = jnp.maximum(row_node, 0)  # clamp: leaf rows produce dead lanes
        v = take_cols(pack, k)  # (rows of pack, N)
        col = v[0].astype(jnp.int32)
        # masked select of each row's split-feature bin over the column
        # axis: sum of G per-column selects (VPU), no 2D gather
        sel = col[None, :] == jnp.arange(G, dtype=jnp.int32)[:, None]  # (G, N)
        fbins = jnp.sum(jnp.where(sel, bins_fm, 0), axis=0)
        if bundle is not None:
            from .learner.bundle import decode_bins

            fbins = decode_bins(fbins, *(v[r].astype(jnp.int32)
                                         for r in (8, 9, 10)))
        fnan = v[7].astype(jnp.int32)
        num_go_left = (fbins <= v[2].astype(jnp.int32)) | (
            (v[3] > 0.5) & (fbins == fnan) & (fnan >= 0)
        )
        if has_cat:
            # the row's word of its node's set by a masked select over
            # the W word rows (a bin past them matches none: right),
            # then its bit: no gather, no (B, N) intermediate
            words = v[n_base:]
            sel_w = (fbins >> _CAT_WORD_SHIFT)[None, :] == jnp.arange(
                words.shape[0], dtype=jnp.int32)[:, None]  # (W, N)
            word = jnp.sum(jnp.where(sel_w, words, 0.0), axis=0).astype(jnp.int32)
            cat_hit = ((word >> (fbins & (CAT_WORD_BITS - 1))) & 1) == 1
            go_left = jnp.where(v[4] > 0.5, cat_hit, num_go_left)
        else:
            go_left = num_go_left
        child = jnp.where(go_left, v[5], v[6]).astype(jnp.int32)
        at_internal = (row_node >= 0) & (row_node < n_nodes)
        row_node = jnp.where(at_internal, child, row_node)
        return it + 1, row_node

    row_node = jnp.where(n_nodes > 0, 0, -1) * jnp.ones(N, jnp.int32)
    _, row_node = lax.while_loop(cond, body, (jnp.int32(0), row_node))
    # all rows now at leaves (negative); a stump stays at node 0
    leaf = jnp.where(row_node < 0, ~row_node, 0)
    return leaf
