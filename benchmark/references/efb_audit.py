"""EFB audit: the first TWO trees of a model trained on a SPARSE one-hot
table (scipy CSR) against plain NumPy at full size, independent of the
program's bundling and of every kernel.

The program stores the table as EFB bundles: one-hot columns that are
(nearly) never non-zero in one row share a device column. Grouping is
counted on a sample, so in the full table a few rows hold two members of
one bundle; such a row keeps ONE of them, the member written last, which
is also the one with the highest merged bin (the reference's push order
and offsets). That is the contract this audit holds the program to, from
the raw CSR:

- the bin matrix against the CSR: every used one-hot column is found in
  exactly one device column at one merged value (``locate``), never
  where the CSR has it 0, and lost only in rows where a member of its
  bundle with a higher value is 1 (checked on every row for every split
  feature, so routing a row by the bin matrix is routing it by the CSR
  under that rule); every split numerical column is found as a
  column whose bins never fall as its raw values rise;
- the mechanism is there: at least 4,000 of the table's columns share a
  device column with another, the matrix has at most 64 columns, and
  trees 1 and 2 split on such merged members;
- trees 1 and 2 (float64 gradients, as ``tree_audit``): a walker of its
  own over the model text routes every training row, one-hot values
  resolved as above and numerical values raw (CSC column lookups, an
  absent entry 0.0); every ``leaf_count`` equals the walk and every leaf
  value is -sum(g) / (sum(h) + lambda) x lr;
- the best exact gain of every node of trees 1 and 2 over each ORIGINAL
  column: a one-hot column's one threshold from its CSR entries (0/1 as
  stored), a numerical column's thresholds over the program's own bins
  (as ``tree_audit``), under ``min_data_in_leaf`` and
  ``min_sum_hessian_in_leaf``; tree 1's root and tree 2's sum of chosen
  over best gain held to the configuration's floors;
- the valid AUC from the walker's margins (one-hot values resolved by the
  same rule) equals the device's, rises, and lies in the configuration's
  band around the f32-channel program's."""

from __future__ import annotations

import time
from concurrent.futures import ThreadPoolExecutor
from typing import Any, Dict, List, Optional, Sequence, Tuple

import numpy as np

from benchmark.harness import modeltext
from benchmark.references.tree_audit import (
    AUC_EVAL_ATOL,
    auc,
    check_leaves,
    _split_gain,
)

MIN_MERGED = 4000  # features that share a device column with another
MAX_COLUMNS = 64  # device columns of the bin matrix
PROBE_ROWS = 32  # rows of a column's CSR entries read to locate it
COLUMN_CHUNK = 256  # one-hot columns a thread bincounts at once
THREADS = 8
BINS = 256  # host bins are uint8


class Table:
    """A CSC table as the walker sees it. ``where[f] = (g, v)``: one-hot
    column f is device column g at merged value v; ``higher[f]``: the
    one-hot columns of the same device column at a higher value."""

    def __init__(self, csc, onehot: np.ndarray, where: Dict[int, Tuple],
                 higher: Dict[int, np.ndarray]) -> None:
        self.csc, self.onehot = csc, onehot
        self.where, self.higher = where, higher
        self._cols: Dict[int, np.ndarray] = {}

    def raw(self, f: int) -> np.ndarray:
        """Column f, every row (absent entries 0.0), float64."""
        lo, hi = self.csc.indptr[f], self.csc.indptr[f + 1]
        out = np.zeros(self.csc.shape[0], np.float64)
        out[self.csc.indices[lo:hi]] = self.csc.data[lo:hi]
        return out

    def ones(self, f: int) -> np.ndarray:
        """Rows where one-hot column f is 1 in the CSR."""
        lo, hi = self.csc.indptr[f], self.csc.indptr[f + 1]
        return self.csc.indices[lo:hi]

    def resolved(self, f: int) -> np.ndarray:
        """One-hot column f as a bundle holds it: 1 where the CSR has it,
        but not where a member of its bundle with a higher merged value
        is 1 too (that member was written last)."""
        out = np.zeros(self.csc.shape[0], bool)
        out[self.ones(f)] = True
        for h in self.higher.get(f, ()):
            out[self.ones(int(h))] = False
        return out

    def column(self, f: int) -> np.ndarray:
        if f not in self._cols:
            self._cols[f] = (self.resolved(f).astype(np.float64)
                             if self.onehot[f] else self.raw(f))
        return self._cols[f]


def route(t: modeltext.PlainTree, col, n: int) -> np.ndarray:
    """Leaf of every row: node by node over the rows that reach it;
    ``col(f, rows)`` gives column f's values at those rows."""
    leaf = np.zeros(n, np.int64)
    if t.num_leaves <= 1:
        return leaf
    if np.any(t.decision_type & 1) or np.any((t.decision_type >> 2) & 3
                                             == 1):
        raise ValueError("categorical or zero-as-missing split: not in "
                         "this configuration's models")
    stack = [(0, np.arange(n))]
    while stack:
        node, rows = stack.pop()
        go = col(int(t.split_feature[node]), rows) <= t.threshold[node]
        for child, sel in ((t.left_child[node], rows[go]),
                           (t.right_child[node], rows[~go])):
            if child < 0:
                leaf[sel] = ~child
            elif sel.size:
                stack.append((int(child), sel))
    return leaf


def onehot_columns(csc) -> np.ndarray:
    """Columns whose every stored entry is 1.0."""
    F = csc.shape[1]
    col = np.repeat(np.arange(F), np.diff(csc.indptr))
    return np.bincount(col[csc.data != 1.0], minlength=F) == 0


def value_counts(bins: np.ndarray) -> np.ndarray:
    """(columns, 256): rows of each device column at each value."""
    with ThreadPoolExecutor(THREADS) as pool:
        return np.stack(list(pool.map(
            lambda b: np.bincount(b, minlength=BINS), bins)))


def locate(rows: np.ndarray, bins: np.ndarray, counts: np.ndarray
           ) -> Optional[Tuple[int, int]]:
    """(device column, merged value) of the one-hot column whose CSR
    entries sit in ``rows``: a column where at least half of a probe of
    those rows share one non-zero value, and where that value lies in no
    other row of the table (``counts``, checked over all of ``rows``).
    None where no column or more than one passes (a column that lost
    every row it has to members written after it)."""
    if not rows.size:
        return None
    probe = rows[np.linspace(0, rows.size - 1, min(PROBE_ROWS, rows.size)
                             ).astype(np.int64)]
    B = bins[:, probe].astype(np.int64)
    mode = np.sort(B, axis=1)[:, B.shape[1] // 2]
    hits = (B == mode[:, None]).sum(axis=1)
    found = [int(g) for g in np.flatnonzero(
        (mode != 0) & (2 * hits >= B.shape[1]))
        if counts[g, mode[g]] == np.count_nonzero(bins[g, rows] == mode[g])]
    if len(found) != 1:
        return None
    return found[0], int(mode[found[0]])


def numeric_column(raw: np.ndarray, bins: np.ndarray,
                   rng: np.random.Generator) -> Optional[int]:
    """The device column that bins a numerical column: its bins never
    fall as the raw values rise and take more than two values."""
    probe = rng.integers(0, raw.size, 4096)
    probe = probe[np.argsort(raw[probe], kind="stable")]
    found = []
    for g in range(bins.shape[0]):
        b = bins[g, probe].astype(np.int64)
        if np.all(np.diff(b) >= 0) and np.unique(b).size > 2:
            found.append(g)
    return found[0] if len(found) == 1 else None


def leaf_sums(tab: Table, leaf: np.ndarray, g: np.ndarray, h: np.ndarray,
              L: int, feats: np.ndarray) -> np.ndarray:
    """(3, len(feats), L): g, h and rows of each leaf where one-hot
    column feats[k] is 1 in the CSR, by chunks of columns on threads."""
    csc = tab.csc

    def chunk(k0: int) -> np.ndarray:
        fs = feats[k0:k0 + COLUMN_CHUNK]
        parts = [csc.indices[csc.indptr[f]:csc.indptr[f + 1]] for f in fs]
        rows = np.concatenate(parts)
        key = (np.repeat(np.arange(len(fs)), [p.size for p in parts]) * L
               + leaf[rows])
        n = len(fs) * L
        return np.stack([np.bincount(key, weights=g[rows], minlength=n),
                         np.bincount(key, weights=h[rows], minlength=n),
                         np.bincount(key, minlength=n).astype(np.float64)]
                        ).reshape(3, len(fs), L)

    with ThreadPoolExecutor(THREADS) as pool:
        return np.concatenate(list(pool.map(
            chunk, range(0, len(feats), COLUMN_CHUNK))), axis=1)


def up_the_tree(t: modeltext.PlainTree, per_leaf: np.ndarray
                ) -> np.ndarray:
    """(..., L) per-leaf sums -> (..., M) per-node sums (a node holds
    the leaves below it)."""
    M = t.num_leaves - 1
    node = np.zeros(per_leaf.shape[:-1] + (M,))
    done = np.zeros(M, bool)
    stack = [0]

    def of(c: int) -> np.ndarray:
        return per_leaf[..., ~c] if c < 0 else node[..., c]

    while stack:
        i = stack[-1]
        kids = (int(t.left_child[i]), int(t.right_child[i]))
        todo = [c for c in kids if c >= 0 and not done[c]]
        if todo:
            stack.extend(todo)
            continue
        node[..., i] = of(kids[0]) + of(kids[1])
        done[i] = True
        stack.pop()
    return node


def gain_shares(t: modeltext.PlainTree, tab: Table, leaf: np.ndarray,
                g: np.ndarray, h: np.ndarray, onehot_used: np.ndarray,
                num_cols: Dict[int, int], bins: np.ndarray, lam: float,
                min_data: float, min_hess: float) -> Tuple[float, float]:
    """(root share, tree share) of the chosen split's exact gain over the
    best exact gain of its node over every original column."""
    L, M = t.num_leaves, t.num_leaves - 1
    if M < 1:
        return 0.0, 0.0
    tot_leaf = np.stack([np.bincount(leaf, weights=g, minlength=L),
                         np.bincount(leaf, weights=h, minlength=L),
                         np.bincount(leaf, minlength=L).astype(float)])
    tot = up_the_tree(t, tot_leaf)  # (3, M)
    G, H, N = tot

    def ok_gain(gl, hl, nl):
        ok = ((nl >= min_data) & (N - nl >= min_data)
              & (hl >= min_hess) & (H - hl >= min_hess))
        safe = np.where(ok, hl, 0.5 * H)
        return np.where(ok, _split_gain(gl, safe, G, H, lam), 0.0)

    # one-hot: the one threshold, value 0 left
    ones = up_the_tree(t, leaf_sums(tab, leaf, g, h, L, onehot_used))
    best = ok_gain(*(tot[:, None, :] - ones)).max(axis=0)
    # numerical: every bin boundary of the program's bins
    for f, gcol in num_cols.items():
        key = leaf * BINS + bins[gcol].astype(np.int64)
        lh = np.stack([np.bincount(key, weights=g, minlength=L * BINS),
                       np.bincount(key, weights=h, minlength=L * BINS),
                       np.bincount(key, minlength=L * BINS).astype(float)]
                      ).reshape(3, L, BINS)
        node = up_the_tree(t, np.moveaxis(lh, 1, 2))  # (3, BINS, M)
        cum = np.cumsum(node, axis=1)[:, :-1]
        best = np.maximum(best, ok_gain(*cum).max(axis=0))
    left = np.stack([tot_leaf[:, ~c] if c < 0 else tot[:, c]
                     for c in t.left_child], axis=1)
    chosen = _split_gain(left[0], left[1], G, H, lam)
    best = np.maximum(best, 0.0)
    root = chosen[0] / best[0] if best[0] > 0 else 0.0
    tree = chosen.sum() / best.sum() if best.sum() > 0 else 0.0
    return float(root), float(tree)


def _used(header: Dict[str, str], F: int) -> np.ndarray:
    infos = header.get("feature_infos", "").split(" ")
    if len(infos) != F:
        return np.ones(F, bool)
    return np.array([s != "none" for s in infos])


def audit(model_str: str, X, y: np.ndarray, Xv, yv: np.ndarray,
          host_bins: np.ndarray, device_auc: Sequence[float],
          params: Dict[str, Any], quality: Dict[str, Any],
          seed: int) -> Dict[str, Any]:
    """Returns {"problems": [...], "facts": {...}}; no problem means the
    model passed. X, Xv: scipy sparse (rows, features)."""
    t0 = time.perf_counter()
    problems: List[str] = []
    facts: Dict[str, Any] = {}
    header, trees = modeltext.parse(model_str)
    rounds = len(trees)
    lr = float(params.get("learning_rate", 0.1))
    lam = float(params.get("lambda_l2", 0.0))
    min_data = float(params.get("min_data_in_leaf", 20))
    min_hess = float(params.get("min_sum_hessian_in_leaf", 1e-3))
    csc, vcsc = X.tocsc(), Xv.tocsc()
    for m in (csc, vcsc):
        m.sort_indices()
    n, F = csc.shape
    y = np.asarray(y, np.float64)
    rng = np.random.default_rng(seed % 2 ** 32)

    # ---- where each column lies in the bin matrix
    split_feats = sorted({int(f) for t in trees
                          for f in t.split_feature})
    onehot = onehot_columns(csc)
    used = _used(header, F)
    where: Dict[int, Tuple[int, int]] = {}
    counts = value_counts(host_bins)
    unseen = []
    for f in np.flatnonzero(onehot & used):
        at = locate(Table(csc, onehot, {}, {}).ones(int(f)), host_bins,
                    counts)
        if at is None:
            unseen.append(int(f))
        else:
            where[int(f)] = at
    by_col: Dict[int, List[Tuple[int, int]]] = {}
    for f, (gcol, v) in where.items():
        by_col.setdefault(gcol, []).append((v, f))
    higher = {}
    merged = set()
    for members in by_col.values():
        members.sort()
        if len(members) > 1:
            merged.update(f for _, f in members)
        for k, (_, f) in enumerate(members):
            higher[f] = np.array([m for _, m in members[k + 1:]], np.int64)
    num_cols: Dict[int, int] = {}
    tab = Table(csc, onehot, where, higher)
    lost = []
    for f in np.flatnonzero(~onehot & used):
        gcol = numeric_column(tab.raw(int(f)), host_bins, rng)
        if gcol is None:
            lost.append(int(f))
        else:
            num_cols[int(f)] = gcol
    lost += [f for f in split_feats if onehot[f] and f not in where]
    G = host_bins.shape[0]
    facts.update(device_columns=int(G), merged_features=len(merged),
                 onehot_columns=int(onehot.sum()),
                 used_columns=int(used.sum()),
                 onehot_columns_not_located=len(unseen))
    if lost:
        problems.append(f"{len(lost)} numerical or split columns not "
                        f"found in the bin matrix (first: {lost[:5]})")
    if len(unseen) * 100 > int(used.sum()):
        problems.append(f"{len(unseen)} used one-hot columns (more than "
                        "1%) are in no device column at a value of their "
                        f"own (first: {unseen[:5]})")
    min_merged = int(quality.get("min_merged_features", MIN_MERGED))
    max_cols = int(quality.get("max_device_columns", MAX_COLUMNS))
    if G > max_cols or len(merged) < min_merged:
        problems.append(
            f"no bundling at the source's shape: {len(merged)} columns "
            f"share a device column (at least {min_merged}), {G} device "
            f"columns (at most {max_cols})")

    # ---- the bins hold what the CSR says, conflicts as the contract
    conflict_rows = wrong = 0
    for f in split_feats:
        if f not in where:
            continue
        gcol, v = where[f]
        held = host_bins[gcol] == v
        raw1 = np.zeros(n, bool)
        raw1[tab.ones(f)] = True
        wrong += int(np.count_nonzero(held & ~raw1))
        conflict_rows += int(np.count_nonzero(raw1 & ~held))
        rule = tab.resolved(f)
        if not np.array_equal(rule, held):
            problems.append(
                f"column {f}: the bin matrix is not the CSR under the "
                f"bundle rule (last member written) in "
                f"{int(np.count_nonzero(rule != held))} of {n} rows")
    facts["split_onehot_rows_lost_to_conflicts"] = conflict_rows
    if wrong:
        problems.append(f"{wrong} rows hold a one-hot value the CSR does "
                        "not have")
    bundled_splits = sum(int(f in merged) for t in trees[:2]
                         for f in t.split_feature)
    facts["trees12_splits_on_merged_members"] = bundled_splits
    if not bundled_splits:
        problems.append("trees 1 and 2 split on no merged member: the "
                        "bundles were never searched")

    # ---- trees 1 and 2: partition, leaf values, split gains
    def col_train(f: int, rows: np.ndarray) -> np.ndarray:
        # a split one-hot column's bins equal tab.resolved(f) on every
        # row (checked above), and are cheaper to index than to rebuild
        if onehot[f] and f in where:
            gcol, v = where[f]
            return (host_bins[gcol, rows] == v).astype(np.float64)
        return tab.column(f)[rows]

    p_bar = float(np.mean(y))
    init = float(np.log(p_bar / (1.0 - p_bar)))
    facts["boost_from_average"] = init
    score = np.full(n, init)
    floors = (("root", float(quality["root_gain_share_min"])),
              ("tree", float(quality["tree2_gain_share_min"])))
    onehot_used = np.flatnonzero(onehot & used)
    for k, t in enumerate(trees[:2], start=1):
        p = 1.0 / (1.0 + np.exp(-score))
        g, h = p - y, p * (1.0 - p)
        leaf = route(t, col_train, n)
        bias = init if k == 1 else 0.0
        check_leaves(k, t, leaf, g, h, lr, lam, bias, problems, facts)
        shares = dict(zip(("root", "tree"), gain_shares(
            t, tab, leaf, g, h, onehot_used, num_cols, host_bins, lam,
            min_data, min_hess)))
        facts[f"tree{k}_root_gain_share"] = shares["root"]
        facts[f"tree{k}_gain_share"] = shares["tree"]
        which, floor = floors[k - 1]
        if shares[which] < floor:
            problems.append(
                f"tree {k}: the exact gain of the chosen split"
                f"{'' if which == 'root' else 's'} is "
                f"{shares[which]:.6f} of the best exact gain "
                f"({which}; floor {floor})")
        score = score - bias + t.leaf_value[leaf]
    if rounds < 2:
        problems.append("fewer than two trees: tree 2 is the only one "
                        "that sees the histogram channels' precision")

    # ---- the model: valid AUC, one-hot values under the same rule
    vtab = Table(vcsc, onehot, where, higher)
    nv = vcsc.shape[0]
    margin = sum((t.leaf_value[route(t, lambda f, r: vtab.column(f)[r],
                                     nv)] for t in trees),
                 np.zeros(nv, np.float64))
    host_auc = auc(np.asarray(yv), margin)
    dev = [float(a) for a in device_auc]
    facts["valid_auc_host"] = host_auc
    facts["valid_auc_device"] = dev
    if len(dev) != rounds:
        problems.append(f"{len(dev)} device evals for {rounds} trees")
    elif abs(dev[-1] - host_auc) > AUC_EVAL_ATOL:
        problems.append(
            f"valid AUC: device eval {dev[-1]!r} vs NumPy {host_auc!r} "
            f"(|diff| > {AUC_EVAL_ATOL})")
    if len(dev) > 1 and not dev[-1] > dev[0]:
        problems.append(f"valid AUC does not rise over the rounds: {dev}")
    band = float(quality["auc_band"])
    refs = {int(k): float(v[rounds - 1])
            for k, v in quality.get("ref_auc", {}).items()
            if len(v) >= rounds}
    if not refs:
        problems.append(
            f"no f32-channel reference AUC recorded for {rounds} rounds")
    else:
        ref = refs.get(seed, sum(refs.values()) / len(refs))
        facts["valid_auc_reference"] = ref
        facts["valid_auc_reference_is_this_seeds"] = seed in refs
        if abs(host_auc - ref) > band:
            whose = (f"seed {seed}" if seed in refs
                     else f"mean of seeds {sorted(refs)}")
            problems.append(
                f"valid AUC {host_auc:.6f} is outside +-{band} of the "
                f"f32-channel run's {ref:.6f} ({whose})")
    facts["efb_audit_s"] = time.perf_counter() - t0
    return {"problems": problems, "facts": facts}
