"""Tree audit for a table with native categorical columns and missing
values: the first TWO trees of a trained model against plain float64
NumPy at full size, independent of every kernel of the program and of
``harness/modeltext.py`` (which handles neither a category set nor a
NaN): this file has a reader and a walker of its own.

What is checked, in the order of ``tree_audit`` (whose AUC, leaf check
and gain formula it imports):

- **the walker** follows the model TEXT on RAW values: a numerical node
  sends ``x <= threshold`` left and a missing value where the node's
  ``decision_type`` says (missing type NaN: the default direction; None:
  as 0.0); a categorical node sends a value left when its bit is set in
  the node's ``cat_threshold`` words (``cat_boundaries``), and NaN, a
  negative value and every category outside the words RIGHT;
- every ``leaf_count`` of trees 1 and 2 equals that walk over all
  training rows, every leaf value equals -sum(g) / (sum(h) + lambda) x
  learning_rate from float64 gradients;
- **the binning** is what the model says it is: ``feature_infos`` names
  each categorical column's kept categories in bin order; over ALL rows
  every kept bin holds exactly its category and the other bin (the last)
  holds none of them, and a numerical column's NaN rows fill one bin of
  their own, the last;
- **the best exact gain of every node under the REFERENCE's rules**
  (``feature_histogram.hpp``), by a loop per column kind over the node's
  float64 histogram on the program's bins: numerical columns by
  ``FindBestThresholdNumerical``, both scans where the column has a NaN
  bin (missing left with every threshold below the last value bin,
  missing right with every threshold up to "all values left"), one
  where it has none; categorical columns by
  ``FindBestThresholdCategoricalInner``: one-vs-rest at most
  ``max_cat_to_onehot`` bins, else the bins with at least ``cat_smooth``
  rows sorted by sum(g) / (sum(h) + ``cat_smooth``), prefixes from both
  ends of at most min(``max_cat_threshold``, (used + 1) / 2) categories,
  evaluated when ``min_data_per_group`` rows have joined since the last
  evaluation, with lambda + ``cat_l2``; ``min_data_in_leaf`` and
  ``min_sum_hessian_in_leaf`` on both sides everywhere; the other bin
  never on the left. Counts are exact (the reference estimates them from
  hessians). The chosen split's exact gain, by its own kind's formula
  from the walk's two sides, over that best, summed over the tree, is
  the precision-sensitive limit: the program searched histograms of 256
  integer levels, so its sorted order and its winner may differ where
  two candidates are close;
- **the mechanism is there**: trees 1 and 2 hold a sorted-subset split,
  a split on a numerical column whose node says missing type NaN, and
  ``feature_infos`` lists categories for every column the configuration
  names categorical. A program that ignores ``categorical_feature``
  trains an all-numerical model and fails here;
- valid AUC from the walker's margins equals the device's, rises, and
  lies in a band around the f32-channel program's."""

from __future__ import annotations

from concurrent.futures import ThreadPoolExecutor
from dataclasses import dataclass
from typing import Any, Dict, List, Sequence, Tuple

import numpy as np

from benchmark.harness.manifest import load_plugin, repo_root

_ta = load_plugin(repo_root(), "references", "tree_audit")

BLOCK = 1 << 20
THREADS = 8
BINS = 256  # host bins are uint8
K_EPSILON = 1e-15
_CAT, _DEFAULT_LEFT = 1, 2


@dataclass
class CatTree:
    """One tree of the model text, arrays as written."""

    num_leaves: int
    split_feature: np.ndarray
    threshold: np.ndarray
    decision_type: np.ndarray
    left_child: np.ndarray
    right_child: np.ndarray
    leaf_value: np.ndarray
    leaf_count: np.ndarray
    cat_boundaries: np.ndarray
    cat_threshold: np.ndarray  # uint32 words


def _arr(kv: Dict[str, str], key: str, dtype) -> np.ndarray:
    text = kv.get(key, "").strip()
    return (np.array(text.split(" "), dtype=dtype) if text
            else np.zeros(0, dtype))


def parse(model_str: str) -> Tuple[Dict[str, str], List[CatTree]]:
    """(header fields, trees) of a model string."""
    head, _, rest = model_str.partition("\nTree=")
    header = dict(ln.split("=", 1) for ln in head.split("\n") if "=" in ln)
    body = rest.split("\nend of trees")[0]
    trees = []
    for block in (("Tree=" + body).split("\nTree=") if rest else []):
        kv = dict(ln.split("=", 1) for ln in block.split("\n") if "=" in ln)
        if kv.get("is_linear", "0") == "1":
            raise ValueError("linear trees are not handled")
        trees.append(CatTree(
            num_leaves=int(kv["num_leaves"]),
            split_feature=_arr(kv, "split_feature", np.int64),
            threshold=_arr(kv, "threshold", np.float64),
            decision_type=_arr(kv, "decision_type", np.int64),
            left_child=_arr(kv, "left_child", np.int64),
            right_child=_arr(kv, "right_child", np.int64),
            leaf_value=_arr(kv, "leaf_value", np.float64),
            leaf_count=_arr(kv, "leaf_count", np.int64),
            cat_boundaries=_arr(kv, "cat_boundaries", np.int64),
            cat_threshold=_arr(kv, "cat_threshold", np.int64
                               ).astype(np.uint32),
        ))
    return header, trees


def go_left(t: CatTree, nd: np.ndarray, x: np.ndarray) -> np.ndarray:
    """The decision of node ``nd[i]`` on raw value ``x[i]``."""
    dt = t.decision_type[nd]
    thr = t.threshold[nd]
    is_cat = (dt & _CAT) != 0
    missing_type = (dt >> 2) & 3
    nan = np.isnan(x)
    xv = np.where(nan, 0.0, x).astype(np.float64)
    is_missing = np.where(missing_type == 2, nan,
                          (missing_type == 1) & (nan | (np.abs(xv) <= 1e-35)))
    num = np.where(is_missing, (dt & _DEFAULT_LEFT) != 0, xv <= thr)
    if not is_cat.any():
        return num
    iv = np.where(nan | (xv < 0), -1, xv).astype(np.int64)
    ci = np.where(is_cat, thr, 0).astype(np.int64)
    lo = t.cat_boundaries[ci]
    words = t.cat_boundaries[ci + 1] - lo
    ok = (iv >= 0) & ((iv >> 5) < words)
    w = t.cat_threshold[np.where(ok, lo + (iv >> 5), 0)]
    hit = ok & (((w >> (iv & 31).astype(np.uint32)) & 1) == 1)
    return np.where(is_cat, hit, num)


def _route_block(t: CatTree, X: np.ndarray) -> np.ndarray:
    node = np.zeros(X.shape[0], np.int64)
    active = np.arange(X.shape[0])
    while active.size:
        nd = node[active]
        x = X[active, t.split_feature[nd]]
        child = np.where(go_left(t, nd, x), t.left_child[nd],
                         t.right_child[nd])
        node[active] = child
        active = active[child >= 0]
    return ~node


def route(t: CatTree, X: np.ndarray) -> np.ndarray:
    """Leaf of every row, in row blocks on a few threads."""
    if t.num_leaves <= 1:
        return np.zeros(X.shape[0], np.int64)
    blocks = [X[i:i + BLOCK] for i in range(0, X.shape[0], BLOCK)]
    with ThreadPoolExecutor(THREADS) as pool:
        return np.concatenate(list(pool.map(
            lambda b: _route_block(t, b), blocks)))


def predict_raw(trees: Sequence[CatTree], X: np.ndarray) -> np.ndarray:
    return sum((t.leaf_value[route(t, X)] for t in trees),
               np.zeros(X.shape[0], np.float64))


# ---------------------------------------------------------------- columns
@dataclass
class Column:
    """What the model and the data say of one column's bins."""

    cats: Tuple[int, ...] = ()  # kept categories in bin order; () = numerical
    bins: int = 0  # bins the rows fill, counted from 0
    nan_bin: int = -1  # numerical: the NaN rows' bin; categorical: other

    @property
    def categorical(self) -> bool:
        return bool(self.cats)


def columns_of(header: Dict[str, str], X: np.ndarray,
               host_bins: np.ndarray, want_cat: Sequence[int],
               problems: List[str]) -> List[Column]:
    """One ``Column`` per feature from ``feature_infos``, each held to the
    rows: a kept bin holds exactly its category, the other bin none of
    the kept ones, NaN rows of a numerical column a last bin of their
    own."""
    infos = header.get("feature_infos", "").split(" ")
    if len(infos) != X.shape[1] or host_bins.shape[0] != X.shape[1]:
        problems.append(
            f"{len(infos)} feature_infos and {host_bins.shape[0]} binned "
            f"columns for {X.shape[1]} raw columns (a trivial column?)")
        return []

    def one(j: int) -> Tuple[Column, List[str]]:
        info, b, x = infos[j], host_bins[j], X[:, j]
        bad: List[str] = []
        nan = np.isnan(x)
        top = int(b.max())
        if info.startswith("[") or info == "none":
            col = Column(bins=top + 1)
            if j in want_cat:
                bad.append(f"column {j} is named categorical and "
                           f"feature_infos says {info[:24]!r}: binned as "
                           "numerical")
            if nan.any():
                col.nan_bin = top
                if (b[nan] != top).any() or (b[~nan] == top).any():
                    bad.append(f"column {j}: NaN rows do not fill a last "
                               "bin of their own")
            return col, bad
        cats = tuple(int(c) for c in info.split(":"))
        col = Column(cats=cats, bins=len(cats) + 1, nan_bin=len(cats))
        if top > len(cats):
            bad.append(f"column {j}: bin {top} past its {len(cats)} kept "
                       "categories and the other bin")
        code = np.where(nan | (x < 0), -1, x).astype(np.int64)
        want = np.full(int(max(code.max(), max(cats))) + 2, len(cats),
                       np.int64)  # [-1] = the slot of NaN / negative
        want[np.asarray(cats)] = np.arange(len(cats))
        wrong = int(np.count_nonzero(want[code] != b))
        if wrong:
            bad.append(f"column {j}: {wrong} rows are not in their "
                       "category's own bin, or a cut / unseen / missing "
                       "value is not in the other bin")
        return col, bad

    with ThreadPoolExecutor(THREADS) as pool:
        out = list(pool.map(one, range(X.shape[1])))
    for _col, bad in out:
        problems.extend(bad)
    return [col for col, _bad in out]


# ------------------------------------------------------------- the search
def _gain(gl, hl, g, h, lam):
    return _ta._split_gain(gl, hl, g, h, lam)


def _best_numerical(hist: np.ndarray, col: Column, lam: float,
                    min_data: int, min_hess: float) -> np.ndarray:
    """(nodes,) best gain of a numerical column: ``hist`` is (3, nodes,
    BINS). Thresholds t keep value bins <= t left."""
    values = col.bins - (1 if col.nan_bin >= 0 else 0)
    tot = hist.sum(-1)  # (3, M)
    G, H, N = (tot[k][:, None] for k in range(3))
    cum = np.cumsum(hist[:, :, :values], axis=-1)

    def best(left):
        gl, hl, nl = left
        ok = ((nl >= min_data) & (N - nl >= min_data)
              & (hl >= min_hess) & (H - hl >= min_hess))
        safe = np.where(ok, hl, 0.5 * H)
        return np.where(ok, _gain(gl, safe, G, H, lam), 0.0).max(-1)

    if col.nan_bin < 0:
        return best(cum[:, :, :-1])
    nan = hist[:, :, col.nan_bin][:, :, None]
    # missing right: up to "every value left"; missing left: below the
    # last value bin
    return np.maximum(best(cum), best(cum[:, :, :-1] + nan))


def _best_categorical(hist: np.ndarray, col: Column, lam: float,
                      min_data: int, min_hess: float, p: Dict[str, Any]
                      ) -> np.ndarray:
    """(nodes,) best gain of a categorical column after
    FindBestThresholdCategoricalInner; the other bin never goes left."""
    M = hist.shape[1]
    k = len(col.cats)
    g, h, n = (hist[c][:, :k] for c in range(3))
    tot = hist.sum(-1)
    out = np.zeros(M)
    if col.bins <= p["max_cat_to_onehot"]:
        G, H, N = (tot[c][:, None] for c in range(3))
        ok = ((n >= min_data) & (N - n >= min_data)
              & (h >= min_hess) & (H - h >= min_hess))
        safe = np.where(ok, h, 0.5 * H)
        return np.where(ok, _gain(g, safe, G, H, lam), 0.0).max(-1)
    lam_cat = lam + p["cat_l2"]
    group = p["min_data_per_group"]
    for i in range(M):
        G, H, N = tot[0, i], tot[1, i], tot[2, i]
        used = np.flatnonzero(n[i] >= p["cat_smooth"])
        if not used.size:
            continue
        ratio = g[i, used] / (h[i, used] + p["cat_smooth"])
        order = used[np.argsort(ratio, kind="stable")]
        max_num = min(int(p["max_cat_threshold"]), (order.size + 1) // 2)
        parent = G * G / (H + lam)
        best = 0.0
        for seq in (order, order[::-1]):
            gl, hl, nl, since = 0.0, K_EPSILON, 0, 0
            for b in seq[:max_num]:
                gl += g[i, b]
                hl += h[i, b]
                nl += n[i, b]
                since += n[i, b]
                if nl < min_data or hl < min_hess:
                    continue
                nr = N - nl
                if nr < min_data or nr < group or H - hl < min_hess:
                    break
                if since < group:
                    continue
                since = 0
                gr = G - gl
                best = max(best, gl * gl / (hl + lam_cat)
                           + gr * gr / (H - hl + lam_cat) - parent)
        out[i] = best
    return out


def node_gains(t: CatTree, leaf: np.ndarray, g: np.ndarray, h: np.ndarray,
               host_bins: np.ndarray, cols: List[Column], lam: float,
               min_data: int, min_hess: float, p: Dict[str, Any]
               ) -> Tuple[np.ndarray, np.ndarray, Dict[str, int]]:
    """Per internal node: (the chosen split's exact gain, the best exact
    gain under the reference's rules), and the tree's splits by kind. A
    node's histogram is the sum of its leaves'; the chosen split's two
    sides are its two subtrees."""
    L, M = t.num_leaves, t.num_leaves - 1
    key0 = leaf.astype(np.int32) * BINS

    def leaf_hists(col: np.ndarray) -> np.ndarray:
        key = key0 + col
        return np.stack([
            np.bincount(key, weights=g, minlength=L * BINS),
            np.bincount(key, weights=h, minlength=L * BINS),
            np.bincount(key, minlength=L * BINS).astype(np.float64),
        ]).reshape(3, L, BINS)

    with ThreadPoolExecutor(THREADS) as pool:
        lh = np.stack(list(pool.map(leaf_hists, host_bins)), axis=1)
    node = np.zeros((3, lh.shape[1], M, BINS))
    done = np.zeros(M, bool)

    def hist_of(child: int) -> np.ndarray:
        return lh[:, :, ~child] if child < 0 else node[:, :, child]

    stack = [0]
    while stack:  # children before their parent
        i = stack[-1]
        kids = (int(t.left_child[i]), int(t.right_child[i]))
        todo = [c for c in kids if c >= 0 and not done[c]]
        if todo:
            stack.extend(todo)
            continue
        node[:, :, i] = hist_of(kids[0]) + hist_of(kids[1])
        done[i] = True
        stack.pop()

    def column_best(j: int) -> np.ndarray:
        if cols[j].categorical:
            return _best_categorical(node[:, j], cols[j], lam, min_data,
                                     min_hess, p)
        return _best_numerical(node[:, j], cols[j], lam, min_data, min_hess)

    with ThreadPoolExecutor(THREADS) as pool:
        best = np.max(list(pool.map(column_best, range(len(cols)))), axis=0)

    tot = node[:, 0].sum(-1)  # (3, M)
    left = np.stack([hist_of(int(c))[:, 0].sum(-1) for c in t.left_child],
                    axis=1)
    is_cat = (t.decision_type & _CAT) != 0
    wide = np.array([cols[int(f)].bins > p["max_cat_to_onehot"]
                     for f in t.split_feature])
    subset = is_cat & wide
    lam_node = np.where(subset, lam + p["cat_l2"], lam)
    chosen = (left[0] ** 2 / (left[1] + lam_node)
              + (tot[0] - left[0]) ** 2 / (tot[1] - left[1] + lam_node)
              - tot[0] ** 2 / (tot[1] + lam))
    dl = ~is_cat & ((t.decision_type & _DEFAULT_LEFT) != 0)
    kinds = {"numerical": int((~is_cat & ~dl).sum()),
             "default_left": int(dl.sum()),
             "cat_onehot": int((is_cat & ~wide).sum()),
             "cat_subset": int(subset.sum())}
    return chosen, np.maximum(best, 0.0), kinds


def gain_shares(t: CatTree, *args) -> Tuple[float, float, Dict[str, int]]:
    """(root share, tree share, splits by kind) of ``node_gains``: the
    chosen gain over the best, at the root and summed over the tree."""
    if t.num_leaves < 2:  # a stump chose nothing
        return 0.0, 0.0, {}
    chosen, best, kinds = node_gains(t, *args)
    root = chosen[0] / best[0] if best[0] > 0 else 0.0
    tree = chosen.sum() / best.sum() if best.sum() > 0 else 0.0
    return float(root), float(tree), kinds


def audit(model_str: str, X: np.ndarray, y: np.ndarray,
          Xv: np.ndarray, yv: np.ndarray, host_bins: np.ndarray,
          device_auc: Sequence[float], params: Dict[str, Any],
          quality: Dict[str, Any], seed: int) -> Dict[str, Any]:
    """{"problems": [...], "facts": {...}}; no problem means the model
    passed."""
    problems: List[str] = []
    facts: Dict[str, Any] = {}
    header, trees = parse(model_str)
    rounds = len(trees)
    lr = float(params.get("learning_rate", 0.1))
    lam = float(params.get("lambda_l2", 0.0))
    min_data = int(params.get("min_data_in_leaf", 20))
    min_hess = float(params.get("min_sum_hessian_in_leaf", 1e-3))
    p = {"max_cat_to_onehot": int(params.get("max_cat_to_onehot", 4)),
         "max_cat_threshold": int(params.get("max_cat_threshold", 32)),
         "cat_smooth": float(params.get("cat_smooth", 10.0)),
         "cat_l2": float(params.get("cat_l2", 10.0)),
         "min_data_per_group": int(params.get("min_data_per_group", 100))}
    want_cat = [int(c) for c in
                str(params.get("categorical_feature", "")).split(",") if c]

    cols = columns_of(header, X, host_bins, want_cat, problems)
    facts["categorical_columns"] = [j for j, c in enumerate(cols)
                                    if c.categorical]
    facts["column_bins"] = [c.bins for c in cols]

    # ---- trees 1 and 2: partition, leaf values, split gains
    p_bar = float(np.mean(y, dtype=np.float64))
    init = float(np.log(p_bar / (1.0 - p_bar)))
    facts["boost_from_average"] = init
    score = np.full(X.shape[0], init)
    floors = (("root", float(quality["root_gain_share_min"])),
              ("tree", float(quality["tree2_gain_share_min"])))
    kinds = dict.fromkeys(
        ("numerical", "default_left", "cat_onehot", "cat_subset"), 0)
    nan_type_splits = 0
    for k, t in enumerate(trees[:2], start=1):
        pr = 1.0 / (1.0 + np.exp(-score))
        g, h = pr - y, pr * (1.0 - pr)
        leaf = route(t, X)
        bias = init if k == 1 else 0.0
        _ta.check_leaves(k, t, leaf, g, h, lr, lam, bias, problems, facts)
        if cols:
            root, tree, kk = gain_shares(t, leaf, g, h, host_bins, cols,
                                         lam, min_data, min_hess, p)
            for name, n in kk.items():
                kinds[name] += n
            shares = {"root": root, "tree": tree}
            facts[f"tree{k}_root_gain_share"] = root
            facts[f"tree{k}_gain_share"] = tree
            which, floor = floors[k - 1]
            if shares[which] < floor:
                problems.append(
                    f"tree {k}: the exact gain of the chosen split"
                    f"{'' if which == 'root' else 's'} is "
                    f"{shares[which]:.6f} of the best exact gain under "
                    f"the reference's rules ({which}; floor {floor})")
            num = (t.decision_type & _CAT) == 0
            nan_type_splits += int(np.count_nonzero(
                num & (((t.decision_type >> 2) & 3) == 2)
                & np.array([not cols[int(f)].categorical
                            for f in t.split_feature])))
        score = score - bias + t.leaf_value[leaf]
    if rounds < 2:
        problems.append("fewer than two trees: tree 2 is the only one "
                        "that sees the histogram channels' precision")

    # ---- the mechanism is there
    facts["splits_by_kind_trees_1_2"] = kinds
    facts["nan_type_numerical_splits_trees_1_2"] = nan_type_splits
    if not kinds["cat_subset"]:
        problems.append("trees 1 and 2 hold no sorted-subset split")
    if not nan_type_splits:
        problems.append("trees 1 and 2 hold no split on a numerical "
                        "column whose node says missing type NaN")

    # ---- the model: valid AUC
    host_auc = _ta.auc(yv, predict_raw(trees, Xv))
    dev = [float(a) for a in device_auc]
    facts["valid_auc_host"] = host_auc
    facts["valid_auc_device"] = dev
    if len(dev) != rounds:
        problems.append(f"{len(dev)} device evals for {rounds} trees")
    elif abs(dev[-1] - host_auc) > _ta.AUC_EVAL_ATOL:
        problems.append(
            f"valid AUC: device eval {dev[-1]!r} vs NumPy {host_auc!r} "
            f"(|diff| > {_ta.AUC_EVAL_ATOL})")
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
    return {"problems": problems, "facts": facts}
