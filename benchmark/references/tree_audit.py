"""Tree audit: the first TWO trees of a trained model against plain NumPy
at full size, independent of every kernel of the program.

What the program does on the chip (and so what can and cannot be
exact): the default trainer discretizes the gradients of each round to
256 integer levels (``higgs-quant``: 4) with stochastic rounding, grows
the tree from histograms of those levels, and then RENEWS every leaf's
output from the true gradients. So, for each audited tree, with the true
gradients recomputed here in float64 (tree 1: binary log-loss at the
boost-from-average score; tree 2: at that score plus tree 1's leaf
values, read from the model by the plain walker):

- the partition is whatever the model says it is: routing every training
  row through the tree here must reproduce every ``leaf_count`` exactly
  (the program counts rows in float32 channels; a lost or doubled row,
  or a count past 2**24 in one cell, shows here);
- every leaf value must equal -sum(g) / (sum(h) + lambda_l2) x
  learning_rate from float64 sums over the rows the leaf holds (tree 1:
  plus the boost-from-average score, which the fused step folds into
  the first stored tree);
- every split was chosen from discretized histograms, so it need not be
  the exact optimum. Its exact gain is set against the best exact gain
  of its own node over the program's own bin boundaries (per-leaf
  histograms by ``np.bincount`` over the host bin matrix, summed up the
  tree). Tree 1 cannot see the channels' precision: its gradients take
  two values, so 256 and 4 levels are both all but exact, and only its
  root is held to a floor. Tree 2 is the first whose gradients are
  continuous: the sum over its nodes of the chosen gain over the sum of
  the best gain must reach the configuration's floor, which is set per
  number of levels.

Then the model as a whole: valid AUC recomputed here from the plain
walker's margins must equal what the device evaluation reported, rise
over the rounds, and lie inside a band around the value an f32-channel
run (``tpu_hist_dtype=bf16x2``) of the same rounds gave on the chip."""

from __future__ import annotations

from concurrent.futures import ThreadPoolExecutor
from typing import Any, Dict, List, Sequence, Tuple

import numpy as np

from benchmark.harness import modeltext

# |leaf value - reference|. The program sums a leaf's gradients in
# float32, on the chip one partial per 2048-row block: that measured up
# to 8.5e-6 at 10.5M rows and up to 1.8e-5 at 23.07M (PR 22, six seeds,
# tree 1: linear in the number of blocks, because the few distinct
# gradient values round the same way at every add). Tree 2 adds as many
# partials of less regular values, so it is expected no further off; it
# was not read on the chip in PR 22. Rounding the gradients to bfloat16
# (8 bits: 2e-3 relative on sum(g)/sum(h), times the 0.1 learning rate
# and |g|/h ~ 2) would move a leaf value by about 4e-4. 1e-4 sits
# between. (The XLA:CPU scatter-add the program falls back to off the
# chip adds row by row and is past 1e-4 from about 1M rows.)
LEAF_VALUE_ATOL = 1e-4
# device eval (float32 sort and prefix sums over the valid rows) against
# the float64 recomputation
AUC_EVAL_ATOL = 1e-6
BINS = 256  # host bins are uint8


def auc(labels: np.ndarray, scores: np.ndarray) -> float:
    """Exact AUC with tie handling (average ranks)."""
    _, inv, cnt = np.unique(scores, return_inverse=True,
                            return_counts=True)
    upto = np.cumsum(cnt)
    ranks = (upto - (cnt - 1) / 2.0)[inv]
    pos = labels > 0
    n_pos, n_neg = int(pos.sum()), int((~pos).sum())
    return float((ranks[pos].sum() - n_pos * (n_pos + 1) / 2.0)
                 / (n_pos * n_neg))


def _split_gain(gl, hl, g, h, lam):
    """Gain of splitting (g, h) into (gl, hl) and the rest."""
    return (gl * gl / (hl + lam) + (g - gl) * (g - gl) / (h - hl + lam)
            - g * g / (h + lam))


def check_leaves(k: int, t: modeltext.PlainTree, leaf: np.ndarray,
                 g: np.ndarray, h: np.ndarray, lr: float, lam: float,
                 bias: float, problems: List[str], facts: Dict[str, Any]
                 ) -> None:
    """Tree ``k``'s leaf counts and leaf values against the rows each
    leaf holds (``leaf``: the plain walker's leaf of every row)."""
    counts = np.bincount(leaf, minlength=t.num_leaves)
    bad = np.flatnonzero(counts != t.leaf_count)
    facts[f"tree{k}_leaves"] = int(t.num_leaves)
    facts[f"tree{k}_leaf_count_mismatches"] = int(bad.size)
    if bad.size:
        i = int(bad[0])
        problems.append(
            f"tree {k}: {bad.size} leaf counts differ from a NumPy walk "
            f"of all {leaf.size} rows (leaf {i}: model {t.leaf_count[i]}, "
            f"walk {counts[i]})")
    sum_g = np.bincount(leaf, weights=g, minlength=t.num_leaves)
    sum_h = np.bincount(leaf, weights=h, minlength=t.num_leaves)
    want = -sum_g / (sum_h + lam) * lr + bias
    err = np.abs(t.leaf_value - want)
    facts[f"tree{k}_leaf_value_max_abs_err"] = float(err.max())
    if err.max() > LEAF_VALUE_ATOL:
        i = int(err.argmax())
        problems.append(
            f"tree {k}: leaf {i} value {t.leaf_value[i]!r} is not "
            f"-sum(g)/sum(h)*lr{' + init' if bias else ''} = {want[i]!r} "
            f"from its {counts[i]} rows (|diff| {err.max():.3e} > "
            f"{LEAF_VALUE_ATOL})")


def gain_shares(t: modeltext.PlainTree, leaf: np.ndarray, g: np.ndarray,
                h: np.ndarray, host_bins: np.ndarray, lam: float,
                min_data: int, min_hess: float) -> Tuple[float, float]:
    """(root share, tree share): the exact gain of the chosen split over
    the best exact gain at the same node, for the root, and summed over
    every internal node. A node's histogram is the sum of its leaves'
    histograms; the chosen split's two sides are its two subtrees."""
    L, M = t.num_leaves, t.num_leaves - 1
    if M < 1:  # a stump chose nothing
        return 0.0, 0.0
    key0 = leaf.astype(np.int32) * BINS

    def leaf_hists(col: np.ndarray) -> np.ndarray:
        key = key0 + col
        return np.stack([
            np.bincount(key, weights=g, minlength=L * BINS),
            np.bincount(key, weights=h, minlength=L * BINS),
            np.bincount(key, minlength=L * BINS).astype(np.float64),
        ]).reshape(3, L, BINS)

    with ThreadPoolExecutor(modeltext.ROUTE_THREADS) as pool:
        # (3, features, leaves, bins)
        lh = np.stack(list(pool.map(leaf_hists, host_bins)), axis=1)
    node = np.zeros((3, lh.shape[1], M, BINS))
    done = np.zeros(M, bool)

    def hist_of(child: int) -> np.ndarray:
        return lh[:, :, ~child] if child < 0 else node[:, :, child]

    stack = [0]
    while stack:  # children before their parent, without recursion
        i = stack[-1]
        kids = (int(t.left_child[i]), int(t.right_child[i]))
        todo = [c for c in kids if c >= 0 and not done[c]]
        if todo:
            stack.extend(todo)
            continue
        node[:, :, i] = hist_of(kids[0]) + hist_of(kids[1])
        done[i] = True
        stack.pop()

    tot = node[:, 0].sum(-1)  # (3, M): every feature sums to the node
    G, H, N = tot[0][None, :, None], tot[1][None, :, None], tot[2]
    cum = np.cumsum(node, axis=-1)[..., :-1]  # rows with bin <= b
    gl, hl, nl = cum
    ok = ((nl >= min_data) & (N[None, :, None] - nl >= min_data)
          & (hl >= min_hess) & (H - hl >= min_hess))
    safe_hl = np.where(ok, hl, 0.5 * H)  # no 0/0 on an excluded side
    best = np.where(ok, _split_gain(gl, safe_hl, G, H, lam), 0.0
                    ).max(axis=(0, 2))
    left = np.stack([hist_of(int(c))[:, 0].sum(-1) for c in t.left_child],
                    axis=1)  # (3, M) of each node's left subtree
    chosen = _split_gain(left[0], left[1], tot[0], tot[1], lam)
    best = np.maximum(best, 0.0)
    root = chosen[0] / best[0] if best[0] > 0 else 0.0
    tree = chosen.sum() / best.sum() if best.sum() > 0 else 0.0
    return float(root), float(tree)


def audit(model_str: str, X: np.ndarray, y: np.ndarray,
          Xv: np.ndarray, yv: np.ndarray, host_bins: np.ndarray,
          device_auc: Sequence[float], params: Dict[str, Any],
          quality: Dict[str, Any], seed: int) -> Dict[str, Any]:
    """Returns {"problems": [...], "facts": {...}}; no problem means the
    model passed. ``quality`` is the configuration's block: the gain
    share floors, the AUC band and the reference AUCs by seed."""
    problems: List[str] = []
    facts: Dict[str, Any] = {}
    _, trees = modeltext.parse(model_str)
    rounds = len(trees)
    lr = float(params.get("learning_rate", 0.1))
    lam = float(params.get("lambda_l2", 0.0))
    min_data = int(params.get("min_data_in_leaf", 20))
    min_hess = float(params.get("min_sum_hessian_in_leaf", 1e-3))

    # ---- trees 1 and 2: partition, leaf values, split gains
    p_bar = float(np.mean(y, dtype=np.float64))
    init = float(np.log(p_bar / (1.0 - p_bar)))
    facts["boost_from_average"] = init
    score = np.full(X.shape[0], init)
    floors = (("root", float(quality["root_gain_share_min"])),
              ("tree", float(quality["tree2_gain_share_min"])))
    for k, t in enumerate(trees[:2], start=1):
        p = 1.0 / (1.0 + np.exp(-score))
        g, h = p - y, p * (1.0 - p)
        leaf = modeltext.route(t, X)
        bias = init if k == 1 else 0.0
        check_leaves(k, t, leaf, g, h, lr, lam, bias, problems, facts)
        shares = dict(zip(("root", "tree"), gain_shares(
            t, leaf, g, h, host_bins, lam, min_data, min_hess)))
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

    # ---- the model: valid AUC
    host_auc = auc(yv, modeltext.predict_raw(trees, Xv))
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
        # a seed with no recorded value is held to the mean of those
        # recorded (the label rule does not depend on the seed)
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
