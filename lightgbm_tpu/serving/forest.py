"""Tensorized forest predictor: the trained model as device tables.

The host predictors (tree.py vectorized walk, native/ fp_predict) walk
pointer-shaped trees row by row; on TPU that shape is hostile — the
win comes from giving every (row, tree) lane the same dense program.
This module lifts the flat per-tree arrays (the same layout
``native.PackedModel`` packs for the C++ walker: feature index,
threshold, decision type, children, leaf values, categorical bitsets,
linear-leaf coefficients) into rectangular ``(T, max_nodes)`` /
``(T, max_leaves)`` tables and traverses **all rows x all trees in
lockstep** under one ``jit``:

- per level, every lane's node parameters come from ONE packed-table
  gather (``take_cols`` — the MXU one-hot contraction training's
  validation traversal already uses, histogram.py:380);
- each lane's split-feature value is a ``take_along_axis`` row gather;
- the loop is a ``lax.while_loop`` bounded by the forest's max depth
  (every lane advances one level per pass, like traverse_tree_bins);
- per-class accumulation is a single ``(N, T) @ (T, K)`` one-hot
  matmul, with a ``(T,)`` weight vector implementing
  ``start_iteration`` / ``num_iteration`` truncation WITHOUT a
  retrace (the weights are an argument, not a static).

Decision semantics mirror ``tree.py`` ``Tree.go_left`` bit for bit
(missing types None/Zero/NaN, default direction, categorical bitsets,
linear-leaf NaN fallback); the parity tests in
tests/test_serving.py assert agreement with the native walker across
model families. Tables ride the jit boundary as ARGUMENTS, so two
models with the same (T, M, L) shapes share one executable — hot-swap
in the registry does not recompile.

Fleet extensions (serving/fleet.py, docs/SERVING.md "Fleet serving"):

- ``pad_forest_tables`` pads a model's tables out to a shape-family's
  dimensions so many models can share ONE stacked executable;
- ``stacked_forest_apply`` scores slot ``s`` of an ``(S, ...)``-stacked
  table set — the model index is a traced argument, so paging a model
  in or out of its HBM slot never recompiles;
- ``pack_contrib_tables`` + ``contrib_apply`` are the device TreeSHAP:
  per-leaf root-to-leaf paths with host-precomputed cover ("zero")
  fractions, row-dependent {0,1} "one" fractions from the same split
  decisions the predictor uses, and the reference's extend/unwind
  permutation-weight DP run in lockstep over every (row, tree, leaf)
  lane (host ``shap.py`` is the parity oracle).

All tables are f32/int32: the scoring jaxprs carry the same
no-f64 / no-host-callback contracts as the training entry points
(analysis/jaxpr_audit.py ``serving_forest`` / ``serving_fleet_stack``
/ ``serving_contrib`` entries).
"""

from __future__ import annotations

from typing import Any, Dict, List, Tuple

import numpy as np

# reference include/LightGBM/bin.h kZeroThreshold (tree.h Decision) —
# the zero-as-missing band, shared with the host walk via binning
from ..binning import K_ZERO_THRESHOLD as _K_ZERO


def pack_forest_tables(models, num_class: int) -> Tuple[Dict[str, np.ndarray], Dict[str, Any]]:
    """Host packing: list of Tree -> rectangular numpy tables + static
    metadata. The numpy side of the split so the jit side is pure
    device math (and so the audit can trace it from shapes alone)."""
    T = len(models)
    K = max(int(num_class), 1)
    n_nodes = [max(t.num_leaves - 1, 0) for t in models]
    M = max(n_nodes + [1])
    L = max([t.num_leaves for t in models] + [1])
    depth = max([t.max_depth() for t in models] + [1])

    feature = np.zeros((T, M), np.int32)
    threshold = np.zeros((T, M), np.float32)
    miss_type = np.zeros((T, M), np.int32)
    default_left = np.zeros((T, M), bool)
    is_cat = np.zeros((T, M), bool)
    # padding nodes route straight to leaf 0 so a runaway lane terminates
    left = np.full((T, M), -1, np.int32)
    right = np.full((T, M), -1, np.int32)
    leaf_value = np.zeros((T, L), np.float32)
    cat_lo = np.zeros((T, M), np.int32)
    cat_nw = np.zeros((T, M), np.int32)
    catw_parts: List[np.ndarray] = []
    wbase = 0
    any_cat = False
    any_linear = any(t.is_linear for t in models)
    Ck = 1
    if any_linear:
        Ck = max(
            (len(f) for t in models if t.is_linear for f in t.leaf_features),
            default=1,
        ) or 1
    leaf_const = np.zeros((T, L), np.float32)
    leaf_nf = np.zeros((T, L), np.int32)
    leaf_feat = np.zeros((T, L, Ck), np.int32)
    leaf_coeff = np.zeros((T, L, Ck), np.float32)
    init_node = np.zeros(T, np.int32)
    max_feature = -1

    for ti, t in enumerate(models):
        n = n_nodes[ti]
        if n == 0:
            init_node[ti] = -1  # stump: lane starts AT leaf 0 (~0 == -1)
        else:
            feature[ti, :n] = t.split_feature[:n]
            # directed f64->f32 cast: never round a threshold UP across
            # its f64 value, or an exactly-f32 feature value in
            # (thr, f32(thr)] would flip from right to left vs the f64
            # host walker — a whole-leaf divergence, not 1e-5 noise
            thr64 = np.asarray(t.threshold[:n], np.float64)
            t32 = thr64.astype(np.float32)
            up = t32.astype(np.float64) > thr64
            t32[up] = np.nextafter(t32[up], np.float32(-np.inf))
            threshold[ti, :n] = t32
            dt = np.asarray(t.decision_type[:n], np.int64)
            miss_type[ti, :n] = (dt >> 2) & 3
            default_left[ti, :n] = (dt & 2) != 0
            is_cat[ti, :n] = (dt & 1) != 0
            left[ti, :n] = t.left_child[:n]
            right[ti, :n] = t.right_child[:n]
            max_feature = max(max_feature, int(np.max(t.split_feature[:n])))
            cat_k = np.flatnonzero(is_cat[ti, :n])
            if len(cat_k):
                any_cat = True
                cb = np.asarray(t.cat_boundaries, np.int64)
                words = np.asarray(t.cat_threshold, np.uint32)
                catw_parts.append(words)
                ci = np.asarray(t.threshold, np.float64)[cat_k].astype(np.int64)
                cat_lo[ti, cat_k] = wbase + cb[ci]
                cat_nw[ti, cat_k] = cb[ci + 1] - cb[ci]
                wbase += len(words)
        lv = np.asarray(t.leaf_value, np.float32)
        leaf_value[ti, : len(lv)] = lv
        leaf_const[ti, : len(lv)] = lv  # non-linear: lin path == leaf_value
        if t.is_linear:
            lc = np.asarray(t.leaf_const, np.float32)
            leaf_const[ti, : len(lc)] = lc
            for li, feats in enumerate(t.leaf_features):
                k = len(feats)
                leaf_nf[ti, li] = k
                if k:
                    leaf_feat[ti, li, :k] = feats
                    leaf_coeff[ti, li, :k] = np.asarray(
                        t.leaf_coeff[li], np.float32
                    )
                    max_feature = max(max_feature, max(feats))

    catw = (
        np.concatenate(catw_parts).astype(np.uint32)
        if catw_parts else np.zeros(1, np.uint32)
    )
    # per-node packed parameter table for the single take_cols gather:
    # every field is exact in f32 (ints < 2^24, thresholds already f32)
    pack = np.stack([
        feature.reshape(-1).astype(np.float32),       # 0
        threshold.reshape(-1),                        # 1
        miss_type.reshape(-1).astype(np.float32),     # 2
        default_left.reshape(-1).astype(np.float32),  # 3
        is_cat.reshape(-1).astype(np.float32),        # 4
        left.reshape(-1).astype(np.float32),          # 5
        right.reshape(-1).astype(np.float32),         # 6
        cat_lo.reshape(-1).astype(np.float32),        # 7
        cat_nw.reshape(-1).astype(np.float32),        # 8
    ])
    class_onehot = np.zeros((T, K), np.float32)
    class_onehot[np.arange(T), np.arange(T) % K] = 1.0

    tables = {
        "pack": pack,                         # (9, T*M) f32
        "catw": catw.view(np.int32),          # (W,) int32 bit-patterns
        "leaf_value": leaf_value,             # (T, L) f32
        "leaf_const": leaf_const,             # (T, L) f32
        "leaf_nf": leaf_nf,                   # (T, L) int32
        "leaf_feat": leaf_feat,               # (T, L, Ck) int32
        "leaf_coeff": leaf_coeff,             # (T, L, Ck) f32
        "init_node": init_node,               # (T,) int32
        "class_onehot": class_onehot,         # (T, K) f32
    }
    meta = {
        "num_trees": T, "num_class": K, "max_nodes": M, "max_leaves": L,
        "max_depth": int(depth), "has_cat": bool(any_cat),
        "linear": bool(any_linear), "max_feature": int(max_feature),
    }
    return tables, meta


def _go_left(v, x, catw, has_cat: bool):
    """Split decision for gathered node params ``v`` (9, *S) against
    gathered feature values ``x`` (*S) — the ONE implementation of
    ``tree.py Tree.go_left`` on device, shared by the traversal loop
    and the TreeSHAP path evaluation so their decisions can never
    drift apart."""
    import jax.numpy as jnp
    from jax import lax

    thr = v[1]
    mt = v[2].astype(jnp.int32)
    dl = v[3] > 0.5
    isna = jnp.isnan(x)
    # missing != NaN: NaN behaves as 0.0 (tree.h Decision)
    xv = jnp.where(isna & (mt != 2), 0.0, x)
    miss = jnp.where(
        mt == 2, isna, (mt == 1) & (jnp.abs(xv) <= _K_ZERO)
    )
    go_left = jnp.where(miss, dl, xv <= thr)
    if has_cat:
        nw = v[8].astype(jnp.int32)
        iv = jnp.nan_to_num(x, nan=-1.0, posinf=-1.0, neginf=-1.0)
        iv = iv.astype(jnp.int32)
        ok = (~isna) & (iv >= 0) & (iv < 32 * nw)
        widx = v[7].astype(jnp.int32) + jnp.maximum(iv, 0) // 32
        W = catw.shape[0]
        w = catw[jnp.clip(widx, 0, W - 1)]
        bit = lax.shift_right_logical(w, jnp.maximum(iv, 0) % 32) & 1
        go_left = jnp.where(v[4] > 0.5, ok & (bit == 1), go_left)
    return go_left


def forest_apply(tables, X, tree_w, *, has_cat: bool = True,
                 linear: bool = False, max_depth: int = 0):
    """Device traversal: (N, F) rows x all T trees -> per-class raw
    scores (N, K) and per-tree leaf indices (N, T).

    `tables` is the pack_forest_tables pytree (jnp arrays); `tree_w`
    is the (T,) f32 per-tree weight implementing iteration truncation.
    Pure jax — jit/shard_map wrapping happens in TensorForest.
    """
    import jax.numpy as jnp
    from jax import lax

    from ..learner.histogram import take_cols

    T, L = tables["leaf_value"].shape
    M = tables["pack"].shape[1] // T
    N = X.shape[0]
    tpos = (jnp.arange(T, dtype=jnp.int32) * M)[None, :]  # (1, T)
    cur0 = jnp.broadcast_to(tables["init_node"][None, :], (N, T))
    # every lane descends one edge per pass, so the forest's max depth
    # (pack_forest_tables meta) bounds the loop tighter than the node
    # count; <=0 falls back to M
    bound = M if max_depth <= 0 else min(int(max_depth), M)

    def cond(s):
        it, cur = s
        return (it < bound) & jnp.any(cur >= 0)

    def body(s):
        it, cur = s
        node = jnp.maximum(cur, 0)  # leaf lanes compute a dead decision
        flat = (tpos + node).reshape(-1)  # (N*T,)
        v = take_cols(tables["pack"], flat)  # (9, N*T)
        v = v.reshape(9, N, T)
        f = v[0].astype(jnp.int32)
        x = jnp.take_along_axis(X, f, axis=1)  # (N, T)
        go_left = _go_left(v, x, tables["catw"], has_cat)
        child = jnp.where(go_left, v[5], v[6]).astype(jnp.int32)
        cur = jnp.where(cur >= 0, child, cur)
        return it + 1, cur

    _, cur = lax.while_loop(cond, body, (jnp.int32(0), cur0))
    leaf = jnp.where(cur < 0, ~cur, 0)  # (N, T)
    lflat = (jnp.arange(T, dtype=jnp.int32) * L)[None, :] + leaf
    val = tables["leaf_value"].reshape(-1)[lflat]  # (N, T)
    if linear:
        Ck = tables["leaf_feat"].shape[2]
        const = tables["leaf_const"].reshape(-1)[lflat]
        nf = tables["leaf_nf"].reshape(-1)[lflat]
        fidx = tables["leaf_feat"].reshape(-1, Ck)[lflat]    # (N, T, Ck)
        co = tables["leaf_coeff"].reshape(-1, Ck)[lflat]
        xg = X[jnp.arange(N, dtype=jnp.int32)[:, None, None], fidx]
        kmask = jnp.arange(Ck, dtype=jnp.int32)[None, None, :] < nf[..., None]
        contrib = jnp.sum(jnp.where(kmask, co * xg, 0.0), axis=-1)
        anynan = jnp.any(kmask & jnp.isnan(xg), axis=-1)
        # linear semantics (tree.cpp:137-153): const + coeffs . x,
        # rows with NaN in a used feature fall back to leaf_value
        val = jnp.where(anynan, val, const + contrib)
    # HIGHEST precision: the default TPU matmul multiplies f32 in bf16,
    # which would round every leaf value to 8 mantissa bits — the
    # 1e-5 parity with the host walker needs the exact f32 product
    score = jnp.dot(val * tree_w[None, :], tables["class_onehot"],
                    precision=lax.Precision.HIGHEST)  # (N, K)
    return score, leaf


def stacked_forest_apply(stack, slot, X, tree_w, *, has_cat: bool = True,
                         linear: bool = False, max_depth: int = 0):
    """Score one slot of an (S, ...)-stacked table set: the fleet's
    scoring entry. ``slot`` is a TRACED int32 scalar (a dynamic index,
    not a static), so every resident model of a shape family scores
    through one executable per bucket — paging a model into or out of
    its HBM slot never recompiles (serving/fleet.py)."""
    tables = {k: v[slot] for k, v in stack.items()}
    return forest_apply(tables, X, tree_w, has_cat=has_cat,
                        linear=linear, max_depth=max_depth)


def pad_forest_tables(tables, meta, *, num_trees: int, max_nodes: int,
                      max_leaves: int, cat_words: int, lin_feats: int):
    """Pad one model's host tables out to a shape family's dimensions
    (all targets >= the model's own) so models of one family can share
    a stacked executable. Padding reuses the packer's inert encodings:
    children -1 (straight to leaf 0), init_node -1 (stump at leaf 0),
    zero leaf values and zero class-onehot rows, so padded trees score
    exactly 0 under any tree-weight vector."""
    T, M = meta["num_trees"], meta["max_nodes"]
    L = meta["max_leaves"]
    K = tables["class_onehot"].shape[1]
    Ck = tables["leaf_feat"].shape[2]
    W = tables["catw"].shape[0]
    T2, M2, L2 = int(num_trees), int(max_nodes), int(max_leaves)
    W2, Ck2 = int(cat_words), int(lin_feats)
    if (T2, M2, L2, W2, Ck2) < (T, M, L, W, Ck):
        raise ValueError("pad targets must cover the model's own dims")
    pack = np.zeros((9, T2, M2), np.float32)
    pack[5:7] = -1.0  # padding nodes route straight to leaf 0
    pack[:, :T, :M] = np.asarray(tables["pack"]).reshape(9, T, M)
    catw = np.zeros(W2, np.int32)
    catw[:W] = np.asarray(tables["catw"])
    init_node = np.full(T2, -1, np.int32)
    init_node[:T] = np.asarray(tables["init_node"])
    class_onehot = np.zeros((T2, K), np.float32)
    class_onehot[:T] = np.asarray(tables["class_onehot"])

    def grow(a, shape):
        out = np.zeros(shape, a.dtype)
        out[tuple(slice(0, s) for s in a.shape)] = a
        return out

    out = {
        "pack": pack.reshape(9, T2 * M2),
        "catw": catw,
        "leaf_value": grow(np.asarray(tables["leaf_value"]), (T2, L2)),
        "leaf_const": grow(np.asarray(tables["leaf_const"]), (T2, L2)),
        "leaf_nf": grow(np.asarray(tables["leaf_nf"]), (T2, L2)),
        "leaf_feat": grow(np.asarray(tables["leaf_feat"]), (T2, L2, Ck2)),
        "leaf_coeff": grow(np.asarray(tables["leaf_coeff"]),
                           (T2, L2, Ck2)),
        "init_node": init_node,
        "class_onehot": class_onehot,
    }
    meta2 = dict(meta, num_trees=T2, max_nodes=M2, max_leaves=L2)
    return out, meta2


def _pow2(n: int) -> int:
    return 1 << max(int(n) - 1, 0).bit_length()


def pack_contrib_tables(models, num_class: int):
    """Host packing for device TreeSHAP: per (tree, leaf), the
    root-to-leaf path as node ids + directions, the path's UNIQUE
    features with their cover ("zero") fractions — everything about
    the recursion that does not depend on the scored row. The
    row-dependent half (the {0,1} "one" fractions) falls out of the
    same per-node split decisions the predictor makes.

    Duplicate features on a path collapse into one slot whose zero
    fraction is the product of its edges' cover ratios and whose one
    fraction is the AND of its edges' hot indicators — exactly the
    reference's unwind-and-re-extend semantics (shap.py _tree_shap).
    Paths pad with (zero=1, one=1) dummy slots to one uniform length;
    such a slot leaves every other feature's permutation weight
    unchanged and contributes nothing itself (one - zero == 0), so the
    device DP runs a single static depth. Path dims quantize to powers
    of two so nearby-depth models share the contrib executable."""
    T = len(models)
    K = max(int(num_class), 1)
    n_nodes = [max(t.num_leaves - 1, 0) for t in models]
    M = max(n_nodes + [1])
    L = max([t.num_leaves for t in models] + [1])

    paths: Dict[Tuple[int, int], List[Tuple[int, int, float, int]]] = {}
    expect = np.zeros(T, np.float32)
    for ti, t in enumerate(models):
        lv = np.asarray(t.leaf_value, np.float64)
        if t.num_leaves == 1:
            expect[ti] = lv[0]
            continue
        cnt_in = np.asarray(t.internal_count, np.float64)
        cnt_lf = np.asarray(t.leaf_count, np.float64)
        total = cnt_in[0]
        expect[ti] = (
            float(np.dot(cnt_lf[: t.num_leaves] / total,
                         lv[: t.num_leaves]))
            if total > 0 else float(np.mean(lv[: t.num_leaves]))
        )

        def count(n: int) -> float:
            return cnt_in[n] if n >= 0 else cnt_lf[~n]

        # iterative DFS: (node, edges so far); edge = (node, dir,
        # cover ratio, feature)
        stack: List[Tuple[int, List[Tuple[int, int, float, int]]]] = [
            (0, [])
        ]
        while stack:
            node, edges = stack.pop()
            if node < 0:
                paths[(ti, ~node)] = edges
                continue
            w = count(node)
            f = int(t.split_feature[node])
            for child, d in ((int(t.left_child[node]), 1),
                             (int(t.right_child[node]), 0)):
                r = count(child) / w if w > 0 else 0.0
                stack.append((child, edges + [(node, d, r, f)]))

    E = _pow2(max([len(e) for e in paths.values()] + [1]))
    P = _pow2(max(
        [len({f for _, _, _, f in e}) for e in paths.values()] + [1]
    ))
    nodes = np.full((T, L, E), -1, np.int32)
    dirs = np.zeros((T, L, E), np.float32)
    slot_oh = np.zeros((T, L, E, P), np.float32)
    zero = np.ones((T, L, P), np.float32)
    feat = np.zeros((T, L, P), np.int32)
    for (ti, li), edges in paths.items():
        slots: Dict[int, int] = {}
        for e, (node, d, r, f) in enumerate(edges):
            s = slots.setdefault(f, len(slots))
            nodes[ti, li, e] = ti * M + node
            dirs[ti, li, e] = d
            slot_oh[ti, li, e, s] = 1.0
            zero[ti, li, s] *= r
            feat[ti, li, s] = f
    tables = {
        "nodes": nodes,          # (T, L, E) int32, flat t*M+node, pad -1
        "dirs": dirs,            # (T, L, E) f32, 1 = path goes left
        "slot_oh": slot_oh,      # (T, L, E, P) f32 edge -> feature slot
        "zero": zero,            # (T, L, P) f32 cover fractions, pad 1
        "feat": feat,            # (T, L, P) int32 feature ids, pad 0
        "expect": expect,        # (T,) f32 cover-weighted mean output
        "tree_class": (np.arange(T, dtype=np.int32) % K),  # (T,)
    }
    cmeta = {"path_edges": int(E), "path_feats": int(P),
             "max_nodes": M, "max_leaves": L}
    return tables, cmeta


def contrib_apply(tables, ctables, X, tree_w, *, has_cat: bool = True):
    """Device TreeSHAP: (N, F) rows -> (N, K*(F+1)) contributions in
    Booster.predict(pred_contrib=True) layout (per class: F feature
    columns then the expected-value bias column; rows sum to the raw
    score). Mirrors host shap.py: one split decision per (row, node),
    per-leaf one/zero fractions, then the reference's extend /
    unwound-sum permutation-weight DP over every (row, tree, leaf)
    lane at one static path depth."""
    import jax.numpy as jnp
    from jax import lax

    T, L = tables["leaf_value"].shape
    M = tables["pack"].shape[1] // T
    N, F = X.shape
    K = tables["class_onehot"].shape[1]
    E = ctables["nodes"].shape[2]
    P = ctables["zero"].shape[2]
    tw = tree_w.astype(jnp.float32)

    # the split decision at EVERY node (the traversal evaluates only
    # the visited one; SHAP weighs both branches of every path)
    v = tables["pack"].reshape(9, 1, T * M)
    f_all = tables["pack"][0].astype(jnp.int32)          # (T*M,)
    x_all = jnp.take(X, f_all, axis=1)                   # (N, T*M)
    gl = _go_left(v, x_all, tables["catw"], has_cat)     # (N, T*M)

    nodes = ctables["nodes"]
    nid = jnp.maximum(nodes, 0).reshape(-1)
    g = jnp.take(gl, nid, axis=1).reshape(N, T, L, E)
    follows = jnp.where(nodes[None] < 0, True,
                        g == (ctables["dirs"][None] > 0.5))
    miss = (~follows).astype(jnp.float32)                # (N, T, L, E)
    # a slot is "hot" (one fraction 1) iff the row follows the path at
    # every edge splitting on that slot's feature
    o = (jnp.einsum("ntle,tlep->ntlp", miss,
                    ctables["slot_oh"]) == 0).astype(jnp.float32)
    z = ctables["zero"]                                  # (T, L, P)

    # extend DP (shap.py _extend): permutation weights w[0..P] per
    # (row, tree, leaf) lane, all P slots extended at static depth
    w = [jnp.ones((N, T, L), jnp.float32)]
    for i in range(1, P + 1):
        one = o[..., i - 1]
        zr = z[None, :, :, i - 1]
        w.append(jnp.zeros((N, T, L), jnp.float32))
        d1 = float(i + 1)
        for j in range(i - 1, -1, -1):
            w[j + 1] = w[j + 1] + one * w[j] * ((j + 1) / d1)
            w[j] = zr * w[j] * ((i - j) / d1)

    # per-slot unwound sums (shap.py _unwound_sum at depth P) -> phi
    lv = tables["leaf_value"]
    d1 = float(P + 1)
    deltas = []
    for i in range(P):
        one = o[..., i]
        zr = z[None, :, :, i]
        zsafe = jnp.maximum(zr, 1e-12)
        hot = one > 0.5
        nxt = w[P]
        total = jnp.zeros((N, T, L), jnp.float32)
        for j in range(P - 1, -1, -1):
            tmp = nxt * (d1 / (j + 1))
            cold = (w[j] / zsafe) * (d1 / (P - j))
            total = total + jnp.where(hot, tmp, cold)
            nxt = jnp.where(hot, w[j] - tmp * zr * ((P - j) / d1), nxt)
        deltas.append(total * (one - zr) * lv[None] * tw[None, :, None])
    delta = jnp.stack(deltas, axis=-1)                   # (N, T, L, P)

    cols = (ctables["tree_class"][:, None, None] * (F + 1)
            + ctables["feat"])                           # (T, L, P)
    out = jnp.zeros((N, K * (F + 1)), jnp.float32)
    out = out.at[:, cols.reshape(-1)].add(delta.reshape(N, -1))
    bias = jnp.dot(tw * ctables["expect"], tables["class_onehot"],
                   precision=lax.Precision.HIGHEST)  # (K,)
    bcols = (jnp.arange(K, dtype=jnp.int32) + 1) * (F + 1) - 1
    out = out.at[:, bcols].add(jnp.broadcast_to(bias[None], (N, K)))
    return out


def replicate_forest(forest: "TensorForest", device) -> "TensorForest":
    """A shallow copy of a (non-mesh) forest with its tables committed
    to ``device``. jit runs committed-input computations on the
    inputs' device, so N replicas score concurrently on N devices —
    each device compiles the shared entry once per bucket, and the
    replicas stay bit-identical (same tables, same program)."""
    import copy

    import jax

    if forest.mesh is not None:
        raise ValueError("replicate_forest needs a single-device forest")
    rep = copy.copy(forest)
    rep.tables = {
        k: jax.device_put(v, device) for k, v in forest.tables.items()
    }
    rep._ctables = None  # contrib tables re-pack on the replica's device
    return rep


_APPLY_JIT = None
_STACK_JIT = None
_CONTRIB_JIT = None


def _stacked_apply_jit():
    """Shared jit of stacked_forest_apply — every same-shaped
    ForestStack scores through one executable per bucket."""
    global _STACK_JIT
    if _STACK_JIT is None:
        import jax

        _STACK_JIT = jax.jit(
            stacked_forest_apply,
            static_argnames=("has_cat", "linear", "max_depth"),
        )
    return _STACK_JIT


def _contrib_apply_jit():
    """Shared jit of contrib_apply — same-shaped models (incl. the
    quantized path dims) share the TreeSHAP executable."""
    global _CONTRIB_JIT
    if _CONTRIB_JIT is None:
        import jax

        _CONTRIB_JIT = jax.jit(
            contrib_apply, static_argnames=("has_cat",)
        )
    return _CONTRIB_JIT


def _forest_apply_jit():
    """Shared module-level jit of forest_apply (lazy so importing the
    package never initializes a backend): every non-mesh TensorForest
    scores through this ONE callable, so same-shaped tables — model
    hot-swaps, registry versions — reuse one executable per bucket."""
    global _APPLY_JIT
    if _APPLY_JIT is None:
        import jax

        _APPLY_JIT = jax.jit(
            forest_apply, static_argnames=("has_cat", "linear", "max_depth")
        )
    return _APPLY_JIT


class TensorForest:
    """A trained forest compiled to device tables + a scoring callable.

    ``mesh=None`` (or a 1-device mesh) uses the shared module-level jit
    — model hot-swaps with identical table shapes reuse the executable.
    With a multi-device mesh the row axis is sharded over
    ``axis_name`` through the same ``jax.shard_map`` seam training
    uses (tables replicated); callers must pad rows to a multiple of
    the mesh size (``BucketDispatcher`` aligns its ladder for this).
    """

    def __init__(self, models, num_class: int = 1,
                 average_output: bool = False, mesh=None,
                 axis_name: str = "data"):
        import jax
        import jax.numpy as jnp

        if not models:
            raise ValueError("TensorForest needs at least one tree")
        tables, meta = pack_forest_tables(models, num_class)
        self.meta = meta
        # retained for lazy contrib packing (references, not copies)
        self._models = list(models)
        self._ctables = None
        # while_loop bound: true max depth rounded UP to a power of two
        # — max_depth is a static jit arg, so quantizing keeps the
        # hot-swap executable-reuse property for same-shaped models
        # with nearby depths (any bound >= true depth is correct)
        d = max(int(meta["max_depth"]), 1)
        self._depth_bound = 1 << (d - 1).bit_length()
        self.num_class = meta["num_class"]
        self.num_trees = meta["num_trees"]
        self.average_output = bool(average_output)
        self.max_feature = meta["max_feature"]
        self.mesh = None
        self.axis_name = axis_name
        n_dev = 1
        if mesh is not None and int(np.prod(mesh.devices.shape)) > 1:
            self.mesh = mesh
            n_dev = int(np.prod(mesh.devices.shape))
        self.num_devices = n_dev
        if self.mesh is None:
            self.tables = {k: jnp.asarray(v) for k, v in tables.items()}
            self._fn = _forest_apply_jit()
        else:
            from jax.sharding import NamedSharding, PartitionSpec as P

            rep = NamedSharding(self.mesh, P())
            self.tables = {
                k: jax.device_put(jnp.asarray(v), rep)
                for k, v in tables.items()
            }
            has_cat, linear = meta["has_cat"], meta["linear"]
            max_depth = self._depth_bound

            def fn(tables, X, tree_w):
                return forest_apply(tables, X, tree_w,
                                    has_cat=has_cat, linear=linear,
                                    max_depth=max_depth)

            tspec = jax.tree.map(lambda _: P(), self.tables)
            self._sharded = jax.jit(jax.shard_map(
                fn, mesh=self.mesh,
                in_specs=(tspec, P(axis_name, None), P()),
                out_specs=(P(axis_name, None), P(axis_name, None)),
                check_vma=False,
            ))
            self._fn = None

    # ------------------------------------------------------------------
    @classmethod
    def from_booster(cls, booster, mesh=None) -> "TensorForest":
        g = booster._gbdt
        return cls(
            list(g.models), g.num_class,
            average_output=bool(getattr(g, "average_output", False)),
            mesh=mesh,
        )

    @property
    def jit_entry(self):
        """The jitted scoring callable — hand this to retrace_guard
        entry_points to assert the compile-per-bucket contract."""
        return self._sharded if self.mesh is not None else self._fn

    def _tree_weights(self, start_iteration: int,
                      num_iteration: int) -> Tuple[np.ndarray, int, int]:
        K = self.num_class
        n_iters = self.num_trees // K
        end = n_iters if num_iteration <= 0 else min(
            n_iters, start_iteration + num_iteration
        )
        tw = np.zeros(self.num_trees, np.float32)
        tw[start_iteration * K: end * K] = 1.0
        return tw, start_iteration, end

    def _check_width(self, X: np.ndarray) -> None:
        if X.shape[1] <= self.max_feature:
            # keep the host walk's error semantics (tree.py predict_leaf
            # raises IndexError on narrow input)
            raise IndexError(
                f"input has {X.shape[1]} features but the model "
                f"references feature {self.max_feature}"
            )

    def apply(self, X, tree_w):
        """Raw device call on an already-padded f32 row block."""
        import jax.numpy as jnp

        tw = jnp.asarray(tree_w, jnp.float32)
        if self.mesh is not None:
            return self._sharded(self.tables, X, tw)
        return self._fn(
            self.tables, X, tw,
            has_cat=self.meta["has_cat"], linear=self.meta["linear"],
            max_depth=self._depth_bound,
        )

    def predict_raw(self, X: np.ndarray, start_iteration: int = 0,
                    num_iteration: int = -1) -> np.ndarray:
        """(K, N) raw margins, matching GBDT.predict_raw layout."""
        import jax.numpy as jnp

        X = np.asarray(X, np.float32)
        self._check_width(X)
        tw, start, end = self._tree_weights(start_iteration, num_iteration)
        N = X.shape[0]
        pad = (-N) % max(self.num_devices, 1)
        if pad:
            X = np.concatenate([X, np.zeros((pad, X.shape[1]), X.dtype)])
        score, _ = self.apply(jnp.asarray(X), tw)
        out = np.asarray(score)[:N].T.astype(np.float64)  # (K, N)
        if self.average_output and end > start:
            out /= end - start
        return out

    def predict_leaf(self, X: np.ndarray, start_iteration: int = 0,
                     num_iteration: int = -1) -> np.ndarray:
        """(N, used_trees) leaf indices (Booster.predict pred_leaf)."""
        import jax.numpy as jnp

        X = np.asarray(X, np.float32)
        self._check_width(X)
        tw, start, end = self._tree_weights(start_iteration, num_iteration)
        N = X.shape[0]
        pad = (-N) % max(self.num_devices, 1)
        if pad:
            X = np.concatenate([X, np.zeros((pad, X.shape[1]), X.dtype)])
        _, leaf = self.apply(jnp.asarray(X), tw)
        K = self.num_class
        return np.asarray(leaf)[:N, start * K: end * K].astype(np.int64)

    # -------------------------------------------------------- contrib
    def contrib_tables(self):
        """Lazy device TreeSHAP tables: packed on the first contrib
        request only — explanation traffic pays for its own HBM.
        Fleet eviction drops the reference (serving/fleet.py) and a
        later request re-packs from the retained host trees."""
        import jax.numpy as jnp

        if self._ctables is None:
            ct, cmeta = pack_contrib_tables(self._models, self.num_class)
            self._ctables = (
                {k: jnp.asarray(v) for k, v in ct.items()}, cmeta
            )
        return self._ctables

    def drop_contrib_tables(self) -> None:
        self._ctables = None

    def apply_contrib(self, X, tree_w):
        """Raw device TreeSHAP on an already-padded f32 row block:
        (N, K*(F+1)) where F is the padded input width."""
        import jax.numpy as jnp

        ct, _ = self.contrib_tables()
        tw = jnp.asarray(tree_w, jnp.float32)
        return _contrib_apply_jit()(
            self.tables, ct, X, tw, has_cat=self.meta["has_cat"]
        )

    def predict_contrib(self, X: np.ndarray, start_iteration: int = 0,
                        num_iteration: int = -1) -> np.ndarray:
        """(N, K*(F+1)) SHAP contributions in Booster.predict
        (pred_contrib=True) layout; host shap.py is the oracle."""
        import jax.numpy as jnp

        X = np.asarray(X, np.float32)
        self._check_width(X)
        tw, start, end = self._tree_weights(start_iteration, num_iteration)
        N, F = X.shape
        out = np.asarray(
            self.apply_contrib(jnp.asarray(X), tw)
        )[:N].astype(np.float64)
        if self.average_output and end > start:
            out /= end - start
        return out
