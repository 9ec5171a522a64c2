"""Sequential leaf-wise tree growth over a physically permuted bin
matrix: the reference-exact parity oracle (tpu_growth_mode=exact).

One split per step, in the reference's best-first order; the measured
program is the rounds grower (rounds.py), and the tests hold it to
this one. This is the TPU formulation of the reference's index-list partition
(src/treelearner/data_partition.hpp: rows stored grouped by leaf as one
permuted array + per-leaf (begin, count)): the bin matrix, channel
matrix, and a row-origin vector are kept PHYSICALLY reordered so every
leaf occupies a contiguous segment. Each split then costs O(parent
segment), not O(N):

- stable partition of the parent segment (ParallelPartitionRunner /
  cuda_data_partition.cu SplitInner): two `nonzero` compactions over a
  static-capacity slice + one gather + one dynamic_update_slice;
- the smaller child's histogram reads a CONTIGUOUS slice (no row
  gather, no full-N mask), the larger sibling comes from parent
  subtraction as in serial_tree_learner.cpp:411;
- total per-tree work matches the reference's sum-of-segment-sizes
  (~depth x N), where the flat row->leaf formulation pays O(N) per
  split (254x N for a 255-leaf tree).

Static shapes come from a capacity ladder (N, N/2, ..., HIST_BLK):
every segment operation runs at the smallest capacity that covers the
segment, with rows outside the segment masked / passed through
untouched.

With `axis_name` set, rows are sharded; histograms and the
smaller-child choice are psum'd (data_parallel_tree_learner.cpp:286)
while each shard stable-partitions its local segment in lockstep.
"""

from __future__ import annotations

from functools import partial
from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from .bundle import BundleInfo, decode_feature_bins, expand_hist
from .histogram import HIST_BLK, build_gh8, histogram, root_sums
from .split import (
    BIG,
    NEG_INF,
    SplitParams,
    SplitRecord,
    best_split,
    feature_best_gains,
    leaf_gain,
    leaf_output,
)


class ForcedSplits(NamedTuple):
    """Traced forced-split plan (serial_tree_learner.cpp:627
    ForceSplits): BFS-ordered (leaf, feature, bin) triples applied
    before best-gain growth; `n` is the actual count (arrays padded to
    a static length)."""

    leaf: jax.Array  # (K,) int32 — leaf id at application time
    feature: jax.Array  # (K,) int32 — used-feature index
    bin: jax.Array  # (K,) int32 — threshold bin
    n: jax.Array  # scalar int32
from .grower import (
    CegbInfo,
    GrowerSpec,
    TreeArrays,
    _empty_best,
    _get_best,
    _set_best,
    make_node_candidates,
    monotone_child_intervals,
    split_leaf_outputs,
)


class _Extras(NamedTuple):
    """Per-node feature bookkeeping (interaction constraints + CEGB)."""

    leaf_groups: jax.Array  # (L, NG) bool — constraint groups still legal
    path_used: jax.Array  # (L, F) bool — features used on the leaf's path
    feat_used: jax.Array  # (F,) bool — used anywhere (CEGB coupled)


def segment_caps(n_rows: int) -> tuple:
    """Static ladder of segment capacities: N, N/2, ..., >= HIST_BLK,
    all HIST_BLK multiples when n_rows itself is one. A non-multiple
    n_rows (per-SHARD rows on a mesh whose count doesn't divide into
    HIST_BLK blocks) clamps the top cap to n_rows instead of rounding
    past the operand — the pallas kernel path needs multiples, but
    such a shard is already on the einsum fallback."""
    caps = []
    c = n_rows
    while c >= HIST_BLK:
        caps.append(min(((c + HIST_BLK - 1) // HIST_BLK) * HIST_BLK,
                        n_rows))
        c //= 2
    if not caps:
        caps.append(n_rows)
    return tuple(caps)


class _PState(NamedTuple):
    i: jax.Array
    pbins: jax.Array  # (F, N) int32, leaf-grouped along the row (lane) axis
    pgh: jax.Array  # (8, N) f32, leaf-grouped (build_gh8 channels)
    pperm: jax.Array  # (N,) int32 — original row index at each position
    seg_begin: jax.Array  # (L,) int32; unused leaves = N (sorts last)
    seg_count: jax.Array  # (L,) int32
    hist: jax.Array  # (L, 3, F, B) — channel-leading, bins on lanes
    leaf_g: jax.Array
    leaf_h: jax.Array
    leaf_c: jax.Array
    leaf_parent: jax.Array
    leaf_min: jax.Array  # (L,) monotone-constraint interval per leaf
    leaf_max: jax.Array
    best: SplitRecord
    tree: TreeArrays
    # (L, F) bool — features whose stored histogram holds GLOBAL sums.
    # Always all-True except under voting (spec.voting_k > 0), where
    # only elected features are reduced across the mesh
    # (voting_parallel_tree_learner.cpp: global hists exist only for
    # elected features); subtraction and search respect this mask.
    hist_valid: jax.Array
    extra: _Extras
    # ancestry matrices for mono_mode=1 (intermediate constraints):
    # anc_in[x, a] = leaf x lies in node a's subtree; anc_left[x, a] =
    # on its LEFT side. Zero-size placeholders when mono_mode == 0.
    anc_in: jax.Array  # (L, L-1) bool or (L, 0)
    anc_left: jax.Array


def _go_left(fbins, rec, fnan):
    return jnp.where(
        rec.is_cat,
        rec.cat_mask[fbins],
        (fbins <= rec.bin) | (rec.default_left & (fbins == fnan) & (fnan >= 0)),
    )


@partial(jax.jit, static_argnames=("spec",))
def grow_tree_permuted(
    bins_fm: jax.Array,  # (F, N) int32
    nan_bin: jax.Array,
    num_bins: jax.Array,
    mono: jax.Array,
    is_cat: jax.Array,
    grad: jax.Array,
    hess: jax.Array,
    mask: jax.Array,  # validity * bagging
    feat_mask: jax.Array,
    params: SplitParams,
    spec: GrowerSpec,
    valid: Optional[jax.Array] = None,
    bundle: Optional[BundleInfo] = None,
    rng_key: Optional[jax.Array] = None,
    group_mat: Optional[jax.Array] = None,  # (NG, F) bool
    cegb: Optional[CegbInfo] = None,
    forced: Optional[ForcedSplits] = None,
) -> Tuple[TreeArrays, jax.Array]:
    """Grow one tree; returns (tree arrays, natural-order row->leaf)."""
    L = spec.num_leaves
    B = spec.num_bins
    G, N = bins_fm.shape  # G = device columns (bundles when spec.efb)
    F = num_bins.shape[0]  # original features
    ax = spec.axis_name
    caps = segment_caps(N)
    Bc = spec.col_bins if (spec.efb and spec.col_bins) else B
    # This grower is the reference-exact parity ORACLE
    # (tpu_growth_mode=exact); production configs — including voting
    # and forced splits, ISSUE 14 — route to the rounds grower
    # (boosting.py mode resolution). The oracle keeps its narrower
    # capability matrix:
    if spec.voting_k and spec.n_forced:
        # the oracle's forced path reads s.hist[fl] at the prescribed
        # feature without pinning it into the election; the rounds
        # grower supports the combination (forced columns pinned into
        # every election, rounds.py vote_reduce)
        raise ValueError(
            "voting_k excludes forced splits on the sequential oracle; "
            "use tpu_growth_mode=rounds for the combination"
        )
    per_node = spec.extra_trees or spec.ff_bynode or spec.cegb or spec.n_groups
    if spec.mono_mode and (per_node or spec.voting_k or spec.n_forced):
        # the intermediate re-search pass uses the plain feature mask
        # and assumes globally-valid histograms
        raise ValueError(
            "monotone intermediate/advanced excludes per-node extras / "
            "voting / forced splits"
        )

    # shared per-node machinery (grower.make_node_candidates): the
    # DeltaGain per-tree-path lazy approximation and its rationale are
    # documented there and in DESIGN_DECISIONS.md
    node_candidates = make_node_candidates(
        spec, params, feat_mask, num_bins, nan_bin, rng_key, group_mat,
        cegb, F,
    )

    def exp_hist(h, g_sum, h_sum, c_sum):
        """Bundle-space histogram -> per-feature for the split scan."""
        if spec.efb:
            return expand_hist(h, g_sum, h_sum, c_sum, bundle)
        return h

    gh8 = build_gh8(grad * mask, hess * mask, mask)  # (8, N)
    root = root_sums(gh8, ax)

    hist0 = histogram(bins_fm, gh8, Bc)
    if ax is not None:
        hist0 = lax.psum(hist0, ax)
    root_out = leaf_output(root[0], root[1], params)
    NG = max(1, spec.n_groups)
    extra0 = _Extras(
        leaf_groups=jnp.ones((L, NG), bool),
        path_used=jnp.zeros((L, F), bool),
        feat_used=(cegb.used if spec.cegb else jnp.zeros(F, bool)),
    )
    if per_node:
        fm0, rb0, pen0 = node_candidates(
            jnp.int32(0), extra0.leaf_groups[0], extra0.path_used[0],
            root[2], extra0.feat_used,
        )
    else:
        fm0, rb0, pen0 = feat_mask, None, None
    rec0 = best_split(exp_hist(hist0, root[0], root[1], root[2]),
                      root[0], root[1], root[2], num_bins, nan_bin,
                      mono, is_cat, params, fm0,
                      dirs=spec.search, parent_output=root_out,
                      penalty=pen0, rand_bin=rb0)

    hist = jnp.zeros((L, 3, G, Bc), jnp.float32).at[0].set(hist0)
    best = _set_best(_empty_best(L, B), jnp.int32(0), rec0, rec0.gain)

    tree = TreeArrays(
        num_nodes=jnp.int32(0),
        node_feature=jnp.zeros(L - 1, jnp.int32),
        node_bin=jnp.zeros(L - 1, jnp.int32),
        node_gain=jnp.zeros(L - 1, jnp.float32),
        node_default_left=jnp.zeros(L - 1, bool),
        node_cat=jnp.zeros(L - 1, bool),
        node_cat_mask=jnp.zeros((L - 1, B), bool),
        node_left=jnp.zeros(L - 1, jnp.int32),
        node_right=jnp.zeros(L - 1, jnp.int32),
        node_value=jnp.zeros(L - 1, jnp.float32),
        node_weight=jnp.zeros(L - 1, jnp.float32),
        node_count=jnp.zeros(L - 1, jnp.float32),
        leaf_value=jnp.zeros(L, jnp.float32).at[0].set(leaf_output(root[0], root[1], params)),
        leaf_weight=jnp.zeros(L, jnp.float32).at[0].set(root[1]),
        leaf_count=jnp.zeros(L, jnp.float32).at[0].set(root[2]),
        leaf_depth=jnp.zeros(L, jnp.int32),
    )

    valid_f = jnp.ones(N, jnp.float32) if valid is None else valid
    n_valid = jnp.sum(valid_f > 0).astype(jnp.int32)  # local (shard) count

    iota_L = jnp.arange(L, dtype=jnp.int32)

    state = _PState(
        i=jnp.int32(0),
        pbins=bins_fm,
        pgh=gh8,
        pperm=jnp.arange(N, dtype=jnp.int32),
        seg_begin=jnp.full(L, N, jnp.int32).at[0].set(0),
        seg_count=jnp.zeros(L, jnp.int32).at[0].set(n_valid),
        hist=hist,
        leaf_g=jnp.zeros(L, jnp.float32).at[0].set(root[0]),
        leaf_h=jnp.zeros(L, jnp.float32).at[0].set(root[1]),
        leaf_c=jnp.zeros(L, jnp.float32).at[0].set(root[2]),
        leaf_parent=jnp.full(L, -1, jnp.int32),
        leaf_min=jnp.full(L, -BIG, jnp.float32),
        leaf_max=jnp.full(L, BIG, jnp.float32),
        best=best,
        tree=tree,
        hist_valid=jnp.ones((L, F), bool),
        extra=extra0,
        anc_in=jnp.zeros((L, L - 1 if spec.mono_mode else 0), bool),
        anc_left=jnp.zeros((L, L - 1 if spec.mono_mode else 0), bool),
    )

    def _forced_valid(s: _PState):
        """Is step s.i a forced split with both children non-empty?"""
        fi = jnp.minimum(s.i, spec.n_forced - 1)
        fl = forced.leaf[fi]
        ff = forced.feature[fi]
        fb = forced.bin[fi]
        fh = exp_hist(s.hist[fl], s.leaf_g[fl], s.leaf_h[fl], s.leaf_c[fl])
        lc = jnp.cumsum(fh[2, ff])[fb]
        return (s.i < forced.n) & (lc > 0) & (s.leaf_c[fl] - lc > 0)

    def cond(s: _PState) -> jax.Array:
        keep = jnp.max(s.best.gain) > 0.0
        if spec.n_forced:
            # only continue for a forced step that can actually split
            # (both children non-empty) — the body falls back to the
            # best-gain split otherwise, which `keep` already guards
            keep = keep | _forced_valid(s)
        return (s.i < L - 1) & keep

    def body(s: _PState) -> _PState:
        i = s.i
        t = s.tree
        l = jnp.argmax(s.best.gain).astype(jnp.int32)
        rec = _get_best(s.best, l)
        if spec.n_forced:
            # forced splits (ForceSplits, serial_tree_learner.cpp:627):
            # the first `forced.n` steps split prescribed leaves at
            # prescribed (feature, threshold-bin), skipping any that
            # would leave an empty child (the reference aborts invalid
            # forced branches)
            fi = jnp.minimum(i, spec.n_forced - 1)
            fl = forced.leaf[fi]
            ff = forced.feature[fi]
            fb = forced.bin[fi]
            fh = exp_hist(s.hist[fl], s.leaf_g[fl], s.leaf_h[fl],
                          s.leaf_c[fl])
            cg = jnp.cumsum(fh[0, ff])
            chs = jnp.cumsum(fh[1, ff])
            cc = jnp.cumsum(fh[2, ff])
            lg, lh, lc = cg[fb], chs[fb], cc[fb]
            pg, ph, pc = s.leaf_g[fl], s.leaf_h[fl], s.leaf_c[fl]
            gain_f = (
                leaf_gain(lg, lh, params) + leaf_gain(pg - lg, ph - lh, params)
                - leaf_gain(pg, ph, params)
            )
            # invalid forced entries (empty child / exhausted plan) fall
            # back to the best-gain split; the cond guarantees that
            # fallback has positive gain. NOTE: after a skipped invalid
            # entry, later forced entries still target their
            # PRE-COMPUTED leaf ids (the reference re-maps by aborting
            # the branch queue — documented deviation for invalid plans)
            use = (i < forced.n) & (lc > 0) & (pc - lc > 0)
            rec_f = SplitRecord(
                gain=gain_f, feature=ff, bin=fb,
                default_left=jnp.asarray(False),
                is_cat=jnp.asarray(False),
                cat_mask=jnp.zeros(B, bool),
                left_g=lg, left_h=lh, left_c=lc,
                right_g=pg - lg, right_h=ph - lh, right_c=pc - lc,
            )
            l = jnp.where(use, fl, l)
            rec = jax.tree.map(
                lambda a, b: jnp.where(use, a, b), rec_f, rec
            )
        new = i + 1

        # ---- tree bookkeeping (Tree::Split semantics, same as flat) ----
        p = s.leaf_parent[l]
        pc = jnp.maximum(p, 0)
        p_is_left = t.node_left[pc] == ~l
        node_left = t.node_left.at[pc].set(
            jnp.where((p >= 0) & p_is_left, i, t.node_left[pc])
        )
        node_right = t.node_right.at[pc].set(
            jnp.where((p >= 0) & ~p_is_left, i, t.node_right[pc])
        )
        node_left = node_left.at[i].set(~l)
        node_right = node_right.at[i].set(~new)

        pmin, pmax = s.leaf_min[l], s.leaf_max[l]
        lo, ro = split_leaf_outputs(rec, params, num_bins, spec.cat_subset,
                                    t.leaf_value[l], pmin, pmax)
        lmin, lmax, rmin, rmax = monotone_child_intervals(
            rec, mono, lo, ro, pmin, pmax
        )
        depth_new = t.leaf_depth[l] + 1

        tree_new = TreeArrays(
            num_nodes=new,
            node_feature=t.node_feature.at[i].set(rec.feature),
            node_bin=t.node_bin.at[i].set(rec.bin),
            node_gain=t.node_gain.at[i].set(rec.gain),
            node_default_left=t.node_default_left.at[i].set(rec.default_left),
            node_cat=t.node_cat.at[i].set(rec.is_cat),
            node_cat_mask=t.node_cat_mask.at[i].set(rec.cat_mask),
            node_left=node_left,
            node_right=node_right,
            node_value=t.node_value.at[i].set(t.leaf_value[l]),
            node_weight=t.node_weight.at[i].set(s.leaf_h[l]),
            node_count=t.node_count.at[i].set(s.leaf_c[l]),
            leaf_value=t.leaf_value.at[l].set(lo).at[new].set(ro),
            leaf_weight=t.leaf_weight.at[l].set(rec.left_h).at[new].set(rec.right_h),
            leaf_count=t.leaf_count.at[l].set(rec.left_c).at[new].set(rec.right_c),
            leaf_depth=t.leaf_depth.at[l].set(depth_new).at[new].set(depth_new),
        )

        b = s.seg_begin[l]
        c = s.seg_count[l]
        fnan = nan_bin[rec.feature]
        fcol_idx = bundle.bundle_of[rec.feature] if spec.efb else rec.feature

        # ---- stable partition of segment [b, b+c) at capacity cap ----
        # (XLA TPU sort is NOT an option here: a 1M-row multi-payload
        # stable sort measured 0.3-2s with minutes of per-shape compile
        # on this backend — nonzero+gather it is.)
        def mk_part(cap: int):
            def part(_):
                start = jnp.clip(b, 0, N - cap)
                off = b - start
                sbins = lax.dynamic_slice(s.pbins, (jnp.int32(0), start), (G, cap))
                sgh = lax.dynamic_slice(s.pgh, (jnp.int32(0), start), (8, cap))
                sperm = lax.dynamic_slice(s.pperm, (start,), (cap,))
                iota = jnp.arange(cap, dtype=jnp.int32)
                in_seg = (iota >= off) & (iota < off + c)
                fcol = lax.dynamic_slice(
                    sbins, (fcol_idx, jnp.int32(0)), (1, cap)
                ).reshape(cap)
                if spec.efb:
                    fcol = decode_feature_bins(fcol, rec.feature, bundle)
                gl = _go_left(fcol, rec, fnan)
                sel_l = in_seg & gl
                n_l = jnp.sum(sel_l).astype(jnp.int32)
                lidx = jnp.nonzero(sel_l, size=cap, fill_value=cap)[0]
                ridx = jnp.nonzero(in_seg & ~gl, size=cap, fill_value=cap)[0]
                rel = iota - off
                src = jnp.where(
                    rel < n_l,
                    jnp.take(lidx, jnp.clip(rel, 0, cap - 1), mode="clip"),
                    jnp.take(ridx, jnp.clip(rel - n_l, 0, cap - 1), mode="clip"),
                )
                src = jnp.where(in_seg, src, iota)
                nb = jnp.take(sbins, src, axis=1, mode="clip")
                ng = jnp.take(sgh, src, axis=1, mode="clip")
                npm = jnp.take(sperm, src, mode="clip")
                pbins = lax.dynamic_update_slice(s.pbins, nb, (jnp.int32(0), start))
                pgh = lax.dynamic_update_slice(s.pgh, ng, (jnp.int32(0), start))
                pperm = lax.dynamic_update_slice(s.pperm, npm, (start,))
                return pbins, pgh, pperm, n_l

            return part

        caps_arr = jnp.asarray(caps, jnp.int32)
        pidx = jnp.clip(jnp.sum(caps_arr >= c) - 1, 0, len(caps) - 1)
        pbins, pgh, pperm, n_l = lax.switch(
            pidx, [mk_part(cp) for cp in caps], None
        )
        n_r = c - n_l

        # ---- children segments; smaller child by GLOBAL count ----
        if ax is not None:
            left_smaller = lax.psum(n_l, ax) <= lax.psum(n_r, ax)
        else:
            left_smaller = n_l <= n_r
        # left child keeps leaf id l at [b, b+n_l); right child (id `new`)
        # occupies [b+n_l, b+c)
        seg_begin = s.seg_begin.at[l].set(b).at[new].set(b + n_l)
        seg_count = s.seg_count.at[l].set(n_l).at[new].set(n_r)

        small_begin = jnp.where(left_smaller, b, b + n_l)
        small_cnt = jnp.where(left_smaller, n_l, n_r)

        # ---- smaller-child histogram over its contiguous slice ----
        def mk_hist(cap: int):
            def h(_):
                start = jnp.clip(small_begin, 0, N - cap)
                off = small_begin - start
                hb = lax.dynamic_slice(pbins, (jnp.int32(0), start), (G, cap))
                hg = lax.dynamic_slice(pgh, (jnp.int32(0), start), (8, cap))
                iota = jnp.arange(cap, dtype=jnp.int32)
                m = ((iota >= off) & (iota < off + small_cnt)).astype(jnp.float32)
                hgm = hg * m[None, :]
                s8 = jnp.sum(hgm, axis=1)
                lsum = jnp.stack([s8[0] + s8[1], s8[2] + s8[3], s8[4]])
                return histogram(hb, hgm, Bc), lsum

            return h

        hidx = jnp.clip(jnp.sum(caps_arr >= small_cnt) - 1, 0, len(caps) - 1)
        small_hist, lsum3 = lax.switch(hidx, [mk_hist(cp) for cp in caps], None)
        valid_parent = s.hist_valid[l]  # (F,)
        if spec.voting_k and ax is not None:
            # ---- voting election (GlobalVoting, parallel_tree_learner
            # .h:152): each shard proposes its top-k COLUMNS by LOCAL
            # gain on the smaller child; votes + summed gains elect 2k;
            # only elected columns cross the mesh. Under EFB the unit of
            # election is the bundle column (a bundle's gain = the best
            # of its member features), so voting composes with bundling
            # — the reference elects features because its storage unit
            # is the feature group (voting_parallel_tree_learner.cpp).
            kG = min(spec.voting_k, G)
            k2 = min(2 * spec.voting_k, G)
            lgains = feature_best_gains(
                exp_hist(small_hist, lsum3[0], lsum3[1], lsum3[2]),
                lsum3[0], lsum3[1], lsum3[2], num_bins,
                nan_bin, mono, is_cat, params, feat_mask,
                dirs=spec.search,
            )  # (F,) local per-feature gains
            if spec.efb:
                col_gain = jnp.full(G, NEG_INF).at[bundle.bundle_of].max(
                    lgains
                )
            else:
                col_gain = lgains
            _, topi = lax.top_k(col_gain, kG)
            in_topk = jnp.zeros(G, bool).at[topi].set(True)
            votes = lax.psum(in_topk.astype(jnp.float32), ax)
            score = lax.psum(
                jnp.where(in_topk, jnp.maximum(col_gain, 0.0), 0.0), ax
            )
            _, eidx = lax.top_k(votes * 1e12 + score, k2)
            elected_cols = jnp.zeros(G, bool).at[eidx].set(True)
            comp = lax.psum(small_hist[:, eidx, :], ax)  # (3, 2k, B) wire
            small_hist = (
                jnp.zeros_like(small_hist).at[:, eidx, :].set(comp)
            )
            elected = (
                elected_cols[bundle.bundle_of] if spec.efb else elected_cols
            )
            valid_small = elected
            valid_large = elected & valid_parent
        else:
            if ax is not None:
                small_hist = lax.psum(small_hist, ax)
            valid_small = valid_parent
            valid_large = valid_parent

        parent_hist = s.hist[l]
        large_hist = parent_hist - small_hist
        left_hist = jnp.where(left_smaller, small_hist, large_hist)
        right_hist = jnp.where(left_smaller, large_hist, small_hist)
        hist = s.hist.at[l].set(left_hist).at[new].set(right_hist)

        # ---- best splits for both children ----
        if spec.voting_k:
            valid_left = jnp.where(left_smaller, valid_small, valid_large)
            valid_right = jnp.where(left_smaller, valid_large, valid_small)
            fm_l = feat_mask & valid_left
            fm_r = feat_mask & valid_right
            hist_valid = s.hist_valid.at[l].set(valid_left).at[new].set(
                valid_right
            )
        else:
            fm_l = fm_r = feat_mask
            hist_valid = s.hist_valid
        if per_node:
            f_split = rec.feature
            onehot_f = jnp.arange(F, dtype=jnp.int32) == f_split
            child_groups = s.extra.leaf_groups[l]
            if spec.n_groups:
                # only groups containing EVERY feature on the path stay
                # legal (col_sampler.hpp interaction filtering)
                child_groups = child_groups & group_mat[:, f_split]
            pu_child = s.extra.path_used[l] | onehot_f
            feat_used_new = s.extra.feat_used | onehot_f
            cn_l = node_candidates(2 * i + 1, child_groups, pu_child,
                                   rec.left_c, feat_used_new)
            cn_r = node_candidates(2 * i + 2, child_groups, pu_child,
                                   rec.right_c, feat_used_new)
            fm_l = fm_l & cn_l[0]
            fm_r = fm_r & cn_r[0]
            rb_l, pen_l = cn_l[1], cn_l[2]
            rb_r, pen_r = cn_r[1], cn_r[2]
            extra_new = _Extras(
                leaf_groups=s.extra.leaf_groups.at[l].set(child_groups)
                .at[new].set(child_groups),
                path_used=s.extra.path_used.at[l].set(pu_child)
                .at[new].set(pu_child),
                feat_used=feat_used_new,
            )
        else:
            rb_l = rb_r = pen_l = pen_r = None
            extra_new = s.extra
        if not spec.mono_mode:
            # mono_mode=1 re-searches EVERY leaf below (the children
            # included) — computing bl/br here would be discarded work
            bl = best_split(
                exp_hist(left_hist, rec.left_g, rec.left_h, rec.left_c),
                rec.left_g, rec.left_h, rec.left_c,
                num_bins, nan_bin, mono, is_cat, params, fm_l,
                dirs=spec.search, parent_output=lo,
                cmin=lmin, cmax=lmax, penalty=pen_l, rand_bin=rb_l)
            br = best_split(
                exp_hist(right_hist, rec.right_g, rec.right_h, rec.right_c),
                rec.right_g, rec.right_h, rec.right_c,
                num_bins, nan_bin, mono, is_cat, params, fm_r,
                dirs=spec.search, parent_output=ro,
                cmin=rmin, cmax=rmax, penalty=pen_r, rand_bin=rb_r)
            depth_ok = (spec.max_depth <= 0) | (depth_new < spec.max_depth)
            best2 = _set_best(
                s.best, l, bl, jnp.where(depth_ok, bl.gain, NEG_INF)
            )
            best2 = _set_best(
                best2, new, br, jnp.where(depth_ok, br.gain, NEG_INF)
            )
        else:
            best2 = s.best  # replaced by the re-search below

        anc_in_new, anc_left_new = s.anc_in, s.anc_left
        if spec.mono_mode:
            # ---- intermediate constraints (monotone_constraints.hpp:516
            # GoUpToFindLeavesToUpdate semantics, batch formulation):
            # 1. extend the ancestry matrices with split i,
            # 2. recompute EVERY leaf's [min, max] from the actual
            #    output extrema of the opposite subtrees of its monotone
            #    ancestors (tightest valid bounds; basic freezes the
            #    midpoint instead),
            # 3. re-search every leaf's best split under the new bounds
            #    (the reference recomputes the leaves_to_update set; one
            #    vmapped pass here keeps shapes static).
            anc_in_new = (
                s.anc_in.at[new].set(s.anc_in[l])
                .at[l, i].set(True).at[new, i].set(True)
            )
            anc_left_new = (
                s.anc_left.at[new].set(s.anc_left[l]).at[l, i].set(True)
            )
            t2 = tree_new
            leaf_out2 = t2.leaf_value
            valid_leaf = iota_L <= new
            node_m = mono[t2.node_feature] * (
                ~t2.node_cat
            ).astype(jnp.int32)  # cat splits never constrain
            node_alive = jnp.arange(L - 1) <= i
            in_l = anc_in_new & anc_left_new & valid_leaf[:, None]
            in_r = anc_in_new & ~anc_left_new & valid_leaf[:, None]
            Lmax = jnp.max(jnp.where(in_l, leaf_out2[:, None], -BIG), axis=0)
            Lmin = jnp.min(jnp.where(in_l, leaf_out2[:, None], BIG), axis=0)
            Rmax = jnp.max(jnp.where(in_r, leaf_out2[:, None], -BIG), axis=0)
            Rmin = jnp.min(jnp.where(in_r, leaf_out2[:, None], BIG), axis=0)
            inc = (node_alive & (node_m > 0))[None, :]
            dec = (node_alive & (node_m < 0))[None, :]
            cmax_mat = jnp.where(in_l & inc, Rmin[None, :], BIG)
            cmax_mat = jnp.where(in_r & dec, Lmin[None, :], cmax_mat)
            cmin_mat = jnp.where(in_r & inc, Lmax[None, :], -BIG)
            cmin_mat = jnp.where(in_l & dec, Rmax[None, :], cmin_mat)
            nmax = jnp.min(cmax_mat, axis=1)  # (L,)
            nmin = jnp.max(cmin_mat, axis=1)
            lmin, lmax = nmin[l], nmax[l]
            rmin, rmax = nmin[new], nmax[new]

            def leaf_best(h_, g_, hh_, c_, po_, mn_, mx_):
                return best_split(
                    exp_hist(h_, g_, hh_, c_), g_, hh_, c_, num_bins,
                    nan_bin, mono, is_cat, params, feat_mask,
                    dirs=spec.search, parent_output=po_,
                    cmin=mn_, cmax=mx_,
                )

            lg_all = s.leaf_g.at[l].set(rec.left_g).at[new].set(rec.right_g)
            lh_all = s.leaf_h.at[l].set(rec.left_h).at[new].set(rec.right_h)
            lc_all = s.leaf_c.at[l].set(rec.left_c).at[new].set(rec.right_c)
            rec_all = jax.vmap(leaf_best)(
                hist, lg_all, lh_all, lc_all, leaf_out2, nmin, nmax
            )
            d_ok = (spec.max_depth <= 0) | (t2.leaf_depth < spec.max_depth)
            best2 = rec_all._replace(
                gain=jnp.where(valid_leaf & d_ok, rec_all.gain, NEG_INF)
            )

        return _PState(
            i=new,
            pbins=pbins,
            pgh=pgh,
            pperm=pperm,
            seg_begin=seg_begin,
            seg_count=seg_count,
            hist=hist,
            leaf_g=s.leaf_g.at[l].set(rec.left_g).at[new].set(rec.right_g),
            leaf_h=s.leaf_h.at[l].set(rec.left_h).at[new].set(rec.right_h),
            leaf_c=s.leaf_c.at[l].set(rec.left_c).at[new].set(rec.right_c),
            leaf_parent=s.leaf_parent.at[l].set(i).at[new].set(i),
            leaf_min=(nmin if spec.mono_mode
                      else s.leaf_min.at[l].set(lmin).at[new].set(rmin)),
            leaf_max=(nmax if spec.mono_mode
                      else s.leaf_max.at[l].set(lmax).at[new].set(rmax)),
            best=best2,
            tree=tree_new,
            hist_valid=hist_valid,
            extra=extra_new,
            anc_in=anc_in_new,
            anc_left=anc_left_new,
        )

    final = lax.while_loop(cond, body, state)

    # ---- natural-order row -> leaf from the leaf segments ----
    # order leaves by segment begin (unused slots and locally-EMPTY
    # leaves — possible on a shard — get begin == N so they sort last
    # and never shadow a sibling sharing their begin); position p then
    # belongs to the last leaf with begin <= p
    eff_begin = jnp.where(final.seg_count > 0, final.seg_begin, N)
    order = jnp.argsort(eff_begin)
    sorted_begin = eff_begin[order]
    pos = jnp.arange(N, dtype=jnp.int32)
    leaf_of_pos = order[
        jnp.clip(jnp.searchsorted(sorted_begin, pos, side="right") - 1, 0, L - 1)
    ].astype(jnp.int32)
    row_leaf = jnp.zeros(N, jnp.int32).at[final.pperm].set(leaf_of_pos)
    if valid is not None:
        row_leaf = jnp.where(valid > 0, row_leaf, -1)
    return final.tree, row_leaf
