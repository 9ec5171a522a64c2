"""Leaf-wise tree growth under jit.

Reimplements the reference's leaf-wise learner loop
(src/treelearner/serial_tree_learner.cpp:182-239 Train, CUDA analog
cuda_single_gpu_tree_learner.cpp) as a `lax.while_loop` with static
shapes:

- the partition is a flat per-row leaf-id vector updated with masked
  `where` (reference CUDA data_index_to_leaf_index,
  cuda_data_partition.cu:113) — no index lists, no compaction;
- per-leaf histograms live in a fixed (num_leaves, 3, F, B) tensor
  (the reference's HistogramPool, feature_histogram.hpp:1367, without
  eviction — recompute-free subtraction needs the parent kept);
- each split computes the smaller child's histogram by masked scan and
  derives the larger by subtraction (serial_tree_learner.cpp:411
  ConstructHistograms smaller-leaf trick);
- leaf numbering matches Tree::Split (src/io/tree.cpp): the left child
  keeps the parent leaf's id, the right child gets id = current number
  of leaves; internal node i is created by split i; children pointers
  use ~leaf (= -(leaf+1)) encoding;
- with `axis_name` set, histograms and root sums are `lax.psum`'d over
  the data mesh axis — the ICI equivalent of the reference's histogram
  reduce-scatter (data_parallel_tree_learner.cpp:286); every shard then
  computes identical splits and partitions its local rows in lockstep.
"""

from __future__ import annotations

from functools import partial
from typing import Any, Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from .bundle import BundleInfo, decode_feature_bins, expand_hist
from .histogram import (
    build_gh8,
    gather_gh8,
    gather_rows,
    hist_capacities,
    histogram,
    root_sums,
)
from .split import (
    BIG, NEG_INF, SearchDirections, SplitParams, SplitRecord, best_split,
    leaf_output,
)


class GrowerSpec(NamedTuple):
    """Static (compile-time) growth configuration."""

    num_leaves: int
    num_bins: int  # uniform bin-axis size B
    max_depth: int  # <= 0 means unlimited
    axis_name: Optional[str] = None
    # static size of the data mesh axis (set by DataParallelGrower).
    # > 1 enables the reduce-scatter histogram wire on eligible paths
    # (rounds.py: integer dtype + per-rank feature ownership — the
    # reference's bin.h:63-81 + data_parallel_tree_learner.cpp:286).
    axis_size: int = 0
    # sorted-subset categorical splits (feature_histogram.hpp:449): set
    # when the dataset has categorical features wider than
    # max_cat_to_onehot; False keeps every categorical one-vs-rest and
    # skips the subset scan entirely (no cost for numerical data)
    cat_subset: bool = False
    # gathered smaller-child histograms: per-split cost tracks leaf size
    # instead of N (the reference's index-list construction,
    # data_partition.hpp); False = masked full scans (simpler, for debug)
    gather_hist: bool = True
    # "permuted": physically leaf-grouped rows, O(segment) per split
    # (permuted.py — the sequential reference-exact oracle); "flat":
    # per-row leaf-id vector, O(N) per split (tree_learner=feature)
    partition: str = "permuted"
    # EFB (dataset.cpp:111 FindGroups): the bin matrix columns are
    # BUNDLES; histograms expand back to per-feature layout before split
    # finding and the partition decodes original bins (bundle.py).
    # col_bins = uniform device bin-axis size of the bundle columns
    # (>= num_bins); 0 means same as num_bins.
    efb: bool = False
    col_bins: int = 0
    # feature parallel (tree_learner=feature, parallel_tree_learner.h:26):
    # the FLAT grower with the FEATURE axis sharded over this mesh axis —
    # every shard holds all rows (the reference's all-ranks-hold-all-data
    # design), finds the best split among its own features, and the
    # global best is an all-gather argmax (SyncUpGlobalBestSplit); the
    # winning shard broadcasts the per-row split decision with one psum.
    feature_axis: Optional[str] = None
    # voting parallel (tree_learner=voting, parallel_tree_learner.h:126):
    # each shard proposes its top-k features by LOCAL gain, a global
    # vote elects ~2k, and only elected feature columns are psum'd
    # across the mesh — the reference's bandwidth cap, applied to the
    # DCN-scale case (within one ICI slice a full psum is cheap and
    # tree_learner=data is the better choice). 0 = off.
    voting_k: int = 0
    # per-node extras (permuted sequential path only):
    # extra_trees: one random numerical threshold per feature per node
    extra_trees: bool = False
    # feature_fraction_bynode < 1: per-node feature subsample (ColSampler)
    ff_bynode: bool = False
    # CEGB penalties active (cost_effective_gradient_boosting.hpp)
    cegb: bool = False
    # number of interaction-constraint groups (0 = unconstrained)
    n_groups: int = 0
    # static length of the forced-split plan (forcedsplits_filename)
    n_forced: int = 0
    # natural-order round-batched growth (rounds.py, tpu_growth_mode):
    # > 0 = split the top-`rounds_slots` positive-gain leaves per device
    # step, smaller-child histograms from ONE slot-packed MXU pass keyed
    # by the row->leaf vector — no physical row movement at all. The TPU
    # fast path; 0 = off (sequential permuted growth).
    rounds_slots: int = 0
    # quantized-gradient channels in rounds mode (use_quantized_grad):
    # grad/hess arrive as INTEGER levels, histograms accumulate exact
    # int sums in 3 bf16 channels per slot (48 slots/pass vs 25), and
    # the split scan runs on scale-multiplied sums — the TPU analog of
    # the reference's int16/int32 histogram path (bin.h:63-81,
    # feature_histogram.hpp:1062 int threshold scan).
    quant: bool = False
    # quant levels fit int8 (num_grad_quant_bins <= 127): the slot-packed
    # kernel runs s8 x s8 -> s32 on the MXU — twice the bf16 rate on v5e
    # and bit-exact integer sums (bin.h:63-81 int histogram analog)
    quant_int8: bool = False
    # num_grad_quant_bins when quant: bounds the per-cell integer sums
    # for the SWAR one-hot scale policy (histogram.int8_oh_shift)
    quant_levels: int = 0
    # monotone constraint method (monotone_constraints_method):
    # 0 = basic (children bounded at the split midpoint, inherited);
    # 1 = intermediate (monotone_constraints.hpp:516): per-leaf bounds
    # recomputed every split from the OPPOSITE subtrees' actual output
    # extrema via an ancestry matrix, and every leaf's cached best
    # split re-searched under the new bounds — less conservative than
    # basic, still violation-free by induction;
    # 2 = advanced (monotone_constraints.hpp:858, rounds grower only):
    # the opposite-subtree extrema are further refined per constrained
    # leaf — only leaves whose per-feature bin ranges overlap the
    # constrained leaf's in every feature but the ancestor's split
    # feature can bound it (pairwise range-intersection tables kept in
    # the round state; strictly no looser than intermediate).
    # Intermediate runs on both the sequential permuted grower
    # (per-split recompute) and the rounds grower (per-round recompute
    # + same-round conflict guard, rounds.py).
    mono_mode: int = 0
    # dataset has at least one categorical feature: rounds-mode partition
    # updates need the per-row category-set test only then; all-numerical
    # datasets (the common benchmark shape) skip that machinery
    # statically — the (L*B,) mask gather it replaces is an element
    # gather per row, ~10 ms/round at 1M rows on the chip
    has_cat: bool = True
    # some used column has a NaN bin / carries a monotone constraint:
    # like has_cat, facts of the Dataset that decide which directions
    # the split search traces (split.SearchDirections)
    has_nan: bool = True
    has_mono: bool = True

    @property
    def search(self) -> SearchDirections:
        return SearchDirections(
            default_left=self.has_nan, categorical=self.has_cat,
            cat_subset=self.cat_subset, monotone_test=self.has_mono)


class CegbInfo(NamedTuple):
    """Traced CEGB penalty tables (DeltaGain inputs)."""

    coupled: jax.Array  # (F,) — one-time per-feature cost (model-wide)
    lazy: jax.Array  # (F,) — per-data cost, charged along each path
    used: jax.Array  # (F,) bool — features already used by earlier trees


class TreeArrays(NamedTuple):
    """Fixed-size tree (reference include/LightGBM/tree.h array layout).

    Node arrays have length num_leaves-1, leaf arrays num_leaves. Child
    pointers: >=0 internal node index, <0 leaf encoded as ~leaf_index.
    """

    num_nodes: jax.Array  # scalar int32 — actual splits performed
    node_feature: jax.Array
    node_bin: jax.Array
    node_gain: jax.Array
    node_default_left: jax.Array
    node_cat: jax.Array
    node_cat_mask: jax.Array  # (L-1, B) bool — cat bins going left
    node_left: jax.Array
    node_right: jax.Array
    node_value: jax.Array  # internal_value: output of the pre-split leaf
    node_weight: jax.Array  # internal_weight: hessian sum
    node_count: jax.Array  # internal_count
    leaf_value: jax.Array
    leaf_weight: jax.Array
    leaf_count: jax.Array
    leaf_depth: jax.Array


class _State(NamedTuple):
    i: jax.Array
    row_leaf: jax.Array
    hist: jax.Array  # (L, 3, F, B) — channel-leading, bins on lanes
    leaf_g: jax.Array
    leaf_h: jax.Array
    leaf_c: jax.Array
    leaf_parent: jax.Array
    leaf_min: jax.Array  # (L,) monotone-constraint interval per leaf
    leaf_max: jax.Array
    best: SplitRecord  # per-leaf arrays (L,)
    tree: TreeArrays


def make_split_params(cfg) -> SplitParams:
    """Build traced split params from a Config (host side)."""
    f = lambda v: jnp.float32(v)
    return SplitParams(
        lambda_l1=f(cfg.lambda_l1),
        lambda_l2=f(cfg.lambda_l2),
        min_data_in_leaf=f(cfg.min_data_in_leaf),
        min_sum_hessian_in_leaf=f(cfg.min_sum_hessian_in_leaf),
        min_gain_to_split=f(cfg.min_gain_to_split),
        max_delta_step=f(cfg.max_delta_step),
        path_smooth=f(cfg.path_smooth),
        cat_smooth=f(cfg.cat_smooth),
        cat_l2=f(cfg.cat_l2),
        max_cat_threshold=jnp.int32(cfg.max_cat_threshold),
        max_cat_to_onehot=jnp.int32(cfg.max_cat_to_onehot),
        min_data_per_group=f(cfg.min_data_per_group),
        cegb_tradeoff=f(cfg.cegb_tradeoff),
        cegb_penalty_split=f(cfg.cegb_penalty_split),
        feature_fraction_bynode=f(cfg.feature_fraction_bynode),
    )


def split_leaf_outputs(rec: SplitRecord, params: SplitParams, num_bins,
                       use_cat_subset: bool, parent_output, cmin, cmax):
    """Left/right child outputs for a chosen split: path smoothing toward
    the parent output, clamped to the PARENT's monotone interval
    (BasicLeafConstraints clone-then-update). Sorted-subset categorical
    splits regularize with l2 + cat_l2 (feature_histogram.cpp:251,346)."""
    if use_cat_subset:
        is_sub = rec.is_cat & (num_bins[rec.feature] > params.max_cat_to_onehot)
        p = params._replace(
            lambda_l2=params.lambda_l2 + jnp.where(is_sub, params.cat_l2, 0.0)
        )
    else:
        p = params
    lo = leaf_output(rec.left_g, rec.left_h, p, rec.left_c, parent_output,
                     cmin, cmax)
    ro = leaf_output(rec.right_g, rec.right_h, p, rec.right_c, parent_output,
                     cmin, cmax)
    return lo, ro


def monotone_child_intervals(rec: SplitRecord, mono, lo, ro, cur_min, cur_max):
    """BasicLeafConstraints::Update (monotone_constraints.hpp:489): a
    NUMERICAL split on a monotone feature tightens the children's output
    intervals around mid = (lo + ro) / 2; both children inherit the
    parent interval otherwise."""
    m = mono[rec.feature]
    upd = (~rec.is_cat) & (m != 0)
    mid = (lo + ro) / 2.0
    lmin = jnp.where(upd & (m < 0), jnp.maximum(cur_min, mid), cur_min)
    lmax = jnp.where(upd & (m > 0), jnp.minimum(cur_max, mid), cur_max)
    rmin = jnp.where(upd & (m > 0), jnp.maximum(cur_min, mid), cur_min)
    rmax = jnp.where(upd & (m < 0), jnp.minimum(cur_max, mid), cur_max)
    return lmin, lmax, rmin, rmax


def make_node_candidates(spec: GrowerSpec, params: SplitParams, feat_mask,
                         num_bins, nan_bin, rng_key, group_mat, cegb,
                         F: int):
    """Per-node split-candidate machinery shared by the permuted and
    rounds growers: interaction-group filtering (ColSampler,
    col_sampler.hpp), feature_fraction_bynode sampling, extra_trees
    random thresholds, and the CEGB DeltaGain penalty
    (cost_effective_gradient_boosting.hpp:79 — with the per-tree-path
    lazy approximation, see DESIGN_DECISIONS.md). Returns
    node_candidates(salt, child_groups, path_used_child, child_count,
    feat_used) -> (feat_mask, rand_bin, penalty), keyed on the node
    index so draws are deterministic per tree position."""

    def node_candidates(salt, child_groups, path_used_child, child_count,
                        feat_used):
        fm = feat_mask
        rb = None
        pen = None
        if spec.n_groups:
            fm = fm & jnp.any(group_mat & child_groups[:, None], axis=0)
        if spec.ff_bynode:
            # sample ceil(frac * currently-valid) from the VALID set
            # (ColSampler samples from used_feature_indices_, so a node
            # always keeps >= 1 candidate)
            k1 = jax.random.fold_in(rng_key, 2 * salt)
            u = jnp.where(fm, jax.random.uniform(k1, (F,)), jnp.inf)
            n_valid = jnp.sum(fm)
            n_pick = jnp.maximum(
                jnp.ceil(
                    params.feature_fraction_bynode * n_valid
                ).astype(jnp.int32),
                1,
            )
            rank = jnp.argsort(jnp.argsort(u))
            fm = fm & (rank < n_pick)
        if spec.extra_trees:
            k2 = jax.random.fold_in(rng_key, 2 * salt + 1)
            u = jax.random.uniform(k2, (F,))
            n_thr = jnp.maximum(num_bins - 1 - (nan_bin >= 0), 1)
            rb = jnp.floor(u * n_thr).astype(jnp.int32)
        if spec.cegb:
            pen = params.cegb_tradeoff * (
                params.cegb_penalty_split * child_count
                + cegb.coupled * (~feat_used).astype(jnp.float32)
                + cegb.lazy * child_count
                * (~path_used_child).astype(jnp.float32)
            )
        return fm, rb, pen

    return node_candidates


def _empty_best(L: int, B: int) -> SplitRecord:
    zi = jnp.zeros(L, jnp.int32)
    zf = jnp.zeros(L, jnp.float32)
    zb = jnp.zeros(L, bool)
    return SplitRecord(
        gain=jnp.full(L, NEG_INF),
        feature=zi, bin=zi, default_left=zb, is_cat=zb,
        cat_mask=jnp.zeros((L, B), bool),
        left_g=zf, left_h=zf, left_c=zf,
        right_g=zf, right_h=zf, right_c=zf,
    )


def _set_best(best: SplitRecord, l: jax.Array, rec: SplitRecord, gain: jax.Array) -> SplitRecord:
    return SplitRecord(
        gain=best.gain.at[l].set(gain),
        feature=best.feature.at[l].set(rec.feature),
        bin=best.bin.at[l].set(rec.bin),
        default_left=best.default_left.at[l].set(rec.default_left),
        is_cat=best.is_cat.at[l].set(rec.is_cat),
        cat_mask=best.cat_mask.at[l].set(rec.cat_mask),
        left_g=best.left_g.at[l].set(rec.left_g),
        left_h=best.left_h.at[l].set(rec.left_h),
        left_c=best.left_c.at[l].set(rec.left_c),
        right_g=best.right_g.at[l].set(rec.right_g),
        right_h=best.right_h.at[l].set(rec.right_h),
        right_c=best.right_c.at[l].set(rec.right_c),
    )


def _get_best(best: SplitRecord, l: jax.Array) -> SplitRecord:
    return jax.tree.map(lambda a: a[l], best)


def grow_tree(
    bins_fm: jax.Array,  # (F, N) int32 — feature-major bin matrix
    nan_bin: jax.Array,  # (F,)
    num_bins: jax.Array,  # (F,)
    mono: jax.Array,  # (F,)
    is_cat: jax.Array,  # (F,)
    grad: jax.Array,  # (N,) f32
    hess: jax.Array,  # (N,) f32
    mask: jax.Array,  # (N,) f32 — validity * bagging mask
    feat_mask: jax.Array,  # (F,) bool — per-tree feature_fraction sample
    params: SplitParams,
    spec: GrowerSpec,
    valid: Optional[jax.Array] = None,  # (N,) f32 — 1 for real rows; None = all
    bundle: Optional[BundleInfo] = None,
    rng_key: Optional[jax.Array] = None,  # extra_trees / ff_bynode sampling
    group_mat: Optional[jax.Array] = None,  # (NG, F) bool — interaction groups
    cegb: Optional[CegbInfo] = None,
    forced: Optional[Any] = None,  # ForcedSplits plan
    gh_scale: Optional[jax.Array] = None,  # (2,) quantized-level scales
    with_stats: bool = False,  # also return the grower's stats, or None
):
    """Grow one tree; returns (tree arrays, per-row leaf assignment),
    and with_stats=True a third item: the rounds grower's
    {"widths", "rounds"} ladder counts, None from the other growers.

    Dispatches on spec.rounds_slots / spec.partition: "rounds"
    (natural-order round-batched, rounds.py — the TPU fast path),
    "permuted" (leaf-grouped rows, one split per step: the
    reference-exact parity oracle, tpu_growth_mode=exact) or "flat"
    (per-row leaf ids, O(N) per split: tree_learner=feature)."""
    if spec.rounds_slots > 0:
        from .rounds import grow_tree_rounds

        return grow_tree_rounds(
            bins_fm, nan_bin, num_bins, mono, is_cat, grad, hess, mask,
            feat_mask, params, spec, valid, bundle, gh_scale,
            rng_key=rng_key, group_mat=group_mat, cegb=cegb,
            forced=forced, with_stats=with_stats,
        )
    if spec.partition == "permuted":
        from .permuted import grow_tree_permuted

        out = grow_tree_permuted(
            bins_fm, nan_bin, num_bins, mono, is_cat, grad, hess, mask,
            feat_mask, params, spec, valid, bundle, rng_key, group_mat, cegb,
            forced
        )
        return (*out, None) if with_stats else out
    if (spec.extra_trees or spec.ff_bynode or spec.cegb or spec.n_groups
            or spec.n_forced):
        raise ValueError(
            "extra_trees / feature_fraction_bynode / cegb / interaction "
            "constraints ride the permuted grower only"
        )
    out = _grow_tree_flat(
        bins_fm, nan_bin, num_bins, mono, is_cat, grad, hess, mask,
        feat_mask, params, spec, valid, bundle
    )
    return (*out, None) if with_stats else out


@partial(jax.jit, static_argnames=("spec",))
def _grow_tree_flat(
    bins_fm: jax.Array,
    nan_bin: jax.Array,
    num_bins: jax.Array,
    mono: jax.Array,
    is_cat: jax.Array,
    grad: jax.Array,
    hess: jax.Array,
    mask: jax.Array,
    feat_mask: jax.Array,
    params: SplitParams,
    spec: GrowerSpec,
    valid: Optional[jax.Array] = None,
    bundle: Optional[BundleInfo] = None,
) -> Tuple[TreeArrays, jax.Array]:
    """Flat row->leaf-id formulation (cuda_data_partition.cu style).

    Padding rows (valid == 0) carry leaf id -1 so they never join a leaf
    or occupy gather capacity; out-of-bag rows (mask 0 but valid 1) are
    partitioned normally for score updates but contribute zero to
    histograms via their zeroed gh channels.
    """
    L = spec.num_leaves
    B = spec.num_bins
    G, N = bins_fm.shape  # G = device columns (bundles when spec.efb)
    ax = spec.axis_name
    caps = hist_capacities(N)
    Bc = spec.col_bins if (spec.efb and spec.col_bins) else B

    fax = spec.feature_axis
    if fax is not None:
        if spec.efb or ax is not None:
            raise ValueError("feature_axis excludes EFB and a data axis")
        my_off = lax.axis_index(fax) * G
        # replicated global tables for winner-record lookups (tiny)
        num_bins_g = lax.all_gather(num_bins, fax).reshape(-1)
        mono_g = lax.all_gather(mono, fax).reshape(-1)
    else:
        my_off = 0
        num_bins_g, mono_g = num_bins, mono

    def select_global(rec: SplitRecord) -> SplitRecord:
        """All-gather each shard's best and keep the max-gain one
        (reference SyncUpGlobalBestSplit allreduce-max,
        parallel_tree_learner.h:209; ties resolve to the lowest shard =
        lowest global feature block)."""
        if fax is None:
            return rec
        rec = rec._replace(feature=rec.feature + my_off)
        stacked = jax.tree.map(lambda a: lax.all_gather(a, fax), rec)
        w = jnp.argmax(stacked.gain)
        return jax.tree.map(lambda a: a[w], stacked)

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
    rec0 = select_global(
        best_split(exp_hist(hist0, root[0], root[1], root[2]),
                   root[0], root[1], root[2], num_bins, nan_bin,
                   mono, is_cat, params, feat_mask,
                   dirs=spec.search, parent_output=root_out))

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

    row_leaf0 = (
        jnp.zeros(N, jnp.int32)
        if valid is None
        else jnp.where(valid > 0, 0, -1).astype(jnp.int32)
    )
    state = _State(
        i=jnp.int32(0),
        row_leaf=row_leaf0,
        hist=hist,
        leaf_g=jnp.zeros(L, jnp.float32).at[0].set(root[0]),
        leaf_h=jnp.zeros(L, jnp.float32).at[0].set(root[1]),
        leaf_c=jnp.zeros(L, jnp.float32).at[0].set(root[2]),
        leaf_parent=jnp.full(L, -1, jnp.int32),
        leaf_min=jnp.full(L, -BIG, jnp.float32),
        leaf_max=jnp.full(L, BIG, jnp.float32),
        best=best,
        tree=tree,
    )

    def cond(s: _State) -> jax.Array:
        return (s.i < L - 1) & (jnp.max(s.best.gain) > 0.0)

    def body(s: _State) -> _State:
        i = s.i
        t = s.tree
        l = jnp.argmax(s.best.gain).astype(jnp.int32)
        rec = _get_best(s.best, l)
        new = i + 1  # id of the new (right) leaf

        # ---- tree bookkeeping (Tree::Split semantics) ----
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
        lo, ro = split_leaf_outputs(rec, params, num_bins_g, spec.cat_subset,
                                    t.leaf_value[l], pmin, pmax)
        lmin, lmax, rmin, rmax = monotone_child_intervals(
            rec, mono_g, lo, ro, pmin, pmax
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

        # ---- partition: update per-row leaf ids (cuda_data_partition.cu) ----
        f = rec.feature  # GLOBAL feature id under feature_axis
        if fax is not None:
            f_loc = jnp.clip(f - my_off, 0, G - 1)
            fbins = lax.dynamic_slice_in_dim(bins_fm, f_loc, 1, axis=0).reshape(N)
            fnan = nan_bin[f_loc]
            gl = jnp.where(
                rec.is_cat,
                rec.cat_mask[fbins],
                (fbins <= rec.bin)
                | (rec.default_left & (fbins == fnan) & (fnan >= 0)),
            )
            mine = (f >= my_off) & (f < my_off + G)
            # only the owning shard's decision counts; broadcast it
            go_left = lax.psum(
                jnp.where(mine, gl, False).astype(jnp.int32), fax
            ) > 0
        else:
            col = bundle.bundle_of[f] if spec.efb else f
            fbins = lax.dynamic_slice_in_dim(bins_fm, col, 1, axis=0).reshape(N)
            if spec.efb:
                fbins = decode_feature_bins(fbins, f, bundle)
            fnan = nan_bin[f]
            go_left = jnp.where(
                rec.is_cat,
                rec.cat_mask[fbins],
                (fbins <= rec.bin)
                | (rec.default_left & (fbins == fnan) & (fnan >= 0)),
            )
        on_leaf = s.row_leaf == l
        row_leaf = jnp.where(on_leaf & ~go_left, new, s.row_leaf)

        # ---- child histograms: smaller by gather/scan, larger by subtraction
        parent_hist = s.hist[l]
        # choose the smaller child by ACTUAL partition counts (incl.
        # out-of-bag rows, which occupy gather capacity). The choice must
        # be GLOBAL when distributed — every shard must scan the same
        # child or the psum mixes left/right histograms.
        n_on_leaf = jnp.sum(on_leaf)
        n_left = jnp.sum(on_leaf & go_left)
        n_right = n_on_leaf - n_left
        if ax is not None:
            left_smaller = lax.psum(n_left, ax) <= lax.psum(n_right, ax)
        else:
            left_smaller = n_left <= n_right
        small_id = jnp.where(left_smaller, l, new)
        if spec.gather_hist:
            on_small = row_leaf == small_id
            # local row count of the globally-chosen child (may exceed N/2
            # on a skewed shard -> full-size fallback bucket)
            cnt_small = jnp.where(left_smaller, n_left, n_right)

            def mk_branch(cap: int):
                def branch(_):
                    idx = jnp.nonzero(on_small, size=cap, fill_value=N)[0]
                    bb = gather_rows(bins_fm, idx)  # (G, cap)
                    gg = gather_gh8(gh8, idx)  # (8, cap)
                    return histogram(bb, gg, Bc)

                return branch

            # smallest capacity >= cnt_small (caps are descending)
            caps_arr = jnp.asarray(caps, jnp.int32)
            bidx = jnp.clip(
                jnp.sum(caps_arr >= cnt_small) - 1, 0, len(caps) - 1
            )
            branches = [mk_branch(c) for c in caps]
            if ax is not None:
                # skewed shard: the globally-smaller child can exceed N/2
                # locally -> full-size fallback
                branches.append(mk_branch(N))
                bidx = jnp.where(cnt_small > caps[0], len(caps), bidx)
            small_hist = lax.switch(bidx, branches, None)
        else:
            on_small_f = (row_leaf == small_id).astype(gh8.dtype)
            small_hist = histogram(bins_fm, gh8 * on_small_f[None, :], Bc)
        if ax is not None:
            small_hist = lax.psum(small_hist, ax)
        large_hist = parent_hist - small_hist
        left_hist = jnp.where(left_smaller, small_hist, large_hist)
        right_hist = jnp.where(left_smaller, large_hist, small_hist)
        hist = s.hist.at[l].set(left_hist).at[new].set(right_hist)

        # ---- best splits for both children ----
        bl = select_global(best_split(
            exp_hist(left_hist, rec.left_g, rec.left_h, rec.left_c),
            rec.left_g, rec.left_h, rec.left_c,
            num_bins, nan_bin, mono, is_cat, params, feat_mask,
            dirs=spec.search, parent_output=lo,
            cmin=lmin, cmax=lmax))
        br = select_global(best_split(
            exp_hist(right_hist, rec.right_g, rec.right_h, rec.right_c),
            rec.right_g, rec.right_h, rec.right_c,
            num_bins, nan_bin, mono, is_cat, params, feat_mask,
            dirs=spec.search, parent_output=ro,
            cmin=rmin, cmax=rmax))
        depth_ok = (spec.max_depth <= 0) | (depth_new < spec.max_depth)
        best2 = _set_best(s.best, l, bl, jnp.where(depth_ok, bl.gain, NEG_INF))
        best2 = _set_best(best2, new, br, jnp.where(depth_ok, br.gain, NEG_INF))

        return _State(
            i=new,
            row_leaf=row_leaf,
            hist=hist,
            leaf_g=s.leaf_g.at[l].set(rec.left_g).at[new].set(rec.right_g),
            leaf_h=s.leaf_h.at[l].set(rec.left_h).at[new].set(rec.right_h),
            leaf_c=s.leaf_c.at[l].set(rec.left_c).at[new].set(rec.right_c),
            leaf_parent=s.leaf_parent.at[l].set(i).at[new].set(i),
            leaf_min=s.leaf_min.at[l].set(lmin).at[new].set(rmin),
            leaf_max=s.leaf_max.at[l].set(lmax).at[new].set(rmax),
            best=best2,
            tree=tree_new,
        )

    final = lax.while_loop(cond, body, state)
    return final.tree, final.row_leaf


@jax.jit
def add_score(score: jax.Array, row_leaf: jax.Array, leaf_value: jax.Array,
              shrinkage: jax.Array) -> jax.Array:
    """ScoreUpdater::AddScore via the partition vector
    (reference score_updater.hpp:21 + data-partition fast path).

    The (N,) lookup from the (L,) leaf table rides the one-hot MXU
    contraction (take_cols): a plain take costs ~8 ms per 1M rows on
    TPU. Invalid rows (row_leaf == -1) contribute 0 on that path."""
    from .histogram import take_cols

    return score + shrinkage * take_cols(leaf_value[None, :], row_leaf)[0]
