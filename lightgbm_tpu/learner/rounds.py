"""Natural-order round-batched leaf-wise growth — the TPU fast path.

The permuted grower (permuted.py) keeps rows physically leaf-grouped so
each split costs O(segment) — but maintaining that layout costs one
full-array gather per split or round (TPUs have no vector-gather
hardware). This grower never moves a row:

- the partition is a per-row leaf-id vector updated with elementwise
  `where` (the reference CUDA data_index_to_leaf_index,
  src/treelearner/cuda/cuda_data_partition.cu:113);
- per round, the top-k positive-gain leaves split AT ONCE
  (k = min(round_slots, remaining leaf budget)); the smaller child of
  every split gets its histogram from ONE slot-packed MXU pass
  (histogram.hist_nat_slots — the multi-leaf batching of the reference
  CUDA kernel, cuda_histogram_constructor.cu:20), the larger sibling
  by parent subtraction (serial_tree_learner.cpp:411); the round that
  spends the last of the leaf budget builds none (its children can
  never split) and only routes its rows (spends_budget below);
- per-tree device work is ~#rounds histogram passes plus O(N)
  elementwise updates — no gathers, no sorts, no prefix sums.

Semantics vs the reference's sequential best-first growth: splitting
the top-k leaves of a round in parallel yields the SAME final tree as
sequential greedy whenever the leaf budget does not bind (a leaf's best
split is independent of every other leaf), and the same set of splits
ordered differently otherwise — except near the budget boundary, where
children created by this round's splits never compete against this
round's remaining candidates. `tpu_growth_mode=exact` keeps the
reference-exact sequential grower; this mode is the default on TPU
hardware where the round batching is worth ~an order of magnitude
(config.h has no analog — the reference CUDA learner batches histogram
construction but still splits one leaf at a time).

This grower is the single production path (ISSUE 14): voting-parallel
(PV-Tree election, only elected bundle columns cross the mesh — one
election per ROUND covering all slots jointly), forced splits (one
prescribed split per round during the forced phase so Tree::Split leaf
numbering matches the BFS plan), and all three monotone methods (basic
/ intermediate / advanced) ride it; the permuted sequential grower
remains only as the reference-exact parity oracle behind
`tpu_growth_mode=exact`.
"""

from __future__ import annotations

from functools import partial
from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from .bundle import BundleInfo, best_split_bundled, decode_feature_bins
from ..timer import device_phase, global_timer
from .histogram import (
    HistPlan,
    _pallas_ok,
    _slot_chunks,
    build_gh8,
    build_gh8_quant,
    can_hist_round,
    hist_nat_slots,
    hist_plan,
    hist_round,
    histogram,
    int8_oh_shift,
    root_sums,
    route_round,
    rs_wire_dtype,
)
from .grower import (
    GrowerSpec,
    TreeArrays,
    _empty_best,
    _set_best,
    make_node_candidates,
    monotone_child_intervals,
    split_leaf_outputs,
)
from .split import (
    NEG_INF,
    BIG,
    SplitParams,
    SplitRecord,
    best_split,
    feature_best_gains,
    leaf_gain,
    leaf_output,
)


# Kernel widths below a program's slot count. A pass costs
# max(VPU side, slots x per-slot MXU time); on a v5e the two cross
# near 21 slots bf16 / 27 int8 (PERF.md section 5), so 16 is the last
# rung the floor pays for. Each rung is one more copy of round_step to
# trace, lower and compile.
LADDER_RUNGS = (8, 16, 32)
# device rows up to which a round takes at most half the remaining
# leaf budget (the budget-aware tail in grow_tree_rounds)
TAIL_EXACT_ROWS = 32 * 8192


def ladder_widths(spec: GrowerSpec) -> Tuple[int, ...]:
    """The round kernel's widths for this program, ascending; the last
    is the slot count itself."""
    slots = min(spec.rounds_slots, max(spec.num_leaves - 1, 1))  # top_k: k <= L
    return tuple(w for w in LADDER_RUNGS if w < slots) + (slots,)


def hist_wire(spec: GrowerSpec, n_local_rows: int) -> str:
    """The wire a data-mesh program's child histograms cross the mesh
    on, decided from static facts alone (the spec and a shard's rows):
    "none" off a mesh; "vote_*" under voting (elected columns only, in
    the narrowest exact integer dtype, else f32); "rs_int16" /
    "rs_int32" (integer reduce-scatter with per-rank feature ownership)
    while the worst-case integer sums stay exact; else "psum_f32", the
    whole (channels, F, B) f32 histogram of every smaller child."""
    n = spec.axis_size
    if spec.axis_name is None or n <= 1:
        return "none"
    dt = rs_wire_dtype(n_local_rows, n, spec.quant_levels)
    if spec.voting_k:
        return f"vote_{dt if spec.quant and dt else 'f32'}"
    per_node = bool(spec.extra_trees or spec.ff_bynode or spec.cegb
                    or spec.n_groups)
    # voting ships a NARROWER payload than reduce-scatter (2k elected
    # columns vs G/n owned); forced splits read arbitrary feature
    # columns of arbitrary leaves and need full-width per-leaf
    # histogram pools, not owned blocks
    # dt: a dtype only while the integer sums fit (histogram.rs_exact_ok)
    if (spec.quant and not spec.efb and not spec.has_cat
            and not spec.cat_subset and not spec.mono_mode and not per_node
            and not spec.n_forced and dt is not None):
        return f"rs_{dt}"
    return "psum_f32"


# the label of routing-only rounds among the per-width round counts
ROUTE_LABEL = "route"
# the label of a tree's first pass among the per-width call counts
ROOT_LABEL = "root"


class HistSchedule(NamedTuple):
    """How a program's histogram passes run, decided once from its
    shapes (hist_schedule)."""

    plan: HistPlan  # at the program's full slot count
    num_bins: int  # of a device column: what the kernels' one-hots span
    use_int8: bool
    oh_shift: int  # SWAR one-hot scale of the int8 kernels
    # one of three rounds: `fused` (hist_round_tpu holds the whole
    # table's tile: partition and histograms in one kernel), `routed`
    # (at width: route_round_tpu over the round's split columns, then
    # hist_nat_tpu by feature blocks), else the XLA partition followed
    # by hist_nat_slots (no Pallas backend, odd row counts)
    fused: bool
    routed: bool
    # ((width label, kernel calls that stream the rows in one pass at
    # that width), ...): ROOT_LABEL, then ladder_widths; empty where no
    # kernel runs
    calls: Tuple[Tuple[str, int], ...]


def hist_schedule(spec: GrowerSpec, n_rows: int, n_cols: int
                  ) -> HistSchedule:
    """Feature blocks and slot chunks of every histogram pass of the
    rounds grower's program over a (n_cols, n_rows) device bin matrix:
    static, from the spec and the shapes (histogram.hist_plan holds the
    VMEM arithmetic). One feature block size serves all of a tree's
    passes, sized at the full slot count."""
    with global_timer.scope("learner.hist_plan"):
        widths = ladder_widths(spec)
        S = widths[-1]
        Bc = spec.col_bins if (spec.efb and spec.col_bins) else spec.num_bins
        # SWAR one-hot scale for the int8 kernels; int8 itself is gated
        # on the policy finding ANY safe shift
        oh_shift = (int8_oh_shift(n_rows, spec.quant_levels)
                    if spec.quant_int8 else 0)
        use_int8 = bool(spec.quant_int8 and oh_shift is not None)
        plan = hist_plan(S, n_cols, Bc, spec.quant, use_int8)
        if plan.blocks > 1:
            fused = False
            routed = _pallas_ok("hist_nat_tpu", n_rows, plan.s_max > 0,
                                f"one slot of a 32-column group at {Bc} "
                                "bins exceeds the VMEM budget")
        else:
            fused = can_hist_round(n_rows, S, n_cols, Bc, spec.quant,
                                   int8=use_int8)
            routed = False
        calls: Tuple[Tuple[str, int], ...] = ()
        if fused or routed or _pallas_ok("hist_nat_tpu", n_rows):
            def n_calls(w: int) -> int:
                # off both kernel rounds hist_nat_slots plans per call
                p = plan if fused or routed else hist_plan(
                    w, n_cols, Bc, spec.quant, use_int8)
                return len(_slot_chunks(w, max(p.s_max, 1)))

            calls = ((ROOT_LABEL, 1),) + tuple(
                (str(w), n_calls(w)) for w in widths)
        return HistSchedule(plan, Bc, use_int8, oh_shift or 0, fused,
                            routed, calls)


def take_rows(table: jax.Array, idx: jax.Array) -> jax.Array:
    """table[idx] for a handful of rows of a large table, as one row
    slice per index and not a gather: XLA lowers the gather to work
    over the WHOLE table (element work over the bin matrix, 7 ms a
    round at 2,000 x 400k, my chip run, PR 30; slices of the whole
    histogram pool through VMEM, PERF.md section 6, PR 33)."""
    return jnp.concatenate([
        lax.dynamic_slice_in_dim(table, idx[k], 1, axis=0)
        for k in range(idx.shape[0])])


def spends_budget(n_cand: jax.Array, budget: jax.Array, slots: int
                  ) -> jax.Array:
    """Will a round of `n_cand` candidates end the tree by spending all
    of its remaining leaf `budget`? Known before the round's data pass:
    the round splits min(n_cand, slots) leaves (every candidate has a
    positive gain or is a valid forced split, and off the monotone
    conflict guard none is deferred), so it spends the budget exactly
    when both reach it. Its children can never split, and the round
    needs neither their histograms nor their best splits."""
    return (n_cand >= budget) & (budget <= slots)


class _NState(NamedTuple):
    i: jax.Array  # splits performed so far
    r: jax.Array  # (W+2,) int32 — rounds executed: r[w] = histogram
    # rounds run at widths[w]; r[W] = routing-only rounds (the round
    # that spends the last of the leaf budget); r[-1] = total. Scalar
    # counters, free at runtime; surfaced by grow_tree_rounds(...,
    # with_stats=True), which the fused step reads for
    # lgbmtpu_grower_rounds_total{width}.
    pleaf: jax.Array  # (N,) int32 row -> leaf; invalid rows carry L
    leaf_g: jax.Array
    leaf_h: jax.Array
    leaf_c: jax.Array
    leaf_parent: jax.Array
    leaf_min: jax.Array  # monotone interval per leaf
    leaf_max: jax.Array
    # ancestry matrices for mono_mode=1 (intermediate constraints),
    # zero-width when mono_mode == 0: anc_in[leaf, node] = node is an
    # ancestor; anc_left[leaf, node] = leaf hangs on its LEFT side
    anc_in: jax.Array  # (L, L-1 | 0) bool
    anc_left: jax.Array  # (L, L-1 | 0) bool
    # per-node feature bookkeeping (interaction constraints + CEGB),
    # zero-width when no per-node extras are active
    leaf_groups: jax.Array  # (L, NG | 0) bool — legal constraint groups
    path_used: jax.Array  # (L, F | 0) bool — features on the leaf's path
    feat_used: jax.Array  # (F | 0,) bool — used anywhere (CEGB coupled)
    # advanced monotone constraints: per-leaf per-feature bin range
    # (lo, hi], refined at each numeric split (left keeps hi=min(hi,
    # bin); right lo=max(lo, bin)). Two leaves can form a violating
    # monotone pair through ancestor a only if their ranges intersect
    # in every feature EXCEPT a's split feature. Zero-width unless
    # mono_mode == 2.
    leaf_flo: jax.Array  # (L, F | 0) int32
    leaf_fhi: jax.Array  # (L, F | 0) int32
    best: SplitRecord  # per-leaf best splits, fields (L,)
    tree: TreeArrays


class _Pools(NamedTuple):
    """The per-leaf tables a round reads whole and writes <= 2S rows
    of. They ride the while loop BESIDE _NState: round_step's branches
    take them as read-only operands and return only their round's rows
    (_Children), which body() scatters into the carry once, in place.
    Returned whole from a lax.switch / lax.cond branch, the compiler
    transposed and copied the pool around every round (seven pool-sized
    copies a round; PERF.md section 6, PR 33)."""

    # the histogram pool, a leaf's (3, G, Bc) histogram as three FLAT
    # rows (the kernels' own output form): one leaf is one contiguous
    # block of the carry whatever the bin count (63 bins as a minor
    # dimension pad to 128 lanes), a row slice reads it and a row
    # scatter writes it, and the carry has no second layout to be
    # turned to; only the rows a round reads are shaped back (pool_rows)
    hist: jax.Array  # (L, 3, G*Bc)
    # voting-parallel: valid[leaf, f] = the stored histogram column
    # holds GLOBAL (mesh-reduced) sums for feature f — all-True except
    # under voting, where only elected columns cross the mesh. Child
    # search and parent subtraction are masked to valid columns
    # (permuted.py hist_valid, lifted onto the round-batched state).
    # Zero-width when voting is off.
    valid: jax.Array  # (L, F | 0) bool


class _Children(NamedTuple):
    """What a round writes into _Pools: its left children, then its
    right ones, padded to twice the program's slot count so that every
    rung and the routing-only round return one shape."""

    leaf: jax.Array  # (2S,) int32 pool rows; L = nothing to write
    hist: jax.Array  # (2S, 3, G*Bc)
    valid: jax.Array  # (2S, F | 0) bool


@partial(jax.jit, static_argnames=("spec", "with_stats"))
def grow_tree_rounds(
    bins_fm: jax.Array,  # (G, N) int32, natural row order
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
    gh_scale: Optional[jax.Array] = None,  # (2,) [g_scale, h_scale]
    rng_key: Optional[jax.Array] = None,  # extra_trees / ff_bynode draws
    group_mat: Optional[jax.Array] = None,  # (NG, F) bool — interaction
    cegb=None,  # CegbInfo penalty tables
    forced=None,  # ForcedSplits plan (permuted.ForcedSplits) when
    # spec.n_forced > 0: (leaf, feature, bin) per step, leaf ids
    # precomputed under Tree::Split numbering
    with_stats: bool = False,  # also return per-width round counters
):
    """Grow one tree; returns (tree arrays, natural-order row->leaf),
    plus a {"widths", "rounds"} stats dict when with_stats=True
    (rounds: per width, then routing-only, then total; _NState.r).

    With spec.quant, grad/hess are INTEGER quantization levels and
    gh_scale carries the per-iteration dequantization scales: histogram
    sums stay exact integers (bf16 products, f32 accumulation) and are
    multiplied by the scales once per histogram before split search —
    the reference's int-histogram arithmetic (gradient_discretizer.cpp,
    feature_histogram.hpp:1062) mapped onto the MXU.

    Trace-safety contract: this function is the workhorse inside the
    fused step, which since round 18 is the BODY of a `lax.scan` chunk
    (boosting.fused_dispatch). Everything here must
    therefore stay traceable with abstract operands — no host branching
    on data values (python `if` only on static spec/params fields), no
    `.item()`/`float()` coercions, shapes independent of the round
    index. The per-round variation (bagging masks, rng_key, gh scales)
    arrives as traced ARGUMENTS; violating this turns one chunk
    executable into a retrace per round and trips
    analysis/retrace.py's guard in tests/test_chunk_scan.py."""
    L = spec.num_leaves
    B = spec.num_bins
    G, N = bins_fm.shape  # G = device columns (bundles when spec.efb)
    F = num_bins.shape[0]
    widths = ladder_widths(spec)  # the S-ladder of body() below
    S = widths[-1]
    ax = spec.axis_name
    Bc = spec.col_bins if (spec.efb and spec.col_bins) else B
    # voting-parallel on the rounds path (ISSUE 14): the per-round
    # election below replaces the full-histogram mesh reduce; only
    # elected bundle columns cross the mesh. Single-host (ax is None)
    # voting degenerates to the plain path — there is no wire to save.
    use_voting = bool(spec.voting_k and ax is not None)
    # per-node extras: extra_trees, ff_bynode, CEGB, interaction
    # constraints ride the rounds grower (off it they would fall onto
    # the much slower sequential permuted grower)
    per_node = bool(spec.extra_trees or spec.ff_bynode or spec.cegb
                    or spec.n_groups)
    if per_node and spec.mono_mode:
        raise ValueError(
            "monotone intermediate/advanced excludes per-node extras "
            "(boosting downgrades the combination to method=basic)"
        )
    if spec.mono_mode and (spec.voting_k or spec.n_forced):
        raise ValueError(
            "monotone intermediate/advanced excludes voting / forced "
            "splits (boosting downgrades the combination to method=basic)"
        )
    if spec.n_forced and forced is None:
        raise ValueError("spec.n_forced requires the forced= split plan")
    if spec.quant and gh_scale is None:
        raise ValueError("spec.quant requires gh_scale (level scales)")
    if per_node and (spec.extra_trees or spec.ff_bynode) \
            and rng_key is None:
        raise ValueError("extra_trees / ff_bynode need rng_key")
    NG = max(1, spec.n_groups)

    # fused partition+histogram kernel: one pass
    # computes the slot-packed child histograms AND the new row->leaf
    # vector; the separate (G, N) split-column select, membership
    # matmul and partition update disappear. Categorical splits ride
    # the kernel too: the row's own split-column bin gets a
    # single-feature SWAR one-hot contracted against the per-slot
    # category masks. At width (a table past one bins tile) the same
    # kernel routes over the round's <= S split columns alone and a
    # blocked slot-keyed pass builds the histograms (use_routed).
    sched = hist_schedule(spec, N, G)
    use_int8, oh_shift = sched.use_int8, sched.oh_shift
    use_fused, use_routed = sched.fused, sched.routed
    # the root pass and a routed round's passes share the program's
    # feature block; every other hist_nat_slots call plans for itself
    nat_plan = sched.plan if use_routed else None
    # ---- reduce-scatter histogram wire: the full
    # psum ships every rank the whole f32 histogram; the reference
    # ships INTEGER histograms through ReduceScatter with per-rank
    # feature ownership (bin.h:63-81, data_parallel_tree_learner
    # .cpp:286) — each rank reduces only its own feature block (wire
    # and histogram-pool memory both /n_ranks, int32 payload), finds
    # the best split among owned features, and the global winner is an
    # all-gather argmax (SyncUpGlobalBestSplit). Quantized sums are
    # exact integers, so the int32 wire is lossless. Irrelevant on ICI
    # where psum is near-free; 4-8x wire on DCN at pod scale.
    # exactness gate (ADVICE r5 medium): the int32 wire is only
    # lossless while the worst-case integer sums fit — global cell sum
    # under 2^31 (int32 wrap) and per-rank f32 accumulation under 2^24
    # (exact-integer range) — else fall back to the f32 psum path.
    # histogram.rs_exact_ok; contract enforced by the jaxpr auditor
    # (analysis/jaxpr_audit.py rounds_quant_rs / _overflow entries).
    n_rs = spec.axis_size
    use_rs = hist_wire(spec, N).startswith("rs_")
    if use_voting:
        kG = min(spec.voting_k, G)
        k2 = min(2 * spec.voting_k, G)
        # narrowest exact integer wire for the elected-column psum:
        # partial sums en route can only shrink below the worst-case
        # global bound rs_wire_dtype checks, so the same policy applies
        vote_dt = (
            rs_wire_dtype(N, max(n_rs, 1), spec.quant_levels)
            if spec.quant else None
        )
    if use_rs:
        Gp = -(-G // n_rs) * n_rs  # feature axis padded to the mesh
        Gn = Gp // n_rs  # features owned per rank
        # narrowest exact wire payload (ROADMAP 3a / ISSUE 12 satellite):
        # int16 halves the off-chip reduce-scatter bytes whenever the
        # worst-case integer sums fit (histogram.rs_wire_dtype); the
        # jaxpr/cost auditors pin the chosen dtype and the exact bytes
        wire_dt = jnp.dtype(rs_wire_dtype(N, n_rs, spec.quant_levels))

        def _pad_tables(t, fill):
            return jnp.concatenate(
                [t, jnp.full((Gp - G,) + t.shape[1:], fill, t.dtype)]
            ) if Gp != G else t

        num_bins_p = _pad_tables(num_bins, 0)  # 0 bins -> no candidates
        nan_bin_p = _pad_tables(nan_bin, -1)
        mono_p = _pad_tables(mono, 0)
        is_cat_p = _pad_tables(is_cat, False)
        feat_mask_p = _pad_tables(feat_mask, False)
        ridx = lax.axis_index(ax)

        def my_block(t):
            """This rank's (Gn,) slice of a padded (Gp,) feature table."""
            return lax.dynamic_slice_in_dim(t, ridx * Gn, Gn)

        def rs_hist(h):
            """(..., G, Bc) local f32 integer sums -> this rank's owned
            (..., Gn, Bc) block, reduced over the mesh in the narrowest
            exact integer dtype (int16 when the sums fit, else int32)."""
            if Gp != G:
                pad = [(0, 0)] * (h.ndim - 2) + [(0, Gp - G), (0, 0)]
                h = jnp.pad(h, pad)
            out = lax.psum_scatter(
                h.astype(wire_dt), ax,
                scatter_dimension=h.ndim - 2, tiled=True,
            )
            return out.astype(jnp.float32)

        def select_global_rec(rec: SplitRecord) -> SplitRecord:
            """All-gather each rank's best and keep the max-gain winner
            (per child when fields are vectors; ties -> lowest rank,
            matching parallel_tree_learner.h:209)."""
            rec = rec._replace(feature=rec.feature + ridx * Gn)
            with device_phase("parallel.reduce"):
                stacked = jax.tree.map(lambda a: lax.all_gather(a, ax), rec)
            if stacked.gain.ndim == 1:  # root: scalar fields
                w = jnp.argmax(stacked.gain)
                return jax.tree.map(lambda a: a[w], stacked)
            w = jnp.argmax(stacked.gain, axis=0)  # (children,)

            def pick(a):  # (n, children, ...) -> (children, ...)
                return jax.vmap(lambda col, wi: col[wi],
                                in_axes=(1, 0))(a, w)

            return jax.tree.map(pick, stacked)
    else:
        Gn = G

    def feature_left(h, g_sum, h_sum, c_sum, f, t):
        """Left sums (g, h, count) of feature f at threshold bin t from
        one leaf's pool row (3, G, Bc): a forced split's one candidate.
        In a bundle, the member's range in its column, and its
        most-frequent bin's mass (the leaf's totals less what the range
        stores) on the left from t = that bin on."""
        if not spec.efb:
            return tuple(jnp.cumsum(h[k, f])[t] for k in range(3))
        col = h[:, bundle.bundle_of[f]]  # (3, Bc)
        lo, m = bundle.off_lo[f], bundle.mfb[f]
        p = jnp.arange(col.shape[-1], dtype=jnp.int32)
        past = (m >= 0) & (t >= m)
        upto = jnp.where(m >= 0, lo + t - past.astype(jnp.int32), t)
        stored = (p >= lo) & (p < lo + bundle.width[f])
        left = jnp.sum(jnp.where(stored & (p <= upto), col, 0.0), axis=1)
        mass = jnp.stack([g_sum, h_sum, c_sum]) - jnp.sum(
            jnp.where(stored, col, 0.0), axis=1)
        left = left + jnp.where(past, mass, 0.0)
        return left[0], left[1], left[2]

    def search(h, *args, **kw):
        """best_split over one leaf's pool row; on a bundled Dataset in
        the bundle's own (column, merged bin) layout (bundle.py: no
        per-feature expansion)."""
        if spec.efb:
            return best_split_bundled(h, *args[:8], bundle.cells, B,
                                      *args[8:], **kw)[0]
        return best_split(h, *args, **kw)

    # shared per-node machinery (grower.make_node_candidates), vmapped
    # over each round's children; the draw ORDER differs from
    # sequential growth, which is fine — round batching already grows a
    # different-but-equivalent greedy tree
    node_candidates = make_node_candidates(
        spec, params, feat_mask, num_bins, nan_bin, rng_key, group_mat,
        cegb, F,
    )

    # device phases of the root pass (timer.DEVICE_PHASES): packing the
    # channels is learner.quantize, the root's totals and its histogram
    # learner.hist, what crosses the mesh parallel.reduce
    if spec.quant:
        with device_phase("learner.quantize"):
            gh8 = build_gh8_quant(grad * mask, hess * mask, mask)  # (8, N)
            scale3 = jnp.stack(
                [gh_scale[0], gh_scale[1], jnp.float32(1.0)]
            )  # (3,)
        with device_phase("learner.hist"):
            s8 = jnp.sum(gh8, axis=1)
            root = jnp.stack([s8[0], s8[1], s8[2]])
            if ax is not None:
                with device_phase("parallel.reduce"):
                    root = lax.psum(root, ax)
            root = root * scale3
            hist0 = hist_nat_slots(
                bins_fm, gh8, jnp.zeros(N, jnp.int32), 1, Bc, quant=True,
                int8=use_int8, oh_shift=oh_shift, plan=nat_plan,
            )[0]
            with device_phase("parallel.reduce"):
                if use_rs:
                    hist0 = rs_hist(hist0)  # (3, Gn, Bc) owned block, int wire
                elif ax is not None:
                    hist0 = lax.psum(hist0, ax)
            hist0 = hist0 * scale3[:, None, None]
    else:
        scale3 = None
        with device_phase("learner.quantize"):
            gh8 = build_gh8(grad * mask, hess * mask, mask)  # (8, N)
        with device_phase("learner.hist"):
            root = root_sums(gh8, ax)
            if use_routed:
                # the single-leaf kernel holds the whole table's tile
                hist0 = hist_nat_slots(
                    bins_fm, gh8, jnp.zeros(N, jnp.int32), 1, Bc,
                    plan=nat_plan)[0]
            else:
                hist0 = histogram(bins_fm, gh8, Bc)
            if ax is not None:
                with device_phase("parallel.reduce"):
                    hist0 = lax.psum(hist0, ax)
    with device_phase("learner.split_search"):
        root_out = leaf_output(root[0], root[1], params)
    if per_node:
        lg0 = jnp.ones((L, NG), bool)
        pu0 = jnp.zeros((L, F), bool)
        fu0 = cegb.used if spec.cegb else jnp.zeros(F, bool)
        fm0, rb0, pen0 = node_candidates(jnp.int32(0), lg0[0], pu0[0],
                                         root[2], fu0)
    else:
        lg0 = jnp.zeros((L, 0), bool)
        pu0 = jnp.zeros((L, 0), bool)
        fu0 = jnp.zeros(0, bool)
        fm0, rb0, pen0 = feat_mask, None, None
    if use_rs:
        # owned-feature search + global winner (local feature ids
        # shifted to global inside select_global_rec)
        nb_t, nan_t = my_block(num_bins_p), my_block(nan_bin_p)
        mono_t, iscat_t = my_block(mono_p), my_block(is_cat_p)
        fm_t = my_block(feat_mask_p)
        with device_phase("learner.split_search"):
            rec0 = select_global_rec(best_split(
                hist0, root[0], root[1], root[2], nb_t, nan_t, mono_t,
                iscat_t, params, fm_t, dirs=spec.search,
                parent_output=root_out))
    else:
        nb_t, nan_t, mono_t, iscat_t, fm_t = (
            num_bins, nan_bin, mono, is_cat, feat_mask)
        with device_phase("learner.split_search"):
            rec0 = search(hist0, root[0], root[1], root[2], num_bins,
                          nan_bin, mono, is_cat, params, fm0,
                          dirs=spec.search,
                          parent_output=root_out,
                          penalty=pen0, rand_bin=rb0)

    Gc = Gn if use_rs else G  # pool feature width (owned block under rs)
    with device_phase("learner.pool_write"):
        hist = jnp.zeros((L, 3, Gc * Bc), jnp.float32).at[0].set(
            hist0.reshape(3, -1))

    def pool_rows(h):
        """(..., 3, G*Bc) rows of the pool -> (..., 3, G, Bc)."""
        return h.reshape(h.shape[:-1] + (Gc, Bc))

    with device_phase("learner.select"):
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
            leaf_value=jnp.zeros(L, jnp.float32).at[0].set(root_out),
            leaf_weight=jnp.zeros(L, jnp.float32).at[0].set(root[1]),
            leaf_count=jnp.zeros(L, jnp.float32).at[0].set(root[2]),
            leaf_depth=jnp.zeros(L, jnp.int32),
        )

    valid_f = jnp.ones(N, jnp.float32) if valid is None else valid
    iota_L = jnp.arange(L, dtype=jnp.int32)

    # ---- S-ladder: early rounds are candidate-limited (1, 2, 4, ...
    # leaves have positive gain), yet the slot-packed kernel's matmul
    # costs M = S x channels rows REGARDLESS of how many slots are
    # live — a full-width S=48 pass for a 1-candidate round wastes
    # ~4 ms of MXU time. The while body therefore switches between
    # kernel widths (LADDER_RUNGS below S, then S) by live candidate
    # count. Selection is unchanged (top-k of a wider k picks the same
    # set), so the grown tree is bit-identical to the single-width
    # formulation.

    # ---- budget-aware tail (small data): round batching deviates from
    # best-first greedy once the leaf budget binds — children created
    # this round never compete against this round's remaining
    # candidates. Capping a round's splits at HALF the remaining budget
    # makes the tail approach exact greedy (the last splits go one at a
    # time). Extra tail rounds cost ~a histogram pass each, so the cap
    # is enabled only where passes are cheap (small N) and the quality
    # effect is measurable: at bench scale (1M x 28, 255 leaves) the
    # boundary effect is statistically negligible while ~5 extra rounds
    # would cost ~15% throughput. Measured on examples/binary (7k rows,
    # 63 leaves): closes most of the rounds-vs-exact AUC gap.
    tail_exact = N <= TAIL_EXACT_ROWS
    # under the monotone conflict guard a round may split fewer leaves
    # than its candidates (round_step defers conflicting ones), so its
    # being the last is not known before its data pass: those programs
    # keep a histogram in every round
    route_last = not spec.mono_mode

    def child_best(h, g_, h__, c_, po, cmn, cmx, fm=None, rb=None,
                   pen=None):
        # under use_rs the tables are this rank's owned block and
        # the winner is elected globally by the caller
        with device_phase("learner.split_search"):
            return search(
                h, g_, h__, c_, nb_t, nan_t,
                mono_t, iscat_t, params, fm_t if fm is None else fm,
                dirs=spec.search, parent_output=po,
                cmin=cmn, cmax=cmx, penalty=pen, rand_bin=rb,
            )

    def body(carry: Tuple[_Pools, _NState]) -> Tuple[_Pools, _NState]:
        pools, s = carry
        with device_phase("learner.select"):
            budget0 = (L - 1) - s.i
            n_pos = jnp.sum(s.best.gain > 0.0).astype(jnp.int32)
            n_cand = jnp.minimum(budget0, n_pos)
            if spec.n_forced:
                # forced phase: ONE split per round so Tree::Split leaf
                # numbering matches the BFS plan's precomputed ids (the
                # plan was laid out for sequential growth); n_pos can be
                # 0 here — the forced split doesn't need positive gain
                n_cand = jnp.where(
                    s.i < forced.n, jnp.int32(1), n_cand
                )
            if tail_exact:
                n_cand = jnp.minimum(
                    n_cand, jnp.maximum((budget0 + 1) // 2, 1))
            bidx = jnp.sum(
                n_cand > jnp.asarray(widths[:-1], jnp.int32)
            ).astype(jnp.int32)

        def ladder(pl: _Pools, st: _NState) -> Tuple[_NState, _Children]:
            return lax.switch(
                bidx,
                [partial(round_step, Sk=w, n_max=n_cand) for w in widths],
                pl, st,
            )

        if not route_last:
            s2, ch = ladder(
                pools, s._replace(r=s.r.at[bidx].add(1).at[-1].add(1)))
        else:
            # ---- the round that spends the last of the leaf budget
            # routes rows only: after it cond() ends the loop on `i`,
            # and nothing reads the children's histograms or their best
            # splits (the reference builds none after its last Split
            # either, serial_tree_learner.cpp Train). One branch at the
            # full slot count serves every tree size: top-k of a wider
            # k picks the same set, and without a histogram block the
            # width costs the pass next to nothing. A cond AROUND the
            # ladder's switch, not a fifth branch of it: as a fifth
            # branch the compiler rounded the 137-column program's
            # split gains differently in their sixth digit (PERF.md
            # section 6, PR 29); around it, every model text is the
            # parent's byte for byte.
            last = spends_budget(n_cand, budget0, S)
            ridx = jnp.where(last, len(widths), bidx).astype(jnp.int32)
            s2, ch = lax.cond(
                last,
                partial(round_step, Sk=S, n_max=n_cand, route_only=True),
                ladder,
                pools, s._replace(r=s.r.at[ridx].add(1).at[-1].add(1)),
            )
        # ---- the round's one write of the pools: <= 2S rows into the
        # loop's carry, in place (a left child keeps its parent's row,
        # a right one takes a fresh row: no two ids meet; pad ids drop)
        with device_phase("learner.pool_write"):
            pools2 = _Pools(
                hist=pools.hist.at[ch.leaf].set(ch.hist, mode="drop"),
                valid=(pools.valid.at[ch.leaf].set(ch.valid, mode="drop")
                       if use_voting else pools.valid),
            )
        if spec.mono_mode:
            # intermediate / advanced constraints, step 3 (round_step):
            # re-search every live leaf's best split under the round's
            # new bounds, from the pool as the round leaves it (one
            # vmapped pass keeps shapes static; the reference
            # recomputes a leaves_to_update set)
            t2 = s2.tree
            rec_all = jax.vmap(child_best)(
                pool_rows(pools2.hist), s2.leaf_g, s2.leaf_h, s2.leaf_c,
                t2.leaf_value, s2.leaf_min, s2.leaf_max,
            )
            with device_phase("learner.select"):
                d_ok = (spec.max_depth <= 0) | (
                    t2.leaf_depth < spec.max_depth)
                s2 = s2._replace(best=rec_all._replace(
                    gain=jnp.where((iota_L <= s2.i) & d_ok, rec_all.gain,
                                   NEG_INF)
                ))
        return pools2, s2

    def round_step(pools: _Pools, s: _NState, Sk: int, n_max=None,
                   route_only: bool = False
                   ) -> Tuple[_NState, _Children]:
        # what of a round no inner phase names is selection and the
        # tree's bookkeeping (timer.DEVICE_PHASES: the innermost names
        # the op)
        with device_phase("learner.select"):
            return _round(pools, s, Sk, n_max, route_only)

    def _round(pools: _Pools, s: _NState, Sk: int, n_max,
               route_only: bool) -> Tuple[_NState, _Children]:
        t = s.tree
        i = s.i
        S = Sk  # kernel width for this round (see the ladder above)
        iota_S = jnp.arange(S, dtype=jnp.int32)

        # ---- select this round's splits: top-k by gain within budget.
        # depth limits were already folded into best.gain when the
        # children were scored. top_k returns gains sorted descending,
        # so active slots form the prefix 0..n_split-1.
        budget = (L - 1) - i
        cap = jnp.minimum(budget, S)
        if n_max is not None:
            cap = jnp.minimum(cap, n_max)  # budget-aware tail (above)
        rec = s.best  # per-leaf records, fields (L,)
        gain_sel = s.best.gain
        if spec.n_forced:
            # ---- forced splits (ForceSplits, serial_tree_learner
            # .cpp:627) on the round-batched grower: while i < forced.n
            # the round splits exactly ONE prescribed leaf at the
            # prescribed (feature, threshold-bin) — body() caps the
            # round budget at 1 during the forced phase so Tree::Split
            # leaf numbering matches the plan's precomputed ids. The
            # per-leaf best record is overwritten at the forced leaf and
            # its selection gain raised to BIG so top_k picks it first;
            # invalid entries (empty child / exhausted plan) fall back
            # to the best-gain split, same documented deviation as the
            # permuted oracle (later entries keep PRE-COMPUTED leaf ids)
            fi = jnp.minimum(i, spec.n_forced - 1)
            fl = forced.leaf[fi]
            ff = forced.feature[fi]
            fb = forced.bin[fi]
            flg, flh, flc = feature_left(
                pool_rows(pools.hist[fl]), s.leaf_g[fl], s.leaf_h[fl],
                s.leaf_c[fl], ff, fb)
            fpg, fph, fpn = s.leaf_g[fl], s.leaf_h[fl], s.leaf_c[fl]
            gain_f = (
                leaf_gain(flg, flh, params)
                + leaf_gain(fpg - flg, fph - flh, params)
                - leaf_gain(fpg, fph, params)
            )
            use_f = (i < forced.n) & (flc > 0) & (fpn - flc > 0)

            def put(a, v):
                return jnp.where(use_f, a.at[fl].set(v), a)

            rec = SplitRecord(
                gain=put(rec.gain, gain_f),
                feature=put(rec.feature, ff),
                bin=put(rec.bin, fb),
                default_left=put(rec.default_left, False),
                is_cat=put(rec.is_cat, False),
                cat_mask=put(rec.cat_mask, jnp.zeros(B, bool)),
                left_g=put(rec.left_g, flg),
                left_h=put(rec.left_h, flh),
                left_c=put(rec.left_c, flc),
                right_g=put(rec.right_g, fpg - flg),
                right_h=put(rec.right_h, fph - flh),
                right_c=put(rec.right_c, fpn - flc),
            )
            gain_sel = put(gain_sel, BIG)
        topv, topl = lax.top_k(gain_sel, S)
        take = (iota_S < cap) & (topv > 0.0)
        if spec.mono_mode:
            # ---- same-round conflict guard (intermediate constraints):
            # two selected leaves on OPPOSITE sides of a shared monotone
            # ancestor may not both split this round — their bounds were
            # computed from each other's PRE-round extrema, so
            # simultaneous updates could cross. Defer every candidate
            # that conflicts with ANY higher-gain candidate (slots are
            # gain-sorted); deferred leaves split next round under
            # refreshed bounds. The sequential reference
            # (monotone_constraints.hpp:516) never faces this because it
            # recomputes bounds after every single split.
            tl_c = jnp.minimum(topl, L - 1)
            a_in = s.anc_in[tl_c]  # (S, L-1)
            a_lf = s.anc_left[tl_c]
            node_m = (mono[t.node_feature] != 0) & ~t.node_cat
            node_alive = jnp.arange(L - 1, dtype=jnp.int32) < i
            mono_n = (node_m & node_alive)[None, None, :]
            conf = jnp.any(
                a_in[:, None, :] & a_in[None, :, :]
                & (a_lf[:, None, :] ^ a_lf[None, :, :]) & mono_n,
                axis=2,
            )  # (S, S) — shares a live monotone ancestor, opposite sides
            earlier = iota_S[None, :] < iota_S[:, None]
            take = take & ~jnp.any(conf & earlier & take[None, :], axis=1)
        sel_leaf = jnp.where(take, topl, L)  # (S,) L = inactive slot
        sel = jnp.zeros(L, bool).at[sel_leaf].set(True, mode="drop")
        n_split = jnp.sum(take).astype(jnp.int32)
        # node rank = cumulative count of TAKEN slots before this one:
        # node ids must stay consecutive even when the monotone conflict
        # guard punches holes in the gain-sorted prefix (without holes
        # this equals the slot index)
        rank_s = (jnp.cumsum(take.astype(jnp.int32)) - 1).astype(jnp.int32)
        rank = jnp.zeros(L, jnp.int32).at[sel_leaf].set(rank_s, mode="drop")
        node_id = i + rank
        new_id = i + 1 + rank
        drop_node = jnp.where(sel, node_id, L - 1)  # L-1 -> mode=drop
        drop_new = jnp.where(sel, new_id, L)

        # ---- outputs / monotone intervals, vectorized over leaves ----
        pmin, pmax = s.leaf_min, s.leaf_max
        lo, ro = split_leaf_outputs(rec, params, num_bins, spec.cat_subset,
                                    t.leaf_value, pmin, pmax)
        lmin, lmax, rmin, rmax = monotone_child_intervals(
            rec, mono, lo, ro, pmin, pmax
        )
        depth_new = t.leaf_depth + 1

        # ---- tree bookkeeping (Tree::Split, batched) ----
        p = s.leaf_parent
        pc = jnp.maximum(p, 0)
        p_is_left = t.node_left[pc] == ~iota_L
        fix = sel & (p >= 0)
        node_left = t.node_left.at[
            jnp.where(fix & p_is_left, pc, L - 1)
        ].set(node_id, mode="drop")
        node_right = t.node_right.at[
            jnp.where(fix & ~p_is_left, pc, L - 1)
        ].set(node_id, mode="drop")
        node_left = node_left.at[drop_node].set(~iota_L, mode="drop")
        node_right = node_right.at[drop_node].set(~drop_new, mode="drop")

        tree_new = TreeArrays(
            num_nodes=i + n_split,
            node_feature=t.node_feature.at[drop_node].set(rec.feature, mode="drop"),
            node_bin=t.node_bin.at[drop_node].set(rec.bin, mode="drop"),
            node_gain=t.node_gain.at[drop_node].set(rec.gain, mode="drop"),
            node_default_left=t.node_default_left.at[drop_node].set(
                rec.default_left, mode="drop"
            ),
            node_cat=t.node_cat.at[drop_node].set(rec.is_cat, mode="drop"),
            node_cat_mask=t.node_cat_mask.at[drop_node].set(
                rec.cat_mask, mode="drop"
            ),
            node_left=node_left,
            node_right=node_right,
            node_value=t.node_value.at[drop_node].set(t.leaf_value, mode="drop"),
            node_weight=t.node_weight.at[drop_node].set(s.leaf_h, mode="drop"),
            node_count=t.node_count.at[drop_node].set(s.leaf_c, mode="drop"),
            leaf_value=jnp.where(sel, lo, t.leaf_value)
            .at[drop_new].set(ro, mode="drop"),
            leaf_weight=jnp.where(sel, rec.left_h, t.leaf_weight)
            .at[drop_new].set(rec.right_h, mode="drop"),
            leaf_count=jnp.where(sel, rec.left_c, t.leaf_count)
            .at[drop_new].set(rec.right_c, mode="drop"),
            leaf_depth=jnp.where(sel, depth_new, t.leaf_depth)
            .at[drop_new].set(depth_new, mode="drop"),
        )

        # ---- per-row split decision for all selected leaves at once ----
        # Every per-row leaf-dependent scalar (split column, threshold
        # bin, default direction, slot rank, smaller side, membership)
        # comes from ONE (N, S) @ (S, k) MXU contraction against the
        # selected leaves' parameters. A (N,) jnp.take from an (L,)
        # table costs ~1 ms each on TPU (no vector-gather hardware) and
        # the old (L*B,) category-mask flat gather ~10 ms; the one-hot
        # matmul is ~20 us for all of them together, because the
        # lookup rides the MXU. The contraction runs in f32:
        # packed values include feature/column ids and bin thresholds,
        # which exceed bf16's exact-integer range (256) on wide or
        # deep-binned datasets; f32 is exact to 2^24 and the (N,S)@(S,9)
        # matmul is far too small for the precision to cost wall time.
        # On the fused-kernel path all of this happens INSIDE the
        # histogram pass (pallas_hist._round_kernel) — see use_fused.
        left_smaller = rec.left_c <= rec.right_c  # (L,) — GLOBAL counts,
        # shard-consistent under data parallelism (derived from the
        # psum'd parent histogram during split search)
        sl_i = jnp.minimum(sel_leaf, L - 1)  # (S,) clipped for indexing
        live = (sel_leaf < L).astype(jnp.float32)  # (S,) pad slots drop
        feat_s = rec.feature[sl_i]  # (S,) tiny gathers from (L,) tables
        col_s = bundle.bundle_of[feat_s] if spec.efb else feat_s
        nan_s = nan_bin[feat_s]
        new_id_s = jnp.where(take, i + 1 + rank_s, L)

        def vote_reduce(sh):
            # ---- GlobalVoting election (parallel_tree_learner.h:152 /
            # voting_parallel_tree_learner.cpp), per ROUND: each shard
            # proposes its top-k columns by LOCAL gain over this round's
            # smaller children (max over live slots), votes + summed
            # gains elect 2k columns, and ONLY those columns cross the
            # mesh (gather-by-index psum, int16/int32 payload when the
            # quantized sums are exact — histogram.rs_wire_dtype). The
            # election unit is the bundle column, so voting composes
            # with EFB. Unlike the permuted oracle's per-SPLIT election
            # this elects once per round for all slots jointly — the
            # same PV-Tree approximation at one wire round per
            # histogram pass (documented deviation; parity tests pin
            # the saturated-election case where both coincide).
            local = sh * scale3[:, None, None] if spec.quant else sh
            # per-slot (g, h, count) totals from column 0's bin sums:
            # bins_fm is dense, so every device column partitions the
            # slot's rows
            lsum = jnp.sum(local[:, :, 0, :], axis=-1)  # (S, 3)

            def slot_gains(h, g_, h__, c_):
                if spec.efb:  # the best gain of each bundle column
                    return best_split_bundled(
                        h, g_, h__, c_, num_bins, nan_bin, mono, is_cat,
                        params, bundle.cells, B, feat_mask,
                        dirs=spec.search)[1]
                return feature_best_gains(
                    h, g_, h__, c_, num_bins,
                    nan_bin, mono, is_cat, params, feat_mask,
                    dirs=spec.search,
                )

            lg_s = jax.vmap(slot_gains)(
                local, lsum[:, 0], lsum[:, 1], lsum[:, 2]
            )  # (S, F | G) local per-column gains
            lg_s = jnp.where(take[:, None], lg_s, NEG_INF)  # dead slots
            col_gain = jnp.max(lg_s, axis=0)  # best over live slots
            _, topi = lax.top_k(col_gain, kG)
            in_topk = jnp.zeros(G, bool).at[topi].set(True)
            votes = lax.psum(in_topk.astype(jnp.float32), ax)
            score = lax.psum(
                jnp.where(in_topk, jnp.maximum(col_gain, 0.0), 0.0), ax
            )
            _, eidx = lax.top_k(votes * 1e12 + score, k2)
            if spec.n_forced:
                # pin the forced plan's columns into every election:
                # forced splits read their prescribed feature's column
                # unconditionally, so it must always carry global sums
                # (this lifts the old voting_k-excludes-forced guard;
                # duplicate indices scatter identical psum'd slices)
                fcols = (bundle.bundle_of[forced.feature] if spec.efb
                         else forced.feature)
                eidx = jnp.concatenate([eidx, fcols])
            elected_cols = jnp.zeros(G, bool).at[eidx].set(True)
            payload = sh[:, :, eidx, :]  # (S, 3, 2k[+n_forced], Bc)
            if vote_dt is not None:
                comp = lax.psum(payload.astype(vote_dt), ax).astype(
                    jnp.float32
                )
            else:
                comp = lax.psum(payload, ax)
            sh = jnp.zeros_like(sh).at[:, :, eidx, :].set(comp)
            el = elected_cols[bundle.bundle_of] if spec.efb else elected_cols
            return sh, el  # el: (F,) feature-space elected mask

        def reduce_slots(sh):
            """Mesh reduce of the (S, 3, G|Gn, Bc) local slot histograms
            — elected-columns-only under voting, reduce-scatter or psum
            otherwise — then the dequantization scale. Returns the
            reduced hists and the elected (F,) mask (None off voting)."""
            el = None
            with device_phase("parallel.reduce"):
                if use_voting:
                    sh, el = vote_reduce(sh)
                elif use_rs:
                    sh = rs_hist(sh)  # int wire, owned block
                elif ax is not None:
                    sh = lax.psum(sh, ax)
            if spec.quant:
                sh = sh * scale3[:, None, None]
            return sh, el

        with device_phase("learner.route"):
            if use_fused or use_routed:
                zs = jnp.zeros(S, jnp.int32)
                if spec.efb:
                    efb_cols = [bundle.off_lo[feat_s], bundle.mfb[feat_s],
                                bundle.width[feat_s]]
                else:
                    efb_cols = [zs, jnp.full(S, -1, jnp.int32), zs]
                params16 = jnp.stack(
                    [
                        sel_leaf, col_s,
                        rec.bin[sl_i],
                        rec.default_left[sl_i].astype(jnp.int32),
                        nan_s,
                        left_smaller[sl_i].astype(jnp.int32),
                        new_id_s,
                    ] + efb_cols + [
                        rec.is_cat[sl_i].astype(jnp.int32),  # col 10
                    ] + [zs] * 5,
                    axis=1,
                ).astype(jnp.int32)  # (S, 16)
                if use_routed:
                    # the routing pass sees the round's split columns as a
                    # table of their own: slot s's column is its row s
                    table = take_rows(bins_fm, col_s)  # (S, N)
                    coh = jnp.eye(S, dtype=jnp.float32)
                else:
                    table = bins_fm
                    coh = (
                        col_s[:, None]
                        == jnp.arange(G, dtype=jnp.int32)[None, :]
                    ).astype(jnp.float32)  # (S, G)
                if spec.has_cat:
                    cm_s = rec.cat_mask[sl_i].astype(jnp.int8)  # (S, B)
                    if Bc > B:  # kernel bin space is the bundle width
                        cm_s = jnp.pad(cm_s, ((0, 0), (0, Bc - B)))
                else:
                    cm_s = None
                if route_only:
                    pleaf_new = route_round(
                        table, s.pleaf, params16, coh, S, Bc, efb=spec.efb,
                        cat_mask=cm_s,
                    )
                elif use_routed:
                    pleaf_new, hslot = route_round(
                        table, s.pleaf, params16, coh, S, Bc, efb=spec.efb,
                        cat_mask=cm_s, with_slot=True,
                    )
                    with device_phase("learner.hist"):
                        slot_hists = hist_nat_slots(
                            bins_fm, gh8, hslot, S, Bc, quant=spec.quant,
                            int8=use_int8, oh_shift=oh_shift, plan=nat_plan,
                        )  # (S, 3, G, Bc)
                        slot_hists, elected = reduce_slots(slot_hists)
                else:
                    with device_phase("learner.hist"):
                        slot_hists, pleaf_new = hist_round(
                            bins_fm, gh8, s.pleaf, params16, coh, S, Bc,
                            quant=spec.quant, int8=use_int8, oh_shift=oh_shift,
                            efb=spec.efb, cat_mask=cm_s,
                        )
                        slot_hists, elected = reduce_slots(slot_hists)
            else:
                pack_cols = [
                    col_s.astype(jnp.float32),  # 0: device bin column
                    rec.bin[sl_i].astype(jnp.float32),  # 1: threshold bin
                    rec.default_left[sl_i].astype(jnp.float32),  # 2
                    rec.is_cat[sl_i].astype(jnp.float32),  # 3
                    nan_s.astype(jnp.float32),  # 4: NaN bin (-1 = none)
                    iota_S.astype(jnp.float32),  # 5: histogram slot index
                    left_smaller[sl_i].astype(jnp.float32),  # 6
                    jnp.ones(S, jnp.float32),  # 7: membership indicator
                    feat_s.astype(jnp.float32),  # 8: true feature id (EFB)
                    new_id_s.astype(jnp.float32),  # 9: new (right) leaf id
                ]
                pack = jnp.stack(pack_cols, axis=1) * live[:, None]  # (S, 10)
                memb = (s.pleaf[:, None] == sel_leaf[None, :])  # (N, S)
                # HIGHEST precision: the default TPU matmul multiplies f32
                # in bf16, which would corrupt packed ids above 256 — the
                # exact case the f32 pack exists for
                vals = lax.dot_general(
                    memb.astype(jnp.float32), pack, (((1,), (0,)), ((), ())),
                    preferred_element_type=jnp.float32,
                    precision=lax.Precision.HIGHEST,
                )  # (N, 10); rows outside every selected leaf are all-zero
                in_split = vals[:, 7] > 0.5
                col_row = vals[:, 0].astype(jnp.int32)
                bin_row = vals[:, 1].astype(jnp.int32)
                dl_row = vals[:, 2] > 0.5
                cat_row = vals[:, 3] > 0.5
                nan_row = vals[:, 4].astype(jnp.int32)
                rank_row = vals[:, 5].astype(jnp.int32)
                small_row = vals[:, 6] > 0.5
                # masked select of each row's split column (no 2D gather)
                col_sel = (col_row[None, :]
                           == jnp.arange(G, dtype=jnp.int32)[:, None])
                fbins = jnp.sum(jnp.where(col_sel, bins_fm, 0), axis=0)
                if spec.efb:
                    f_row = vals[:, 8].astype(jnp.int32)
                    fbins = decode_feature_bins(fbins, f_row, bundle)
                if spec.has_cat:
                    # category-set membership as a bin-one-hot contraction:
                    # hit[r] = cat_mask[slot(r), fbins[r]] without the
                    # (L*B,) flat gather
                    ob = (fbins[:, None]
                          == jnp.arange(B, dtype=jnp.int32)[None, :])
                    cm_sel = (rec.cat_mask[sl_i].astype(jnp.bfloat16)
                              * live[:, None])  # (S, B)
                    hits = lax.dot_general(
                        ob.astype(jnp.bfloat16), cm_sel,
                        (((1,), (1,)), ((), ())),
                        preferred_element_type=jnp.float32,
                    )  # (N, S)
                    cat_hit = jnp.sum(hits * memb, axis=1) > 0.5
                else:
                    cat_hit = jnp.zeros_like(in_split)
                go_left = jnp.where(
                    cat_row,
                    cat_hit,
                    (fbins <= bin_row)
                    | (dl_row & (fbins == nan_row) & (nan_row >= 0)),
                )
                new_id_row = vals[:, 9].astype(jnp.int32)
                pleaf_new = jnp.where(
                    in_split & ~go_left, new_id_row, s.pleaf
                ).astype(jnp.int32)

                if not route_only:
                    # ---- smaller-child histograms: one slot-packed pass ----
                    with device_phase("learner.hist"):
                        go_small = go_left == small_row
                        hslot = jnp.where(
                            in_split & go_small, rank_row, S
                        ).astype(jnp.int32)
                        slot_hists = hist_nat_slots(
                            bins_fm, gh8, hslot, S, Bc, quant=spec.quant,
                            int8=use_int8, oh_shift=oh_shift,
                        )  # (S, 3, G, Bc)
                        slot_hists, elected = reduce_slots(slot_hists)

        leaf_g2 = jnp.where(sel, rec.left_g, s.leaf_g) \
            .at[drop_new].set(rec.right_g, mode="drop")
        leaf_h2 = jnp.where(sel, rec.left_h, s.leaf_h) \
            .at[drop_new].set(rec.right_h, mode="drop")
        leaf_c2 = jnp.where(sel, rec.left_c, s.leaf_c) \
            .at[drop_new].set(rec.right_c, mode="drop")
        leaf_parent2 = jnp.where(sel, node_id, s.leaf_parent) \
            .at[drop_new].set(node_id, mode="drop")

        def children(leaf, hist, valid) -> _Children:
            """The round's pool rows at the program's full 2S."""
            with device_phase("learner.pool_write"):
                pad = [(0, 2 * widths[-1] - leaf.shape[0])]
                return _Children(
                    leaf=jnp.pad(leaf, pad, constant_values=L),
                    hist=jnp.pad(hist, pad + [(0, 0)] * 2),
                    valid=jnp.pad(valid, pad + [(0, 0)]),
                )

        if route_only:
            # the tree and the rows' leaves are all a last round leaves
            # behind; the best splits and the constraint carries pass
            # through, read by nobody, and no pool row is written
            return s._replace(
                i=i + n_split, pleaf=pleaf_new, leaf_g=leaf_g2,
                leaf_h=leaf_h2, leaf_c=leaf_c2, leaf_parent=leaf_parent2,
                tree=tree_new,
            ), children(
                jnp.zeros(0, jnp.int32),
                jnp.zeros((0,) + pools.hist.shape[1:], jnp.float32),
                jnp.zeros((0,) + pools.valid.shape[1:], bool),
            )

        # ---- per-slot child hists: smaller from the pass, larger by
        # subtraction; body() scatters both into the pool. Work stays
        # O(S), not O(L) — only the <= S split leaves are touched.
        with device_phase("learner.subtract"):
            sl_c = sl_i  # (S,) clipped for gathers (computed above)
            # (elementwise on whole histograms, so on flat (S, 3*G*Bc)
            # views: what the pool takes and what the search reads are
            # two shapes of the one result. On 4-D operands or on pool
            # rows the CPU backend fused the fused and the routed program
            # differently and their model texts parted in a last digit;
            # the chip reads the same either way: PERF.md section 6, PR 33)
            slot_flat = slot_hists.reshape(S, -1)
            parent_s = take_rows(pools.hist, sl_c).reshape(S, -1)
            large_s = parent_s - slot_flat
            ls_s = left_smaller[sl_c][:, None]
            left_s = jnp.where(ls_s, slot_flat, large_s)
            right_s = jnp.where(ls_s, large_s, slot_flat)
            ch_flat = jnp.concatenate([left_s, right_s])  # (2S, 3*G*Bc)
            ch_hist = ch_flat.reshape(2 * S, 3, Gc, Bc)
            ch_leaf = jnp.concatenate([sel_leaf, new_id_s])

        ch_valid = jnp.zeros((2 * S,) + pools.valid.shape[1:], bool)
        if use_voting:
            # the smaller child's histogram holds global sums exactly at
            # the elected columns; the larger sibling's subtraction is
            # additionally only sound where the PARENT's stored column
            # was global (permuted.py valid_small / valid_large)
            valid_parent_s = pools.valid[sl_c]  # (S, F)
            valid_small = jnp.broadcast_to(
                elected[None, :], valid_parent_s.shape
            )
            valid_large = valid_small & valid_parent_s
            ls_v = left_smaller[sl_c][:, None]
            valid_left = jnp.where(ls_v, valid_small, valid_large)
            valid_right = jnp.where(ls_v, valid_large, valid_small)
            # only columns whose stored sums are global may be
            # searched — unelected columns hold local/garbage sums
            ch_valid = jnp.concatenate([valid_left, valid_right])

        # ---- best splits for the new children, batched over 2S ----
        anc_in2, anc_left2 = s.anc_in, s.anc_left
        flo2, fhi2 = s.leaf_flo, s.leaf_fhi
        lg2, pu2, fu2 = s.leaf_groups, s.path_used, s.feat_used
        if not spec.mono_mode:
            ch_g = jnp.concatenate([rec.left_g[sl_c], rec.right_g[sl_c]])
            ch_h = jnp.concatenate([rec.left_h[sl_c], rec.right_h[sl_c]])
            ch_c = jnp.concatenate([rec.left_c[sl_c], rec.right_c[sl_c]])
            ch_po = jnp.concatenate([lo[sl_c], ro[sl_c]])
            ch_mn = jnp.concatenate([lmin[sl_c], rmin[sl_c]])
            ch_mx = jnp.concatenate([lmax[sl_c], rmax[sl_c]])
            if per_node:
                # per-node candidate machinery for this round's 2S
                # children (permuted.py node_candidates semantics)
                f_split_s = rec.feature[sl_c]  # (S,)
                onehot_f = (jnp.arange(F, dtype=jnp.int32)[None, :]
                            == f_split_s[:, None])  # (S, F)
                child_groups = s.leaf_groups[sl_c]  # (S, NG)
                if spec.n_groups:
                    child_groups = child_groups & group_mat[:, f_split_s].T
                pu_child = s.path_used[sl_c] | onehot_f  # (S, F)
                fu2 = s.feat_used | jnp.any(
                    onehot_f & take[:, None], axis=0
                )
                node_id_sl2 = i + rank_s  # (S,)
                salts = jnp.concatenate(
                    [2 * node_id_sl2 + 1, 2 * node_id_sl2 + 2])
                cg2 = jnp.concatenate([child_groups, child_groups])
                puc2 = jnp.concatenate([pu_child, pu_child])
                ch_fm, ch_rb, ch_pen = jax.vmap(
                    node_candidates, in_axes=(0, 0, 0, 0, None)
                )(salts, cg2, puc2, ch_c, fu2)
                if use_voting:
                    ch_fm = ch_fm & ch_valid
                ch_rec = jax.vmap(child_best)(
                    ch_hist, ch_g, ch_h, ch_c, ch_po, ch_mn, ch_mx,
                    ch_fm, ch_rb, ch_pen,
                )
                lg2 = s.leaf_groups.at[sel_leaf].set(
                    child_groups, mode="drop"
                ).at[new_id_s].set(child_groups, mode="drop")
                pu2 = s.path_used.at[sel_leaf].set(
                    pu_child, mode="drop"
                ).at[new_id_s].set(pu_child, mode="drop")
            elif use_voting:
                ch_rec = jax.vmap(child_best)(
                    ch_hist, ch_g, ch_h, ch_c, ch_po, ch_mn, ch_mx,
                    feat_mask[None, :] & ch_valid,
                )
            else:
                ch_rec = jax.vmap(child_best)(
                    ch_hist, ch_g, ch_h, ch_c, ch_po, ch_mn, ch_mx
                )
            if use_rs:
                # global winner per child across feature owners
                ch_rec = select_global_rec(ch_rec)
            depth_ok_s = (spec.max_depth <= 0) | (
                depth_new[sl_c] < spec.max_depth)
            ch_gain = jnp.where(
                jnp.concatenate([depth_ok_s, depth_ok_s]), ch_rec.gain,
                NEG_INF
            )

            def scat(dst, val):
                return dst.at[ch_leaf].set(val, mode="drop")

            best2 = SplitRecord(
                gain=scat(s.best.gain, ch_gain),
                feature=scat(s.best.feature, ch_rec.feature),
                bin=scat(s.best.bin, ch_rec.bin),
                default_left=scat(s.best.default_left, ch_rec.default_left),
                is_cat=scat(s.best.is_cat, ch_rec.is_cat),
                cat_mask=scat(s.best.cat_mask, ch_rec.cat_mask),
                left_g=scat(s.best.left_g, ch_rec.left_g),
                left_h=scat(s.best.left_h, ch_rec.left_h),
                left_c=scat(s.best.left_c, ch_rec.left_c),
                right_g=scat(s.best.right_g, ch_rec.right_g),
                right_h=scat(s.best.right_h, ch_rec.right_h),
                right_c=scat(s.best.right_c, ch_rec.right_c),
            )
            nmin = jnp.where(sel, lmin, s.leaf_min) \
                .at[drop_new].set(rmin, mode="drop")
            nmax = jnp.where(sel, lmax, s.leaf_max) \
                .at[drop_new].set(rmax, mode="drop")
        else:
            # ---- intermediate constraints, round-batched (the
            # permuted grower's batch formulation of
            # monotone_constraints.hpp:516 GoUpToFindLeavesToUpdate):
            # 1. extend the ancestry matrices with this round's splits,
            # 2. recompute EVERY leaf's [min, max] from the actual
            #    output extrema of the opposite subtrees of its
            #    monotone ancestors,
            # 3. re-search every live leaf's best split under the new
            #    bounds (one vmapped pass keeps shapes static; the
            #    reference recomputes a leaves_to_update set).
            # left child keeps the parent's leaf id (bit set in place,
            # anc_left too); the right child copies the parent's
            # pre-round ancestry row (slot-indexed scatter, pads drop)
            iota_n = jnp.arange(L - 1, dtype=jnp.int32)
            node_id_sl = i + rank_s  # (S,) this round's node per slot
            rows_in = s.anc_in[sl_c] | (
                (iota_n[None, :] == node_id_sl[:, None]) & take[:, None]
            )  # (S, L-1)
            rows_lf = s.anc_left[sl_c]
            nm_leaf = (iota_n[None, :] == node_id[:, None]) & sel[:, None]
            anc_in2 = (s.anc_in | nm_leaf).at[new_id_s].set(
                rows_in, mode="drop")
            anc_left2 = (s.anc_left | nm_leaf).at[new_id_s].set(
                rows_lf, mode="drop")
            i_new = i + n_split
            leaf_out2 = tree_new.leaf_value
            valid_leaf = iota_L <= i_new
            node_m = mono[tree_new.node_feature] * (
                ~tree_new.node_cat).astype(jnp.int32)
            node_alive = jnp.arange(L - 1, dtype=jnp.int32) < i_new
            in_l = anc_in2 & anc_left2 & valid_leaf[:, None]
            in_r = anc_in2 & ~anc_left2 & valid_leaf[:, None]
            if spec.mono_mode == 2:
                # ---- advanced constraints (monotone_constraints
                # .hpp:858 AdvancedLeafConstraints): the opposite-
                # subtree extremum bounding leaf x through monotone
                # ancestor a is taken only over leaves r whose feature-
                # domain can actually meet x's — i.e. their bin ranges
                # intersect in every feature EXCEPT a's split feature
                # (x and r always differ there; a violating pair needs
                # a point equal in all other features, and two leaves
                # whose (lo, hi] bin intervals are disjoint in some
                # other feature admit no such point). Bin-interval
                # overlap over-approximates value equality, so the
                # refinement never drops a needed constraint; it is
                # strictly no looser than the intermediate broadcast.
                # 1. refine per-(leaf, feature) ranges with this
                # round's splits: numeric splits shrink the split
                # feature's interval (left hi=min(hi, bin); right
                # lo=max(lo, bin)); categorical splits and features
                # with a NaN bin keep the full range — their rows
                # don't partition by bin interval (conservative).
                refine = sel & ~rec.is_cat & (nan_bin[rec.feature] < 0)
                f_oh = (
                    jnp.arange(F, dtype=jnp.int32)[None, :]
                    == rec.feature[:, None]
                ) & refine[:, None]  # (L, F)
                hi_l = jnp.where(
                    f_oh, jnp.minimum(s.leaf_fhi, rec.bin[:, None]),
                    s.leaf_fhi,
                )
                lo_r = jnp.where(
                    f_oh, jnp.maximum(s.leaf_flo, rec.bin[:, None]),
                    s.leaf_flo,
                )
                # left child keeps the parent id in place; right child
                # scatters the parent's pre-round row, lo raised
                flo2 = s.leaf_flo.at[new_id_s].set(
                    lo_r[sl_c], mode="drop")
                fhi2 = jnp.where(sel[:, None], hi_l, s.leaf_fhi).at[
                    new_id_s].set(s.leaf_fhi[sl_c], mode="drop")
                # 2. pairwise per-feature (lo, hi] intersection and the
                # per-ancestor comparability mask ok_pair[x, r, a]:
                # ranges overlap everywhere except possibly on a's
                # split feature
                ivf = (
                    jnp.maximum(flo2[:, None, :], flo2[None, :, :])
                    < jnp.minimum(fhi2[:, None, :], fhi2[None, :, :])
                )  # (L, L, F)
                n_bad = jnp.sum(~ivf, axis=2)  # (L, L)
                bad_fa = ~jnp.take(
                    ivf,
                    jnp.minimum(tree_new.node_feature, F - 1),
                    axis=2,
                )  # (L, L, L-1) — disjoint on node a's split feature?
                ok_pair = (
                    n_bad[:, :, None] - bad_fa.astype(jnp.int32)
                ) <= 0
                # 3. per-(x, a) refined opposite-subtree extrema
                # replacing the intermediate method's broadcast rows

                def _ext(in_m, red, init):
                    sel_m = in_m[None, :, :] & ok_pair  # (L, L, L-1)
                    return red(
                        jnp.where(sel_m, leaf_out2[None, :, None], init),
                        axis=1,
                    )  # (L, L-1)

                Lmax = _ext(in_l, jnp.max, -BIG)
                Lmin = _ext(in_l, jnp.min, BIG)
                Rmax = _ext(in_r, jnp.max, -BIG)
                Rmin = _ext(in_r, jnp.min, BIG)
            else:
                Lmax = jnp.max(
                    jnp.where(in_l, leaf_out2[:, None], -BIG), axis=0
                )[None, :]
                Lmin = jnp.min(
                    jnp.where(in_l, leaf_out2[:, None], BIG), axis=0
                )[None, :]
                Rmax = jnp.max(
                    jnp.where(in_r, leaf_out2[:, None], -BIG), axis=0
                )[None, :]
                Rmin = jnp.min(
                    jnp.where(in_r, leaf_out2[:, None], BIG), axis=0
                )[None, :]
            inc = (node_alive & (node_m > 0))[None, :]
            dec = (node_alive & (node_m < 0))[None, :]
            cmax_mat = jnp.where(in_l & inc, Rmin, BIG)
            cmax_mat = jnp.where(in_r & dec, Lmin, cmax_mat)
            cmin_mat = jnp.where(in_r & inc, Lmax, -BIG)
            cmin_mat = jnp.where(in_l & dec, Rmax, cmin_mat)
            nmax = jnp.min(cmax_mat, axis=1)  # (L,)
            nmin = jnp.max(cmin_mat, axis=1)
            # step 3 reads the pool as this round leaves it: body()
            # re-searches after its scatter
            best2 = s.best

        return _NState(
            i=i + n_split,
            r=s.r,
            pleaf=pleaf_new,
            leaf_g=leaf_g2,
            leaf_h=leaf_h2,
            leaf_c=leaf_c2,
            leaf_parent=leaf_parent2,
            leaf_min=nmin,
            leaf_max=nmax,
            anc_in=anc_in2,
            anc_left=anc_left2,
            leaf_groups=lg2,
            path_used=pu2,
            feat_used=fu2,
            leaf_flo=flo2,
            leaf_fhi=fhi2,
            best=best2,
            tree=tree_new,
        ), children(ch_leaf, ch_flat.reshape(2 * S, 3, -1), ch_valid)

    def _forced_valid(pools: _Pools, s: _NState):
        """Is step s.i a forced split with both children non-empty?"""
        fi = jnp.minimum(s.i, spec.n_forced - 1)
        fl = forced.leaf[fi]
        ff = forced.feature[fi]
        fb = forced.bin[fi]
        lc = feature_left(pool_rows(pools.hist[fl]), s.leaf_g[fl],
                          s.leaf_h[fl], s.leaf_c[fl], ff, fb)[2]
        return (s.i < forced.n) & (lc > 0) & (s.leaf_c[fl] - lc > 0)

    def cond(carry: Tuple[_Pools, _NState]) -> jax.Array:
        pools, s = carry
        with device_phase("learner.select"):
            keep = jnp.max(s.best.gain) > 0.0
            if spec.n_forced:
                # only continue for a forced step that can actually
                # split (both children non-empty) — the round body falls
                # back to the best-gain split otherwise, which `keep`
                # already guards
                keep = keep | _forced_valid(pools, s)
            return (s.i < L - 1) & keep

    with device_phase("learner.select"):
        state = _NState(
            i=jnp.int32(0),
            r=jnp.zeros(len(widths) + 2, jnp.int32),
            pleaf=jnp.where(valid_f > 0, 0, L).astype(jnp.int32),
            leaf_g=jnp.zeros(L, jnp.float32).at[0].set(root[0]),
            leaf_h=jnp.zeros(L, jnp.float32).at[0].set(root[1]),
            leaf_c=jnp.zeros(L, jnp.float32).at[0].set(root[2]),
            leaf_parent=jnp.full(L, -1, jnp.int32),
            leaf_min=jnp.full(L, -BIG, jnp.float32),
            leaf_max=jnp.full(L, BIG, jnp.float32),
            anc_in=jnp.zeros((L, L - 1 if spec.mono_mode else 0), bool),
            anc_left=jnp.zeros((L, L - 1 if spec.mono_mode else 0), bool),
            leaf_groups=lg0,
            path_used=pu0,
            feat_used=fu0,
            leaf_flo=jnp.full(
                (L, F if spec.mono_mode == 2 else 0), -1, jnp.int32
            ),
            leaf_fhi=jnp.full(
                (L, F if spec.mono_mode == 2 else 0), B, jnp.int32
            ),
            best=best,
            tree=tree,
        )
        # root histogram always crosses the mesh in full, so every column
        # starts globally valid
        pools0 = _Pools(hist=hist,
                        valid=jnp.ones((L, F if use_voting else 0), bool))
    _, final = lax.while_loop(cond, body, (pools0, state))

    row_leaf = final.pleaf
    if valid is not None:
        with device_phase("learner.route"):
            row_leaf = jnp.where(valid > 0, row_leaf, -1)
    if with_stats:
        return final.tree, row_leaf, {"widths": widths, "rounds": final.r}
    return final.tree, row_leaf
