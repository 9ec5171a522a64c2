"""GBDT boosting driver (reference src/boosting/gbdt.cpp).

Owns the training loop state: per-dataset device scores, the objective,
the sampling strategy, and the growing list of trees. Each iteration:

  gradients (device, objective)  ->  sampling mask (bagging/GOSS)
  ->  grow_tree (jit; one call per class-tree)  ->  leaf renewal for
  percentile objectives (RenewTreeOutput, objective_function.h:55)
  ->  score updates: train via the partition vector
  (score_updater.hpp AddScore fast path), valid via device tree
  traversal  ->  host Tree for the model list.

Boost-from-average follows gbdt.cpp:327-445: the initial score is added
to all scorers before the first iteration and folded into the first
tree's leaf values afterwards (Tree::AddBias), so saved models are
self-contained.
"""

from __future__ import annotations

from dataclasses import dataclass, field
from functools import partial
from typing import Any, Dict, List, NamedTuple, Optional, Sequence, Tuple

import numpy as np

from . import log
from ._backend import on_tpu, platform
from .config import Config
from .dataset import BinnedDataset
from .learner import GrowerSpec, grow_tree, make_split_params
from .learner.grower import TreeArrays, add_score
from .metrics import Metric, create_metrics
from .objectives import ObjectiveFunction, create_objective
from .sample_strategy import create_sample_strategy
from .timer import device_phase, global_timer as _gt

# float32 holds every integer up to 2**24 and every EVEN one up to 2**25:
# past that a node's row count may have no float32 value at all (_grow)
F32_EVEN_ROWS = 1 << 25
from .tree import Tree, num_cat_words, traverse_tree_bins

# canonical per-round host phase names (docs/OBSERVABILITY.md): the
# eager loops (fast/sync) emit the three phases each iteration; the
# fused loop — whose phases live inside one jit — emits one span per
# DISPATCH. A dispatch is a C-round lax.scan, so the span covers the
# whole chunk; _ObsHooks divides it by the dispatch's round count (the
# booster's _last_dispatch_rounds) to keep per-round record durations.
# obs.tracing records these as trace-event spans, and a jax.profiler
# trace holds each as a host event `lgbm:<name>` (timer.Timer.scope).
ROUND_PHASES = (
    "round: gradients",
    "round: grow",
    "round: score update",
)
FUSED_ROUND_PHASE = "round: fused step"


@dataclass
class _ScoreSet:
    dataset: BinnedDataset
    score: Any  # (K, Npad) device f32
    name: str
    metrics: List[Metric] = field(default_factory=list)


# process-level fused-step memo (cv folds / repeated trains reuse one
# traced+compiled step; see _build_fused) and the lightweight metric
# name records fused_collect reads. LRU-capped: each jitted step's
# closure pins its first booster's device arrays (bin matrix, scores),
# so an unbounded dict would grow without limit across a parameter
# sweep — 8 entries covers cv + realistic repeated-train patterns.
from collections import OrderedDict as _OrderedDict

_FUSED_STEP_CACHE: "_OrderedDict[Any, Any]" = _OrderedDict()
_FUSED_STEP_CACHE_MAX = 8

# objective attributes that hold FOLD-VARYING values read inside traced
# gradient code: device label/weight arrays, MAPE's label-derived
# weights, is_unbalance's label-count-derived class weights, and the
# ranking objectives' query layout with its label / gain grids and
# 1/MaxDCG (`_rank`, a dict of per-bucket arrays). The fused step
# rebinds these from its `data` argument during tracing so the memoized
# executable is fold-agnostic (anything outside this list that varies
# per fold must gate memo_ok instead).
_OBJ_FOLD_ATTRS = ("label", "weight", "_label_weight", "_pos_w", "_neg_w",
                   "_rank")

# device-array objective attributes OUTSIDE the rebind list that are
# legitimately excluded, each with the gate that keeps the fused memo
# safe. Everything else holding a jax.Array fails _audit_fold_attrs
# loudly (ADVICE r5 item 3); the static twin of this check is
# analysis/jaxpr_audit.audit_fold_attrs.
_OBJ_FOLD_EXEMPT = {
    "_pos_biases": "lambdarank position debiasing sets has_host_state, "
                   "which makes the booster fused-ineligible entirely",
}


def _audit_fold_attrs(objective) -> None:
    """Build-time assertion: a fold-varying device array outside
    _OBJ_FOLD_ATTRS would be baked into the memoized fused step as a
    constant and silently reuse another booster's fold data. Fail
    loudly instead — run only when memo_ok (the cache-sharing case).
    Scans pytree LEAVES so device arrays hiding inside containers
    (tuples, dicts, NamedTuples) are caught too."""
    import jax

    def holds_device_array(v) -> bool:
        return any(
            isinstance(leaf, jax.Array)
            for leaf in jax.tree_util.tree_leaves(v)
        )

    extra = sorted(
        a for a, v in vars(objective).items()
        if a not in _OBJ_FOLD_ATTRS
        and a not in _OBJ_FOLD_EXEMPT
        and holds_device_array(v)
    )
    if extra:
        log.fatal(
            f"objective {type(objective).__name__} holds device-array "
            f"attribute(s) {extra} outside _OBJ_FOLD_ATTRS: the fused "
            "step memo would bake them into a cached executable and "
            "share them across cv folds / repeated trains. Add them to "
            "_OBJ_FOLD_ATTRS (rebind per fold) or _OBJ_FOLD_EXEMPT "
            "(with the gate that makes the memo safe)."
        )


class _EvalNames(NamedTuple):
    names: List[str]
    higher_better: List[bool]


class _PendingChunk(NamedTuple):
    """One chunk-scan dispatch awaiting readback: ``trees`` is a tuple
    of K TreeArrays whose every field is stacked ``(C, ...)`` by the
    scan. Only the first ``n_active`` rounds are real; the tail past
    the dispatch's ``it_end`` is an algebraic no-op on device (zeroed
    leaf values, frozen iteration counter) and is sliced off on the
    host at materialize, never entering the model list."""

    trees: Any
    n_rounds: int
    n_active: int


# device_trees placeholder for rounds living inside a not-yet-fetched
# _PendingChunk; _materialize() replaces it with (host TreeArrays,
# None). Every consumer of device_trees content materializes first
# (fused_truncate, rollback via the `models` property, refit/splice),
# so a None read here is a loud bug, not a silent wrong answer.
_PENDING_SLOT: Tuple[Any, Any] = (None, None)


def _pick_chunk(rounds_left: int, ladder: Sequence[int]) -> int:
    """Largest ladder rung that fits, else the smallest rung (the
    masked-tail dispatch). Greedy decomposition over a fixed ladder
    bounds distinct scan executables at len(ladder) for ANY round
    count — the retrace-guard contract."""
    for c in sorted(ladder, reverse=True):
        if c <= rounds_left:
            return c
    return min(ladder)


class _FusedProgram:
    """Traced programs for one fused-step memo key: the raw step body
    and lazily-built C-round lax.scan chunk jits (one per ladder rung
    actually dispatched). Cached in _FUSED_STEP_CACHE, so the memo key
    effectively grows the chunk length through ``chunks`` — cv folds
    and repeated trains share the scan executables."""

    def __init__(self, step_fn, donate):
        self.step_fn = step_fn
        self._donate = donate
        self.chunks: Dict[int, Any] = {}

    def chunk_body(self, length: int):
        """Un-jitted C-round chunk callable: scans the per-round step,
        stacking the K per-round tree pytrees to (C, ...) and the eval
        rows to (C, E). Exposed un-jitted so the analysis suite can
        make_jaxpr it (the `fused_chunk_scan` entry)."""
        from jax import lax

        step_fn = self.step_fn

        def chunk(state, data):
            def body(st, _):
                st2, trees, eval_row = step_fn(st, data)
                return st2, (trees, eval_row)

            new_state, (trees, eval_mat) = lax.scan(
                body, state, xs=None, length=length
            )
            return new_state, trees, eval_mat

        return chunk

    def chunk(self, length: int):
        import jax

        fn = self.chunks.get(length)
        if fn is None:
            fn = jax.jit(self.chunk_body(length),
                         donate_argnums=self._donate)
            self.chunks[length] = fn
        return fn


def _obj_grads(objective, score, it):
    """Call an objective's gradient fn, passing the iteration to
    stochastic objectives (rank_xendcg redraws its perturbation each
    iteration; everything else ignores it)."""
    if getattr(objective, "needs_iter", False):
        return objective.get_gradients(score, it)
    return objective.get_gradients(score)


def _jit_traverse():
    import jax

    return jax.jit(traverse_tree_bins)


def _load_forced_splits(path: str, ds: "BinnedDataset"):
    """Read a forcedsplits json into a BFS plan (ForceSplits,
    serial_tree_learner.cpp:627): each node {feature, threshold,
    left?, right?}; thresholds map to bins via the feature's mapper.
    Returns a learner ForcedSplits or None on any problem (warned)."""
    import json as _json

    import jax.numpy as jnp

    from .binning import BinType
    from .learner.permuted import ForcedSplits

    try:
        with open(path) as f:
            root = _json.load(f)
    except (OSError, ValueError) as e:
        log.warning(f"cannot read forcedsplits_filename {path}: {e}")
        return None
    used_pos = {int(f): i for i, f in enumerate(ds.used_features)}
    from collections import deque

    leaves, feats, bins_ = [], [], []
    q = deque([(root, 0)])
    i = 0
    while q:
        node, leaf = q.popleft()
        if not isinstance(node, dict) or "feature" not in node:
            continue
        f_orig = int(node["feature"])
        if f_orig not in used_pos:
            log.warning(
                f"forced split on unused/trivial feature {f_orig}; "
                "skipping this branch"
            )
            continue
        m = ds.mappers[f_orig]
        if m.bin_type == BinType.CATEGORICAL:
            log.warning(
                "forced splits on categorical features are not supported; "
                f"skipping feature {f_orig}"
            )
            continue
        thr = float(node.get("threshold", 0.0))
        b = int(np.searchsorted(m.upper_bounds, thr, side="left"))
        b = min(b, max(m.num_bin - 2, 0))
        leaves.append(leaf)
        feats.append(used_pos[f_orig])
        bins_.append(b)
        new_leaf = i + 1  # right child's leaf id (Tree::Split numbering)
        if isinstance(node.get("left"), dict):
            q.append((node["left"], leaf))
        if isinstance(node.get("right"), dict):
            q.append((node["right"], new_leaf))
        i += 1
    if not leaves:
        return None
    return ForcedSplits(
        leaf=jnp.asarray(leaves, jnp.int32),
        feature=jnp.asarray(feats, jnp.int32),
        bin=jnp.asarray(bins_, jnp.int32),
        n=jnp.int32(len(leaves)),
    )


class GBDT:
    """Training driver (reference gbdt.h:37)."""

    def __init__(self, config: Config, train_set: Optional[BinnedDataset]):
        import jax.numpy as jnp

        from ._cache import ensure_compile_cache

        ensure_compile_cache()
        self.config = config
        self.train_set = train_set
        self.objective: Optional[ObjectiveFunction] = create_objective(config)
        self.num_class = config.num_model_per_iteration
        self.shrinkage_rate = config.learning_rate
        self.average_output = False  # RF mode divides prediction by #iters
        self._models: List[Tree] = []  # flat, iteration-major (models_[it*K + k])
        self.device_trees: List[Tuple[TreeArrays, Any]] = []  # (arrays w/ final leaf values, None)
        self.iter_ = 0
        self.best_iteration = -1
        self.valids: List[_ScoreSet] = []
        self._traverse = _jit_traverse()
        # flight-recorder hooks (obs/recorder.py): engine.train installs
        # a recorder here when record_file/anomaly_policy is configured;
        # the loops then publish gh norms (eager: _prepare_gradients;
        # fused: the eval-row tail collected into _last_gh_rows)
        self.recorder = None
        self._last_gh_norm: Optional[Tuple[float, float]] = None
        self._last_gh_rows: List[Tuple[float, float]] = []
        # ---- async training pipeline (the TPU analog of the reference's
        # synchronous per-iteration loop): a device->host readback
        # stalls the dispatch queue until the device drains, so the
        # fast path materializes host trees lazily in batches (one
        # device_get) and checks the "no splittable leaf" stop
        # condition only every _check_every iterations. DART/RF and
        # leaf-renewal objectives need per-iter host work and force the
        # synchronous path.
        self._pending: List[Any] = []  # TreeArrays (per-round) / _PendingChunk
        self._pending_meta: List[Tuple[int, float, float]] = []  # (k, bias, shrinkage)
        # dispatch-count probe: executable launches issued by
        # fused_dispatch (one per chunk). _last_dispatch_rounds holds
        # the round count of each dispatch in the most recent chunk so
        # _ObsHooks can expand per-dispatch spans into per-round
        # durations.
        self.fused_dispatch_count = 0
        self._last_dispatch_rounds: List[int] = []
        self._stopped = False
        # aligned to max(config.DEFAULT_CHUNK_LADDER) so a full driver
        # chunk dispatches as ONE top-rung lax.scan (50 used to shred
        # into 16+16+16+4 and the 64 rung never fired)
        self._check_every = 64
        self._force_sync = False
        self._force_sync_reason: Optional[str] = None
        self._init_iters = 0  # loaded iterations under continued training
        # resolved histogram channel layout (tpu_hist_dtype policy);
        # overwritten below when a train_set selects the real path
        self.hist_dtype = "bf16x2"
        self._hist_levels = 0
        self._int_packed = False

        if train_set is None:
            return  # prediction-only booster (model loaded from file)

        from .config import warn_unimplemented

        warn_unimplemented(config)
        # true-gradient leaf renewal bypasses the grower's monotone
        # interval clamp and path smoothing — refuse the combination
        # rather than silently violate a declared constraint. The same
        # guard gates the internal int-packed path's always-on renewal
        # (_grow_maybe_quantized).
        self._true_renew_ok = not (
            config.path_smooth > 0
            or (train_set.monotone_constraints is not None
                and np.any(train_set.monotone_constraints != 0))
        )
        self._quant_renew_ok = True
        if config.use_quantized_grad and config.quant_train_renew_leaf \
                and not self._true_renew_ok:
            self._quant_renew_ok = False
            log.warning(
                "quant_train_renew_leaf is disabled: true-gradient leaf "
                "renewal would bypass monotone constraints / path_smooth"
            )

        # ---- tree learner selection (reference tree_learner.cpp:17-59):
        # "data"/"voting" route growth through the sharded grower over a
        # 1-D device mesh (rows sharded, histograms psum'd over ICI —
        # data_parallel_tree_learner.cpp:286). Voting's top-k election
        # exists to cap socket bytes; on a TPU mesh the histogram reduce
        # is an XLA collective riding ICI, so both configs use the same
        # reduction (identical results to "data" by construction).
        self._mesh = None
        self._dp = None
        self._parallel_mode = None  # None | "data" | "feature"
        import jax

        n_dev = jax.device_count()
        if config.tree_learner in ("data", "voting") and n_dev > 1:
            from .learner.histogram import HIST_BLK
            from .parallel.data_parallel import make_mesh

            if config.tree_learner == "voting":
                log.info(
                    f"tree_learner=voting: top-{config.top_k} local-gain "
                    "vote elects features per round (per split on the "
                    "exact oracle); only elected columns are psum'd "
                    "across the mesh "
                    "(voting_parallel_tree_learner.cpp semantics)"
                )
            self._mesh = make_mesh()
            self._parallel_mode = "data"
            n_mesh = int(self._mesh.devices.size)
            blk = HIST_BLK
            if HIST_BLK % n_mesh != 0 or on_tpu():
                blk = HIST_BLK * n_mesh  # per-shard rows stay pallas-aligned
            train_set.ensure_row_block(blk)
            if jax.process_count() > 1:
                # pre-partitioned ranks hold UNEVEN shards; NamedSharding
                # tiles evenly, so every rank pads to the cluster-wide
                # max — AFTER the final row_block is set above (padded
                # counts are row_block multiples, identical across
                # ranks, so their max is too)
                from jax.experimental import multihost_utils

                padded = np.asarray(train_set.num_rows_padded(), np.int64)
                target = int(np.max(
                    multihost_utils.process_allgather(padded)
                ))
                train_set.ensure_min_padded_rows(target)
        elif config.tree_learner == "feature" and n_dev > 1:
            if train_set.bundle_layout is not None:
                log.warning(
                    "tree_learner=feature requires EFB off (feature == "
                    "column); falling back to serial growth. Set "
                    "enable_bundle=false."
                )
            else:
                from .parallel.data_parallel import make_mesh

                self._mesh = make_mesh(axis_name="feature")
                self._parallel_mode = "feature"
                log.info(
                    f"tree_learner=feature: {len(train_set.used_features)} "
                    f"features sharded over {n_dev} devices "
                    "(feature_parallel_tree_learner.cpp semantics)"
                )
        # where this Booster's data sets live: the training set's rows
        # over the data mesh (tree_learner=data), and in ONE process the
        # valid sets, labels and weights on the same mesh too (valid
        # sets replicated), all resident per Dataset (dataset.py) as
        # the one-chip copies are. A multi-process cluster keeps its
        # per-rank copies of everything but the training rows.
        self._data_mesh = self._mesh if self._parallel_mode == "data" \
            else None
        self._rows_mesh = self._data_mesh if jax.process_count() == 1 \
            else None
        # objective/strategy init AFTER ensure_row_block: they cache
        # padded per-row arrays and must see the final row padding
        if self.objective is not None:
            with _gt.scope("boosting.objective_init"):
                self.objective.mesh = self._rows_mesh
                self.objective.init(train_set)
        self.strategy = create_sample_strategy(
            config, train_set.num_data, group=train_set.metadata.group
        )
        with _gt.scope("boosting.device_inputs"):
            self.dev = train_set.device_arrays(self._data_mesh)
        from .binning import BinType

        cat_subset = any(
            m.bin_type == BinType.CATEGORICAL
            and m.num_bin > config.max_cat_to_onehot
            for m in train_set.used_mappers()
        )
        # voting composes with EFB: the election unit is the bundle
        # column (permuted.py voting block), so no bundle guard here
        use_voting = (
            config.tree_learner == "voting" and self._mesh is not None
        )
        # ---- per-node extras: extra_trees, feature_fraction_bynode,
        # interaction constraints, CEGB (permuted sequential path only)
        from .config import parse_interaction_constraints

        groups = parse_interaction_constraints(
            config.interaction_constraints, len(train_set.mappers)
        )
        self._group_mat = None
        n_groups = 0
        if groups:
            used_pos = {int(f): i for i, f in enumerate(train_set.used_features)}
            gm = np.zeros((len(groups), len(train_set.used_features)), bool)
            for gi, gr in enumerate(groups):
                for f in gr:
                    if f in used_pos:
                        gm[gi, used_pos[f]] = True
            self._group_mat = jnp.asarray(gm)
            n_groups = len(groups)
        self._cegb_info = None
        use_cegb = (
            config.cegb_penalty_split > 0.0
            or len(config.cegb_penalty_feature_coupled) > 0
            or len(config.cegb_penalty_feature_lazy) > 0
        )
        if use_cegb:
            from .learner.grower import CegbInfo

            fu = len(train_set.used_features)

            def _pen(t):
                if not t:
                    return np.zeros(fu, np.float32)
                if len(t) != len(train_set.mappers):
                    log.fatal(
                        "cegb_penalty_feature_* must have one entry per feature"
                    )
                return np.asarray(
                    [t[int(f)] for f in train_set.used_features], np.float32
                )

            self._cegb_info = CegbInfo(
                coupled=jnp.asarray(_pen(config.cegb_penalty_feature_coupled)),
                lazy=jnp.asarray(_pen(config.cegb_penalty_feature_lazy)),
                used=jnp.zeros(fu, bool),
            )
            if len(config.cegb_penalty_feature_coupled) > 0:
                # coupled costs are charged once per feature MODEL-WIDE
                # (is_feature_used_in_split_); the fused loop cannot see
                # cross-iteration feature usage, so run synchronously
                self._force_sync = True
                self._force_sync_reason = (
                    "coupled CEGB penalties track model-wide feature use"
                )
        # forced splits (forcedsplits_filename, serial_tree_learner.cpp
        # ForceSplits): read the BFS plan once; leaf ids at application
        # time are precomputed (left child keeps the parent id, right
        # child gets i+1 — Tree::Split numbering)
        self._forced = None
        n_forced = 0
        if config.forcedsplits_filename:
            self._forced = _load_forced_splits(
                config.forcedsplits_filename, train_set
            )
            if self._forced is not None:
                n_forced = int(self._forced.leaf.shape[0])
        # voting + forced splits compose on the rounds grower: the
        # forced plan's bundle columns are pinned into every election,
        # so the prescribed features always carry globally-reduced
        # sums (rounds.py vote_reduce; the old warn-and-disable guard
        # predates the election pinning)
        if config.tpu_debug_check_split:
            self._force_sync = True  # the check reads back per iteration
            self._force_sync_reason = "tpu_debug_check_split reads back per iteration"
        if config.linear_tree:
            # leaf ridge fits run host-side per iteration (the reference
            # solves with Eigen on CPU too, linear_tree_learner.cpp:344)
            self._force_sync = True
            self._force_sync_reason = "linear_tree leaf fits run on host"
            if train_set.raw_data is None:
                log.fatal(
                    "linear_tree requires raw feature values; construct "
                    "the Dataset with linear_tree in its params"
                )
        use_extra = config.extra_trees
        use_bynode = config.feature_fraction_bynode < 1.0
        if (use_extra or use_bynode or use_cegb or n_groups) and (
            self._parallel_mode == "feature"
        ):
            log.warning(
                "extra_trees / feature_fraction_bynode / cegb / interaction"
                "_constraints are not supported with tree_learner=feature; "
                "ignoring them"
            )
            use_extra = use_bynode = use_cegb = False
            n_groups = 0
            self._cegb_info = self._group_mat = None
        if n_forced and self._parallel_mode == "feature":
            # the feature-parallel grower rides the flat partition which
            # has no forced-split support — dropping the plan (with a
            # warning) beats crashing at the first iteration
            log.warning(
                "forcedsplits_filename is not supported with "
                "tree_learner=feature; ignoring the forced-split plan"
            )
            self._forced = None
            n_forced = 0
        self._node_key = (
            jax.random.key(config.extra_seed) if (use_extra or use_bynode)
            else None
        )
        # ---- monotone constraint method: 1 = intermediate (both the
        # sequential permuted grower — per-split recompute — and the
        # rounds grower — per-round recompute + conflict guard);
        # 2 = advanced (per-leaf range-overlap refinement of the
        # opposite-subtree extrema, rounds grower only). Both exclude
        # per-node extras, forced splits and voting (the re-search
        # ignores their per-node state / election masks).
        mono_any = (
            train_set.monotone_constraints is not None
            and np.any(np.asarray(train_set.monotone_constraints) != 0)
        )
        mono_mode = 0
        if mono_any:
            mono_mode = {"intermediate": 1, "advanced": 2}.get(
                config.monotone_constraints_method, 0
            )
        if mono_mode and (use_extra or use_bynode or use_cegb or n_groups
                          or n_forced or use_voting
                          or self._parallel_mode == "feature"):
            log.warning(
                "monotone_constraints_method=intermediate/advanced is "
                "incompatible with per-node extras / forced splits / "
                "voting / tree_learner=feature; falling back to "
                "method=basic"
            )
            mono_mode = 0
        # ---- growth strategy (tpu_growth_mode): natural-order
        # round-batched growth is the single production grower
        # (ISSUE 14). Monotone constraints (basic / intermediate /
        # advanced), per-node extras (extra_trees /
        # feature_fraction_bynode / CEGB / interaction constraints),
        # voting-parallel (per-round election, elected columns only on
        # the wire) and forced splits all ride it; only
        # feature-parallel still requires the flat grower, and the
        # sequential permuted grower remains as the reference-exact
        # parity oracle behind tpu_growth_mode=exact.
        rounds_ok = self._parallel_mode != "feature"
        mode = config.tpu_growth_mode
        tpu = on_tpu()
        if mode == "auto":
            use_rounds = tpu and rounds_ok
        else:
            use_rounds = mode == "rounds"
            if use_rounds and not rounds_ok:
                log.warning(
                    "tpu_growth_mode=rounds is incompatible with "
                    "tree_learner=feature; falling back to exact "
                    "sequential growth"
                )
                use_rounds = False
        if mono_mode == 2 and not use_rounds:
            # the advanced range-overlap refinement lives in the rounds
            # grower's per-round state; the sequential oracle implements
            # intermediate only
            log.warning(
                "monotone_constraints_method=advanced rides the rounds "
                "grower only (tpu_growth_mode=rounds); using "
                "method=intermediate on the sequential path"
            )
            mono_mode = 1
        if use_voting and n_forced and not use_rounds:
            # the sequential oracle cannot pin forced columns into its
            # per-split election (stale non-elected histogram columns
            # would corrupt the forced splits; permuted.py raises on the
            # combination) — keep the forced plan and drop the election,
            # the pre-unification fallback
            log.warning(
                "tree_learner=voting with forcedsplits_filename composes "
                "on the rounds grower (tpu_growth_mode=rounds pins the "
                "forced columns into every election); the sequential "
                "exact path runs with the election disabled"
            )
            use_voting = False
        # histogram channel-dtype policy (tpu_hist_dtype, ISSUE 12): on
        # the rounds path the DEFAULT (unquantized-API) trainer also
        # discretizes g/h per round to narrow integer levels and rides
        # the 3-channel slot-packed histogram kernels; f32 scales are
        # recovered before gain/leaf math and leaf outputs are renewed
        # from the true gradients, so the public semantics stay put.
        from .learner.quantize import resolve_hist_dtype

        self.hist_dtype, self._hist_levels, hd_warn = resolve_hist_dtype(
            config.tpu_hist_dtype, config.use_quantized_grad,
            config.num_grad_quant_bins, use_rounds, on_tpu=tpu,
        )
        if hd_warn and config.set_explicitly("tpu_hist_dtype"):
            log.warning(hd_warn)
        # int-packed channels on the default path (no public quant API)
        int_packed = self._hist_levels > 0
        self._int_packed = int_packed
        self.spec = GrowerSpec(
            num_leaves=config.num_leaves,
            num_bins=train_set.max_num_bin,
            max_depth=config.max_depth,
            axis_name="data" if self._parallel_mode == "data" else None,
            cat_subset=cat_subset,
            efb=train_set.bundle_layout is not None,
            col_bins=train_set.col_bins,
            # slot defaults are chip-tuned END TO END:
            # quant ch3 S=48 beat both 42 (0.258 vs 0.302 ms/split) and
            # 64 (10.06 vs 9.83 trees/s); non-quant S=32 measured
            # SLOWER than 25 end to end (4.39 vs 4.75 — wider passes
            # waste width on candidate-limited rounds) so 25 stays
            rounds_slots=(
                min(config.tpu_round_slots
                    or (48 if (config.use_quantized_grad or int_packed)
                        else 25),
                    config.num_leaves)
                if use_rounds else 0
            ),
            # int levels must be bf16-exact (integers <= 256); larger
            # num_grad_quant_bins rides the dequantized 5-channel path.
            # The internal hist_dtype policy (int_packed) reuses the same
            # 3-channel integer machinery with its own level count.
            quant=bool(use_rounds
                       and ((config.use_quantized_grad
                             and config.num_grad_quant_bins <= 256)
                            or int_packed)),
            # levels within int8 range (g <= bins/2, h <= bins): the
            # kernel runs s8 x s8 -> s32 on the MXU. rounds.py further
            # gates on histogram.int8_oh_shift finding a SWAR scale
            # whose worst-case s32 cell sum cannot overflow (ADVICE r4)
            quant_int8=bool(use_rounds
                            and ((config.use_quantized_grad
                                  and config.num_grad_quant_bins <= 127)
                                 or (int_packed
                                     and self._hist_levels <= 127))),
            quant_levels=(config.num_grad_quant_bins
                          if config.use_quantized_grad
                          else self._hist_levels),
            mono_mode=mono_mode,
            voting_k=config.top_k if use_voting else 0,
            extra_trees=use_extra,
            ff_bynode=use_bynode,
            cegb=use_cegb,
            n_groups=n_groups,
            n_forced=n_forced,
            has_cat=any(
                m.bin_type == BinType.CATEGORICAL
                for m in train_set.used_mappers()
            ),
            # a categorical column's other bin never goes left: only a
            # NUMERICAL column's NaN bin makes default-left a direction
            has_nan=any(
                m.nan_bin >= 0 and m.bin_type != BinType.CATEGORICAL
                for m in train_set.used_mappers()
            ),
            has_mono=bool(mono_any),
        )
        self.params = make_split_params(config)
        # ---- provenance for the flight recorder / run manifest
        # (docs/OBSERVABILITY.md): which learner family actually trains
        # after mode resolution, and the voting election footprint
        g_dev = int(self.dev["bins"].shape[0])
        self.tree_learner_resolved = (
            "voting" if use_voting
            else self._parallel_mode if self._parallel_mode in (
                "data", "feature")
            else "serial"
        )
        self.voting_elected_cols = (
            min(2 * config.top_k + n_forced, g_dev) if use_voting else None
        )
        # per-tree wire estimate; refined by the data-parallel grower's
        # voting-aware wire_bytes_per_tree once it exists (below)
        self.voting_wire_bytes_est = None
        # the wire the child histograms cross a data mesh on
        # (learner/rounds.py hist_wire); "none" off a mesh
        self.hist_wire_resolved = "none"
        with _gt.scope("boosting.score_init"):
            self.train = _ScoreSet(
                train_set,
                self._init_score_arr(train_set),
                "training",
                [m for m in create_metrics(config)],
            )
            meta = train_set.metadata
            for m in self.train.metrics:
                m.init(meta.label, meta.weight, meta.group)
            # the data set's own device copy, shared with the objective
            self._label_dev = train_set.device_label(self._rows_mesh)
        self._boosted_from_average = False
        self._init_scores = [0.0] * self.num_class
        self._feat_rng = np.random.RandomState(config.feature_fraction_seed)
        if self._parallel_mode == "data":
            from .parallel.data_parallel import shared_grower

            if jax.process_count() > 1:
                # multi-controller cluster: rides the per-iteration sync
                # path (every jit takes the global arrays as arguments).
                # Bringing it onto the fused, memoized step that a
                # one-process mesh takes is out of scope so far: its
                # valid sets, labels and scores are per-rank copies
                # re-assembled per Booster below, not resident mesh
                # copies of the Dataset
                self._force_sync = True
                self._force_sync_reason = (
                    "multi-process runs synchronize per iteration"
                )
                if self.config.bagging_freq > 0 and \
                        self.config.bagging_fraction < 1.0:
                    log.warning(
                        "bagging under multi-host training is not yet "
                        "global-row aware; disabling bagging"
                    )
                    self.config.bagging_freq = 0

            # one grower per (mesh, spec) in the process; the training
            # set's rows are on the mesh already (self.dev above: the
            # Dataset's own resident mesh copy, pushed shard by shard)
            self._dp = shared_grower(self._mesh, self.spec)
            if use_voting:
                self.voting_wire_bytes_est = self._dp.wire_bytes_per_tree(
                    int(self.dev["bins"].shape[0])
                )
            from .learner.rounds import hist_wire
            from .obs.metrics import record_parallel_mesh

            n_mesh = int(self._mesh.devices.size)
            self.hist_wire_resolved = (
                hist_wire(self._dp.spec,
                          int(self.dev["bins"].shape[1]) // n_mesh)
                if self.spec.rounds_slots > 0 else "psum_f32"
            )
            record_parallel_mesh(n_mesh, self.hist_wire_resolved)
            if jax.process_count() > 1:
                from .parallel.multihost import global_rows

                self.train.score = global_rows(
                    np.asarray(self.train.score), self._mesh, axis=1
                )
                if self._label_dev is not None:
                    self._label_dev = global_rows(
                        np.asarray(self._label_dev), self._mesh, axis=0
                    )
                # objective per-row device arrays follow the same global
                # row sharding (each rank contributed its shard), as
                # copies: the data set's own arrays stay as they are. The
                # HOST statistics (_bfs_label & friends) gather BEFORE
                # the swap, on every rank alike: a label derived on the
                # device (reg_sqrt) is read back for them, and np.asarray
                # on the global arrays would raise (non-addressable shards)
                o = self.objective
                if o is not None:
                    o._bfs_label()
                    o._np_weight()
                    if getattr(o, "_label_weight", None) is not None:
                        o._bfs_label_weight()
                    for attr in ("label", "weight", "_label_weight"):
                        a = getattr(o, attr, None)
                        if a is not None:
                            setattr(o, attr, global_rows(
                                np.asarray(a), self._mesh, axis=0
                            ))
        elif self._parallel_mode == "feature":
            from .parallel.feature_parallel import FeatureParallelGrower

            self._dp = FeatureParallelGrower(self._mesh, self.spec)
            with _gt.scope("boosting.device_inputs"):
                self.dev = self._dp.shard_inputs(self.dev)
            train_set.invalidate_device_cache()

    # ------------------------------------------------------------------
    def _record_collective_wire(self, n_trees: int) -> None:
        """Runtime collective wire accounting (docs/OBSERVABILITY.md):
        count an ESTIMATE of the histogram-reduce payload for n_trees
        freshly dispatched trees: 4-byte lanes x channels x columns x
        bins x leaves (data_parallel.wire_bytes_per_tree), whichever
        wire the grower resolved to (the gauge
        lgbmtpu_parallel_hist_wire names it: an int16 reduce-scatter
        ships half of this, a psum of smaller children only less than
        all leaves). Called only from host-side loop code — never
        inside a trace, where it would tick once per compile instead of
        once per dispatch."""
        if self._dp is None or self._parallel_mode != "data":
            return
        fn = getattr(self._dp, "wire_bytes_per_tree", None)
        if fn is None:
            return
        from .obs.metrics import record_collective_wire

        record_collective_wire(
            "data_parallel_grow",
            fn(int(self.dev["bins"].shape[0])) * n_trees,
        )

    # ------------------------------------------------------------------
    def _renewal_setup(self):
        """(alpha, weights) for device percentile leaf renewal, or
        (None, None) when the objective doesn't renew. MAPE renews with
        its label-derived weights (regression_objective.hpp:641)."""
        import jax.numpy as jnp

        o = self.objective
        if o is None or not o.is_renew_tree_output:
            return None, None
        alpha = float(o.renew_percentile())
        w = getattr(o, "_label_weight", None)
        if w is None:
            w = o.weight
        if w is None:
            w = jnp.ones(self.train_set.num_rows_padded(), jnp.float32)
        return alpha, w

    def _quantize(self, gk, hk, it, k, num_bins=None):
        """use_quantized_grad: discretize this tree's gradients to
        INTEGER levels + scales (gradient_discretizer.cpp
        DiscretizeGradients); traceable. `num_bins` overrides the
        public quant level count (the internal hist_dtype policy passes
        its own 256/127)."""
        import jax

        from .learner.quantize import discretize_gradients_int

        c = self.config
        key = jax.random.fold_in(
            jax.random.key(c.data_random_seed), it * self.num_class + k
        )
        return discretize_gradients_int(
            gk, hk, key, num_bins or c.num_grad_quant_bins,
            c.stochastic_rounding,
        )

    def _grow_int_packed(self, gk, hk, mask, feat_mask, valid, it, k,
                         bins=None, tables=None, with_stats=False):
        """Internal hist_dtype=int16/int8 policy (ISSUE 12): the default
        API path discretizes g/h to self._hist_levels integer levels,
        accumulates 3 narrow channels through the rounds grower's
        spec.quant machinery (scales recovered before gain math), and
        renews leaf outputs from the TRUE gradients so the public
        semantics stay within stochastic-rounding noise of bf16x2."""
        with device_phase("learner.quantize"):
            gq, hq, scale = self._quantize(gk, hk, it, k,
                                           num_bins=self._hist_levels)
        arrays, row_leaf, *stats = self._grow(
            gq, hq, mask, feat_mask, valid, it, k, gh_scale=scale,
            bins=bins, tables=tables, with_stats=with_stats,
        )
        if self._true_renew_ok:
            from .learner.quantize import renew_leaf_with_true_gradients

            arrays = arrays._replace(
                leaf_value=renew_leaf_with_true_gradients(
                    arrays.leaf_value, row_leaf, gk, hk, mask,
                    self.params, self.spec.num_leaves,
                )
            )
        return (arrays, row_leaf, *stats)

    def _grow_maybe_quantized(self, gk, hk, mask, feat_mask, valid, it, k,
                              bins=None, tables=None, with_stats=False):
        """One tree: quantize gradients first when use_quantized_grad
        (all paths — fast, fused, sync/DART, RF — share this so none can
        silently skip quantization), optionally renewing leaf outputs
        with the true gradients afterward. with_stats=True appends the
        grower's stats (grower.grow_tree) to the returned pair."""
        c = self.config
        if not c.use_quantized_grad:
            if self._int_packed and self.spec.quant:
                return self._grow_int_packed(
                    gk, hk, mask, feat_mask, valid, it, k,
                    bins=bins, tables=tables, with_stats=with_stats,
                )
            return self._grow(gk, hk, mask, feat_mask, valid, it, k,
                              bins=bins, tables=tables,
                              with_stats=with_stats)
        with device_phase("learner.quantize"):
            gq, hq, scale = self._quantize(gk, hk, it, k)
        if self.spec.quant:
            # rounds grower consumes the integer levels directly: exact
            # int histogram sums in 3 channels/slot (48 slots/MXU pass)
            arrays, row_leaf, *stats = self._grow(
                gq, hq, mask, feat_mask, valid, it, k, gh_scale=scale,
                bins=bins, tables=tables, with_stats=with_stats,
            )
        else:
            arrays, row_leaf, *stats = self._grow(
                gq * scale[0], hq * scale[1], mask, feat_mask, valid, it, k,
                bins=bins, tables=tables, with_stats=with_stats,
            )
        if c.quant_train_renew_leaf and self._quant_renew_ok:
            from .learner.quantize import renew_leaf_with_true_gradients

            arrays = arrays._replace(
                leaf_value=renew_leaf_with_true_gradients(
                    arrays.leaf_value, row_leaf, gk, hk, mask,
                    self.params, self.spec.num_leaves,
                )
            )
        return (arrays, row_leaf, *stats)

    def _apply_renewal(self, arrays, row_leaf, score_k, mask, renew_alpha,
                       renew_w, label=None):
        """Device percentile leaf refit (shared by fast + fused paths).
        `label` overrides the captured label array (the fused step
        passes its traced jit-argument copy)."""
        from .learner.renewal import renew_leaf_values

        with device_phase("boosting.renew"):
            resid = (self._label_dev if label is None else label) - score_k
            return arrays._replace(
                leaf_value=renew_leaf_values(
                    arrays.leaf_value, row_leaf, resid, renew_w * mask,
                    renew_alpha, self.spec.num_leaves,
                )
            )

    # ------------------------------------------------------------------
    def _grow(self, gk, hk, mask, feat_mask, valid, it=0, k=0, gh_scale=None,
              bins=None, tables=None, with_stats=False):
        """Grow one tree on the training set — serial, or sharded over the
        data mesh when tree_learner=data/voting (lockstep trees on every
        shard, reference data_parallel_tree_learner.cpp). Traceable: used
        both eagerly and inside the fused jit step (it may be traced).
        `bins` / `tables` override the training bin matrix and the small
        per-feature tables — the fused step passes its traced
        jit-argument copies so the executable neither embeds the matrix
        as a constant nor bakes fold-specific tables into the trace."""
        import jax

        d = self.dev if bins is None else dict(self.dev, bins=bins)
        if tables is not None:
            d = dict(d, **tables)
        rng_key = None
        if self._node_key is not None:
            rng_key = jax.random.fold_in(
                self._node_key, it * self.num_class + k
            )
        if self._dp is not None:
            args = (
                d["bins"], d["nan_bin"], d["num_bins"], d["mono"], d["is_cat"],
                gk, hk, mask, feat_mask, self.params, valid,
                d.get("bundle"), rng_key, self._group_mat, self._cegb_info,
                self._forced, gh_scale,
            )
            if self._parallel_mode == "data":
                # the rounds grower's round counts come back replicated
                return self._dp(*args, with_stats=with_stats)
            out = self._dp(*args)  # feature-parallel: the flat grower
            return (*out, None) if with_stats else out
        tree, row_leaf, *stats = grow_tree(
            d["bins"], d["nan_bin"], d["num_bins"], d["mono"], d["is_cat"],
            gk, hk, mask, feat_mask, self.params, self.spec, valid=valid,
            bundle=d.get("bundle"), rng_key=rng_key,
            group_mat=self._group_mat, cegb=self._cegb_info,
            forced=self._forced, gh_scale=gh_scale, with_stats=with_stats,
        )
        if self.train_set.num_data > F32_EVEN_ROWS:
            import jax.numpy as jnp

            # the grower carries counts in float32: a node of such a
            # table can hold a count float32 has no value for, and the
            # error rides the larger child down to a leaf (my chip run,
            # PR 36: 2 of 255 leaf counts off by a few rows at 34.6M
            # rows). The leaves' counts are taken from the rows instead,
            # as the data-parallel grower does past 2**24 rows a mesh
            # (data_parallel._recount_leaves): a leaf under 2**24 in-bag
            # rows counts exactly.
            from .learner.histogram import seg_sum

            with device_phase("learner.select"):
                tree = tree._replace(leaf_count=jnp.round(seg_sum(
                    mask[None, :], row_leaf, self.spec.num_leaves)[0]))
        return (tree, row_leaf, *stats)

    # ------------------------------------------------------------------
    def _init_score_arr(self, ds: BinnedDataset):
        import jax
        import jax.numpy as jnp

        npad = ds.num_rows_padded()
        init = ds.metadata.init_score
        # under a one-process data mesh the score is created where its
        # rows live (the training set's sharded like them, a valid
        # set's replicated), never moved there afterwards
        where = self._score_sharding(ds)
        if init is None:
            # made on the device, and one per Booster: the fused step
            # donates its score, so it is never a data set's to share
            return jnp.zeros((self.num_class, npad), jnp.float32,
                             device=where)
        score = np.zeros((self.num_class, npad), dtype=np.float32)
        init = np.asarray(init, dtype=np.float32)
        if init.size == ds.num_data * self.num_class:
            score[:, : ds.num_data] = init.reshape(self.num_class, ds.num_data)
        else:
            score[:, : ds.num_data] = init[None, :]
        return jnp.asarray(score) if where is None \
            else jax.device_put(score, where)

    def _score_sharding(self, ds: BinnedDataset):
        """Sharding of a (K, rows) score of `ds` under the one-process
        data mesh; None off it (the default device)."""
        if self._rows_mesh is None:
            return None
        from jax.sharding import NamedSharding, PartitionSpec as P

        return NamedSharding(
            self._rows_mesh,
            P(None, "data") if ds is self.train_set else P())

    def _replicated(self, x):
        """`x` on the device: replicated over the one-process data
        mesh, or off it an array of the default device."""
        import jax
        import jax.numpy as jnp

        if self._rows_mesh is None:
            return jnp.asarray(x)
        from jax.sharding import NamedSharding, PartitionSpec as P

        return jax.device_put(x, NamedSharding(self._rows_mesh, P()))

    def _add_init(self, score, k: int, init: float):
        """`score` with the init score of class k added to its row k.
        Under the one-process data mesh as one elementwise add, which
        stays where the score's rows are: the indexed update would
        broadcast `init` to a whole row on ONE chip first (218 MB at
        54.5M rows) and re-shard it."""
        if self._rows_mesh is None:
            return score.at[k].add(init)
        col = np.zeros((self.num_class, 1), np.float32)
        col[k] = init
        return score + col

    def _dev_of(self, ds: BinnedDataset) -> Dict[str, Any]:
        """device_arrays() of one of this Booster's data sets, in the
        layout this Booster computes on: the training set's is
        self.dev; a valid set's is its one-chip copy, or under the
        one-process data mesh its replicated mesh copy."""
        if ds is self.train_set:
            return self.dev
        return ds.device_arrays(self._rows_mesh, shard_rows=False)

    def _rows_of(self, ds: BinnedDataset, kind: str):
        """The label or weight of `ds` in the same layout (_dev_of)."""
        push = ds.device_label if kind == "label" else ds.device_weight
        return push(self._rows_mesh, shard_rows=ds is self.train_set)

    def add_valid(self, valid_set: BinnedDataset, name: str) -> None:
        with _gt.scope("boosting.score_init"):
            ss = _ScoreSet(
                valid_set,
                self._init_score_arr(valid_set),
                name,
                [m for m in create_metrics(self.config)],
            )
            meta = valid_set.metadata
            for m in ss.metrics:
                m.init(meta.label, meta.weight, meta.group)
        self.valids.append(ss)

    @property
    def has_init_score(self) -> bool:
        return self.train_set.metadata.init_score is not None

    # ------------------------------------------------------------------
    @property
    def models(self) -> List[Tree]:
        self._materialize()
        return self._models

    @models.setter
    def models(self, value: List[Tree]) -> None:
        self._pending = []
        self._pending_meta = []
        self._models = value

    def _materialize(self) -> None:
        """Fetch all pending device trees in ONE device_get and convert to
        host Trees; detects the reference's stop condition (an iteration
        where no class-tree could split, gbdt.cpp:429-452) after the fact
        and drops that iteration and everything behind it."""
        if not self._pending:
            return
        import jax
        import jax.numpy as jnp

        from .obs.metrics import record_tree_splits

        with _gt.scope("materialize host trees (readback)"):
            fetched = jax.device_get(self._pending)
        meta = self._pending_meta
        self._pending = []
        self._pending_meta = []
        K = self.num_class
        base = len(self._models)  # device_trees index of host[0]
        # flatten chunk-scan dispatches into the per-class-tree stream
        # the loop below expects: a _PendingChunk holds K TreeArrays
        # stacked (C, ...) — slice out each LIVE round (masked tail
        # rounds past it_end were never appended to device_trees/meta)
        host: List[Any] = []
        for item in fetched:
            if isinstance(item, _PendingChunk):
                for r in range(item.n_active):
                    for a in item.trees:
                        host.append(
                            jax.tree.map(lambda x, _r=r: x[_r], a)
                        )
            else:
                host.append(item)
        # chunk dispatches park _PENDING_SLOT placeholders in
        # device_trees; back-fill them with the host-sliced arrays so
        # rollback paths (stop detection below, fused_truncate,
        # rollback_one_iter) can traverse them. Re-wrap as jnp arrays:
        # device_trees entries are contractually jax (set_leaf_output
        # edits them with .at[].set, scoring restacks them).
        for j, a in enumerate(host):
            if self.device_trees[base + j] is _PENDING_SLOT:
                self.device_trees[base + j] = (
                    jax.tree.map(jnp.asarray, a), None
                )
        for i0 in range(0, len(host), K):
            group = host[i0 : i0 + K]
            if all(int(a.num_nodes) == 0 for a in group):
                if base + i0 == 0:
                    # first-ever iteration has no splits: keep K constant
                    # trees carrying the bias (sync path / gbdt.cpp:429-441
                    # keep the len==K model set)
                    for a, (k, bias, shrink) in zip(group, meta[i0 : i0 + K]):
                        if (
                            abs(bias) < 1e-15
                            and self.objective is not None
                            and not self.config.boost_from_average
                            and not self.has_init_score
                        ):
                            bias = self.objective.boost_from_score(k)
                            if abs(bias) > 1e-15:
                                self.train.score = self.train.score.at[k].add(bias)
                                for vs in self.valids:
                                    vs.score = vs.score.at[k].add(bias)
                        t = Tree(num_leaves=1, shrinkage=1.0)
                        t.leaf_value = np.array([bias], np.float64)
                        self._models.append(t)
                    i0 += K
                # roll back score contributions of any blindly-trained
                # later iterations that DID split (possible under bagging)
                for j in range(i0, len(host)):
                    if int(host[j].num_nodes) == 0:
                        continue
                    arrays, _ = self.device_trees[base + j]
                    k = meta[j][0]
                    leaf = self._traverse(
                        arrays, self.dev["bins"], self.dev["nan_bin"],
                        self.dev.get("bundle"),
                    )
                    self.train.score = self.train.score.at[k].add(
                        -arrays.leaf_value[leaf]
                    )
                    for vs in self.valids:
                        vdev = self._dev_of(vs.dataset)
                        vleaf = self._traverse(arrays, vdev["bins"], vdev["nan_bin"], vdev.get("bundle"))
                        vs.score = vs.score.at[k].add(-arrays.leaf_value[vleaf])
                log.warning(
                    "Stopped training because there are no more leaves that meet the split requirements"
                )
                del self.device_trees[len(self._models) :]
                self.iter_ = len(self._models) // K
                self._stopped = True
                return
            for a, (k, bias, shrink) in zip(group, meta[i0 : i0 + K]):
                n_nodes = int(a.num_nodes)
                if n_nodes > 0:
                    # device leaf_value already carries shrinkage + bias
                    tree = Tree.from_arrays(a, self.train_set, 1.0)
                    tree.shrinkage = shrink
                    record_tree_splits(tree, self.train_set.mappers,
                                       self.config.max_cat_to_onehot)
                else:
                    tree = Tree(num_leaves=1, shrinkage=1.0)
                    tree.leaf_value = np.array([bias], np.float64)
                self._models.append(tree)

    def train_one_iter(
        self, grad: Optional[np.ndarray] = None, hess: Optional[np.ndarray] = None
    ) -> bool:
        """One boosting iteration; returns True when training should stop
        (no splittable leaf), matching GBDT::TrainOneIter (gbdt.cpp:352)."""
        if self._stopped:
            return True
        # leaf renewal runs on device (learner/renewal.py), so renewal
        # objectives ride the fast path too; custom fobj with a renewal
        # objective still renews (the reference's UpdateOneIterCustom
        # calls RenewTreeOutput as well)
        fast = not self._force_sync and (
            grad is not None or self.objective is not None
        )
        if fast:
            return self._train_one_iter_fast(grad, hess)
        return self._train_one_iter_sync(grad, hess)

    def _prepare_gradients(self, grad, hess):
        """Shared per-iteration prep: boost-from-average on the first
        iteration (gbdt.cpp:327), then objective gradients at the current
        score — or padding of caller-supplied custom grad/hess.
        Returns (grad_dev (K, Np), hess_dev (K, Np), init_scores)."""
        import jax.numpy as jnp

        K = self.num_class
        ds = self.train_set
        init_scores = [0.0] * K
        if grad is None or hess is None:
            if self.objective is None:
                log.fatal("custom objective requires explicit grad/hess")
            if (
                not self._models
                and not self._pending
                and self.config.boost_from_average
                and not self.has_init_score
            ):
                for k in range(K):
                    with _gt.scope("objective.boost_from_score"):
                        init = self.objective.boost_from_score(k)
                    if abs(init) > 1e-15:
                        init_scores[k] = init
                        self.train.score = self.train.score.at[k].add(init)
                        for vs in self.valids:
                            vs.score = vs.score.at[k].add(init)
                        log.info(f"Start training from score {init:f}")
            score = self.train.score if K > 1 else self.train.score[0]
            g, h = _obj_grads(self.objective, score, self.iter_)
            grad_dev = jnp.reshape(g, (K, -1)).astype(jnp.float32)
            hess_dev = jnp.reshape(h, (K, -1)).astype(jnp.float32)
        else:
            grad = np.asarray(grad, dtype=np.float32).reshape(K, ds.num_data)
            hess = np.asarray(hess, dtype=np.float32).reshape(K, ds.num_data)
            npad = ds.num_rows_padded()
            gp = np.zeros((K, npad), np.float32)
            hp = np.zeros((K, npad), np.float32)
            gp[:, : ds.num_data] = grad
            hp[:, : ds.num_data] = hess
            grad_dev, hess_dev = jnp.asarray(gp), jnp.asarray(hp)
        # flight-recorder gh summaries (eager loops only — the fused
        # step computes its own inside the trace). Host-side float()
        # syncs, so this runs ONLY when a recorder/sentinel is active;
        # the default path stays readback-free.
        if getattr(self, "recorder", None) is not None:
            self._last_gh_norm = (
                float(jnp.sqrt(jnp.sum(grad_dev * grad_dev))),
                float(jnp.sqrt(jnp.sum(hess_dev * hess_dev))),
            )
        return grad_dev, hess_dev, init_scores

    def _train_one_iter_fast(
        self, grad: Optional[np.ndarray] = None, hess: Optional[np.ndarray] = None
    ) -> bool:
        """Sync-free iteration: no device->host reads; host trees and the
        stop check are deferred to _materialize()."""
        import jax
        import jax.numpy as jnp

        K = self.num_class
        with _gt.scope(ROUND_PHASES[0]):
            grad_dev, hess_dev, init_scores = self._prepare_gradients(
                grad, hess
            )
        renew_alpha, renew_w = self._renewal_setup()

        one = jnp.float32(1.0)
        for k in range(K):
            with _gt.scope(ROUND_PHASES[1]):
                gk, hk = grad_dev[k], hess_dev[k]
                mask, gk, hk = self.strategy.sample(
                    self.iter_, gk, hk, self.dev["valid"], self._label_dev
                )
                feat_mask = self._sample_features(k=k)
                arrays, row_leaf = self._grow_maybe_quantized(
                    gk, hk, mask, feat_mask, self.dev["valid"], self.iter_, k
                )
                ok = (arrays.num_nodes > 0).astype(jnp.float32)
                if renew_alpha is not None:
                    arrays = self._apply_renewal(
                        arrays, row_leaf, self.train.score[k], mask,
                        renew_alpha, renew_w,
                    )
                lv = arrays.leaf_value * (self.shrinkage_rate * ok)
            with _gt.scope(ROUND_PHASES[2]):
                # score updates use the UNBIASED shrunk leaf values — the
                # score already received init_scores[k] at BoostFromAverage
                # (mirrors _train_one_iter_sync; adding the bias here too
                # would double-count it)
                self.train.score = self.train.score.at[k].set(
                    add_score(self.train.score[k], row_leaf, lv, one)
                )
                for vs in self.valids:
                    vdev = self._dev_of(vs.dataset)
                    leaf = self._traverse(arrays, vdev["bins"], vdev["nan_bin"], vdev.get("bundle"))
                    vs.score = vs.score.at[k].set(
                        add_score(vs.score[k], leaf, lv, one)
                    )
                if abs(init_scores[k]) > 1e-15:
                    # AddBias (gbdt.cpp:424-426): only the STORED tree
                    # carries the boost-from-average bias
                    lv = lv + init_scores[k] * ok
                arrays = arrays._replace(leaf_value=lv)
                self.device_trees.append((arrays, None))
                self._pending.append(arrays)
                self._pending_meta.append(
                    (k, init_scores[k], self.shrinkage_rate)
                )
                # start the device->host copies now so _materialize is
                # ~free
                jax.tree.map(lambda a: a.copy_to_host_async(), arrays)

        self._record_collective_wire(K)
        self.iter_ += 1
        if self.iter_ % self._check_every == 0:
            self._materialize()
            return self._stopped
        return False

    def _train_one_iter_sync(
        self, grad: Optional[np.ndarray] = None, hess: Optional[np.ndarray] = None
    ) -> bool:
        import jax.numpy as jnp

        import time as _time

        K = self.num_class
        ds = self.train_set
        self._materialize()  # keep model list ordering if modes ever mix
        with _gt.scope(ROUND_PHASES[0]):
            grad_dev, hess_dev, init_scores = self._prepare_gradients(
                grad, hess
            )

        should_continue = False
        for k in range(K):
            with _gt.scope(ROUND_PHASES[1]):
                gk, hk = grad_dev[k], hess_dev[k]
                mask, gk, hk = self.strategy.sample(
                    self.iter_, gk, hk, self.dev["valid"], self._label_dev
                )
                feat_mask = self._sample_features(k=k)
                arrays, row_leaf = self._grow_maybe_quantized(
                    gk, hk, mask, feat_mask, self.dev["valid"], self.iter_, k
                )
            if self.config.tpu_debug_check_split:
                self._check_split(arrays, row_leaf, hk, mask)
            t_up = _time.perf_counter()
            n_nodes = int(arrays.num_nodes)
            if n_nodes > 0:
                should_continue = True
                if self._cegb_info is not None:
                    # charge coupled costs: mark this tree's features used
                    # model-wide (is_feature_used_in_split_)
                    used = self._cegb_info.used
                    nf = np.asarray(arrays.node_feature[:n_nodes])
                    used = used.at[jnp.asarray(nf)].set(True)
                    self._cegb_info = self._cegb_info._replace(used=used)
                if (
                    self.objective is not None
                    and self.objective.is_renew_tree_output
                ):
                    arrays = self._renew_tree_output(arrays, row_leaf, k, mask)
                # host tree applies shrinkage itself; device copy carries
                # the final (shrunk) leaf values for score updates
                tree = Tree.from_arrays(arrays, ds, self.shrinkage_rate)
                final_leaf = arrays.leaf_value * self.shrinkage_rate
                arrays = arrays._replace(leaf_value=final_leaf)
                one = jnp.float32(1.0)
                if self.config.linear_tree:
                    # fit ridge models on each leaf's path features
                    # (linear_tree_learner.cpp CalculateLinear) and apply
                    # per-row linear outputs to the scores
                    from .binning import BinType

                    n = ds.num_data
                    rl = np.asarray(row_leaf)[:n]
                    cat_set = {
                        int(f)
                        for f in ds.used_features
                        if ds.mappers[int(f)].bin_type == BinType.CATEGORICAL
                    }
                    tree.fit_linear_leaves(
                        rl, np.asarray(gk)[:n], np.asarray(hk)[:n],
                        ds.raw_data, cat_set, self.config.linear_lambda,
                        self.shrinkage_rate,
                        row_mask=np.asarray(mask)[:n] > 0,
                    )
                    vals = tree.linear_leaf_outputs(ds.raw_data, rl)
                    out = np.zeros(ds.num_rows_padded(), np.float32)
                    out[:n] = vals
                    self.train.score = self.train.score.at[k].add(
                        jnp.asarray(out)
                    )
                    for vs in self.valids:
                        vraw = vs.dataset.raw_data
                        vn = vs.dataset.num_data
                        vleafs = tree.predict_leaf(vraw)
                        vvals = tree.linear_leaf_outputs(vraw, vleafs)
                        vout = np.zeros(vs.dataset.num_rows_padded(), np.float32)
                        vout[:vn] = vvals
                        vs.score = vs.score.at[k].add(jnp.asarray(vout))
                else:
                    self.train.score = self.train.score.at[k].set(
                        add_score(self.train.score[k], row_leaf, final_leaf, one)
                    )
                    for vs in self.valids:
                        vdev = self._dev_of(vs.dataset)
                        leaf = self._traverse(arrays, vdev["bins"], vdev["nan_bin"], vdev.get("bundle"))
                        vs.score = vs.score.at[k].set(
                            add_score(vs.score[k], leaf, final_leaf, one)
                        )
                if abs(init_scores[k]) > 1e-15:
                    # AddBias: the stored tree (host AND device) carries the
                    # boost-from-average bias; the score got it separately at
                    # BoostFromAverage, so score == sum(stored trees) exactly
                    # (matters for DART drops, gbdt.cpp:424-426)
                    tree.leaf_value = tree.leaf_value + init_scores[k]
                    if tree.is_linear:
                        tree.leaf_const = tree.leaf_const + init_scores[k]
                    arrays = arrays._replace(
                        leaf_value=arrays.leaf_value + init_scores[k]
                    )
                self.device_trees.append((arrays, None))
                self.models.append(tree)
            else:
                # stump: constant tree (gbdt.cpp:429-441)
                bias = 0.0
                if len(self.models) < K:
                    if (
                        self.objective is not None
                        and not self.config.boost_from_average
                        and not self.has_init_score
                    ):
                        bias = self.objective.boost_from_score(k)
                        self.train.score = self.train.score.at[k].add(bias)
                        for vs in self.valids:
                            vs.score = vs.score.at[k].add(bias)
                    else:
                        bias = init_scores[k]
                t = Tree(num_leaves=1, shrinkage=1.0)
                t.leaf_value = np.array([bias], np.float64)
                self.models.append(t)
                self.device_trees.append((arrays, None))
            _gt.add(ROUND_PHASES[2], _time.perf_counter() - t_up,
                    start=t_up)

        if not should_continue:
            log.warning(
                "Stopped training because there are no more leaves that meet the split requirements"
            )
            if len(self.models) > K:
                for _ in range(K):
                    self.models.pop()
                    self.device_trees.pop()
            return True
        self._record_collective_wire(K)
        self.iter_ += 1
        return False

    # ------------------------------------------------------------------
    # Fused device loop ("fast path v2"): ONE jit dispatch per iteration
    # covering gradients -> sampling -> growth -> score updates -> metric
    # evaluation, with zero host readbacks. Trees and per-iteration metric
    # vectors accumulate as device handles; the engine fetches a whole
    # chunk in one device_get and replays callbacks host-side. This is
    # the TPU reformulation of GBDT::Train (gbdt.cpp:245): the loop body
    # is identical, only the host/device boundary moved from "every op"
    # to "every chunk": a readback is cheap by itself (~0.7 ms for a
    # scalar on a v5e) but it waits for everything dispatched before
    # it, so one per iteration serializes host and device.
    def fused_eligible(self) -> bool:
        return self.fused_ineligible_reason() is None

    def fused_ineligible_reason(self) -> Optional[str]:
        """None when the fused loop applies; otherwise a one-line reason
        (surfaced by engine.train so users know WHY they are on the
        slower per-iteration sync path)."""
        if self._force_sync:
            return (
                self._force_sync_reason
                or "this configuration requires the per-iteration sync loop"
            )
        if self.objective is None:
            return "no built-in objective (custom fobj)"
        if not getattr(self.objective, "is_device_gradients", True):
            return f"objective {self.objective.name} computes host gradients"
        if getattr(self.objective, "has_host_state", False):
            # e.g. lambdarank position-bias factors: cross-iteration
            # host-held state the fused trace could not update
            return (
                f"objective {self.objective.name} keeps cross-iteration "
                "host state (e.g. position debiasing)"
            )
        from .device_metrics import supported_names

        for ss in [self.train] + self.valids:
            if supported_names(ss.metrics) is None:
                return (
                    f"metric(s) {ss.metrics and [m.name for m in ss.metrics]}"
                    " have no device implementation"
                )
        return None

    def _build_fused(self, track_train: bool):
        import jax
        import jax.numpy as jnp

        from .device_metrics import (DeviceEvalSet, rank_eval_arrays,
                                     supported_names)
        from .learner.histogram import row_mesh
        from .learner.rounds import hist_schedule, ladder_widths

        data_mesh = self._mesh if self._parallel_mode == "data" else None

        K = self.num_class
        ds = self.train_set
        c = self.config
        # ---- every per-fold array rides the `data` jit ARGUMENT so the
        # traced step is fold-agnostic: cv folds and repeated trains
        # with identical shapes+config reuse ONE trace+executable (each
        # Booster used to pay its own trace + compile-cache
        # deserialize). Big matrices additionally
        # must be args so they are not embedded as constants (152 MB
        # jit_step, round 4). NOT donated: callers keep their handles.
        sets = ([self.train] if track_train else []) + self.valids
        eval_specs = []  # (set name, metric names, higher_better)
        eval_arrs = []  # per set: label/weight/valid device arrays, and
        # the query layout + per-query statistics of its ranking metrics
        eval_groups = []  # host group arrays (the host fallback's only)
        for ss in sets:
            names, hb = supported_names(ss.metrics)
            # each set's resident copies in this Booster's layout (under
            # a data mesh: the Dataset's mesh copies, never a one-chip
            # copy moved over)
            dev = self._dev_of(ss.dataset)
            meta = ss.dataset.metadata
            label = self._rows_of(ss.dataset, "label")
            weight = self._rows_of(ss.dataset, "weight")
            eval_specs.append((ss.name, tuple(names), tuple(hb)))
            eval_groups.append(meta.group)
            eval_arrs.append(
                {"label": label, "weight": weight, "valid": dev["valid"],
                 "rank": rank_eval_arrays(c, names, ss.dataset)}
            )
        self._f_eval_sets = [(nm, _EvalNames(list(n), list(h)))
                             for nm, n, h in eval_specs]
        n_valid_sets = len(self.valids)
        vdevs = [self._dev_of(vs.dataset) for vs in self.valids]
        frac = c.feature_fraction
        F = ds.num_used_features
        n_feat = max(1, int(np.ceil(frac * F))) if frac < 1.0 else F
        objective = self.objective
        strategy = self.strategy
        # all-numerical datasets statically skip the category-set test
        # in the per-iteration valid traversal (hot: runs inside step)
        traverse = partial(traverse_tree_bins, has_cat=self.spec.has_cat)
        renew_alpha, renew_w = self._renewal_setup()
        track_train_eval = track_train
        # flight recorder / sentinels configured -> the step also
        # returns gh norms on the eval-row tail (static at build time;
        # part of the memo key through the config string)
        want_gh = bool(
            getattr(c, "record_file", "")
            or getattr(c, "anomaly_policy", "off") != "off"
        )
        # the step ends its eval row with the rounds grower's round
        # counts: one per ladder width, the routing-only rounds, and
        # their total (under a data mesh too: every shard counts the
        # same rounds, data_parallel.py)
        ladder_ws = (
            ladder_widths(self.spec) if self.spec.rounds_slots > 0 else ()
        )
        self._f_ladder_widths = ladder_ws
        from .obs.metrics import (
            record_hist_schedule, record_split_search,
            record_traverse_cat_words)

        record_split_search(self.spec.search)
        record_traverse_cat_words(
            num_cat_words(self.spec.num_bins) if self.spec.has_cat else 0)
        if ladder_ws:
            # the schedule is a shard's: the rows one kernel call sees
            n_cols, n_rows = self.dev["bins"].shape
            n_rows //= (int(data_mesh.devices.size) if data_mesh is not None
                        else 1)
            record_hist_schedule(
                hist_schedule(self.spec, n_rows, n_cols), n_cols)
        # memo eligibility must be known BEFORE tracing. Query groups do
        # not bar it: the ranking objectives and metrics read their
        # layout, grids and per-query statistics from `data` (shapes in
        # the key below), never from a closure. What does bar it bakes
        # data of THIS booster into the trace: a forced-splits plan,
        # by-query bagging's group draw, the feature-parallel grower's
        # own arrays. A data mesh does not: the step reads every array
        # from `data`, and the mesh and the shardings are in the key
        memo_ok = (
            self._forced is None
            and not getattr(self.strategy, "by_query", False)
            and self._parallel_mode != "feature"
        )
        if memo_ok:
            # the memoized executable outlives this booster — every
            # fold-varying device attr must be in the rebind list
            _audit_fold_attrs(objective)
        closure_evals = None
        if not memo_ok:
            closure_evals = [
                DeviceEvalSet(c, list(spec[1]), list(spec[2]),
                              ea["label"], ea["weight"], ea["valid"], K,
                              group=grp, rank=ea["rank"])
                for spec, ea, grp in zip(eval_specs, eval_arrs,
                                         eval_groups)
            ]

        # the step hands each score back where it found it (_init_score_arr),
        # so a chunk's output state is its next input's layout and one
        # executable serves every dispatch
        score_at = self._score_sharding(ds)
        state_rep = None  # every other leaf of the state: replicated
        if score_at is not None:
            state_rep = jax.sharding.NamedSharding(
                data_mesh, jax.sharding.PartitionSpec())

        def step(state, data):
            # name the data mesh for the per-row Pallas kernels (score
            # update, valid traversal, leaf renewal) the step calls
            # outside the grower's shard_map
            with row_mesh(data_mesh):
                new_state, trees, eval_row = step_body(state, data)
            if score_at is not None:
                pin = jax.lax.with_sharding_constraint
                # every leaf replicated, then the training score over
                # its rows: the last constraint on a value is the one
                # that holds (the form the chip run of PR 32 measured)
                new_state = jax.tree.map(
                    lambda a: pin(a, state_rep), new_state)
                new_state["score"] = pin(new_state["score"], score_at)
            return new_state, trees, eval_row

        def step_body(state, data):
            score = state["score"]
            vscores = state["vscores"]
            it = state["it"]
            shrink = state["shrink"]
            init_vec = state["init"]
            # chunk-scan activity mask: a round is live unless the
            # no-splittable-leaf stop already fired (`stopped`, sticky)
            # or it lies past this dispatch's round budget (`it_end`,
            # the masked tail of a ladder-rung scan). Inactive rounds
            # are algebraic no-ops — zeroed leaf values freeze every
            # score and `it` stops advancing, so RNG streams and state
            # re-align bit-exactly with the per-round loop at the next
            # dispatch boundary.
            stopped = state["stopped"]
            active = jnp.logical_and(
                jnp.logical_not(stopped), it < data["it_end"]
            )
            actf = active.astype(jnp.float32)
            s_for_grad = score if K > 1 else score[0]
            # fold-varying objective attributes arrive as args: rebind
            # the traced values around the gradient call (restored right
            # after, so no tracer leaks outlive the trace)
            saved = {a: getattr(objective, a)
                     for a in data["obj_arrs"]}
            for a, v in data["obj_arrs"].items():
                setattr(objective, a, v)
            try:
                with device_phase("objective.gradients"):
                    g, h = _obj_grads(objective, s_for_grad, it)
            finally:
                for a, v in saved.items():
                    setattr(objective, a, v)
            if memo_ok:
                evals = [
                    DeviceEvalSet(c, list(spec[1]), list(spec[2]),
                                  ea["label"], ea["weight"], ea["valid"],
                                  K, rank=ea["rank"])
                    for spec, ea in zip(eval_specs, data["eval_arrs"])
                ]
            else:
                evals = closure_evals
            grad = jnp.reshape(g, (K, -1)).astype(jnp.float32)
            hess = jnp.reshape(h, (K, -1)).astype(jnp.float32)
            valid_mask = data["valid"]
            trees = []
            grew = []  # per-class split indicators (pre-mask)
            ladder_rounds = []  # per class-tree (W+1,) round counts
            for k in range(K):
                gk, hk = grad[k], hess[k]
                mask, gk, hk = strategy.sample(
                    it, gk, hk, valid_mask, data["obj_arrs"]["label"]
                )
                if frac < 1.0:
                    fkey = jax.random.fold_in(
                        jax.random.key(c.feature_fraction_seed), it * K + k
                    )
                    feat_mask = jax.random.permutation(fkey, F) < n_feat
                else:
                    feat_mask = jnp.ones(F, dtype=bool)
                arrays, row_leaf, *ladder = self._grow_maybe_quantized(
                    gk, hk, mask, feat_mask, valid_mask, it, k,
                    bins=data["bins"], tables=data["tables"],
                    with_stats=bool(ladder_ws),
                )
                ladder_rounds += [st["rounds"] for st in ladder]
                grew.append(arrays.num_nodes > 0)
                # `actf` folds the activity mask in: post-stop / masked-
                # tail rounds store zeroed leaf values (ok=0), so every
                # score update and rollback subtraction below is an
                # exact 0.0 and the carried state stays frozen
                ok = (arrays.num_nodes > 0).astype(jnp.float32) * actf
                if renew_alpha is not None:
                    # percentile leaf refit on device (RenewTreeOutput,
                    # gbdt.cpp:418 — before shrinkage, in-bag rows only)
                    arrays = self._apply_renewal(
                        arrays, row_leaf, score[k], mask, renew_alpha,
                        data["renew_w"],
                        label=data["obj_arrs"]["label"],
                    )
                with device_phase("boosting.score_update"):
                    lv = arrays.leaf_value * (shrink * ok)
                    one = jnp.float32(1.0)
                    score = score.at[k].set(
                        add_score(score[k], row_leaf, lv, one)
                    )
                new_vs = []
                for vi in range(n_valid_sets):
                    with device_phase("metrics.valid_eval"):
                        vleaf = traverse(
                            arrays, data["vbins"][vi],
                            data["vtables"][vi]["nan_bin"],
                            data["vtables"][vi].get("bundle"),
                        )
                        new_vs.append(
                            vscores[vi].at[k].set(
                                add_score(vscores[vi][k], vleaf, lv, one)
                            )
                        )
                vscores = tuple(new_vs)
                # stored tree carries the boost-from-average bias on
                # the first iteration only (AddBias, gbdt.cpp:424);
                # the score got it at fused_start
                lv_stored = lv + init_vec[k] * ok * (it == 0)
                trees.append(arrays._replace(leaf_value=lv_stored))
            # metric evaluation entirely on device
            eval_scores = ([score] if track_train_eval else []) + list(vscores)
            with device_phase("metrics.valid_eval"):
                rows = [f(s) for f, s in zip(evals, eval_scores)]
                eval_row = (
                    # `rows` is a host list: truthiness = len, not a tracer
                    jnp.concatenate(rows) if rows else jnp.zeros(0, jnp.float32)  # lint: allow[tracer-branch]
                )
            # gradient/hessian norm summaries ride the eval row's tail
            # (two scalars; fused_collect slices them off) so the
            # flight recorder gets per-round gh norms from the fused
            # loop with zero extra readbacks (docs/OBSERVABILITY.md).
            # Gated on the recorder config so the DEFAULT step keeps
            # its exact trace — persistent compile-cache entries and
            # the step memo stay valid for non-recorded runs.
            if want_gh:
                gh_row = jnp.stack([
                    jnp.sqrt(jnp.sum(grad * grad)),
                    jnp.sqrt(jnp.sum(hess * hess)),
                ])
                eval_row = jnp.concatenate([eval_row, gh_row])
            # the rounds grower's per-width round counts (rounds.py
            # ladder) end the row, summed over the class trees, for
            # lgbmtpu_grower_rounds_total: they ride this readback too
            if ladder_rounds:
                eval_row = jnp.concatenate(
                    [eval_row, sum(ladder_rounds).astype(jnp.float32)]
                )
            # the reference's stop condition (no class-tree could split,
            # gbdt.cpp:429-452) carried as a sticky device mask: once an
            # ACTIVE round grows K stumps, every later round in this and
            # any subsequent chunk is a no-op. `it` advances only on
            # active rounds so the fold_in(seed, it*K+k) RNG streams of
            # masked tail rounds are never consumed — the next chunk
            # replays them bit-exactly as live rounds.
            all_stump = jnp.logical_not(
                jnp.any(jnp.stack(grew))
            )
            new_state = {
                "score": score,
                "vscores": vscores,
                "it": it + active.astype(jnp.int32),
                "shrink": shrink,
                "init": init_vec,
                "stopped": jnp.logical_or(
                    stopped, jnp.logical_and(active, all_stump)
                ),
            }
            return new_state, tuple(trees), eval_row

        self._f_data = {
            "bins": self.dev["bins"],
            "vbins": [vd["bins"] for vd in vdevs],
            "tables": {k: self.dev[k] for k in
                       ("nan_bin", "num_bins", "mono", "is_cat")},
            "vtables": [
                {"nan_bin": vd["nan_bin"], "bundle": vd.get("bundle")}
                for vd in vdevs
            ],
            "valid": self.dev["valid"],
            "obj_arrs": {
                a: (jnp.float32(v) if isinstance(v, float) else v)
                for a in _OBJ_FOLD_ATTRS
                for v in [getattr(objective, a, None)]
                if v is not None
            },
            "renew_w": renew_w,
            "eval_arrs": eval_arrs,
            # absolute round limit for the current dispatch; overwritten
            # by fused_dispatch before every launch. Rides `data` (not
            # the carry) so a ladder-rung scan of ANY requested length
            # reuses one executable — the masked tail handles the rest.
            "it_end": jnp.int32(0),
        }
        if self.dev.get("bundle") is not None:
            self._f_data["tables"]["bundle"] = self.dev["bundle"]

        # ---- process-level step memo: reuse the traced+compiled step
        # across Boosters (cv folds, repeated trains) when nothing
        # STATIC differs. The key covers the full resolved config, the
        # grower spec, objective/strategy classes, and the (state, data)
        # pytree structure with shapes+dtypes.
        key = None
        if memo_ok:
            # under a data mesh a leaf's sharding (mesh devices, axis
            # name, partition) is part of its fingerprint; off it the
            # fingerprint is shape and dtype as before
            data_fp = jax.tree.map(
                lambda a: (getattr(a, "shape", None),
                           str(getattr(a, "dtype", type(a))))
                + ((str(getattr(a, "sharding", None)),)
                   if data_mesh is not None else ()),
                self._f_data,
            )
            key = (
                type(self).__name__, K, track_train, self.spec,
                type(objective).__name__, type(strategy).__name__,
                str(sorted((k2, str(v)) for k2, v in c._values.items())),
                str(eval_specs), str(data_fp), n_valid_sets, data_mesh,
            )
            cached = _FUSED_STEP_CACHE.get(key)
            if cached is not None:
                _FUSED_STEP_CACHE.move_to_end(key)  # LRU touch
                self._f_program = cached
                return
        # donate the loop state on accelerators (scores are the big
        # per-iteration buffers); NOT on CPU — XLA:CPU donation has
        # produced heap corruption under this runtime (malloc-internal
        # segfaults mid-suite, always under a fused_dispatch frame),
        # and CPU runs are tests/CI where the extra score copy is noise
        donate = () if platform() == "cpu" else (0,)
        self._f_program = _FusedProgram(step, donate)
        if key is not None:
            _FUSED_STEP_CACHE[key] = self._f_program
            while len(_FUSED_STEP_CACHE) > _FUSED_STEP_CACHE_MAX:
                _FUSED_STEP_CACHE.popitem(last=False)

    def fused_start(self, track_train: bool) -> None:
        """Initialize the device loop state; performs BoostFromAverage."""
        with _gt.scope("boosting.fused_start"):
            self._fused_start(track_train)

    def _fused_start(self, track_train: bool) -> None:
        import jax.numpy as jnp

        K = self.num_class
        init_scores = [0.0] * K
        if (
            not self._models
            and not self._pending
            and self.config.boost_from_average
            and not self.has_init_score
        ):
            for k in range(K):
                with _gt.scope("objective.boost_from_score"):
                    init = self.objective.boost_from_score(k)
                if abs(init) > 1e-15:
                    init_scores[k] = init
                    self.train.score = self._add_init(
                        self.train.score, k, init)
                    for vs in self.valids:
                        vs.score = self._add_init(vs.score, k, init)
                    log.info(f"Start training from score {init:f}")
        self._init_scores = init_scores
        with _gt.scope("boosting.build_step"):
            self._build_fused(track_train)
        # under the one-process data mesh the small leaves of the state
        # start where a chunk hands them back, replicated on the mesh:
        # uncommitted on one chip they made a job's FIRST dispatch
        # another program than its second (two traces, two compiles)
        at = self._replicated
        self._fstate = {
            "score": self.train.score,
            "vscores": tuple(vs.score for vs in self.valids),
            "it": at(jnp.int32(self.iter_)),
            "shrink": at(jnp.float32(self.shrinkage_rate)),
            "init": at(np.asarray(init_scores, np.float32)),
            "stopped": at(np.asarray(False)),
        }
        # entries are (device rows, n_active): a chunk's (C, E) stack
        # whose first n_active rows are live — fused_collect slices on
        # the host
        self._f_evals: List[Tuple[Any, int]] = []
        self._last_dispatch_rounds = []

    def fused_dispatch(self, n: int) -> None:
        """Dispatch n fused iterations without any host synchronization.

        n is greedily decomposed over the ``config.DEFAULT_CHUNK_LADDER``
        rungs, largest-first, and each rung launches ONE jitted
        ``lax.scan`` of the per-round step — one executable launch and
        one host pytree unpack per CHUNK instead of per round, the
        all-device inner loop of ROADMAP item 2. A remainder shorter
        than the smallest rung still dispatches
        that rung: rounds at or past ``it_end`` are algebraic no-ops on
        device (zeroed leaf values, frozen scores/``it``) and their
        stacked outputs are sliced off at materialize, so truncation is
        exact and no chunk size ever retraces.

        The ``FUSED_ROUND_PHASE`` span covers one DISPATCH (a whole
        chunk) and only its async host cost — device time lands in
        "fused collect"; per-dispatch round counts land in
        ``_last_dispatch_rounds`` so the flight recorder can apportion
        the span across rounds.
        """
        import jax.numpy as jnp

        # read at call time: tests swap the ladder for (1,)
        from .config import DEFAULT_CHUNK_LADDER

        if n <= 0:
            return
        K = self.num_class
        self._last_dispatch_rounds = []
        self._f_data["it_end"] = jnp.int32(self.iter_ + n)
        left = n
        while left > 0:
            length = _pick_chunk(left, DEFAULT_CHUNK_LADDER)
            n_act = min(length, left)
            chunk_fn = self._f_program.chunk(length)
            with _gt.scope(FUSED_ROUND_PHASE):
                self._fstate, trees, eval_mat = chunk_fn(
                    self._fstate, self._f_data
                )
            self.fused_dispatch_count += 1
            self._last_dispatch_rounds.append(n_act)
            self._pending.append(_PendingChunk(trees, length, n_act))
            for _r in range(n_act):
                for k in range(K):
                    self.device_trees.append(_PENDING_SLOT)
                    self._pending_meta.append(
                        (k,
                         self._init_scores[k] if self.iter_ == 0 else 0.0,
                         self.shrinkage_rate)
                    )
                self.iter_ += 1
            self._f_evals.append((eval_mat, n_act))
            left -= n_act
        self._record_collective_wire(n * K)
        # keep canonical score handles current (no sync; handle reassign)
        self.train.score = self._fstate["score"]
        for vs, s in zip(self.valids, self._fstate["vscores"]):
            vs.score = s

    def fused_collect(self) -> List[List[Tuple[str, str, float, bool]]]:
        """One chunk boundary: fetch eval rows + materialize trees.
        Returns per-iteration evaluation tuple lists (possibly truncated
        when the no-splittable-leaf stop condition fired mid-chunk)."""
        import jax

        n_iter_before = len(self._models) // self.num_class
        evals = self._f_evals
        self._f_evals = []
        rows: List[np.ndarray] = []
        if evals:
            # ONE batched readback over the chunks' (C, E) stacks, each
            # host-sliced to its live rounds (the masked tail never
            # produced real evals)
            fetched = jax.device_get([e for e, _na in evals])
            for got, (_e, n_act) in zip(fetched, evals):
                rows.extend(np.asarray(got)[:n_act])
        mat = (
            np.stack(rows) if rows else np.zeros((0, 0), np.float32)
        )
        widths = self._f_ladder_widths
        if widths and mat.shape[0]:
            from .learner.rounds import ROUTE_LABEL
            from .obs.metrics import record_grower_rounds

            # the row's tail: per width, routing-only, then total
            n = len(widths) + 2
            record_grower_rounds(widths + (ROUTE_LABEL,),
                                 mat[:, -n:-1].sum(axis=0))
            mat = mat[:, :-n]
        self._materialize()
        n_iter_after = len(self._models) // self.num_class
        produced = n_iter_after - n_iter_before
        records: List[List[Tuple[str, str, float, bool]]] = []
        gh_rows: List[Tuple[float, float]] = []
        for r in range(min(produced, mat.shape[0])):
            row = mat[r]
            out: List[Tuple[str, str, float, bool]] = []
            j = 0
            for name, des in self._f_eval_sets:
                for mname, hb in zip(des.names, des.higher_better):
                    out.append((name, mname, float(row[j]), hb))
                    j += 1
            records.append(out)
            # the step appends [gnorm, hnorm] after the metric columns
            # (see _build_fused) — slice them off for the recorder
            if row.shape[0] >= j + 2:
                gh_rows.append((float(row[j]), float(row[j + 1])))
        self._last_gh_rows = gh_rows
        return records

    def fused_truncate(self, n_iters: int) -> None:
        """Drop models beyond n_iters iterations (early stop fired before
        the chunk boundary; matches reference stop-at-callback timing).
        Rolls the dropped trees' contributions back out of the train and
        valid scores so booster state stays consistent with the stored
        model (same contract as rollback_one_iter)."""
        K = self.num_class
        self._materialize()
        for mi in range(n_iters * K, len(self.device_trees)):
            arrays, _ = self.device_trees[mi]
            k = mi % K
            if self._models[mi].num_leaves > 1:
                leaf = self._traverse(arrays, self.dev["bins"], self.dev["nan_bin"], self.dev.get("bundle"))
                self.train.score = self.train.score.at[k].add(
                    -arrays.leaf_value[leaf]
                )
                for vs in self.valids:
                    vdev = self._dev_of(vs.dataset)
                    vleaf = self._traverse(arrays, vdev["bins"], vdev["nan_bin"], vdev.get("bundle"))
                    vs.score = vs.score.at[k].add(-arrays.leaf_value[vleaf])
        del self._models[n_iters * K:]
        del self.device_trees[n_iters * K:]
        self.iter_ = min(self.iter_, n_iters)

    # ------------------------------------------------------------------
    def _sample_features(self, it=None, k: int = 0):
        """Per-tree feature_fraction mask (ColSampler, col_sampler.hpp:20).
        Keyed on (feature_fraction_seed, iter*K + k) so the sync and fused
        paths draw identical masks for the same iteration."""
        import jax
        import jax.numpy as jnp

        F = self.train_set.num_used_features
        frac = self.config.feature_fraction
        if frac >= 1.0:
            return jnp.ones(F, dtype=bool)
        n = max(1, int(np.ceil(frac * F)))
        if it is None:
            it = self.iter_
        fkey = jax.random.fold_in(
            jax.random.key(self.config.feature_fraction_seed),
            it * self.num_class + k,
        )
        return jax.random.permutation(fkey, F) < n

    def _check_split(self, arrays, row_leaf, hk, mask) -> None:
        """USE_DEBUG split validation (serial_tree_learner.h:174
        CheckSplit / cuda_single_gpu_tree_learner.hpp:72
        CheckSplitValid): recompute per-leaf counts and hessian sums
        from the PARTITION (row->leaf) and assert they match the
        histogram-derived tree arrays — catches kernel/partition drift
        at the iteration it happens. Sync path only
        (tpu_debug_check_split=true)."""
        from .parallel.multihost import host_global_array

        L = self.spec.num_leaves
        rl = host_global_array(row_leaf)
        m = host_global_array(mask)
        h = host_global_array(hk)
        n_nodes = int(arrays.num_nodes)
        if n_nodes <= 0:
            return
        ok = (rl >= 0) & (m > 0)
        cnt = np.bincount(rl[ok], minlength=L).astype(np.float64)
        hw = (h * m).astype(np.float64)  # the grower sums hess * mask
        hsum = np.bincount(rl[ok], weights=hw[ok], minlength=L)
        if self.config.use_quantized_grad:
            # quantized growth sums DISCRETIZED hessians; only the
            # partition counts are comparable against raw hk
            hsum = None
        t_cnt = np.asarray(arrays.leaf_count, np.float64)
        t_h = np.asarray(arrays.leaf_weight, np.float64)
        nl = n_nodes + 1
        if not np.allclose(cnt[:nl], t_cnt[:nl], atol=0.5):
            bad = int(np.argmax(np.abs(cnt[:nl] - t_cnt[:nl])))
            log.fatal(
                f"CheckSplit: leaf {bad} partition count {cnt[bad]} != "
                f"histogram-derived count {t_cnt[bad]} "
                f"(iteration {self.iter_})"
            )
        if hsum is not None and not np.allclose(
            hsum[:nl], t_h[:nl], rtol=1e-3, atol=1e-3
        ):
            bad = int(np.argmax(np.abs(hsum[:nl] - t_h[:nl])))
            log.fatal(
                f"CheckSplit: leaf {bad} partition hessian sum "
                f"{hsum[bad]} != histogram-derived {t_h[bad]} "
                f"(iteration {self.iter_})"
            )

    def _renew_tree_output(
        self, arrays: TreeArrays, row_leaf, k: int, mask, resid=None
    ) -> TreeArrays:
        """Percentile leaf refit for l1/huber/quantile/mape
        (RegressionL1loss::RenewTreeOutput). RF passes its own residuals
        (label - init score, rf.hpp residual_getter)."""
        import jax
        import jax.numpy as jnp

        ds = self.train_set
        if jax.process_count() > 1:
            # global-row view: fetch the sharded arrays whole and build
            # label/weight in the same process-concatenated PADDED
            # layout (padding rows carry mask 0, so `bag` drops them)
            from .parallel.multihost import gather_host_rows, host_global_array

            rl = host_global_array(row_leaf)
            bag = host_global_array(mask) > 0
            label = gather_host_rows(
                ds.padded(ds.metadata.label).astype(np.float64)
            )
            if resid is None:
                score = host_global_array(
                    self.train.score[k]
                ).astype(np.float64)
                resid = label - score
            if ds.metadata.weight is not None:
                w = gather_host_rows(
                    ds.padded(ds.metadata.weight).astype(np.float64)
                )
            else:
                w = np.ones(len(label))
            if hasattr(self.objective, "_label_weight"):  # mape
                w = host_global_array(
                    self.objective._label_weight
                ).astype(np.float64)
        else:
            n = ds.num_data
            rl = np.asarray(row_leaf)[:n]
            bag = np.asarray(mask)[:n] > 0
            label = np.asarray(ds.metadata.label, dtype=np.float64)
            if resid is None:
                score = np.asarray(self.train.score[k])[:n].astype(np.float64)
                resid = label - score
            w = (
                np.asarray(ds.metadata.weight, dtype=np.float64)
                if ds.metadata.weight is not None
                else np.ones(n)
            )
            if hasattr(self.objective, "_label_weight"):  # mape
                w = np.asarray(self.objective._label_weight)[:n].astype(np.float64)
        alpha = self.objective.renew_percentile()
        lv = np.asarray(arrays.leaf_value).copy()
        n_leaves = int(arrays.num_nodes) + 1
        for leaf in range(n_leaves):
            sel = (rl == leaf) & bag
            if not np.any(sel):
                continue
            r, ww = resid[sel], w[sel]
            order = np.argsort(r)
            cw = np.cumsum(ww[order])
            t = alpha * cw[-1]
            idx = min(int(np.searchsorted(cw, t)), len(r) - 1)
            lv[leaf] = r[order][idx]
        return arrays._replace(leaf_value=jnp.asarray(lv))

    # ------------------------------------------------------------------
    def rollback_one_iter(self) -> None:
        """GBDT::RollbackOneIter (gbdt.cpp:462)."""
        if self.iter_ <= 0:
            return
        K = self.num_class
        for k in reversed(range(K)):
            tree = self.models.pop()
            arrays, _ = self.device_trees.pop()
            if tree.num_leaves > 1:
                leaf = self._traverse(arrays, self.dev["bins"], self.dev["nan_bin"], self.dev.get("bundle"))
                self.train.score = self.train.score.at[k].add(-arrays.leaf_value[leaf])
                for vs in self.valids:
                    vdev = self._dev_of(vs.dataset)
                    vleaf = self._traverse(arrays, vdev["bins"], vdev["nan_bin"], vdev.get("bundle"))
                    vs.score = vs.score.at[k].add(-arrays.leaf_value[vleaf])
            else:
                # stump: its constant (boost-from-score bias) was added to
                # the scores directly — remove it too
                bias = float(tree.leaf_value[0])
                if abs(bias) > 1e-15:
                    self.train.score = self.train.score.at[k].add(-bias)
                    for vs in self.valids:
                        vs.score = vs.score.at[k].add(-bias)
        self.iter_ -= 1

    # ------------------------------------------------------------------
    def eval_set(self, ss: _ScoreSet) -> List[Tuple[str, str, float, bool]]:
        n = ss.dataset.num_data
        score = np.asarray(ss.score)[:, :n].astype(np.float64)
        s = score if self.num_class > 1 else score[0]
        out = []
        for m in ss.metrics:
            for name, val, hb in m.eval(s):
                out.append((ss.name, name, val, hb))
        return out

    def eval_train(self):
        return self.eval_set(self.train)

    def eval_valid(self):
        out = []
        for vs in self.valids:
            out.extend(self.eval_set(vs))
        return out

    def get_score(self, ss: _ScoreSet) -> np.ndarray:
        n = ss.dataset.num_data
        return np.asarray(ss.score)[:, :n].astype(np.float64)

    # ------------------------------------------------------------------
    def num_trees(self) -> int:
        return len(self.models)

    def current_iteration(self) -> int:
        return self.iter_

    def _single_row_predictor(self, start: int, end: int):
        """Packed low-latency predictor (c_api.cpp:66
        SingleRowPredictorInner): all trees' node arrays stacked into
        (T, M) matrices ONCE, so a single row walks every tree in
        lockstep with ~max_depth vectorized steps instead of T Python
        dispatches. Numeric splits only; categorical / linear models
        return None (batch path). Cached per (start, end, model count)."""
        key = (start, end, len(self.models))
        cached = getattr(self, "_srp_cache", None)
        if cached is not None and cached[0] == key:
            return cached[1]
        from .tree import _CAT_MASK

        K = self.num_class
        models = [self.models[it * K + k]
                  for it in range(start, end) for k in range(K)]
        if not models or any(
            t.is_linear or (np.asarray(t.decision_type) & _CAT_MASK).any()
            for t in models
        ):
            self._srp_cache = (key, None)
            return None
        T = len(models)
        M = max(max(t.num_leaves - 1, 1) for t in models)
        L = max(t.num_leaves for t in models)
        feat = np.zeros((T, M), np.int64)
        thr = np.zeros((T, M), np.float64)
        mt = np.zeros((T, M), np.int8)  # missing type
        dl = np.zeros((T, M), bool)  # default left
        lc = np.zeros((T, M), np.int64)
        rc = np.zeros((T, M), np.int64)
        lv = np.zeros((T, L), np.float64)
        cls = np.zeros(T, np.int64)
        cur0 = np.zeros(T, np.int64)
        for t, m in enumerate(models):
            n = max(m.num_leaves - 1, 0)
            if n == 0:
                cur0[t] = -1  # stump: straight to leaf 0
            else:
                feat[t, :n] = m.split_feature[:n]
                thr[t, :n] = m.threshold[:n]
                dt = np.asarray(m.decision_type[:n], np.int64)
                mt[t, :n] = (dt >> 2) & 3
                dl[t, :n] = (dt & 2) != 0
                lc[t, :n] = m.left_child[:n]
                rc[t, :n] = m.right_child[:n]
            lv[t, : m.num_leaves] = m.leaf_value[: m.num_leaves]
            cls[t] = t % K
        srp = dict(feat=feat, thr=thr, mt=mt, dl=dl, lc=lc, rc=rc, lv=lv,
                   cls=cls, cur0=cur0, T=T, K=K)
        self._srp_cache = (key, srp)
        return srp

    def _predict_one_packed(self, srp, x: np.ndarray) -> np.ndarray:
        """One row through the packed predictor -> (K,) raw margins."""
        tidx = np.arange(srp["T"])
        cur = srp["cur0"].copy()
        active = cur >= 0
        while active.any():
            nodes = np.where(active, cur, 0)
            f = srp["feat"][tidx, nodes]
            v = x[f]
            m = srp["mt"][tidx, nodes]
            isna = np.isnan(v)
            miss = np.where(m == 2, isna,
                            (m == 1) & (isna | (np.abs(v) <= 1e-35)))
            v = np.where(isna & (m != 2), 0.0, v)
            gl = np.where(miss, srp["dl"][tidx, nodes],
                          v <= srp["thr"][tidx, nodes])
            nxt = np.where(gl, srp["lc"][tidx, nodes], srp["rc"][tidx, nodes])
            cur = np.where(active, nxt, cur)
            active = cur >= 0
        vals = srp["lv"][tidx, ~cur]
        out = np.zeros(srp["K"])
        np.add.at(out, srp["cls"], vals)
        return out

    def predict_raw(
        self,
        X: np.ndarray,
        start_iteration: int = 0,
        num_iteration: int = -1,
        early_stop: Optional[Tuple[int, float]] = None,
    ) -> np.ndarray:
        """Raw margin prediction over host trees (gbdt_prediction.cpp).

        early_stop = (freq, margin_threshold) enables the reference's
        per-row prediction early stop (prediction_early_stop.cpp): every
        freq iterations, rows whose margin — 2|p| for binary/regression,
        top1-top2 for multiclass — exceeds the threshold stop
        accumulating further trees (vectorized over rows here)."""
        X = np.asarray(X, dtype=np.float64)
        K = self.num_class
        n_iters = len(self.models) // K
        end = n_iters if num_iteration <= 0 else min(n_iters, start_iteration + num_iteration)
        out = np.zeros((K, X.shape[0]))
        if early_stop is None and X.shape[0] <= 4:
            # latency path: a handful of rows costs less through the
            # packed lockstep walk than through T per-tree dispatches
            srp = self._single_row_predictor(start_iteration, end)
            if srp is not None:
                for r in range(X.shape[0]):
                    out[:, r] = self._predict_one_packed(srp, X[r])
                if self.average_output and end > start_iteration:
                    out /= end - start_iteration
                return out
        if early_stop is None:
            # batch fast path: the native threaded walker does ~50M
            # row-trees/s vs ~1.4M for the numpy level walk; linear-leaf
            # trees keep the host path (per-leaf ridge outputs)
            if (X.shape[0] > 256
                    and not any(t.is_linear for t in self.models)):
                from . import native

                pm = self._packed_model()
                if pm is not None:
                    X = np.ascontiguousarray(X)  # once, not per class
                    ok = True
                    for k in range(K):
                        idx = np.arange(start_iteration, end) * K + k
                        res = native.predict_packed(
                            pm, X, idx.astype(np.int32)
                        )
                        if res is None:
                            ok = False
                            break
                        out[k] = res
                    if ok:
                        if self.average_output and end > start_iteration:
                            out /= end - start_iteration
                        return out
                    out[:] = 0.0  # partial fill must not double-count
            for it in range(start_iteration, end):
                for k in range(K):
                    out[k] += self.models[it * K + k].predict(X)
        else:
            freq, margin_thr = early_stop
            active = np.ones(X.shape[0], bool)
            Xa = X  # resliced only when rows deactivate
            for it in range(start_iteration, end):
                for k in range(K):
                    out[k][active] += self.models[it * K + k].predict(Xa)
                if (it - start_iteration + 1) % max(freq, 1) == 0:
                    if K >= 2:
                        part = np.partition(out[:, active], K - 2, axis=0)
                        margin = part[K - 1] - part[K - 2]
                    else:
                        margin = 2.0 * np.abs(out[0][active])
                    keep = margin <= margin_thr
                    idx = np.flatnonzero(active)
                    active[idx[~keep]] = False
                    if not active.any():
                        break
                    Xa = X[active]
        if self.average_output and end > start_iteration:
            out /= end - start_iteration
        return out

    def _packed_model(self):
        """Flat native-predictor arrays; rebuilt per call (packing is
        ~ms against the walk it accelerates, and models mutate in place
        through refit/set_leaf_output/rollback so caching would need
        invalidation hooks at every mutation site)."""
        try:
            from . import native

            if native.get_lib() is None:
                return None
            return native.PackedModel(self.models)
        except Exception:  # noqa: BLE001 — fall back to the host walk
            return None

    def predict(self, X, start_iteration=0, num_iteration=-1, raw_score=False,
                early_stop=None):
        raw = self.predict_raw(X, start_iteration, num_iteration,
                               early_stop=early_stop)
        if not raw_score and self.objective is not None:
            raw = self.objective.convert_output(raw)
        if self.num_class == 1:
            return raw[0]
        return raw.T  # (N, K)

    def predict_leaf_index(self, X, start_iteration=0, num_iteration=-1):
        X = np.asarray(X, dtype=np.float64)
        K = self.num_class
        n_iters = len(self.models) // K
        end = n_iters if num_iteration <= 0 else min(n_iters, start_iteration + num_iteration)
        cols = []
        for it in range(start_iteration, end):
            for k in range(K):
                cols.append(self.models[it * K + k].predict_leaf(X))
        return np.stack(cols, axis=1) if cols else np.zeros((X.shape[0], 0), np.int64)

    def predict_contrib(self, X, start_iteration=0, num_iteration=-1):
        """SHAP feature contributions (tree.h:140 PredictContrib)."""
        from .shap import predict_contrib

        X = np.asarray(X, dtype=np.float64)
        nf = self.train_set.num_total_features if self.train_set else len(
            getattr(self, "feature_names", []) or []
        )
        if nf == 0:
            nf = max((int(np.max(t.split_feature)) for t in self.models
                      if len(t.split_feature)), default=-1) + 1
            nf = max(nf, X.shape[1])
        return predict_contrib(
            self.models, X, nf, self.num_class, start_iteration,
            num_iteration, self.average_output,
        )

    def refit(self, X: np.ndarray, label: np.ndarray, weight=None, group=None) -> None:
        """Refit leaf values of the existing tree structures on new data
        (gbdt.cpp:266 RefitTree + tree_learner FitByExistingTree): walk
        each model tree over the new rows, recompute leaf outputs from
        the objective's gradients at the progressively-updated score, and
        blend with refit_decay_rate."""
        import jax.numpy as jnp

        X = np.asarray(X, dtype=np.float64)
        label = np.asarray(label, dtype=np.float32)
        N = X.shape[0]
        K = self.num_class
        c = self.config
        decay = c.refit_decay_rate
        lam = c.lambda_l2

        # leaf assignment of every (row, model tree) on the new data
        leaf_pred = self.predict_leaf_index(X)  # (N, num_models)

        # a featureless data set so a fresh objective can init on the new
        # data (row_block 1 = no padding: gradients run in plain numpy here)
        from .dataset import Metadata
        from .objectives import create_objective

        md = Metadata(
            label=label,
            weight=None if weight is None else np.asarray(weight, np.float32),
            group=None if group is None else np.asarray(group, np.int32),
        )
        obj = create_objective(c)
        if obj is None:
            log.fatal("Cannot refit without an objective function")
        obj.init(BinnedDataset(
            bins=np.empty((0, N), np.uint8), mappers=[],
            used_features=np.empty(0, np.int64), num_data=N, metadata=md,
            feature_names=[], max_num_bin=1, row_block=1,
        ))

        score = np.zeros((K, N), np.float64)
        for it in range(len(self.models) // K):
            gs, hs = _obj_grads(obj, jnp.asarray(
                score if K > 1 else score[0], jnp.float32), it)
            gs = np.asarray(gs, np.float64).reshape(K, N)
            hs = np.asarray(hs, np.float64).reshape(K, N)
            for k in range(K):
                mi = it * K + k
                t = self.models[mi]
                g, h = gs[k], hs[k]
                leaves = leaf_pred[:, mi]
                sum_g = np.bincount(leaves, weights=g, minlength=t.num_leaves)
                sum_h = np.bincount(leaves, weights=h, minlength=t.num_leaves)
                shrink = t.shrinkage
                # full CalculateSplittedLeafOutput: L1 soft-threshold +
                # max_delta_step clip (feature_histogram.hpp, mirrored by
                # learner/split.py leaf_output)
                tg = np.sign(sum_g) * np.maximum(np.abs(sum_g) - c.lambda_l1, 0.0)
                new_out = np.where(
                    sum_h + lam > 1e-15, -tg / (sum_h + lam), 0.0
                )
                if c.max_delta_step > 0.0:
                    new_out = np.clip(new_out, -c.max_delta_step, c.max_delta_step)
                new_out = new_out * shrink
                # cover stats (leaf_count/internal_count) stay as trained,
                # like the reference's FitByExistingTree
                t.leaf_value = decay * t.leaf_value + (1.0 - decay) * new_out
                score[k] += t.leaf_value[leaves]
        # keep device copies consistent (device leaf_value mirrors the
        # final host leaf_value, see train_one_iter)
        for mi, (arrays, aux) in enumerate(self.device_trees):
            if mi < len(self.models):
                lv = arrays.leaf_value
                host = np.zeros(lv.shape, np.float32)
                n = min(len(host), len(self.models[mi].leaf_value))
                host[:n] = self.models[mi].leaf_value[:n]
                self.device_trees[mi] = (
                    arrays._replace(leaf_value=jnp.asarray(host)), aux
                )

    def feature_importance(self, importance_type: str = "split") -> np.ndarray:
        nf = self.train_set.num_total_features if self.train_set else (
            max((int(np.max(t.split_feature)) for t in self.models if len(t.split_feature)), default=-1) + 1
        )
        imp = np.zeros(nf)
        for t in self.models:
            if importance_type == "gain":
                imp += t.feature_importance_gain(nf)
            else:
                imp += t.feature_importance_split(nf)
        return imp


# ======================================================================
class DART(GBDT):
    """DART: Dropouts meet Multiple Additive Regression Trees
    (reference src/boosting/dart.hpp:23).

    Before each iteration a random subset of past iterations is dropped:
    their score contributions are removed so gradients see the reduced
    ensemble, the new tree is trained with shrinkage lr/(1+k), and the
    dropped trees are permanently renormalized by k/(k+1) (xgboost mode:
    lr/(lr+k) and k/(lr+k)) — dart.hpp DroppingTrees/Normalize.
    """

    def __init__(self, config: Config, train_set: Optional[BinnedDataset]):
        super().__init__(config, train_set)
        self._force_sync = True  # dropout mutates past trees every iter
        self._force_sync_reason = "DART dropout mutates past trees every iteration"
        self._tree_weight: List[float] = []  # per-iteration weights
        self._sum_weight = 0.0
        self._pending_drops: Optional[List[int]] = None

    def _tree_score_delta(self, ss: _ScoreSet, arrays: TreeArrays, k: int, scale: float):
        """score[k] += scale * tree(arrays) over dataset ss."""
        import jax.numpy as jnp

        dev = self._dev_of(ss.dataset)
        leaf = self._traverse(arrays, dev["bins"], dev["nan_bin"], dev.get("bundle"))
        ss.score = ss.score.at[k].set(
            add_score(ss.score[k], leaf, arrays.leaf_value, jnp.float32(scale))
        )

    def _select_drops(self) -> List[int]:
        c = self.config
        # drop decisions are a pure function of (drop_seed, iter_), not
        # of a sequential stream: a crash-resumed process has consumed
        # zero draws, so stream position can never survive a restart —
        # per-iteration keying is what makes DART resume deterministic
        # (mirrors the fold_in(seed, iter) keying of bagging RNG)
        rng = np.random.RandomState(
            (int(c.drop_seed) * 2654435761 + self.iter_) % (2 ** 32)
        )
        if rng.rand() < c.skip_drop or self.iter_ == 0:
            return []
        drops: List[int] = []
        if not c.uniform_drop:
            inv_avg = len(self._tree_weight) / max(self._sum_weight, 1e-300)
            rate = c.drop_rate
            if c.max_drop > 0:
                rate = min(rate, c.max_drop * inv_avg / max(self._sum_weight, 1e-300))
            for i in range(self.iter_):
                if rng.rand() < rate * self._tree_weight[i] * inv_avg:
                    drops.append(i)
                    if len(drops) >= c.max_drop > 0:
                        break
        else:
            rate = c.drop_rate
            if c.max_drop > 0:
                rate = min(rate, c.max_drop / max(1, self.iter_))
            for i in range(self.iter_):
                if rng.rand() < rate:
                    drops.append(i)
                    if len(drops) >= c.max_drop > 0:
                        break
        return drops

    def before_gradients(self) -> None:
        """Apply the per-iteration dropout to the train score (the
        reference does this lazily in GetTrainingScore, dart.hpp:80-86,
        so custom-objective gradients also see the dropped ensemble).
        Idempotent within one iteration."""
        if self._pending_drops is not None:
            return
        c = self.config
        K = self.num_class
        drops = self._select_drops()
        k_drop = float(len(drops))

        # drop: remove contributions from the TRAIN score only (valid
        # scores are corrected during normalize, dart.hpp Normalize)
        for i in drops:
            for k in range(K):
                arrays, _ = self.device_trees[i * K + k]
                if int(arrays.num_nodes) > 0:
                    self._tree_score_delta(self.train, arrays, k, -1.0)

        if not c.xgboost_dart_mode:
            self.shrinkage_rate = c.learning_rate / (1.0 + k_drop)
        else:
            self.shrinkage_rate = (
                c.learning_rate if not drops
                else c.learning_rate / (c.learning_rate + k_drop)
            )
        self._pending_drops = drops

    def train_one_iter(self, grad=None, hess=None) -> bool:
        c = self.config
        K = self.num_class
        self.before_gradients()
        drops = self._pending_drops or []
        self._pending_drops = None
        k_drop = float(len(drops))

        ret = super().train_one_iter(grad, hess)
        if ret:
            # aborted: restore the dropped trees so the train score again
            # matches the stored ensemble
            for i in drops:
                for k in range(K):
                    arrays, _ = self.device_trees[i * K + k]
                    if int(arrays.num_nodes) > 0:
                        self._tree_score_delta(self.train, arrays, k, 1.0)
            return ret

        # normalize dropped trees: permanent weight factor + score fixes
        if drops:
            if not c.xgboost_dart_mode:
                factor = k_drop / (k_drop + 1.0)  # new_weight = w * factor
                valid_delta = -1.0 / (k_drop + 1.0)  # valid: w -> w*factor
            else:
                factor = k_drop / (k_drop + c.learning_rate)
                valid_delta = -c.learning_rate / (k_drop + c.learning_rate)
            for i in drops:
                for k in range(K):
                    arrays, aux = self.device_trees[i * K + k]
                    if int(arrays.num_nodes) == 0:
                        continue
                    for vs in self.valids:
                        self._tree_score_delta(vs, arrays, k, valid_delta)
                    # train score currently lacks the tree entirely
                    self._tree_score_delta(self.train, arrays, k, factor)
                    new_arrays = arrays._replace(leaf_value=arrays.leaf_value * factor)
                    self.device_trees[i * K + k] = (new_arrays, aux)
                    self.models[i * K + k].leaf_value = (
                        self.models[i * K + k].leaf_value * factor
                    )
                    self.models[i * K + k].shrinkage *= factor
                if not c.uniform_drop:
                    if not c.xgboost_dart_mode:
                        self._sum_weight -= self._tree_weight[i] / (k_drop + 1.0)
                    else:
                        self._sum_weight -= self._tree_weight[i] / (k_drop + c.learning_rate)
                    self._tree_weight[i] *= factor
        if not c.uniform_drop:
            self._tree_weight.append(self.shrinkage_rate)
            self._sum_weight += self.shrinkage_rate
        return False


# ======================================================================
class RF(GBDT):
    """Random-forest mode (reference src/boosting/rf.hpp:25): no
    shrinkage, gradients computed once from the constant init score,
    prediction is the average over trees (average_output)."""

    def __init__(self, config: Config, train_set: Optional[BinnedDataset]):
        c = config
        if train_set is not None:
            if c.data_sample_strategy == "bagging":
                bag_ok = c.bagging_freq > 0 and 0.0 < c.bagging_fraction < 1.0
                feat_ok = 0.0 < c.feature_fraction < 1.0
                if not (bag_ok or feat_ok):
                    log.fatal(
                        "RF mode requires bagging (bagging_freq>0, bagging_fraction in (0,1)) "
                        "or feature_fraction in (0,1)"
                    )
        super().__init__(config, train_set)
        self._force_sync = True  # per-iter running-average score updates
        self._force_sync_reason = "random forest averages scores per iteration"
        self.average_output = True
        self.shrinkage_rate = 1.0
        if train_set is None:
            return
        if self.objective is None:
            log.fatal("RF mode does not support custom objective functions")
        # boosting one time: constant init score -> fixed gradients (rf.hpp Boosting)
        import jax.numpy as jnp

        K = self.num_class
        npad = train_set.num_rows_padded()
        self._rf_init_scores = [
            (self.objective.boost_from_score(k) if c.boost_from_average else 0.0)
            for k in range(K)
        ]
        const = jnp.asarray(
            np.repeat(np.asarray(self._rf_init_scores, np.float32)[:, None], npad, axis=1)
        )
        score = const if K > 1 else const[0]
        g, h = _obj_grads(self.objective, score, 0)
        self._rf_grad = jnp.reshape(g, (K, -1)).astype(jnp.float32)
        self._rf_hess = jnp.reshape(h, (K, -1)).astype(jnp.float32)

    def train_one_iter(self, grad=None, hess=None) -> bool:
        import jax.numpy as jnp

        if grad is not None or hess is not None:
            log.fatal("RF mode does not support custom objective functions")
        K = self.num_class
        ds = self.train_set
        m = float(self.iter_)  # trees already averaged into the score
        for k in range(K):
            gk, hk = self._rf_grad[k], self._rf_hess[k]
            mask, gk, hk = self.strategy.sample(
                self.iter_, gk, hk, self.dev["valid"], self._label_dev
            )
            feat_mask = self._sample_features(k=k)
            arrays, row_leaf = self._grow_maybe_quantized(
                gk, hk, mask, feat_mask, self.dev["valid"], self.iter_, k
            )
            n_nodes = int(arrays.num_nodes)
            if n_nodes > 0 and self._cegb_info is not None:
                import jax.numpy as jnp

                nf = np.asarray(arrays.node_feature[:n_nodes])
                self._cegb_info = self._cegb_info._replace(
                    used=self._cegb_info.used.at[jnp.asarray(nf)].set(True)
                )
            init_k = self._rf_init_scores[k]
            if n_nodes > 0:
                if self.objective is not None and self.objective.is_renew_tree_output:
                    label = np.asarray(ds.metadata.label, dtype=np.float64)
                    arrays = self._renew_tree_output(
                        arrays, row_leaf, k, mask, resid=label - init_k
                    )
                tree = Tree.from_arrays(arrays, ds, 1.0)
                # AddBias: each tree is a standalone predictor incl. init
                tree.leaf_value = tree.leaf_value + init_k
                arrays = arrays._replace(leaf_value=arrays.leaf_value + init_k)
            else:
                tree = Tree(num_leaves=1, shrinkage=1.0)
                tree.leaf_value = np.array([init_k], np.float64)
                arrays = arrays._replace(
                    leaf_value=arrays.leaf_value.at[0].set(init_k)
                )
            # running average: score = (score*m + tree)/(m+1)  (rf.hpp
            # MultiplyScore/UpdateScore/MultiplyScore sequence)
            sc = self.train.score[k] * m
            sc = add_score(sc, row_leaf, arrays.leaf_value, jnp.float32(1.0))
            self.train.score = self.train.score.at[k].set(sc / (m + 1.0))
            for vs in self.valids:
                vdev = self._dev_of(vs.dataset)
                leaf = self._traverse(arrays, vdev["bins"], vdev["nan_bin"], vdev.get("bundle"))
                vsc = vs.score[k] * m
                vsc = add_score(vsc, leaf, arrays.leaf_value, jnp.float32(1.0))
                vs.score = vs.score.at[k].set(vsc / (m + 1.0))
            self.models.append(tree)
            self.device_trees.append((arrays, None))
        self.iter_ += 1
        return False

    def rollback_one_iter(self) -> None:
        if self.iter_ <= 0:
            return
        K = self.num_class
        m = float(self.iter_)
        for k in reversed(range(K)):
            self.models.pop()
            arrays, _ = self.device_trees.pop()
            leaf = self._traverse(arrays, self.dev["bins"], self.dev["nan_bin"], self.dev.get("bundle"))
            sc = self.train.score[k] * m - arrays.leaf_value[leaf]
            self.train.score = self.train.score.at[k].set(sc / (m - 1.0) if m > 1 else sc * 0)
            for vs in self.valids:
                vdev = self._dev_of(vs.dataset)
                vleaf = self._traverse(arrays, vdev["bins"], vdev["nan_bin"], vdev.get("bundle"))
                vsc = vs.score[k] * m - arrays.leaf_value[vleaf]
                vs.score = vs.score.at[k].set(vsc / (m - 1.0) if m > 1 else vsc * 0)
        self.iter_ -= 1


def splice_continued(base: GBDT, delta: GBDT) -> GBDT:
    """Graft a continuation's trees onto the model it warm-started from.

    The online loop's init_score handoff (docs/RESILIENCE.md): the
    candidate v(n+1) is trained as a FRESH booster over the microbatch
    with ``init_score`` = v(n)'s raw margins, so the delta trees encode
    only the residual on top of v(n). Raw scores add, therefore
    ``base.models + delta.models`` scores exactly v(n+1) — no
    ``_continue_from`` replay of every historical tree per cycle
    (that is O(total trees); this splice is O(new trees)). Mutates and
    returns ``base``.
    """
    if base.num_class != delta.num_class:
        raise ValueError(
            f"cannot splice: num_tree_per_iteration mismatch "
            f"({base.num_class} vs {delta.num_class})"
        )
    if base.average_output or delta.average_output:
        raise ValueError(
            "cannot splice averaged (rf) models: predictions divide by "
            "iteration count, so tree lists do not compose by append"
        )
    combined = list(base.models) + list(delta.models)
    if len(combined) % base.num_class:
        raise ValueError(
            f"cannot splice: {len(combined)} trees is not a whole number "
            f"of {base.num_class}-tree iterations"
        )
    base.models = combined  # setter also clears any pending device trees
    base.iter_ = len(combined) // base.num_class
    return base


# ---------------------------------------------------------------------------
# analysis-suite tracing hooks (analysis/jaxpr_audit `fused_chunk_scan`)

_TRACE_CHUNK_GBDT: Optional["GBDT"] = None
_TRACE_CHUNK_JAXPRS: Dict[int, Any] = {}


def _trace_chunk_gbdt() -> "GBDT":
    """Tiny synthetic regression booster shared by the chunk-scan trace
    entries (one per C). Pinned to the rounds grower so the audited
    program is the TPU-default scan body, and kept minuscule — the
    entry's eqn/cost budgets gate structure, not scale."""
    global _TRACE_CHUNK_GBDT
    if _TRACE_CHUNK_GBDT is None:
        from .basic import Booster, Dataset

        rs = np.random.RandomState(0)
        x = rs.randn(256, 8).astype(np.float64)
        y = x @ rs.randn(8) + 0.1 * rs.randn(256)
        ds = Dataset(x, label=y, free_raw_data=False,
                     params={"min_data_in_leaf": 4, "max_bin": 15})
        bst = Booster(
            params={
                "objective": "regression", "num_leaves": 7,
                "min_data_in_leaf": 4, "max_bin": 15,
                "tpu_growth_mode": "rounds", "verbosity": -1,
            },
            train_set=ds,
        )
        g = bst._gbdt
        g.fused_start(track_train=False)
        _TRACE_CHUNK_GBDT = g
    return _TRACE_CHUNK_GBDT


def trace_fused_chunk(length: int = 4):
    """ClosedJaxpr of one C-round chunk-scan dispatch (the fused mega-
    entry). The scan body is traced ONCE regardless of C — length is a
    jaxpr param — so the analysis C-invariance audit can assert equal
    eqn counts across two lengths to catch accidental unrolling, and
    the committed eqn/flops/bytes budgets must not scale with C."""
    got = _TRACE_CHUNK_JAXPRS.get(length)
    if got is None:
        import jax

        g = _trace_chunk_gbdt()
        chunk = g._f_program.chunk_body(length)
        got = jax.make_jaxpr(chunk)(g._fstate, g._f_data)
        _TRACE_CHUNK_JAXPRS[length] = got
    return got


def create_boosting(config: Config, train_set: Optional[BinnedDataset]) -> GBDT:
    """Boosting factory (reference src/boosting/boosting.cpp:40)."""
    b = config.boosting
    if b == "gbdt":
        return GBDT(config, train_set)
    if b == "dart":
        return DART(config, train_set)
    if b == "rf":
        return RF(config, train_set)
    log.fatal(f"Unknown boosting type {b}")
