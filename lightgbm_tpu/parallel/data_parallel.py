"""Data-parallel tree growth: rows sharded, histograms psum'd.

Reference algorithm: src/treelearner/data_parallel_tree_learner.cpp —
  BeforeTrain: allreduce root (count, sum_grad, sum_hess)      (:169-221)
  FindBestSplits: local hists for all features -> ReduceScatter (:286)
  best split on aggregated hists -> allreduce-max split         (:443)
  Split: identical on all ranks using global counts             (:453)

Here the whole loop lives inside one `shard_map`-wrapped jit: `grow_tree`
takes `axis_name="data"` and issues `lax.psum` on root sums and on each
smaller-child histogram; everything downstream is computed redundantly
(and identically) on every shard, so trees stay in lockstep without any
split broadcast — the same invariant the reference relies on
(SURVEY §3.3). The psum payload per split is one (3, F, B) f32 histogram,
matching the reference's wire payload of histogram pairs. Under
tree_learner=voting the per-round election (rounds.py vote_reduce) cuts
that payload to the elected ~2k columns, in int16 when the quantized
sums provably fit.
"""

from __future__ import annotations

from collections import OrderedDict
from functools import partial
from typing import Any, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from .._backend import on_tpu
from ..learner.grower import GrowerSpec, TreeArrays, grow_tree
from ..learner.histogram import row_mesh
from ..learner.split import SplitParams
from ..timer import device_phase


def make_mesh(devices=None, axis_name: str = "data") -> Mesh:
    """1-D data mesh over all (or given) devices."""
    if devices is None:
        devices = jax.devices()
    return Mesh(np.asarray(devices), (axis_name,))


# float32 holds every integer up to 2**24 and no odd one past it. The
# growers count rows in float32 (the histograms' count channel, the split
# records, TreeArrays): on one chip's rows that is exact in practice, but
# a mesh's GLOBAL table passes it (54.5M rows over four chips), and a
# node past 2**24 rows then hands its children counts that are a few
# rows off: a small leaf split off a 27M-row node read 296,048 for
# 296,043 rows (benchmark/references/tree_audit.py, PR 32).
F32_EXACT_ROWS = 1 << 24


def _recount_leaves(row_leaf, mask, num_leaves: int, axis_name: str):
    """(L,) leaf counts from the rows themselves: each shard counts its
    in-bag rows per leaf (exact: a shard's rows stay under 2**24 per
    leaf), and the shards' counts are summed as integers. Exact for
    every leaf that float32 can hold at all."""
    from ..learner.histogram import seg_sum

    with device_phase("parallel.reduce"):
        local = seg_sum(mask[None, :], row_leaf, num_leaves)[0]
        total = jax.lax.psum(jnp.round(local).astype(jnp.int32), axis_name)
        return total.astype(jnp.float32)


_GROWERS: "OrderedDict[Any, DataParallelGrower]" = OrderedDict()
_GROWERS_MAX = 8


def shared_grower(mesh: Mesh, spec: GrowerSpec) -> "DataParallelGrower":
    """The data-parallel grower of (mesh, spec), built once per process
    and shared by every Booster that resolves to the same pair: its
    jit(shard_map) is then one object, so a second Booster re-traces
    nothing (a fresh jit per Booster was a fresh trace per Booster)."""
    key = (mesh, spec)
    g = _GROWERS.get(key)
    if g is None:
        g = _GROWERS[key] = DataParallelGrower(mesh, spec)
        while len(_GROWERS) > _GROWERS_MAX:
            _GROWERS.popitem(last=False)
    else:
        _GROWERS.move_to_end(key)
    return g


class DataParallelGrower:
    """Wraps grow_tree in shard_map over a 1-D data mesh.

    Rows (the leading `nblocks` axis of the blocked bin matrix and every
    per-row vector) are sharded over `axis_name`; per-feature vectors and
    split params are replicated; the returned TreeArrays are replicated
    (verified identical by construction) and row_leaf stays row-sharded.
    """

    def __init__(self, mesh: Mesh, spec: GrowerSpec, axis_name: str = "data"):
        self.mesh = mesh
        self.axis_name = axis_name
        n = int(mesh.devices.size)
        self.spec = spec._replace(axis_name=axis_name, axis_size=n)
        # (num_features -> payload bytes per grown tree) memo
        self._wire_est: dict = {}
        s = self.spec
        if (n > 1 and s.quant and not s.efb and not s.has_cat
                and not s.cat_subset and not s.mono_mode
                and not s.voting_k and not s.n_forced
                and not (s.extra_trees or s.ff_bynode or s.cegb
                         or s.n_groups)):
            from .. import log

            # ring collective wire per rank per round: allreduce moves
            # ~2(n-1)/n of the buffer, reduce-scatter (n-1)/n — and the
            # per-rank histogram pool shrinks to its owned feature block
            log.info(
                f"data-parallel histogram wire: int32 reduce-scatter "
                f"with per-rank feature ownership ({n} ranks) — ~2x "
                f"less wire per round and 1/{n} the histogram-pool "
                f"memory vs the f32 full-psum path (bin.h:63-81, "
                f"data_parallel_tree_learner.cpp:286); engaged only "
                f"while the worst-case integer sums stay exact "
                f"(histogram.rs_exact_ok: global < 2^31, per-shard "
                f"< 2^24), else the f32 psum path"
            )

        row = P(axis_name)  # shard the row axis of per-row vectors
        bins_spec = P(None, axis_name)  # bins are (F, N): rows on axis 1
        rep = P()

        def fn(with_stats, bins, nan_bin, num_bins, mono, is_cat, grad,
               hess, mask, feat_mask, params, valid, bundle, rng_key,
               group_mat, cegb, forced, gh_scale):
            # inside shard_map every kernel already sees one shard
            with row_mesh(None):
                tree, row_leaf, *stats = grow_tree(
                    bins, nan_bin, num_bins, mono, is_cat, grad, hess,
                    mask, feat_mask, params, self.spec, valid=valid,
                    bundle=bundle, rng_key=rng_key, group_mat=group_mat,
                    cegb=cegb, forced=forced, gh_scale=gh_scale,
                    with_stats=with_stats,
                )
                if bins.shape[1] * n > F32_EXACT_ROWS:
                    tree = tree._replace(leaf_count=_recount_leaves(
                        row_leaf, mask, self.spec.num_leaves, axis_name))
            # tree state is identical on all shards (computed from psum'd
            # histograms); mark it replicated for the out_spec
            with device_phase("parallel.reduce"):
                tree = jax.tree.map(lambda a: jax.lax.pmean(a, axis_name) if jnp.issubdtype(a.dtype, jnp.floating) else a, tree)
            # so are the rounds grower's round counts: every shard runs
            # the same ladder on the same global leaf counts
            return (tree, row_leaf, *(st["rounds"] for st in stats))

        in_specs = (bins_spec, rep, rep, rep, rep, row, row, row, rep, rep,
                    row, rep, rep, rep, rep, rep, rep)
        out_specs = (jax.tree.map(lambda _: rep, _tree_arrays_structure(spec)), row)

        def build(with_stats: bool):
            return jax.jit(
                jax.shard_map(
                    partial(fn, with_stats),
                    mesh=mesh,
                    in_specs=in_specs,
                    out_specs=out_specs + (rep,) * with_stats,
                    check_vma=False,
                )
            )

        self._fn = build(False)
        # with the rounds grower's per-width round counts as a third
        # output (boosting's fused step reads them for
        # lgbmtpu_grower_rounds_total, as on one chip)
        self._fn_stats = build(True) if self.spec.rounds_slots > 0 else None

    def __call__(self, bins, nan_bin, num_bins, mono, is_cat, grad, hess, mask,
                 feat_mask, params: SplitParams, valid, bundle=None,
                 rng_key=None, group_mat=None, cegb=None, forced=None,
                 gh_scale=None, with_stats: bool = False):
        """(tree, row_leaf); with_stats=True appends the grower's stats
        as grow_tree does: {"rounds"} from the rounds grower, else
        None."""
        args = (
            bins, nan_bin, num_bins, mono, is_cat, grad, hess, mask, feat_mask,
            params, valid, bundle, rng_key, group_mat, cegb, forced, gh_scale,
        )
        if not with_stats:
            return self._fn(*args)
        if self._fn_stats is None:
            return (*self._fn(*args), None)
        tree, row_leaf, rounds = self._fn_stats(*args)
        return tree, row_leaf, {"rounds": rounds}

    def wire_bytes_per_tree(self, num_features: int) -> int:
        """Host-side estimate of the collective payload per grown tree:
        one (channels, F, B) histogram reduce per split plus the root
        sums, 4-byte lanes (f32 psum or int32 reduce-scatter) — the
        RUNTIME twin of the static wire pins in
        analysis/cost_budget.json (obs/manifest.py puts the two side by
        side). Memoized per num_features. Boosting records this from
        its HOST loops, never from traced code, so the counter ticks
        per dispatched tree."""
        if self.spec.axis_size <= 1:
            return 0
        F = int(num_features)
        est = self._wire_est.get(F)
        if est is None:
            s = self.spec
            cols = F
            if s.voting_k:
                # voting-parallel: only the elected columns (2k, plus
                # any pinned forced-plan columns) cross the mesh per
                # round (rounds.py vote_reduce). 4-byte lanes is the
                # conservative bound — the quantized election wire may
                # ride int16 (histogram.rs_wire_dtype, decided from the
                # traced row count); the exact per-config payload is
                # pinned statically in analysis cost_budget.json.
                cols = min(2 * int(s.voting_k) + int(s.n_forced), F)
            per_split = 3 * cols * int(s.num_bins) * 4
            est = per_split * int(s.num_leaves)
            self._wire_est[F] = est
        return est


def check_shard_rows(n_rows: int, mesh: Mesh) -> None:
    """A row-sharded array's rows a chip must be whole Pallas row
    blocks: on a TPU anything else would send the histograms to the
    einsum formulation, which is not a program to run there (GBDT pads
    the training set to HIST_BLK x devices before the first push)."""
    from ..learner.histogram import HIST_BLK

    n_local = int(mesh.devices.size) // max(jax.process_count(), 1)
    if on_tpu() and (n_rows // max(n_local, 1)) % HIST_BLK != 0:
        raise ValueError(
            f"per-shard rows ({n_rows}/{n_local}) are not a multiple of "
            f"the pallas histogram block ({HIST_BLK}): pad the rows to "
            "row_block * num_devices (BinnedDataset.ensure_row_block)"
        )


def put_rows(host_rows, shape: Tuple[int, ...], mesh: Mesh, axis: int,
             shard: bool = True) -> jax.Array:
    """A device array of `shape` over `mesh` from the HOST:
    `host_rows(lo, hi)` gives rows [lo, hi) along `axis`. Sharded over
    the mesh's one axis when `shard` (each chip is sent its own rows
    and nothing else: no chip stages the whole array, nothing is
    re-sharded on the device), else replicated. In a multi-process
    cluster this process's rows ARE its shard (pre_partition
    semantics: shards concatenate in process order)."""
    name = mesh.axis_names[0]
    spec = [None] * len(shape)
    if shard:
        spec[axis] = name
    sharding = NamedSharding(mesh, P(*spec))
    n = shape[axis]
    if jax.process_count() > 1:
        return jax.make_array_from_process_local_data(
            sharding, host_rows(0, n))

    def piece(index):
        lo, hi, _ = index[axis].indices(n)
        return host_rows(lo, hi)

    return jax.make_array_from_callback(tuple(shape), sharding, piece)


def put_replicated(tree, mesh: Mesh):
    """Small host (or device) arrays of a pytree, replicated over the
    mesh; None stays None."""
    rep = NamedSharding(mesh, P())
    if jax.process_count() > 1:
        return jax.tree.map(
            lambda a: jax.make_array_from_process_local_data(
                rep, np.asarray(a)), tree)
    return jax.tree.map(lambda a: jax.device_put(a, rep), tree)


def _tree_arrays_structure(spec: GrowerSpec) -> TreeArrays:
    """A dummy TreeArrays with the right pytree structure for out_specs."""
    L = spec.num_leaves
    z = jnp.zeros
    return TreeArrays(
        num_nodes=z((), jnp.int32),
        node_feature=z(L - 1, jnp.int32), node_bin=z(L - 1, jnp.int32),
        node_gain=z(L - 1, jnp.float32), node_default_left=z(L - 1, bool),
        node_cat=z(L - 1, bool),
        node_cat_mask=z((L - 1, spec.num_bins), bool),
        node_left=z(L - 1, jnp.int32),
        node_right=z(L - 1, jnp.int32), node_value=z(L - 1, jnp.float32),
        node_weight=z(L - 1, jnp.float32), node_count=z(L - 1, jnp.float32),
        leaf_value=z(L, jnp.float32), leaf_weight=z(L, jnp.float32),
        leaf_count=z(L, jnp.float32), leaf_depth=z(L, jnp.int32),
    )
