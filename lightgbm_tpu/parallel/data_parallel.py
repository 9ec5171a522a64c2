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

from functools import partial
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from .._backend import on_tpu
from ..learner.grower import GrowerSpec, TreeArrays, grow_tree
from ..learner.histogram import row_mesh
from ..learner.split import SplitParams


def make_mesh(devices=None, axis_name: str = "data") -> Mesh:
    """1-D data mesh over all (or given) devices."""
    if devices is None:
        devices = jax.devices()
    return Mesh(np.asarray(devices), (axis_name,))


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

        def fn(bins, nan_bin, num_bins, mono, is_cat, grad, hess, mask,
               feat_mask, params, valid, bundle, rng_key, group_mat, cegb,
               forced, gh_scale):
            # inside shard_map every kernel already sees one shard
            with row_mesh(None):
                tree, row_leaf = grow_tree(
                    bins, nan_bin, num_bins, mono, is_cat, grad, hess,
                    mask, feat_mask, params, self.spec, valid=valid,
                    bundle=bundle, rng_key=rng_key, group_mat=group_mat,
                    cegb=cegb, forced=forced, gh_scale=gh_scale,
                )
            # tree state is identical on all shards (computed from psum'd
            # histograms); mark it replicated for the out_spec
            tree = jax.tree.map(lambda a: jax.lax.pmean(a, axis_name) if jnp.issubdtype(a.dtype, jnp.floating) else a, tree)
            return tree, row_leaf

        in_specs = (bins_spec, rep, rep, rep, rep, row, row, row, rep, rep,
                    row, rep, rep, rep, rep, rep, rep)
        out_specs = (jax.tree.map(lambda _: rep, _tree_arrays_structure(spec)), row)
        self._fn = jax.jit(
            jax.shard_map(
                fn,
                mesh=mesh,
                in_specs=in_specs,
                out_specs=out_specs,
                check_vma=False,
            )
        )

    def __call__(self, bins, nan_bin, num_bins, mono, is_cat, grad, hess, mask,
                 feat_mask, params: SplitParams, valid, bundle=None,
                 rng_key=None, group_mat=None, cegb=None, forced=None,
                 gh_scale=None) -> Tuple[TreeArrays, jax.Array]:
        return self._fn(
            bins, nan_bin, num_bins, mono, is_cat, grad, hess, mask, feat_mask,
            params, valid, bundle, rng_key, group_mat, cegb, forced, gh_scale,
        )

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

    def shard_inputs(self, dev: dict) -> dict:
        """device_put the dataset arrays with the right shardings.

        Multi-process clusters (jax.distributed): per-row arrays are
        PROCESS-LOCAL shards assembled into global arrays
        (pre_partition=true semantics, each rank contributed its rows);
        single-process meshes device_put directly."""
        from ..learner.histogram import HIST_BLK

        n_dev = self.mesh.devices.size
        n_rows = dev["bins"].shape[1]
        multiproc = jax.process_count() > 1
        local_dev = n_dev // jax.process_count() if multiproc else n_dev
        if on_tpu() and (n_rows // max(local_dev, 1)) % HIST_BLK != 0:
            from .. import log

            log.warning(
                f"per-shard rows ({n_rows}/{local_dev}) are not a multiple of "
                f"the pallas histogram block ({HIST_BLK}); histograms will use "
                f"the slow einsum fallback — pad rows to row_block*num_devices"
            )
        row = NamedSharding(self.mesh, P(self.axis_name))
        rep = NamedSharding(self.mesh, P())
        out = dict(dev)
        if multiproc:
            from .multihost import global_rows

            def put_rep(a):
                return jax.make_array_from_process_local_data(
                    rep, np.asarray(a)
                )

            out["bins"] = global_rows(np.asarray(dev["bins"]), self.mesh, axis=1)
            out["valid"] = global_rows(np.asarray(dev["valid"]), self.mesh, axis=0)
        else:

            def put_rep(a):
                return jax.device_put(a, rep)

            out["bins"] = jax.device_put(
                dev["bins"], NamedSharding(self.mesh, P(None, self.axis_name))
            )
            out["valid"] = jax.device_put(dev["valid"], row)
        for k in ("nan_bin", "num_bins", "mono", "is_cat"):
            out[k] = put_rep(dev[k])
        if dev.get("bundle") is not None:
            out["bundle"] = jax.tree.map(put_rep, dev["bundle"])
        return out


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
