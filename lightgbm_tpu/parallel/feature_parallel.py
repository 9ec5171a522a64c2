"""Feature-parallel tree learner over a 1-D mesh.

The reference's feature-parallel design
(src/treelearner/feature_parallel_tree_learner.cpp, decl
parallel_tree_learner.h:26): every rank holds ALL rows, features are
partitioned across ranks, each rank scans only its own features, and
the global best split is an allreduce-max (SyncUpGlobalBestSplit) —
no histogram traffic at all, only one small split record plus (here)
one per-row bit-vector psum from the winning shard.

TPU formulation: shard_map over a ("feature",) mesh with the FLAT
grower (grower.py spec.feature_axis) — rows replicated, the bin
matrix sharded on its feature axis, per-feature tables sharded
alongside. The feature axis is padded with trivial 1-bin columns to a
multiple of the mesh size (a 1-bin feature has no valid threshold, so
padding can never win a split).
"""

from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..learner.grower import GrowerSpec, TreeArrays, grow_tree
from ..learner.split import SplitParams


class FeatureParallelGrower:
    """Wraps the flat grower in shard_map over a 1-D feature mesh."""

    def __init__(self, mesh: Mesh, spec: GrowerSpec, axis_name: str = "feature"):
        self.mesh = mesh
        self.axis_name = axis_name
        self.n_dev = mesh.devices.size
        self.spec = spec._replace(
            partition="flat", feature_axis=axis_name, axis_name=None
        )

        fshard = P(axis_name)  # per-feature tables
        bins_spec = P(axis_name, None)  # (F, N): features on axis 0
        rep = P()

        def fn(bins, nan_bin, num_bins, mono, is_cat, grad, hess, mask,
               feat_mask, params, valid):
            tree, row_leaf = grow_tree(
                bins, nan_bin, num_bins, mono, is_cat, grad, hess, mask,
                feat_mask, params, self.spec, valid=valid,
            )
            # tree state is identical on every shard (built from the
            # all-gathered winner records); mark it replicated
            tree = jax.tree.map(
                lambda a: jax.lax.pmean(a, axis_name)
                if jnp.issubdtype(a.dtype, jnp.floating) else a,
                tree,
            )
            return tree, row_leaf

        in_specs = (bins_spec, fshard, fshard, fshard, fshard,
                    rep, rep, rep, fshard, rep, rep)
        self._fn = jax.jit(
            jax.shard_map(
                fn,
                mesh=mesh,
                in_specs=in_specs,
                out_specs=(rep, rep),
                check_vma=False,
            )
        )

    # ------------------------------------------------------------------
    def padded_features(self, f: int) -> int:
        d = self.n_dev
        return ((f + d - 1) // d) * d

    def shard_inputs(self, dev: dict) -> dict:
        """Pad the feature axis to a mesh multiple and device_put with
        feature shardings. Padding columns are trivial 1-bin features."""
        f, n = dev["bins"].shape
        fp = self.padded_features(f)
        pad = fp - f
        out = dict(dev)
        bins = np.asarray(dev["bins"])
        if pad:
            bins = np.concatenate(
                [bins, np.zeros((pad, n), bins.dtype)], axis=0
            )
        host = {
            "bins": bins,
            "nan_bin": np.concatenate(
                [np.asarray(dev["nan_bin"]), np.full(pad, -1, np.int32)]
            ),
            "num_bins": np.concatenate(
                [np.asarray(dev["num_bins"]), np.ones(pad, np.int32)]
            ),
            "mono": np.concatenate(
                [np.asarray(dev["mono"]), np.zeros(pad, np.int32)]
            ),
            "is_cat": np.concatenate(
                [np.asarray(dev["is_cat"]), np.zeros(pad, bool)]
            ),
        }
        fs = NamedSharding(self.mesh, P(self.axis_name))
        out["bins"] = jax.device_put(
            host["bins"], NamedSharding(self.mesh, P(self.axis_name, None))
        )
        for k in ("nan_bin", "num_bins", "mono", "is_cat"):
            out[k] = jax.device_put(host[k], fs)
        rep = NamedSharding(self.mesh, P())
        out["valid"] = jax.device_put(dev["valid"], rep)
        return out

    def __call__(self, bins, nan_bin, num_bins, mono, is_cat, grad, hess,
                 mask, feat_mask, params: SplitParams, valid, bundle=None,
                 rng_key=None, group_mat=None, cegb=None, forced=None,
                 gh_scale=None) -> Tuple[TreeArrays, jax.Array]:
        del bundle, rng_key, group_mat, cegb, forced  # unsupported (warned)
        del gh_scale  # quantized rounds mode never routes here
        fp = bins.shape[0]
        pad = fp - feat_mask.shape[0]
        if pad:
            feat_mask = jnp.concatenate([feat_mask, jnp.zeros(pad, bool)])
        fs = NamedSharding(self.mesh, P(self.axis_name))
        feat_mask = jax.device_put(feat_mask, fs)
        return self._fn(
            bins, nan_bin, num_bins, mono, is_cat, grad, hess, mask,
            feat_mask, params, valid,
        )
