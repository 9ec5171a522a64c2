"""Binned dataset: host construction + device residency.

Equivalent of the reference Dataset/FeatureGroup/Metadata stack
(include/LightGBM/dataset.h:487, src/io/dataset.cpp, src/io/metadata.cpp),
reshaped for TPU:

- all features are stored as ONE dense feature-major bin matrix
  (num_used_features, num_rows_padded) in the narrowest integer dtype,
  padded on the row axis to a block multiple so histogram matmuls tile
  cleanly onto the MXU;
- trivial (constant) features are dropped up front (feature_pre_filter);
- metadata (label/weight/group/init_score/position, reference
  dataset.h:48-399) is validated host-side and shipped as device arrays.

There is no FixHistogram equivalent: the reference omits each feature's
most-frequent bin from sparse storage and reconstructs it from parent
sums (dataset.h:768); our dense device matrix stores every bin, so
histograms are complete by construction.
"""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional, Sequence, Tuple

import numpy as np

from . import log
from .binning import BinMapper, BinType, MissingType
from .config import Config

from .learner.histogram import HIST_BLK

DEFAULT_ROW_BLOCK = HIST_BLK  # pallas histogram row block


# columns of one slab (_column_slab), and the threads that build bin
# mappers from slabs (numpy's sort / unique and the native FindBin
# release the GIL)
_SLAB_COLS = 32
_BIN_THREADS = 8


def _column_slabs(cols: Sequence[int]) -> List[Tuple[int, np.ndarray]]:
    """[(position in `cols` of a slab's first column, the slab's column
    ids)]: `cols` in runs of _SLAB_COLS."""
    cols = np.asarray(cols, dtype=np.int64)
    return [(i0, cols[i0:i0 + _SLAB_COLS])
            for i0 in range(0, len(cols), _SLAB_COLS)]


def _column_slab(data: np.ndarray, idx: np.ndarray) -> np.ndarray:
    """Columns `idx` of a row-major (N, F) matrix as a (len(idx), N)
    float64 C-contiguous slab: one blocked transpose in place of a
    strided read per column (a 2,000-column float32 row is 8 kB, so a
    single column's values lie a cache line apart each). float32 ->
    float64 is exact, so the values a mapper sees do not depend on the
    path."""
    if len(idx) and idx[-1] - idx[0] == len(idx) - 1:
        part = data[:, idx[0]:idx[-1] + 1]  # a run: a view
    else:
        part = data[:, idx]
    return np.ascontiguousarray(part.T, dtype=np.float64)


def _pushed_bytes(arr) -> int:
    """Bytes that went host -> device for `arr`: every addressable
    shard (a replicated array moves one copy per chip)."""
    return sum(int(s.data.nbytes) for s in arr.addressable_shards)


def _choose_bin_dtype(max_num_bin: int) -> Any:
    if max_num_bin <= 256:
        return np.uint8
    if max_num_bin <= 65536:
        return np.uint16
    return np.int32


def bin_chunk(proto: "BinnedDataset", chunk: np.ndarray, dtype) -> np.ndarray:
    """Bin one (rows, features) float chunk with a constructed dataset's
    mappers (+ EFB encode) -> (G, rows) device-column matrix. Shared by
    the Sequence streaming path and the two_round text loader — the
    chunked second pass of the reference's two-pass extract
    (dataset_loader.cpp:1399)."""
    used = proto.used_features
    sub = np.empty((len(used), chunk.shape[0]), dtype=dtype)
    for i, f in enumerate(used):
        sub[i] = proto.mappers[f].values_to_bins(chunk[:, f]).astype(dtype)
    if proto.bundle_layout is not None:
        from .bundling import encode

        um = [proto.mappers[f] for f in used]
        sub, _ = encode(
            sub, proto.bundle_layout,
            [m.num_bin for m in um],
            [m.most_freq_bin for m in um],
            dtype,
        )
    return sub


@dataclass
class Metadata:
    """Labels/weights/query groups/init scores (reference dataset.h:48)."""

    label: Optional[np.ndarray] = None
    weight: Optional[np.ndarray] = None
    group: Optional[np.ndarray] = None  # per-query sizes (reference convention)
    init_score: Optional[np.ndarray] = None
    position: Optional[np.ndarray] = None

    def query_boundaries(self) -> Optional[np.ndarray]:
        if self.group is None:
            return None
        return np.concatenate([[0], np.cumsum(self.group)]).astype(np.int64)

    def check(self, num_data: int) -> None:
        if self.label is not None and len(self.label) != num_data:
            log.fatal(f"label length {len(self.label)} != num_data {num_data}")
        if self.weight is not None and len(self.weight) != num_data:
            log.fatal(f"weight length {len(self.weight)} != num_data {num_data}")
        if self.group is not None and int(np.sum(self.group)) != num_data:
            log.fatal("sum of query group sizes != num_data")


@dataclass
class BinnedDataset:
    """Host-side binned dataset + on-demand device arrays."""

    bins: np.ndarray  # (num_used_features, num_rows) int
    mappers: List[BinMapper]  # one per ORIGINAL feature
    used_features: np.ndarray  # original indices of non-trivial features
    num_data: int
    metadata: Metadata
    feature_names: List[str]
    max_num_bin: int  # uniform bin-axis size on device
    row_block: int
    monotone_constraints: Optional[np.ndarray] = None  # per used feature, in {-1,0,1}
    raw_data: Optional[np.ndarray] = None  # kept for linear trees / refit
    # EFB (bundling.py): when set, `bins` holds BUNDLE columns (G, N)
    # and these describe the feature -> column mapping
    bundle_layout: Optional[Any] = None
    bundle_expand: Optional[np.ndarray] = None  # (F, max_num_bin) int32
    _device: Optional[Dict[str, Any]] = field(default=None, repr=False)
    # label-sized state that is a function of this data set alone and
    # is shared by every Booster built on it (device_label/_weight,
    # label_stat): kind -> (host array, padded rows, device array), and
    # the host statistics of the (label, weight) pair in _stats_of
    _rows_dev: Dict[Any, Any] = field(default_factory=dict, repr=False)
    # the device copies laid over a data mesh (tree_learner=data in one
    # process), beside the one-chip copy above and resident like it:
    # (mesh, rows sharded or replicated) -> (padded rows, arrays)
    _mesh_dev: Dict[Any, Any] = field(default_factory=dict, repr=False)
    # ranking: the query layout and what is derived from it and the
    # labels (rank_layout / rank_part), dropped with _device
    _rank: Dict[Any, Any] = field(default_factory=dict, repr=False)
    _stats: Dict[Any, Any] = field(default_factory=dict, repr=False)
    _stats_of: Tuple[Any, Any] = field(default=(None, None), repr=False)

    # ---------------- construction ----------------
    @staticmethod
    def from_numpy(
        data: np.ndarray,
        config: Config,
        label: Optional[np.ndarray] = None,
        weight: Optional[np.ndarray] = None,
        group: Optional[np.ndarray] = None,
        init_score: Optional[np.ndarray] = None,
        position: Optional[np.ndarray] = None,
        categorical_feature: Optional[Sequence[int]] = None,
        feature_names: Optional[Sequence[str]] = None,
        reference: Optional["BinnedDataset"] = None,
        keep_raw: bool = False,
    ) -> "BinnedDataset":
        """Build bin mappers from a sample and bin the full matrix.

        Mirrors DatasetLoader::ConstructFromSampleData semantics
        (src/io/dataset_loader.cpp:1079): sample up to
        bin_construct_sample_cnt rows, FindBin per feature, then bin all
        rows. With `reference`, reuse its mappers (python-package aligned
        valid-set behavior, basic.py Dataset reference semantics).
        """
        data = np.asarray(data)
        if data.ndim != 2:
            log.fatal("data must be 2-dimensional")
        if data.dtype not in (np.float32, np.float64):
            data = data.astype(np.float64)
        num_data, num_features = data.shape
        cat_set = set(int(c) for c in (categorical_feature or ()))

        if feature_names is None:
            feature_names = [f"Column_{i}" for i in range(num_features)]
        feature_names = list(feature_names)

        if reference is not None:
            mappers = reference.mappers
            if len(mappers) != num_features:
                log.fatal("reference dataset has different number of features")
            used = reference.used_features.copy()
            max_num_bin = reference.max_num_bin
            mono = reference.monotone_constraints
        else:
            rng = np.random.RandomState(config.data_random_seed)
            sample_cnt = min(num_data, config.bin_construct_sample_cnt)
            if sample_cnt < num_data:
                sample_idx = np.sort(rng.choice(num_data, sample_cnt, replace=False))
                sample = data[sample_idx]
            else:
                sample = data
            max_bin_by_feature = list(config.max_bin_by_feature)
            from .binning import load_forced_bins

            forced_map = load_forced_bins(
                config.forcedbins_filename, num_features
            )

            def slab_mappers(slab):
                f0, idx = slab
                cols = _column_slab(sample, idx)
                return [
                    BinMapper.from_sample(
                        col,
                        total_sample_cnt=len(sample),
                        # the reference passes config max_bin straight to
                        # FindBin (dataset_loader.cpp:652) — num_bin ends
                        # <= max_bin, NOT max_bin+1
                        max_bin=(
                            max_bin_by_feature[f]
                            if f < len(max_bin_by_feature)
                            else config.max_bin
                        ),
                        min_data_in_bin=config.min_data_in_bin,
                        use_missing=config.use_missing,
                        zero_as_missing=config.zero_as_missing,
                        bin_type=BinType.CATEGORICAL if f in cat_set else BinType.NUMERICAL,
                        max_cat_threshold=config.max_cat_threshold,
                        forced_bounds=forced_map.get(f),
                    )
                    for f, col in enumerate(cols, start=f0)
                ]

            # a feature's mapper depends on its own column alone, so the
            # slabs go to threads
            from concurrent.futures import ThreadPoolExecutor

            with ThreadPoolExecutor(_BIN_THREADS) as pool:
                mappers = [m for ms in pool.map(
                    slab_mappers, _column_slabs(range(num_features)))
                    for m in ms]
            used = np.array(
                [f for f in range(num_features) if not mappers[f].is_trivial],
                dtype=np.int64,
            )
            if len(used) == 0:
                log.fatal("cannot construct Dataset: all features are constant")
            max_num_bin = max(mappers[f].num_bin for f in used)
            mono = None
            mc = list(config.monotone_constraints)
            if mc:
                if len(mc) != num_features:
                    log.fatal("monotone_constraints length must equal num features")
                mono = np.array([mc[f] for f in used], dtype=np.int8)

        # bin the full matrix, feature-major
        dtype = _choose_bin_dtype(max_num_bin)
        bins = np.empty((len(used), num_data), dtype=dtype)
        for i0, idx in _column_slabs(used):
            for i, col in enumerate(_column_slab(data, idx), start=i0):
                bins[i] = mappers[used[i]].values_to_bins(col).astype(dtype)

        # EFB bundling (dataset.cpp:111 FindGroups / :250
        # FastFeatureBundling): merge near-exclusive sparse features into
        # shared columns. A reference dataset's layout is reused verbatim
        # (valid sets must bin + bundle identically).
        bundle_layout = None
        bundle_expand = None
        if reference is not None:
            bundle_layout = reference.bundle_layout
            bundle_expand = reference.bundle_expand
            if bundle_layout is not None:
                from .bundling import encode

                um = [mappers[f] for f in used]
                merged, _ = encode(
                    bins, bundle_layout,
                    [m.num_bin for m in um],
                    [m.most_freq_bin for m in um],
                    _choose_bin_dtype(bundle_layout.col_bins),
                )
                bins = merged
        elif config.enable_bundle and len(used) > 1:
            from .bundling import bundle_features

            um = [mappers[f] for f in used]
            res = bundle_features(bins, um, config.max_bin)
            if res is not None:
                bins, bundle_layout, bundle_expand = res
                log.info(
                    f"EFB: bundled {len(used)} features into "
                    f"{bundle_layout.num_columns} columns "
                    f"(col bins={bundle_layout.col_bins})"
                )

        meta = Metadata(
            label=None if label is None else np.asarray(label, dtype=np.float32).ravel(),
            weight=None if weight is None else np.asarray(weight, dtype=np.float32).ravel(),
            group=None if group is None else np.asarray(group, dtype=np.int64).ravel(),
            init_score=None if init_score is None else np.asarray(init_score, dtype=np.float64).ravel(),
            position=None if position is None else np.asarray(position, dtype=np.int32).ravel(),
        )
        meta.check(num_data)

        row_block = config.tpu_row_block or DEFAULT_ROW_BLOCK
        if row_block % HIST_BLK != 0:
            # non-HIST_BLK-multiple padding would silently route every
            # histogram to the einsum fallback on TPU; round up instead
            rounded = ((row_block + HIST_BLK - 1) // HIST_BLK) * HIST_BLK
            log.warning(
                f"tpu_row_block={row_block} is not a multiple of the pallas "
                f"histogram block ({HIST_BLK}); rounding up to {rounded}"
            )
            row_block = rounded
        return BinnedDataset(
            bins=bins,
            mappers=mappers,
            used_features=used,
            num_data=num_data,
            metadata=meta,
            feature_names=feature_names,
            max_num_bin=max_num_bin,
            row_block=row_block,
            monotone_constraints=mono,
            raw_data=data if keep_raw else None,
            bundle_layout=bundle_layout,
            bundle_expand=bundle_expand,
        )

    @staticmethod
    def from_csr(
        data,  # scipy sparse matrix (any format with tocsc/tocsr)
        config: Config,
        label: Optional[np.ndarray] = None,
        weight: Optional[np.ndarray] = None,
        group: Optional[np.ndarray] = None,
        init_score: Optional[np.ndarray] = None,
        position: Optional[np.ndarray] = None,
        feature_names: Optional[Sequence[str]] = None,
        reference: Optional["BinnedDataset"] = None,
    ) -> "BinnedDataset":
        """Sparse construction WITHOUT densifying the raw matrix.

        The reference keeps sparse columns delta-encoded
        (sparse_bin.hpp:73) and streams Criteo-scale text via two_round
        (dataset_loader.cpp:210). Here: mappers bin each column's
        NONZERO values (implicit zeros inferred from row counts — the
        same inference FindBin does for its zero-omitting sample), EFB
        conflict counts are sorted row-index intersections
        (bundling.find_groups_sparse), and only the BUNDLED (G, N) bin
        matrix is ever materialized — host peak is O(nnz) + the int
        bundle matrix, never the 8-byte dense (N, F). Categorical
        features and linear trees ride the dense path."""
        csc = data.tocsc()
        csc.sort_indices()
        num_data, num_features = csc.shape

        if reference is not None:
            mappers = reference.mappers
            if len(mappers) != num_features:
                log.fatal("reference dataset has different number of features")
            used = reference.used_features.copy()
            max_num_bin = reference.max_num_bin
            mono = reference.monotone_constraints
        else:
            rng = np.random.RandomState(config.data_random_seed)
            sample_cnt = min(num_data, config.bin_construct_sample_cnt)
            if sample_cnt < num_data:
                idx = np.sort(rng.choice(num_data, sample_cnt, replace=False))
                s_csc = data.tocsr()[idx].tocsc()
            else:
                s_csc = csc
            mb_list = list(config.max_bin_by_feature)
            from .binning import load_forced_bins

            forced_map = load_forced_bins(
                config.forcedbins_filename, num_features
            )
            mappers = []
            for f in range(num_features):
                vals = s_csc.data[s_csc.indptr[f]: s_csc.indptr[f + 1]]
                mb = mb_list[f] if f < len(mb_list) else config.max_bin
                mappers.append(
                    BinMapper.from_sample(
                        vals,
                        total_sample_cnt=s_csc.shape[0],
                        max_bin=mb,
                        min_data_in_bin=config.min_data_in_bin,
                        use_missing=config.use_missing,
                        zero_as_missing=config.zero_as_missing,
                        forced_bounds=forced_map.get(f),
                    )
                )
            used = np.array(
                [f for f in range(num_features) if not mappers[f].is_trivial],
                dtype=np.int64,
            )
            if len(used) == 0:
                log.fatal("cannot construct Dataset: all features are constant")
            max_num_bin = max(mappers[f].num_bin for f in used)
            mono = None
            mc = list(config.monotone_constraints)
            if mc:
                if len(mc) != num_features:
                    log.fatal(
                        "monotone_constraints length must equal num features"
                    )
                mono = np.array([mc[f] for f in used], dtype=np.int8)

        # per-used-feature nonzero (rows, bins) + non-default row sets
        nz = []
        nd_rows: List[Optional[np.ndarray]] = []
        for f in used:
            f = int(f)
            lo, hi = csc.indptr[f], csc.indptr[f + 1]
            rows = csc.indices[lo:hi]
            b = mappers[f].values_to_bins(csc.data[lo:hi])
            nz.append((rows, b))
            m = mappers[f]
            # mergeable only when the implicit zeros sit in the
            # most-freq bin (merged columns never store that bin)
            if m.most_freq_bin == m.default_bin:
                nd_rows.append(np.asarray(rows[b != m.most_freq_bin]))
            else:
                nd_rows.append(None)

        from .bundling import (
            build_expand_idx,
            build_layout,
            find_groups_sparse,
        )

        um = [mappers[int(f)] for f in used]
        u_bins = [m.num_bin for m in um]
        if reference is not None:
            bundle_layout = reference.bundle_layout
            bundle_expand = reference.bundle_expand
            groups = (
                bundle_layout.groups if bundle_layout is not None
                else [[i] for i in range(len(used))]
            )
            layout = bundle_layout
        elif config.enable_bundle and len(used) > 1:
            groups = find_groups_sparse(
                nd_rows, u_bins, num_data,
                max(config.max_bin + 1, 256),  # same cap as the dense path
            )
            if all(len(g) == 1 for g in groups):
                layout = None
                groups = [[i] for i in range(len(used))]
            else:
                layout = build_layout(groups, u_bins)
                log.info(
                    f"EFB (sparse): bundled {len(used)} features into "
                    f"{layout.num_columns} columns "
                    f"(col bins={layout.col_bins})"
                )
        else:
            layout = None
            groups = [[i] for i in range(len(used))]

        col_bins = layout.col_bins if layout is not None else max_num_bin
        dtype = _choose_bin_dtype(max(col_bins, max_num_bin))
        G = len(groups)
        bins = np.zeros((G, num_data), dtype=dtype)
        mfb = np.full(len(used), -1, np.int32)
        for gid, feats in enumerate(groups):
            if len(feats) == 1:
                i = feats[0]
                rows, b = nz[i]
                db = um[i].default_bin
                if db != 0:
                    bins[gid, :] = db
                bins[gid, rows] = b.astype(dtype)
                continue
            col = bins[gid]
            for i in feats:
                rows, b = nz[i]
                m = int(um[i].most_freq_bin)
                mfb[i] = m
                db = int(um[i].default_bin)
                if db != m:
                    # a reference layout built densely may merge a
                    # feature whose most-freq bin is NOT the zero bin;
                    # its IMPLICIT zero rows then carry default_bin and
                    # must be offset-encoded like any non-mfb bin
                    # (the fresh sparse path never merges such features)
                    imp = np.setdiff1d(
                        np.arange(num_data, dtype=rows.dtype), rows,
                        assume_unique=True,
                    )
                    col[imp] = dtype(
                        int(layout.off_lo[i]) + db - (db > m)
                    )
                ndm = b != m
                shifted = b[ndm].astype(np.int64) - (b[ndm] > m)
                col[rows[ndm]] = (layout.off_lo[i] + shifted).astype(dtype)
        bundle_layout = None
        bundle_expand = None
        if layout is not None:
            if reference is None:
                layout = layout._replace(mfb=mfb)
                bundle_expand = build_expand_idx(layout, u_bins, max_num_bin)
            else:
                bundle_expand = reference.bundle_expand
            bundle_layout = layout

        meta = Metadata(
            label=None if label is None else np.asarray(label, dtype=np.float32).ravel(),
            weight=None if weight is None else np.asarray(weight, dtype=np.float32).ravel(),
            group=None if group is None else np.asarray(group, dtype=np.int64).ravel(),
            init_score=None if init_score is None else np.asarray(init_score, dtype=np.float64).ravel(),
            position=None if position is None else np.asarray(position, dtype=np.int32).ravel(),
        )
        meta.check(num_data)

        row_block = config.tpu_row_block or DEFAULT_ROW_BLOCK
        if row_block % HIST_BLK != 0:
            row_block = ((row_block + HIST_BLK - 1) // HIST_BLK) * HIST_BLK
        return BinnedDataset(
            bins=bins,
            mappers=mappers,
            used_features=used,
            num_data=num_data,
            metadata=meta,
            feature_names=(
                list(feature_names) if feature_names is not None
                else [f"Column_{i}" for i in range(num_features)]
            ),
            max_num_bin=max_num_bin,
            row_block=row_block,
            monotone_constraints=mono,
            raw_data=None,
            bundle_layout=bundle_layout,
            bundle_expand=bundle_expand,
        )

    @staticmethod
    def from_sequences(
        seqs: Sequence[Any],
        config: Config,
        label: Optional[np.ndarray] = None,
        weight: Optional[np.ndarray] = None,
        group: Optional[np.ndarray] = None,
        init_score: Optional[np.ndarray] = None,
        position: Optional[np.ndarray] = None,
        categorical_feature: Optional[Sequence[int]] = None,
        feature_names: Optional[Sequence[str]] = None,
    ) -> "BinnedDataset":
        """Two-pass streaming construction from random-access Sequences
        (reference python Sequence ABC basic.py:905 + streaming push
        APIs dataset.h:518-627): pass 1 samples rows across all
        sequences and builds the bin mappers; pass 2 streams
        batch-sized chunks straight into the int bin matrix — the full
        float64 matrix is never materialized (4-8x peak-memory saving,
        the reason the reference's two_round/push path exists).
        """
        lens = [len(s) for s in seqs]
        total = int(np.sum(lens))
        if total == 0:
            log.fatal("cannot construct Dataset from empty sequences")
        rng = np.random.RandomState(config.data_random_seed)
        n_sample = min(total, config.bin_construct_sample_cnt)
        idx = np.sort(rng.choice(total, n_sample, replace=False))
        bounds = np.concatenate([[0], np.cumsum(lens)])

        def _rows(global_rows: np.ndarray) -> np.ndarray:
            out = []
            for g in global_rows:
                s = int(np.searchsorted(bounds, g, side="right")) - 1
                row = np.asarray(seqs[s][int(g - bounds[s])], np.float64)
                out.append(row.reshape(-1))
            return np.asarray(out)

        sample = _rows(idx)
        # mappers/EFB layout from the sample; then stream-bin all rows
        proto = BinnedDataset.from_numpy(
            sample, config,
            categorical_feature=categorical_feature,
            feature_names=feature_names,
        )
        G = proto.bins.shape[0]
        dtype = proto.bins.dtype
        bins = np.empty((G, total), dtype=dtype)
        row0 = 0
        for s in seqs:
            bs = int(getattr(s, "batch_size", 4096) or 4096)
            for lo in range(0, len(s), bs):
                chunk = np.asarray(s[lo : lo + bs], np.float64)
                if chunk.ndim == 1:
                    chunk = chunk.reshape(1, -1)
                bins[:, row0 : row0 + chunk.shape[0]] = bin_chunk(
                    proto, chunk, dtype
                )
                row0 += chunk.shape[0]
        meta = Metadata(
            label=None if label is None else np.asarray(label, np.float32).ravel(),
            weight=None if weight is None else np.asarray(weight, np.float32).ravel(),
            group=None if group is None else np.asarray(group, np.int64).ravel(),
            init_score=None if init_score is None else np.asarray(init_score, np.float64).ravel(),
            position=None if position is None else np.asarray(position, np.int32).ravel(),
        )
        meta.check(total)
        return BinnedDataset(
            bins=bins,
            mappers=proto.mappers,
            used_features=proto.used_features,
            num_data=total,
            metadata=meta,
            feature_names=list(proto.feature_names),
            max_num_bin=proto.max_num_bin,
            row_block=proto.row_block,
            monotone_constraints=proto.monotone_constraints,
            raw_data=None,
            bundle_layout=proto.bundle_layout,
            bundle_expand=proto.bundle_expand,
        )

    def _subset_metadata(self, idx: np.ndarray) -> Metadata:
        """Slice metadata for a row subset (query-group aligned when
        possible). Shared by the in-RAM and streamed copy_subrow."""
        meta = self.metadata
        group = None
        if meta.group is not None:
            # only query-aligned subsets keep ranking metadata
            qb = meta.query_boundaries()
            starts = set(qb[:-1].tolist())
            sizes = []
            i = 0
            aligned = True
            while i < len(idx):
                if int(idx[i]) not in starts:
                    aligned = False
                    break
                q = int(np.searchsorted(qb, idx[i], side="right")) - 1
                qlen = int(qb[q + 1] - qb[q])
                if i + qlen > len(idx) or not np.array_equal(
                    idx[i : i + qlen], np.arange(idx[i], idx[i] + qlen)
                ):
                    aligned = False
                    break
                sizes.append(qlen)
                i += qlen
            if aligned:
                group = np.asarray(sizes, dtype=np.int64)
            else:
                log.warning(
                    "subset indices do not align with query boundaries; group info dropped"
                )
        return Metadata(
            label=None if meta.label is None else meta.label[idx],
            weight=None if meta.weight is None else meta.weight[idx],
            group=group,
            init_score=None if meta.init_score is None else meta.init_score[idx],
            position=None if meta.position is None else meta.position[idx],
        )

    def copy_subrow(self, indices: np.ndarray) -> "BinnedDataset":
        """Row subset sharing bin mappers (reference Dataset::CopySubrow,
        dataset.h — used by bagging-subset and python Dataset.subset)."""
        idx = np.asarray(indices, dtype=np.int64)
        sub_meta = self._subset_metadata(idx)
        return BinnedDataset(
            bins=np.ascontiguousarray(self.bins[:, idx]),
            mappers=self.mappers,
            used_features=self.used_features,
            num_data=len(idx),
            metadata=sub_meta,
            feature_names=self.feature_names,
            max_num_bin=self.max_num_bin,
            row_block=self.row_block,
            monotone_constraints=self.monotone_constraints,
            raw_data=None if self.raw_data is None else self.raw_data[idx],
            bundle_layout=self.bundle_layout,
            bundle_expand=self.bundle_expand,
        )

    # ---------------- derived host info ----------------
    @property
    def num_used_features(self) -> int:
        return len(self.used_features)

    @property
    def num_total_features(self) -> int:
        return len(self.mappers)

    def used_mappers(self) -> List[BinMapper]:
        return [self.mappers[f] for f in self.used_features]

    def num_rows_padded(self) -> int:
        b = self.row_block
        n = ((self.num_data + b - 1) // b) * b
        return max(n, getattr(self, "_min_padded_rows", 0))

    def ensure_min_padded_rows(self, target: int) -> None:
        """Force the padded row count up to `target` (a row_block
        multiple). Multi-host pre-partitioned training needs EQUAL
        per-rank shards for the global mesh sharding — ranks pad to the
        cluster-wide maximum (reference pre_partition keeps uneven
        shards because its collectives carry explicit sizes;
        NamedSharding tiles evenly)."""
        if target % self.row_block != 0:
            raise ValueError((target, self.row_block))
        if target > self.num_rows_padded():
            self._min_padded_rows = int(target)
            self.invalidate_device_cache()

    def ensure_row_block(self, blk: int) -> None:
        """Raise the device row padding so per-shard rows stay a pallas
        block multiple under a data mesh (data-parallel training). Must
        run before the first device push; drops any cached arrays."""
        if self.row_block % blk != 0:
            # a Python int: the padded row count ends up in array shapes
            # and shard indices, where JAX wants ints, not np.int64
            g = int(np.gcd(self.row_block, blk))
            self.row_block = int(self.row_block) // g * int(blk)
            self.invalidate_device_cache()

    def invalidate_device_cache(self) -> None:
        """Drop cached device arrays, the one-chip copy and every mesh
        copy (next device_arrays() / device_label() re-pushes). Used
        when the row padding changes; nothing a Booster does to train
        calls it otherwise."""
        self._device = None
        self._mesh_dev = {}
        self._rows_dev = {}
        self._rank = {}

    # ---------------- device arrays ----------------
    def device_arrays(self, mesh=None, shard_rows: bool = True
                      ) -> Dict[str, Any]:
        """Push the bin matrix + per-feature info to device (cached).

        Returns dict with:
          bins      (F, Np) int32 — feature-major bin matrix, rows padded
                    with bin 0 to a row_block multiple; rows ride the
                    LANE axis (TPU memory tiles pad the minor-most dim to
                    128, so the long axis must be last)
          valid     (Np,)  float32  — 1.0 for real rows, 0.0 for padding
          nan_bin   (F,)   int32    — NaN bin index per feature, -1 if none
          num_bins  (F,)   int32    — per-feature bin count
          mono      (F,)   int32    — monotone constraint per feature
          is_cat    (F,)   bool     — categorical flag

        Without a mesh: one copy on the default device. With a data
        mesh (tree_learner=data): a second resident copy keyed by (mesh,
        shard_rows) and the row padding, built from the HOST arrays with
        the sharding given to the transfer, so each chip receives its
        own rows only: bins P(None, "data") and valid P("data") when
        shard_rows (the training set), everything replicated otherwise
        (a valid set: its traversal and metrics run whole on every
        chip). Per-feature vectors are replicated either way."""
        if mesh is not None:
            return self._mesh_arrays(mesh, shard_rows)
        if self._device is not None:
            return self._device
        import jax.numpy as jnp

        from .obs.metrics import record_dataset_push
        from .timer import global_timer

        npad = self.num_rows_padded()
        with global_timer.scope("dataset.device_push"):
            bins = jnp.asarray(self._host_bins(0, npad))
        record_dataset_push("bins", _pushed_bytes(bins))
        self._device = {
            "bins": bins,
            "valid": jnp.asarray(self._host_valid(0, npad)),
            **{k: jnp.asarray(v) for k, v in self._host_tables().items()},
            "bundle": self._bundle_info(),
        }
        return self._device

    def _host_bins(self, lo: int, hi: int) -> np.ndarray:
        """Padded rows [lo, hi) of the device bin matrix, on the host."""
        out = np.zeros((self.bins.shape[0], hi - lo), dtype=np.int32)
        n = min(max(self.num_data - lo, 0), hi - lo)
        out[:, :n] = self.bins[:, lo:lo + n]
        return out

    def _host_valid(self, lo: int, hi: int) -> np.ndarray:
        out = np.zeros(hi - lo, dtype=np.float32)
        out[:min(max(self.num_data - lo, 0), hi - lo)] = 1.0
        return out

    def _host_tables(self) -> Dict[str, np.ndarray]:
        """The per-feature vectors of device_arrays(), on the host."""
        um = self.used_mappers()
        return {
            "nan_bin": np.array([m.nan_bin for m in um], dtype=np.int32),
            "num_bins": np.array([m.num_bin for m in um], dtype=np.int32),
            "mono": (
                self.monotone_constraints.astype(np.int32)
                if self.monotone_constraints is not None
                else np.zeros(self.num_used_features, dtype=np.int32)
            ),
            "is_cat": np.array(
                [m.bin_type == BinType.CATEGORICAL for m in um]),
        }

    def _mesh_arrays(self, mesh, shard_rows: bool) -> Dict[str, Any]:
        npad = self.num_rows_padded()
        ent = self._mesh_dev.get((mesh, shard_rows))
        if ent is not None and ent[0] == npad:
            return ent[1]
        from .obs.metrics import record_dataset_push
        from .parallel.data_parallel import (check_shard_rows,
                                             put_replicated, put_rows)
        from .timer import global_timer

        if shard_rows:
            check_shard_rows(npad, mesh)
        with global_timer.scope("dataset.device_push"):
            bins = put_rows(self._host_bins, (self.bins.shape[0], npad),
                            mesh, 1, shard_rows)
        record_dataset_push("bins", _pushed_bytes(bins))
        dev = {
            "bins": bins,
            "valid": put_rows(self._host_valid, (npad,), mesh, 0,
                              shard_rows),
            **put_replicated(self._host_tables(), mesh),
            "bundle": put_replicated(self._bundle_info(), mesh),
        }
        self._mesh_dev[(mesh, shard_rows)] = (npad, dev)
        return dev

    def device_label(self, mesh=None, shard_rows: bool = True):
        """Padded label on the device, pushed once per data set (and
        per mesh layout, see device_arrays) and shared by every Booster
        (objective.label, GBDT._label_dev, the fused step's eval
        arrays). Never donated, never re-sharded in place. None without
        labels."""
        return self._device_rows("label", mesh, shard_rows)

    def device_weight(self, mesh=None, shard_rows: bool = True):
        """Padded weight on the device (see device_label); None when
        unweighted."""
        return self._device_rows("weight", mesh, shard_rows)

    def _device_rows(self, kind: str, mesh=None, shard_rows: bool = True):
        from .obs.metrics import record_dataset_push, record_label_cache

        host = getattr(self.metadata, kind)
        if host is None:
            return None
        npad = self.num_rows_padded()
        key = kind if mesh is None else (kind, mesh, shard_rows)
        ent = self._rows_dev.get(key)
        # keyed on the host array's IDENTITY (the entry keeps it alive,
        # so the id cannot be reused) and on the row padding: whoever
        # replaces metadata.label / .weight (Dataset.set_label, set_weight,
        # set_field, the loaders) needs no hook, the next lookup misses
        if ent is not None and ent[0] is host and ent[1] == npad:
            record_label_cache(kind, hit=True)
            return ent[2]
        record_label_cache(kind, hit=False)
        # padded() is a fresh host array: on a CPU backend the device
        # array may alias it, never the caller's own label array
        padded = self.padded(host)
        if mesh is None:
            import jax.numpy as jnp

            dev = jnp.asarray(padded)
        else:
            from .parallel.data_parallel import put_rows

            dev = put_rows(lambda lo, hi: padded[lo:hi], (npad,), mesh, 0,
                           shard_rows)
        record_dataset_push("rows", _pushed_bytes(dev))
        self._rows_dev[key] = (host, npad, dev)
        return dev

    # ---------------- ranking: per-Dataset query layout ----------------
    def rank_layout(self):
        """(QueryLayout, its device arrays): the ragged query layout of
        this data set (learner/ranking.py), built and pushed once and
        shared by the objective and every ranking metric of every
        Booster. Keyed on the group array's identity and the row
        padding, like _device_rows."""
        group = self.metadata.group
        npad = self.num_rows_padded()
        ent = self._rank.get("layout")
        if ent is not None and ent[0] is group and ent[1] == npad:
            return ent[2], ent[3]
        from .learner.ranking import build_query_layout
        from .timer import global_timer

        with global_timer.scope("dataset.query_layout"):
            layout = build_query_layout(group, npad)
            dev = layout.device()
        self._rank = {"layout": (group, npad, layout, dev)}
        return layout, dev

    def rank_part(self, key, compute):
        """Device arrays derived from the layout and the labels (label
        and gain grids, 1/MaxDCG per query, positives per query):
        `compute(layout)` runs once per (label array, key)."""
        layout, _ = self.rank_layout()
        label = self.metadata.label
        ent = self._rank.get(key)
        if ent is not None and ent[0] is label:
            return ent[1]
        value = compute(layout)
        self._rank[key] = (label, value)
        return value

    def label_stat(self, key, compute):
        """Host statistic of this data set's label / weight (a
        check_label verdict, class counts, an init score), computed once
        per (label array, weight array, key) and shared by every
        Booster. Host values only: they outlive
        invalidate_device_cache()."""
        from .obs.metrics import record_label_cache

        src = (self.metadata.label, self.metadata.weight)
        if self._stats_of[0] is not src[0] or self._stats_of[1] is not src[1]:
            self._stats, self._stats_of = {}, src
        if key in self._stats:
            record_label_cache("stats", hit=True)
            return self._stats[key]
        record_label_cache("stats", hit=False)
        value = self._stats[key] = compute()
        return value

    def _bundle_info(self):
        """Device BundleInfo for the growers, or None without EFB."""
        if self.bundle_layout is None:
            return None
        import jax.numpy as jnp

        from .learner.bundle import BundleInfo

        lay = self.bundle_layout
        um = self.used_mappers()
        width = np.array(
            [m.num_bin - (1 if lay.mfb[i] >= 0 else 0) for i, m in enumerate(um)],
            dtype=np.int32,
        )
        return BundleInfo(
            bundle_of=jnp.asarray(lay.bundle_of),
            off_lo=jnp.asarray(lay.off_lo),
            mfb=jnp.asarray(lay.mfb),
            expand_idx=jnp.asarray(self.bundle_expand),
            width=jnp.asarray(width),
        )

    @property
    def col_bins(self) -> int:
        """Uniform device bin-axis size of the stored columns."""
        if self.bundle_layout is not None:
            return max(self.bundle_layout.col_bins, self.max_num_bin)
        return self.max_num_bin

    def padded(self, arr: Optional[np.ndarray], fill: float = 0.0, dtype=np.float32) -> np.ndarray:
        """Pad a per-row array to num_rows_padded."""
        npad = self.num_rows_padded()
        out = np.full(npad, fill, dtype=dtype)
        if arr is not None:
            out[: self.num_data] = arr
        return out

    def feature_infos(self) -> List[str]:
        return [m.feature_info_str() for m in self.mappers]
