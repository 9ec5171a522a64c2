"""Streaming two-pass binning over a chunk store (docs/DATA_PLANE.md
"Two-pass protocol").

Pass 1 reads each raw chunk once and keeps only the sampled rows —
the SAME `data_random_seed` + `bin_construct_sample_cnt` draw the
in-RAM `BinnedDataset.from_numpy` makes, so the fitted bin mappers are
identical to the in-RAM path on the same data (and when the dataset is
small enough that the sample IS the data, the EFB layout is too, which
makes the whole fit bit-exact; at larger scale the layout derives from
the sample exactly like the Sequence streaming path).

Pass 2 re-reads chunks sequentially and spools the packed (G, rows)
bin representation into a second "binned" store with the SAME chunk
boundaries. At no point are two raw chunks resident: iteration holds
one chunk, `bin_chunk` emits the int matrix, and the raw chunk is
dropped before the next read.

The resulting :class:`StreamedBinnedDataset` never holds the full
(G, N) host matrix either — `device_arrays` assembles the device-
resident bin matrix chunk-by-chunk via the double-buffered prefetcher
(`prefetch.py`), recording per-chunk peak RSS for the run manifest.
"""

from __future__ import annotations

import functools
import time
from dataclasses import dataclass
from pathlib import Path
from typing import Any, Dict, List, Optional, Sequence, Tuple

import numpy as np

from . import ram_budget_bytes, record_stats, warn_over_budget
from .. import log
from .._backend import platform
from ..config import Config
from ..dataset import (
    BinnedDataset,
    Metadata,
    _choose_bin_dtype,
    bin_chunk,
)
from ..learner.histogram import HIST_BLK
from .prefetch import (
    ChunkPrefetcher,
    chunk_update_step,
    prefetch_depth,
    read_rss_mb,
)
from .store import ChunkStore, ChunkStoreError, SpooledData, spool_numpy

# bounds for the auto-derived chunk size (rows); both HIST_BLK multiples
_MIN_CHUNK_ROWS = HIST_BLK
_MAX_CHUNK_ROWS = 1 << 20


def resolve_chunk_rows(n_features: int, config: Config) -> int:
    """Chunk size in rows: explicit ``data_chunk_rows`` wins; otherwise
    size chunks so ~4 raw float64 chunks fit in ``ram_budget_mb``
    (1 resident + prefetch depth + slack), rounded to a HIST_BLK
    multiple and clamped."""
    if config.data_chunk_rows:
        rows = int(config.data_chunk_rows)
    else:
        budget = ram_budget_bytes(config.ram_budget_mb)
        per_row = max(1, int(n_features)) * 8
        rows = budget // (4 * per_row)
    rows = max(_MIN_CHUNK_ROWS, min(_MAX_CHUNK_ROWS, rows))
    return (rows // HIST_BLK) * HIST_BLK


# ---------------------------------------------------------------------------
# pass 1: fit mappers from the exact from_numpy sample draw
# ---------------------------------------------------------------------------
def _gather_sample(store: ChunkStore, config: Config) -> np.ndarray:
    """(sample_cnt, F) float64 drawn with the from_numpy RNG: same
    seed, same sorted choice over global row indices — chunk reads just
    slice the rows that landed in this chunk's range."""
    total = store.total_rows
    rng = np.random.RandomState(config.data_random_seed)
    sample_cnt = min(total, config.bin_construct_sample_cnt)
    if sample_cnt < total:
        idx = np.sort(rng.choice(total, sample_cnt, replace=False))
    else:
        idx = np.arange(total, dtype=np.int64)
    sample = np.empty((len(idx), store.n_features), dtype=np.float64)
    for _ci, row0, arrays in store.iter_chunks():
        rows = arrays["cols"].shape[1]
        lo = int(np.searchsorted(idx, row0))
        hi = int(np.searchsorted(idx, row0 + rows))
        if hi > lo:
            sample[lo:hi] = arrays["cols"].T[idx[lo:hi] - row0]
    return sample


def stream_bin(
    store: ChunkStore,
    config: Config,
    bin_root,
    categorical_feature: Optional[Sequence[int]] = None,
    feature_names: Optional[Sequence[str]] = None,
) -> Tuple[BinnedDataset, ChunkStore]:
    """Two-pass binning: returns (proto, binned store). The proto
    carries mappers/EFB/feature bookkeeping but an EMPTY bin matrix —
    the bins live on disk, chunked on the raw store's boundaries."""
    t0 = time.monotonic()
    if not store.complete:
        raise ChunkStoreError(
            f"spool at {store.root} is not finalized; resume + finalize "
            "it before binning"
        )
    if store.total_rows == 0:
        log.fatal("cannot construct Dataset from an empty spool")
    sample = _gather_sample(store, config)
    if not feature_names and store.manifest.get("feature_names"):
        feature_names = list(store.manifest["feature_names"])
    proto = BinnedDataset.from_numpy(
        sample, config,
        categorical_feature=categorical_feature,
        feature_names=feature_names,
    )
    dtype = proto.bins.dtype
    G = proto.bins.shape[0]
    # the sample's bin matrix is dead weight from here on
    proto.bins = np.empty((G, 0), dtype=dtype)
    proto.invalidate_device_cache()
    t1 = time.monotonic()
    record_stats("pass1", {
        "sample_rows": int(sample.shape[0]),
        "total_rows": int(store.total_rows),
        "seconds": round(t1 - t0, 3),
        "rss_mb": round(read_rss_mb(), 1),
    })
    del sample

    bin_store = ChunkStore.create(
        bin_root, n_features=G, chunk_rows=store.chunk_rows,
        kind="binned", value_dtype=str(np.dtype(dtype)),
        extra={"raw_spool": str(store.root)},
    )
    rss_per_chunk: List[float] = []
    for _ci, _row0, arrays in store.iter_chunks():
        chunk = np.ascontiguousarray(arrays["cols"].T)
        del arrays  # drop the raw chunk before the next read
        bin_store.append_binned(bin_chunk(proto, chunk, dtype))
        del chunk
        rss_per_chunk.append(round(read_rss_mb(), 1))
    bin_store.finalize()
    t2 = time.monotonic()
    record_stats("pass2", {
        "chunks": bin_store.num_chunks,
        "chunk_rows": store.chunk_rows,
        "seconds": round(t2 - t1, 3),
        "rows_per_sec": round(store.total_rows / max(1e-9, t2 - t1)),
        "rss_mb_per_chunk": rss_per_chunk,
        "binned_bytes": bin_store.spool_bytes(),
    })
    return proto, bin_store


# ---------------------------------------------------------------------------
# streamed dataset: disk-resident bins, chunk-wise device assembly
# ---------------------------------------------------------------------------
@functools.lru_cache(maxsize=None)
def _jitted_step(donate: bool):
    import jax

    # XLA:CPU donation on update-in-place steps has a history of
    # segfaults (see tests/conftest.py NOTE); gate it to accelerators
    return jax.jit(
        chunk_update_step, donate_argnums=(0,) if donate else ()
    )


@dataclass
class StreamedBinnedDataset(BinnedDataset):
    """BinnedDataset whose bin matrix lives in a binned chunk store.

    ``bins`` holds a (G, 0) placeholder; every consumer goes through
    ``device_arrays()`` (the single chokepoint in boosting/basic), which
    assembles the (G, Np) device matrix chunk-by-chunk behind the
    prefetcher instead of pushing one giant host array. Host-matrix
    consumers (save_binary, subset) use :meth:`materialize_bins` /
    :meth:`copy_subrow`, which stream and warn when the result exceeds
    the RAM budget."""

    bin_store: Optional[ChunkStore] = None
    ram_budget_mb: int = 0

    def device_arrays(self, mesh=None, shard_rows: bool = True
                      ) -> Dict[str, Any]:
        if mesh is not None:
            return self._mesh_arrays(mesh, shard_rows)
        if self._device is not None:
            return self._device
        import jax
        import jax.numpy as jnp

        assert self.bin_store is not None
        store = self.bin_store
        npad = self.num_rows_padded()
        G = store.n_features  # bundle columns
        chunk_rows = store.chunk_rows

        def load(idx: int) -> Tuple[np.ndarray, Dict[str, Any]]:
            # host-only (reader thread): read + verify + widen + pad
            arrays = store.read_chunk(idx)
            b = arrays["bins"].astype(np.int32)
            lo = int(store.chunk_meta(idx)["row0"])
            rows = b.shape[1]
            # pad to a constant width (tail pads to the buffer edge) so
            # the update step compiles at most twice: body + tail
            width = chunk_rows if idx < store.num_chunks - 1 \
                else max(npad - lo, rows)
            if rows != width:
                padded = np.zeros((G, width), dtype=np.int32)
                padded[:, :rows] = b
                b = padded
            return b, {"lo": lo, "rows": rows}

        chunk_bytes = G * chunk_rows * 4
        depth = prefetch_depth(
            chunk_bytes, ram_budget_bytes(self.ram_budget_mb)
        )
        donate = platform() != "cpu"
        step = _jitted_step(donate)
        t0 = time.monotonic()
        buf = jnp.zeros((G, npad), dtype=jnp.int32)
        per_chunk: List[Dict[str, Any]] = []
        prev_rss = read_rss_mb()
        with ChunkPrefetcher(load, store.num_chunks, depth=depth) as pf:
            for idx, dev_chunk, info in pf:
                buf = step(buf, dev_chunk, np.int32(info["lo"]))
                buf.block_until_ready()
                rss = read_rss_mb()
                per_chunk.append({
                    "chunk": idx,
                    "rows": info["rows"],
                    "rss_mb": round(rss, 1),
                    "rss_delta_mb": round(rss - prev_rss, 1),
                })
                prev_rss = rss
        # flatness: spread of steady-state RSS (chunk 0 excluded — it
        # pays the one-time device buffer + compile cost)
        steady = [c["rss_mb"] for c in per_chunk[1:]] or \
                 [c["rss_mb"] for c in per_chunk]
        record_stats("assemble", {
            "chunks": len(per_chunk),
            "chunk_rows": chunk_rows,
            "prefetch_depth": depth,
            "donate": donate,
            "seconds": round(time.monotonic() - t0, 3),
            "per_chunk": per_chunk,
            "peak_rss_mb": round(max(c["rss_mb"] for c in per_chunk), 1),
            "rss_spread_mb": round(max(steady) - min(steady), 1),
        })

        self._device = {
            "bins": buf,
            "valid": jnp.asarray(self._host_valid(0, npad)),
            **{k: jnp.asarray(v) for k, v in self._host_tables().items()},
            "bundle": self._bundle_info(),
        }
        return self._device

    def _mesh_arrays(self, mesh, shard_rows: bool) -> Dict[str, Any]:
        """A data mesh's copy of a streamed set: the chunk-assembled
        one-chip matrix laid over the mesh on the device (there is no
        host matrix to push shard by shard from)."""
        npad = self.num_rows_padded()
        ent = self._mesh_dev.get((mesh, shard_rows))
        if ent is not None and ent[0] == npad:
            return ent[1]
        import jax
        from jax.sharding import NamedSharding, PartitionSpec as P

        one = self.device_arrays()
        ax = mesh.axis_names[0] if shard_rows else None
        rep = NamedSharding(mesh, P())
        dev = jax.tree.map(lambda a: jax.device_put(a, rep), one)
        dev["bins"] = jax.device_put(
            one["bins"], NamedSharding(mesh, P(None, ax)))
        dev["valid"] = jax.device_put(
            one["valid"], NamedSharding(mesh, P(ax)))
        self._mesh_dev[(mesh, shard_rows)] = (npad, dev)
        return dev

    # ------------------------------------------------ host-matrix paths
    def materialize_bins(self) -> np.ndarray:
        """Stream the full (G, N) bin matrix back into host memory
        (save_binary etc.) — warns through the budget path first."""
        assert self.bin_store is not None
        store = self.bin_store
        dtype = _choose_bin_dtype(self.col_bins)
        nbytes = store.n_features * self.num_data * np.dtype(dtype).itemsize
        warn_over_budget(
            f"materializing the binned matrix of {self.num_data} rows",
            nbytes, self.ram_budget_mb,
            "prefer the chunked consumers (device_arrays/save chunked)",
        )
        out = np.empty((store.n_features, self.num_data), dtype=dtype)
        for _ci, row0, arrays in store.iter_chunks():
            b = arrays["bins"]
            out[:, row0: row0 + b.shape[1]] = b.astype(dtype)
        return out

    def copy_subrow(self, indices: np.ndarray) -> "BinnedDataset":
        """Subset by streaming only the chunks that hold selected rows;
        returns an ORDINARY in-RAM BinnedDataset (subsets are small —
        bagging/valid slices — by the time anyone calls this)."""
        idx = np.asarray(indices, dtype=np.int64)
        assert self.bin_store is not None
        store = self.bin_store
        dtype = _choose_bin_dtype(self.col_bins)
        sub = np.empty((store.n_features, len(idx)), dtype=dtype)
        order = np.argsort(idx, kind="stable")
        sidx = idx[order]
        pos = 0
        for ci in range(store.num_chunks):
            meta = store.chunk_meta(ci)
            row0, rows = int(meta["row0"]), int(meta["rows"])
            hi = int(np.searchsorted(sidx, row0 + rows))
            if hi <= pos:
                continue
            arrays = store.read_chunk(ci)
            local = sidx[pos:hi] - row0
            sub[:, order[pos:hi]] = arrays["bins"][:, local].astype(dtype)
            pos = hi
            if pos == len(sidx):
                break
        return BinnedDataset(
            bins=sub,
            mappers=self.mappers,
            used_features=self.used_features,
            num_data=len(idx),
            metadata=self._subset_metadata(idx),
            feature_names=self.feature_names,
            max_num_bin=self.max_num_bin,
            row_block=self.row_block,
            monotone_constraints=self.monotone_constraints,
            bundle_layout=self.bundle_layout,
            bundle_expand=self.bundle_expand,
        )


# ---------------------------------------------------------------------------
# entry point: raw input of any kind -> StreamedBinnedDataset
# ---------------------------------------------------------------------------
def construct_chunked(
    data: Any,
    config: Config,
    label: Optional[np.ndarray] = None,
    weight: Optional[np.ndarray] = None,
    group: Optional[np.ndarray] = None,
    init_score: Optional[np.ndarray] = None,
    position: Optional[np.ndarray] = None,
    categorical_feature: Optional[Sequence[int]] = None,
    feature_names: Optional[Sequence[str]] = None,
) -> StreamedBinnedDataset:
    """data_source=chunked construct: spool `data` (numpy matrix,
    SpooledData handle, Sequence list, or delimited text path) into a
    raw chunk store, stream-bin it, and return the disk-backed
    dataset. Spool placement: ``data_spool_dir`` or a self-cleaning
    temp dir."""
    t0 = time.monotonic()
    owned, root = _spool_root(config)
    qid = None

    if isinstance(data, SpooledData):
        store = data.store
        if not store.complete:
            store.finalize()
    elif isinstance(data, (str, Path)):
        from .store import spool_text_file

        store, names = spool_text_file(
            data, root / "raw",
            chunk_rows=resolve_chunk_rows(1, config)
            if config.data_chunk_rows == 0 else int(config.data_chunk_rows),
            header=config.header,
            label_column=config.label_column or 0,
            weight_column=config.weight_column,
            group_column=config.group_column,
            ignore_column=config.ignore_column,
        )
        if names and feature_names is None:
            feature_names = names
        if label is None:
            label = store.gather_meta("label")
        if weight is None:
            weight = store.gather_meta("weight")
        qid = store.gather_meta("qid")
    elif isinstance(data, np.ndarray) or hasattr(data, "__array__"):
        X = np.asarray(data)
        store = spool_numpy(
            X, root / "raw",
            chunk_rows=resolve_chunk_rows(X.shape[1], config),
        )
    elif isinstance(data, (list, tuple)) or hasattr(data, "__getitem__"):
        seqs = data if isinstance(data, (list, tuple)) else [data]
        nf = int(np.asarray(seqs[0][0]).reshape(-1).shape[0])
        chunk_rows = resolve_chunk_rows(nf, config)
        store = ChunkStore.create(
            root / "raw", n_features=nf, chunk_rows=chunk_rows
        )
        for s in seqs:
            bs = int(getattr(s, "batch_size", 4096) or 4096)
            for lo in range(0, len(s), bs):
                block = np.asarray(s[lo: lo + bs], np.float64)
                if block.ndim == 1:
                    block = block.reshape(1, -1)
                store.append_rows(block)
        store.finalize()
    else:
        raise ChunkStoreError(
            f"data_source=chunked cannot ingest {type(data).__name__}"
        )

    t1 = time.monotonic()
    record_stats("spool", {
        "rows": store.total_rows,
        "features": store.n_features,
        "chunks": store.num_chunks,
        "chunk_rows": store.chunk_rows,
        "spool_bytes": store.spool_bytes(),
        "seconds": round(t1 - t0, 3),
        "rows_per_sec": round(store.total_rows / max(1e-9, t1 - t0)),
        "root": str(store.root),
        "owned_tmp": owned,
    })
    warn_over_budget(
        f"raw dataset of {store.total_rows} rows x {store.n_features} "
        "features", store.total_rows * store.n_features * 8,
        config.ram_budget_mb,
        "streaming it chunked from disk (data_source=chunked active)",
    )

    proto, bin_store = stream_bin(
        store, config, root / "binned",
        categorical_feature=categorical_feature,
        feature_names=feature_names,
    )
    if group is None and qid is not None:
        # qid column -> per-query sizes (contiguous qids, text convention)
        _vals, counts = np.unique(qid, return_counts=True)
        change = np.nonzero(np.diff(qid))[0]
        bounds = np.concatenate([[0], change + 1, [len(qid)]])
        group = np.diff(bounds).astype(np.int64)
        del counts
    meta = Metadata(
        label=None if label is None else np.asarray(label, np.float32).ravel(),
        weight=None if weight is None else np.asarray(weight, np.float32).ravel(),
        group=None if group is None else np.asarray(group, np.int64).ravel(),
        init_score=None if init_score is None
        else np.asarray(init_score, np.float64).ravel(),
        position=None if position is None
        else np.asarray(position, np.int32).ravel(),
    )
    meta.check(store.total_rows)
    return StreamedBinnedDataset(
        bins=proto.bins,  # (G, 0) placeholder
        mappers=proto.mappers,
        used_features=proto.used_features,
        num_data=store.total_rows,
        metadata=meta,
        feature_names=list(proto.feature_names),
        max_num_bin=proto.max_num_bin,
        row_block=proto.row_block,
        monotone_constraints=proto.monotone_constraints,
        bundle_layout=proto.bundle_layout,
        bundle_expand=proto.bundle_expand,
        bin_store=bin_store,
        ram_budget_mb=config.ram_budget_mb,
    )


def _spool_root(config: Config) -> Tuple[bool, Path]:
    if config.data_spool_dir:
        root = Path(config.data_spool_dir)
        root.mkdir(parents=True, exist_ok=True)
        return False, root
    import atexit
    import shutil
    import tempfile

    tmp = Path(tempfile.mkdtemp(prefix="lgbm_tpu_spool_"))
    atexit.register(shutil.rmtree, tmp, ignore_errors=True)
    return True, tmp
