"""User-facing Dataset and Booster (reference python-package/lightgbm/basic.py).

The reference Dataset (basic.py:1746) and Booster (basic.py:3543) wrap C
handles over a ctypes ABI; here they wrap the host BinnedDataset and the
GBDT driver directly — the "ABI" is the jit boundary. Construction is
lazy like the reference: `Dataset.construct()` runs binning on first use
so that `reference=` mapper sharing and `free_raw_data` semantics hold.
"""

from __future__ import annotations

import copy
import os
from pathlib import Path
from typing import Any, Dict, Iterable, List, Optional, Sequence, Tuple, Union

import numpy as np

from . import log
from .boosting import GBDT
from .config import Config
from .dataset import BinnedDataset
from .log import LightGBMError

_ArrayLike = Union[np.ndarray, "list", "tuple"]


def set_network(
    machines: Any,
    local_listen_port: int = 12400,
    listen_time_out: int = 120,
    num_machines: int = 1,
    *,
    machine_list_file: str = "",
    machine_rank: "int | None" = None,
) -> None:
    """Join the multi-host training cluster (reference
    basic.py:3773 set_network -> LGBM_NetworkInit; positional order
    matches: machines, local_listen_port, listen_time_out,
    num_machines). On the TPU build this forms the JAX multi-controller
    cluster (parallel/multihost.py); collectives then ride ICI/DCN
    through the same grower code as single-host. listen_time_out is
    accepted for API parity (the cluster handshake timeout is managed
    by jax.distributed)."""
    del listen_time_out
    from .parallel import multihost

    if machines is not None and not isinstance(machines, str):
        machines = ",".join(str(m) for m in machines)
    multihost.init_distributed(
        machines=machines or None,
        machine_list_file=machine_list_file or None,
        num_machines=num_machines if num_machines > 1 else None,
        local_listen_port=local_listen_port,
        machine_rank=machine_rank,
    )


class Sequence:
    """Generic random-access data sequence for streaming Dataset
    construction (reference basic.py:905 Sequence ABC). Subclass with
    `__len__` and `__getitem__` (int row or slice -> numpy rows) and
    optionally set `batch_size`; pass one Sequence or a list of them as
    `Dataset(data=...)` — the binned matrix is built in two streaming
    passes without ever materializing the full float64 matrix."""

    batch_size: int = 4096

    def __len__(self) -> int:  # pragma: no cover - abstract
        raise NotImplementedError

    def __getitem__(self, idx):  # pragma: no cover - abstract
        raise NotImplementedError


def _is_sequence_input(data: Any) -> bool:
    if isinstance(data, Sequence):
        return True
    return (
        isinstance(data, list)
        and len(data) > 0
        and all(isinstance(s, Sequence) for s in data)
    )


def _to_2d_numpy(data: Any, keep_float32: bool = False
                 ) -> Tuple[np.ndarray, Optional[List[str]]]:
    """(2-D float64 matrix, column names or None). `keep_float32`
    leaves a float32 array as it is, for a caller that converts what it
    reads column by column (Dataset.construct's binning): the whole
    matrix as float64 is twice its bytes, written once and read once."""
    feature_name = None
    try:  # pandas support without importing pandas eagerly
        import pandas as pd  # type: ignore

        if isinstance(data, pd.DataFrame):
            feature_name = [str(c) for c in data.columns]
            return data.to_numpy(dtype=np.float64), feature_name
        if isinstance(data, pd.Series):
            return data.to_numpy(dtype=np.float64).reshape(-1, 1), None
    except ImportError:
        pass
    # Arrow ingest (reference include/LightGBM/arrow.h + c_api.cpp:1645
    # LGBM_DatasetCreateFromArrow): accept pyarrow Table / RecordBatch
    # column-wise; nulls -> NaN
    tname = type(data).__module__ + "." + type(data).__name__
    if tname.startswith("pyarrow."):
        import pyarrow as pa  # already imported: data IS a pyarrow object

        def _col64(col):
            # cast first so nullable bool/int columns become float64
            # with nulls -> NaN (a raw to_numpy would yield an object
            # array of None that np.asarray cannot float)
            return np.asarray(
                col.cast(pa.float64()).to_numpy(zero_copy_only=False)
            )

        if isinstance(data, pa.RecordBatch):
            data = pa.Table.from_batches([data])
        if isinstance(data, pa.Table):
            feature_name = [str(c) for c in data.column_names]
            cols = [_col64(data.column(i)) for i in range(data.num_columns)]
            return np.column_stack(cols), feature_name
        if isinstance(data, (pa.ChunkedArray, pa.Array)):
            return _col64(data).reshape(-1, 1), None
    if hasattr(data, "toarray"):  # scipy sparse
        return np.asarray(data.toarray(), dtype=np.float64), None
    arr = np.asarray(data)
    if arr.ndim == 1:
        arr = arr.reshape(-1, 1)
    if keep_float32 and arr.dtype == np.float32:
        return arr, feature_name
    return arr.astype(np.float64, copy=False), feature_name


def _to_1d(v: Any) -> Optional[np.ndarray]:
    if v is None:
        return None
    try:
        import pandas as pd  # type: ignore

        if isinstance(v, (pd.Series, pd.DataFrame)):
            return v.to_numpy().ravel()
    except ImportError:
        pass
    if (type(v).__module__ + "." + type(v).__name__).startswith("pyarrow."):
        import pyarrow as pa  # already imported: v IS a pyarrow object

        if isinstance(v, (pa.ChunkedArray, pa.Array)):
            return np.asarray(
                v.cast(pa.float64()).to_numpy(zero_copy_only=False)
            ).ravel()
        if isinstance(v, pa.Table):
            if v.num_columns != 1:
                raise ValueError(
                    f"expected a 1-column table, got {v.num_columns} columns"
                )
            return np.asarray(
                v.column(0).cast(pa.float64()).to_numpy(zero_copy_only=False)
            ).ravel()
    return np.asarray(v).ravel()


class Dataset:
    """Dataset wrapper (reference basic.py:1746)."""

    def __init__(
        self,
        data: Any,
        label: Any = None,
        reference: Optional["Dataset"] = None,
        weight: Any = None,
        group: Any = None,
        init_score: Any = None,
        feature_name: Union[str, List[str]] = "auto",
        categorical_feature: Union[str, List[Union[int, str]]] = "auto",
        params: Optional[Dict[str, Any]] = None,
        free_raw_data: bool = True,
        position: Any = None,
    ):
        self.data = data
        self.label = _to_1d(label)
        self.reference = reference
        self.weight = _to_1d(weight)
        self.group = _to_1d(group)
        self.position = _to_1d(position)
        self.init_score = _to_1d(init_score)
        self.feature_name = feature_name
        self.categorical_feature = categorical_feature
        self.params = copy.deepcopy(params) or {}
        self.free_raw_data = free_raw_data
        self._binned: Optional[BinnedDataset] = None
        self.used_indices: Optional[np.ndarray] = None
        self.pandas_categorical = None

    # ------------------------------------------------------------------
    def _resolve_categorical(self, feature_names: List[str]) -> List[int]:
        """Column indices of the categorical features: the constructor's
        ``categorical_feature`` if given, else the Dataset's ``params``
        (``categorical_feature`` or an alias; "0,1,2", "name:a,b" or a
        list), as the reference reads it for every input. Given both,
        the constructor wins and the parameter is ignored with the
        reference's warning."""
        from .config import resolve_alias

        cf = self.categorical_feature
        in_params = [v for k, v in self.params.items()
                     if resolve_alias(k) == "categorical_feature"
                     and v not in (None, "", "auto")]
        if cf == "auto" or cf is None or cf == "":
            if not in_params:
                return []
            cf = in_params[-1]
            if isinstance(cf, str):
                from .parsers import _resolve_columns

                return _resolve_columns(cf, feature_names)
        elif in_params:
            log.warning(
                "categorical_feature keyword has been found in `params` "
                "and will be ignored.\nPlease use categorical_feature "
                "argument of the Dataset constructor to pass this "
                "parameter.")
        out = []
        for c in cf:
            if isinstance(c, str):
                if c in feature_names:
                    out.append(feature_names.index(c))
                else:
                    log.warning(f"Unknown categorical feature {c}")
            else:
                out.append(int(c))
        return out

    def _construct_chunked(self, cfg, _gt):
        """data_source=chunked construct. Returns the streamed binned
        dataset, or None when this input must use a legacy path."""
        from .data.store import ChunkStoreError, SpooledData

        if self.reference is not None:
            log.warning(
                "data_source=chunked: valid sets with reference= must "
                "bin with the training set's mappers; using the in-RAM "
                "path"
            )
            return None
        if cfg.linear_tree:
            log.warning(
                "data_source=chunked does not retain raw feature "
                "values required by linear_tree; using the in-RAM path"
            )
            return None
        data = self.data
        if isinstance(data, (str, Path)):
            from .parsers import is_binary_file

            if is_binary_file(str(data)):
                return None  # .bin caches load pre-binned as-is
        elif hasattr(data, "tocsc") and hasattr(data, "tocsr"):
            log.warning(
                "data_source=chunked does not ingest scipy sparse "
                "matrices; using the sparse in-RAM path"
            )
            return None
        names = (
            [str(n) for n in self.feature_name]
            if isinstance(self.feature_name, list)
            else None
        )
        cat = self._resolve_categorical(names or [])
        if _is_sequence_input(data):
            if not isinstance(data, list):
                data = [data]
        elif not isinstance(data, (str, Path, SpooledData, np.ndarray)):
            arr, pandas_names = _to_2d_numpy(data)
            data = arr
            if names is None and pandas_names is not None:
                names = pandas_names
        from .data.streaming import construct_chunked

        try:
            with _gt.scope("dataset construct (chunked stream)"):
                return construct_chunked(
                    data, cfg,
                    label=self.label,
                    weight=self.weight,
                    group=self.group,
                    init_score=self.init_score,
                    position=self.position,
                    categorical_feature=cat,
                    feature_names=names,
                )
        except ChunkStoreError as e:
            log.warning(
                f"data_source=chunked ingestion failed ({e}); falling "
                "back to the in-RAM path"
            )
            return None

    def construct(self) -> "Dataset":
        if self._binned is not None:
            return self
        self._construct()
        if self.reference is None:
            self._record_columns()
        return self

    def _record_columns(self) -> None:
        """The column-kind gauges of a training Dataset
        (obs/metrics.py record_dataset_columns)."""
        from .binning import BinType
        from .obs.metrics import default_registry, record_dataset_columns

        if not default_registry().enabled:
            return  # no pass over the bins for gauges nobody reads
        b = self._binned
        um = b.used_mappers()
        other = None
        if b.bundle_layout is None and b.bins.shape[0] == len(um):
            other = sum(
                int(np.count_nonzero(b.bins[i] == m.nan_bin))
                for i, m in enumerate(um)
                if m.bin_type == BinType.CATEGORICAL)
        record_dataset_columns(
            um, Config(self.params).max_cat_to_onehot, other)

    def _construct(self) -> "Dataset":
        if self.data is None:
            log.fatal("Cannot construct Dataset: raw data was freed")
        from .timer import global_timer as _gt

        from .data.store import SpooledData

        cfg_src = Config(self.params)
        if (cfg_src.data_source == "chunked"
                or isinstance(self.data, SpooledData)):
            # out-of-core construct (docs/DATA_PLANE.md): spool to a
            # chunk store, stream two-pass binning, assemble the device
            # matrix chunk-wise. Ineligible inputs warn and fall
            # through to the legacy paths below.
            binned = self._construct_chunked(cfg_src, _gt)
            if binned is not None:
                self._binned = binned
                if self.feature_name == "auto" and binned.feature_names:
                    self.feature_name = list(binned.feature_names)
                if self.free_raw_data:
                    self.data = None
                return self

        if _is_sequence_input(self.data):
            # streaming two-pass path (reference Sequence / push APIs)
            seqs = self.data if isinstance(self.data, list) else [self.data]
            cfg = Config(self.params)
            names = (
                [str(n) for n in self.feature_name]
                if isinstance(self.feature_name, list)
                else None
            )
            cat = self._resolve_categorical(names or [])
            if cfg.linear_tree:
                log.fatal(
                    "linear_tree needs raw feature values; Sequence "
                    "streaming does not retain them"
                )
            with _gt.scope("dataset construct (streaming binning)"):
                self._binned = BinnedDataset.from_sequences(
                    seqs,
                    cfg,
                    label=self.label,
                    weight=self.weight,
                    group=self.group,
                    init_score=self.init_score,
                    position=self.position,
                    categorical_feature=cat,
                    feature_names=names,
                )
            if self.free_raw_data:
                self.data = None
            return self
        if isinstance(self.data, (str, Path)):
            # file-path input (reference Dataset accepts text or binary
            # data files directly; DatasetLoader::LoadFromFile): .bin
            # caches load pre-binned, text files parse CSV/TSV/LibSVM
            from .config import resolve_alias as _ra
            from .parsers import is_binary_file, load_binary, load_text_file

            path = str(self.data)
            fp = {_ra(k): v for k, v in self.params.items()}
            cfg_file = Config(self.params)
            # two_round streaming (dataset_loader.cpp:210): EXPLICIT
            # config only, matching the reference (it streams only on
            # two_round=true) — host memory stays O(chunk) + the binned
            # matrix instead of O(file). Streamed bin boundaries come
            # from reservoir-sampled rows, so auto-switching at a size
            # threshold would silently change model output when a file
            # crosses 1 GB (ADVICE r5 low); large files get a warning
            # instead. Ineligible cases fall through to the whole-file
            # loader: linear_tree (needs raw values), reference=
            # datasets (must bin with the TRAINING set's mappers),
            # constructor-level categorical_feature (column names
            # unknown pre-parse).
            stream_ok = (
                not is_binary_file(path)
                and not cfg_file.linear_tree
                and self.reference is None
                and self.categorical_feature in ("auto", None, "")
            )
            want_stream = cfg_file.two_round
            if not want_stream and stream_ok:
                # single memory-budget warning path (data plane knob):
                # ram_budget_mb=0 keeps the legacy 1 GB threshold
                from .data import warn_over_budget

                warn_over_budget(
                    f"text file {path}", os.path.getsize(path),
                    cfg_file.ram_budget_mb,
                    "pass two_round=true or data_source=chunked to "
                    "stream it with bounded host memory (streamed "
                    "binning samples rows, so results may differ "
                    "slightly from the whole-file loader; parity "
                    "deviation documented in docs/DESIGN_DECISIONS.md)",
                )
            if want_stream and not stream_ok:
                log.warning(
                    "two_round streaming skipped: linear_tree / "
                    "reference= / constructor categorical_feature need "
                    "the whole-file loader"
                )
            if want_stream and stream_ok:
                from .parsers import load_text_file_two_round

                with _gt.scope("dataset construct (two_round stream)"):
                    res = load_text_file_two_round(
                        path, cfg_file,
                        header=str(fp.get("header", "false")).lower()
                        in ("true", "1"),
                        label_column=fp.get("label_column", 0),
                        weight_column=fp.get("weight_column", ""),
                        group_column=fp.get("group_column", ""),
                        ignore_column=fp.get("ignore_column", ""),
                        categorical_feature=fp.get(
                            "categorical_feature", ""),
                    )
                if res is not None:  # None = LibSVM fallback
                    self._binned = res["binned"]
                    md = self._binned.metadata
                    if self.label is not None:
                        md.label = np.asarray(self.label, np.float32)
                    if self.weight is not None:
                        md.weight = np.asarray(self.weight, np.float32)
                    if self.group is not None:
                        md.group = np.asarray(self.group, np.int64)
                    if self.init_score is not None:
                        md.init_score = np.asarray(
                            self.init_score, np.float64)
                    if self.position is not None:
                        md.position = np.asarray(self.position, np.int32)
                    if (self.feature_name == "auto"
                            and res["feature_names"]):
                        self.feature_name = res["feature_names"]
                    if self.free_raw_data:
                        self.data = None
                    return self
            with _gt.scope("dataset construct (file)"):
                if is_binary_file(path):
                    self._binned = load_binary(path)
                    md = self._binned.metadata
                    if self.label is not None:
                        md.label = np.asarray(self.label, np.float32)
                    if self.weight is not None:
                        md.weight = np.asarray(self.weight, np.float32)
                    if self.group is not None:
                        md.group = np.asarray(self.group, np.int64)
                    if self.init_score is not None:
                        md.init_score = np.asarray(self.init_score,
                                                   np.float64)
                    if self.position is not None:
                        md.position = np.asarray(self.position, np.int32)
                    if self.free_raw_data:
                        self.data = None
                    return self
                loaded = load_text_file(
                    path,
                    header=str(fp.get("header", "false")).lower()
                    in ("true", "1"),
                    label_column=fp.get("label_column", 0),
                    weight_column=fp.get("weight_column", ""),
                    group_column=fp.get("group_column", ""),
                    ignore_column=fp.get("ignore_column", ""),
                    categorical_feature=fp.get("categorical_feature", ""),
                )
                self.data = loaded["X"]
                if self.label is None and loaded["label"] is not None:
                    self.label = np.asarray(loaded["label"])
                if self.weight is None and loaded["weight"] is not None:
                    self.weight = np.asarray(loaded["weight"])
                if self.group is None and loaded["group"] is not None:
                    self.group = np.asarray(loaded["group"])
                if (self.init_score is None
                        and loaded.get("init_score") is not None):
                    self.init_score = np.asarray(loaded["init_score"])
                if (self.feature_name == "auto"
                        and loaded["feature_names"]):
                    self.feature_name = loaded["feature_names"]
                if (self.categorical_feature == "auto"
                        and loaded["categorical_feature"]):
                    self.categorical_feature = loaded[
                        "categorical_feature"
                    ]
            # fall through to the numpy path below with the parsed matrix
        cfg0 = Config(self.params)
        _sparse_names = (
            [str(n) for n in self.feature_name]
            if isinstance(self.feature_name, list)
            else []
        )
        if (hasattr(self.data, "tocsc") and hasattr(self.data, "tocsr")
                and not self._resolve_categorical(_sparse_names)
                and not cfg0.linear_tree):
            # scipy sparse: bin from column indices, never densify
            # (sparse_bin.hpp:73 / dataset_loader.cpp:210 two_round)
            names = _sparse_names or None
            ref_binned = None
            if self.reference is not None:
                self.reference.construct()
                ref_binned = self.reference._binned
            with _gt.scope("dataset construct (sparse binning)"):
                self._binned = BinnedDataset.from_csr(
                    self.data,
                    cfg0,
                    label=self.label,
                    weight=self.weight,
                    group=self.group,
                    init_score=self.init_score,
                    position=self.position,
                    feature_names=names,
                    reference=ref_binned,
                )
            if self.free_raw_data:
                self.data = None
            return self
        cfg = Config(self.params)
        keep_raw = bool(cfg.linear_tree)
        # binning converts each column as it reads it; only the raw
        # values that linear trees keep have to be float64 as a whole
        arr, pandas_names = _to_2d_numpy(self.data,
                                         keep_float32=not keep_raw)
        if isinstance(self.feature_name, list):
            names = [str(n) for n in self.feature_name]
        elif pandas_names is not None:
            names = pandas_names
        else:
            names = [f"Column_{i}" for i in range(arr.shape[1])]
        ref_binned = None
        if self.reference is not None:
            self.reference.construct()
            ref_binned = self.reference._binned
        cat = self._resolve_categorical(names)
        with _gt.scope("dataset construct (binning)"):
            self._binned = BinnedDataset.from_numpy(
                arr,
                cfg,
                label=self.label,
                weight=self.weight,
                group=self.group,
                init_score=self.init_score,
                position=self.position,
                categorical_feature=cat,
                feature_names=names,
                reference=ref_binned,
                keep_raw=keep_raw,
            )
        if self.free_raw_data:
            self.data = None
        return self

    # ------------------------------------------------------------------
    @classmethod
    def from_binned(cls, binned) -> "Dataset":
        """Wrap an already-binned dataset (the .bin cache fast path,
        reference dataset_loader.cpp:424 LoadFromBinFile)."""
        ds = cls(data=None, free_raw_data=True)
        ds.label = binned.metadata.label
        ds.weight = binned.metadata.weight
        ds.group = binned.metadata.group
        ds.init_score = binned.metadata.init_score
        ds.feature_name = binned.feature_names
        ds._binned = binned
        return ds

    # ------------------------------------------------------------------
    def create_valid(
        self, data, label=None, weight=None, group=None, init_score=None,
        params=None, position=None,
    ) -> "Dataset":
        return Dataset(
            data, label=label, reference=self, weight=weight, group=group,
            init_score=init_score, params=params or self.params, position=position,
        )

    def set_label(self, label) -> "Dataset":
        self.label = _to_1d(label)
        if self._binned is not None:
            self._binned.metadata.label = np.asarray(self.label, dtype=np.float32)
        return self

    def set_weight(self, weight) -> "Dataset":
        self.weight = _to_1d(weight)
        if self._binned is not None:
            self._binned.metadata.weight = (
                np.asarray(self.weight, dtype=np.float32) if weight is not None else None
            )
        return self

    def set_group(self, group) -> "Dataset":
        self.group = _to_1d(group)
        if self._binned is not None:
            self._binned.metadata.group = (
                np.asarray(self.group, dtype=np.int64) if group is not None else None
            )
        return self

    def set_init_score(self, init_score) -> "Dataset":
        self.init_score = _to_1d(init_score)
        if self._binned is not None:
            self._binned.metadata.init_score = (
                np.asarray(self.init_score, dtype=np.float64)
                if init_score is not None
                else None
            )
        return self

    def set_position(self, position) -> "Dataset":
        self.position = _to_1d(position)
        if self._binned is not None:
            self._binned.metadata.position = (
                np.asarray(self.position, dtype=np.int32)
                if position is not None else None
            )
        return self

    def get_label(self):
        return self.label

    def get_weight(self):
        return self.weight

    def get_group(self):
        return self.group

    def get_init_score(self):
        return self.init_score

    def get_position(self):
        return self.position

    _FIELDS = ("label", "weight", "group", "init_score", "position")

    def set_field(self, field_name: str, data) -> "Dataset":
        """Generic metadata setter (LGBM_DatasetSetField;
        reference basic.py Dataset.set_field)."""
        if field_name not in self._FIELDS:
            raise KeyError(f"unknown field {field_name!r}")
        return getattr(self, f"set_{field_name}")(data)

    def get_field(self, field_name: str):
        """Generic metadata getter (LGBM_DatasetGetField)."""
        if field_name not in self._FIELDS:
            raise KeyError(f"unknown field {field_name!r}")
        return getattr(self, f"get_{field_name}")()

    def get_data(self):
        """The raw data this Dataset was built from (reference
        basic.py Dataset.get_data). Unavailable once raw data was
        freed (free_raw_data=True after construct)."""
        if self.data is None:
            raise LightGBMError(
                "Cannot call get_data after freeing raw data; "
                "set free_raw_data=False when constructing the Dataset"
            )
        return self.data

    def get_params(self) -> Dict[str, Any]:
        """The Dataset-relevant parameters this Dataset carries
        (reference basic.py Dataset.get_params)."""
        from .config import DATASET_PARAMS, resolve_alias

        return {
            k: v for k, v in self.params.items()
            if resolve_alias(k) in DATASET_PARAMS
        }

    def set_reference(self, reference: "Dataset") -> "Dataset":
        """Bin this Dataset with another Dataset's bin mappers
        (reference basic.py Dataset.set_reference)."""
        if self._binned is not None and self.reference is not reference:
            raise LightGBMError(
                "Cannot set reference after the Dataset was constructed; "
                "pass reference= at creation"
            )
        self.reference = reference
        return self

    def get_ref_chain(self, ref_limit: int = 100):
        """Set of Datasets reachable through .reference links
        (reference basic.py Dataset.get_ref_chain)."""
        head = self
        chain = set()
        while len(chain) < ref_limit:
            if isinstance(head, Dataset):
                chain.add(head)
                if head.reference is not None:
                    head = head.reference
                else:
                    break
            else:
                break
        return chain

    def set_feature_name(self, feature_name) -> "Dataset":
        """Set feature names; after construction renames in place
        (reference basic.py Dataset.set_feature_name)."""
        self.feature_name = feature_name
        if self._binned is not None and feature_name != "auto":
            names = list(feature_name)
            if len(names) != self._binned.num_total_features:
                raise LightGBMError(
                    f"Length of feature names {len(names)} does not match "
                    f"number of features {self._binned.num_total_features}"
                )
            self._binned.feature_names = names
        return self

    def set_categorical_feature(self, categorical_feature) -> "Dataset":
        """Set categorical features; binding happens at construct
        (reference basic.py Dataset.set_categorical_feature)."""
        if self.categorical_feature == categorical_feature:
            return self
        if self._binned is not None:
            raise LightGBMError(
                "Cannot set categorical feature after the Dataset was "
                "constructed; set it at creation"
            )
        self.categorical_feature = categorical_feature
        return self

    def feature_num_bin(self, feature: Union[int, str]) -> int:
        """Number of bins for a feature (LGBM_DatasetGetFeatureNumBin)."""
        self.construct()
        if isinstance(feature, str):
            feature = self._binned.feature_names.index(feature)
        return int(self._binned.mappers[feature].num_bin)

    def save_binary(self, filename: Union[str, Path]) -> "Dataset":
        """Persist the binned form to a fast-reload binary file
        (Dataset::SaveBinaryFile, dataset.h:700; reload by passing the
        path as Dataset(data=...) — parsers.py binary cache format)."""
        from .parsers import save_binary as _save

        self.construct()
        _save(self._binned, str(filename))
        return self

    def add_features_from(self, other: "Dataset") -> "Dataset":
        """Horizontally stack another Dataset's features into this one
        (reference basic.py Dataset.add_features_from /
        LGBM_DatasetAddFeaturesFrom). TPU deviation: the reference
        splices the other dataset's FeatureGroups into this one's bin
        structure; here both raw matrices are concatenated and binning
        re-runs at next construct — requires raw data on both sides
        (free_raw_data=False)."""
        if self.data is None or other.data is None:
            raise LightGBMError(
                "add_features_from requires raw data on both Datasets "
                "(free_raw_data=False)"
            )
        a, a_names = _to_2d_numpy(self.data)
        b, b_names = _to_2d_numpy(other.data)
        if a.shape[0] != b.shape[0]:
            raise LightGBMError(
                f"Cannot add features from a Dataset with {b.shape[0]} "
                f"rows to one with {a.shape[0]} rows"
            )
        self.data = np.concatenate([a, b], axis=1)
        if (isinstance(self.feature_name, list)
                and isinstance(other.feature_name, list)):
            self.feature_name = list(self.feature_name) + list(
                other.feature_name
            )
        else:
            self.feature_name = "auto"
        cf_a = self.categorical_feature
        cf_b = other.categorical_feature
        if cf_a != "auto" or cf_b != "auto":
            # string names survive the merge (feature-name lists were
            # concatenated above); integer indices from `other` shift by
            # this dataset's original width
            merged = [] if cf_a == "auto" else list(cf_a)
            if cf_b != "auto":
                merged += [
                    c if isinstance(c, str) else c + a.shape[1]
                    for c in cf_b
                ]
            self.categorical_feature = merged
        self._binned = None  # re-bin with the widened matrix
        return self

    def num_data(self) -> int:
        if self._binned is not None:
            return self._binned.num_data
        if isinstance(self.data, (str, Path)):
            self.construct()  # file input: shape is unknown until parsed
            return self._binned.num_data
        arr, _ = _to_2d_numpy(self.data)
        return arr.shape[0]

    def num_feature(self) -> int:
        if self._binned is not None:
            return self._binned.num_total_features
        if isinstance(self.data, (str, Path)):
            self.construct()
            return self._binned.num_total_features
        arr, _ = _to_2d_numpy(self.data)
        return arr.shape[1]

    def get_feature_name(self) -> List[str]:
        self.construct()
        return list(self._binned.feature_names)

    def subset(self, used_indices: Sequence[int], params=None) -> "Dataset":
        idx = np.asarray(used_indices)
        if self._binned is not None:
            # binned-level subset (Dataset::CopySubrow): shares mappers,
            # keeps all metadata incl. group/position
            sub = Dataset.__new__(Dataset)
            sub.__dict__.update(
                data=None,
                label=None if self.label is None else self.label[idx],
                reference=self,
                weight=None if self.weight is None else self.weight[idx],
                group=None,
                position=None if self.position is None else self.position[idx],
                init_score=None if self.init_score is None else self.init_score[idx],
                feature_name=self.feature_name,
                categorical_feature=self.categorical_feature,
                params=copy.deepcopy(params or self.params),
                free_raw_data=self.free_raw_data,
                _binned=self._binned.copy_subrow(idx),
                used_indices=idx,
                pandas_categorical=self.pandas_categorical,
            )
            sub.group = (
                None if sub._binned.metadata.group is None
                else np.asarray(sub._binned.metadata.group)
            )
            return sub
        if self.data is None:
            log.fatal("Cannot subset: raw data was freed")
        arr, _ = _to_2d_numpy(self.data)
        sub = Dataset(
            arr[idx],
            label=None if self.label is None else self.label[idx],
            reference=self,
            weight=None if self.weight is None else self.weight[idx],
            position=None if self.position is None else self.position[idx],
            init_score=None if self.init_score is None else self.init_score[idx],
            feature_name=self.feature_name,
            categorical_feature=self.categorical_feature,
            params=params or self.params,
            free_raw_data=self.free_raw_data,
        )
        sub.used_indices = idx
        return sub


class Booster:
    """Booster wrapper (reference basic.py:3543)."""

    def __init__(
        self,
        params: Optional[Dict[str, Any]] = None,
        train_set: Optional[Dataset] = None,
        model_file: Optional[Union[str, Path]] = None,
        model_str: Optional[str] = None,
    ):
        self.params = copy.deepcopy(params) or {}
        self.best_iteration = -1
        self.best_score: Dict[str, Dict[str, float]] = {}
        self._train_data_name = "training"
        self.pandas_categorical = None

        if train_set is not None:
            if not isinstance(train_set, Dataset):
                raise TypeError(f"Training data should be Dataset instance, met {type(train_set).__name__}")
            # distributed network params join the multi-host cluster
            # BEFORE any backend touch (reference basic.py:3606: Booster
            # calls set_network when machines/num_machines are present).
            # Aliases resolve through the config table (num_machine,
            # machine_list/mlist, local_port, workers, ...).
            from .config import resolve_alias as _ra

            net = {}
            for k, v in self.params.items():
                net.setdefault(_ra(k), v)
            nm = int(net.get("num_machines", 1))
            if nm > 1:
                set_network(
                    machines=net.get("machines", ""),
                    local_listen_port=int(net.get("local_listen_port", 12400)),
                    num_machines=nm,
                    machine_list_file=net.get("machine_list_filename", ""),
                )
            # params relevant to dataset CONSTRUCTION merge into the
            # dataset (binding at first construct); the booster's config
            # takes only dataset-relevant keys from the dataset so one
            # training's params never leak into the next booster using
            # the same Dataset
            from .config import DATASET_PARAMS, resolve_alias

            train_set.params = {**train_set.params, **self.params}
            train_set.construct()
            ds_part = {
                k: v
                for k, v in train_set.params.items()
                if resolve_alias(k) in DATASET_PARAMS
            }
            self.config = Config({**ds_part, **self.params})
            from .boosting import create_boosting

            self._gbdt = create_boosting(self.config, train_set._binned)
            self.train_set = train_set
            self._valid_sets: List[Dataset] = []
            self._name_valid_sets: List[str] = []
        elif model_file is not None or model_str is not None:
            from .model_io import load_model_string

            if model_file is not None:
                model_str = Path(model_file).read_text()
            self.config, self._gbdt = load_model_string(model_str)
            self.train_set = None
            self._valid_sets = []
            self._name_valid_sets = []
        else:
            raise TypeError("At least one of train_set, model_file or model_str should be not None.")

    # ------------------------------------------------------------------
    def add_valid(self, data: Dataset, name: str) -> "Booster":
        if not isinstance(data, Dataset):
            raise TypeError(f"Validation data should be Dataset instance, met {type(data).__name__}")
        if data.reference is not self.train_set:
            data.reference = self.train_set
        data.construct()
        self._gbdt.add_valid(data._binned, name)
        self._valid_sets.append(data)
        self._name_valid_sets.append(name)
        return self

    def _continue_from(self, init_booster: "Booster") -> None:
        """Continued training (reference input_model / python init_model,
        boosting.h:311): adopt the loaded model's trees and seed every
        score set with their binned-traversal predictions, then keep
        appending trees. Call after add_valid."""
        from .tree import tree_to_arrays

        from . import log

        gb = self._gbdt
        src = init_booster._gbdt
        K = gb.num_class
        if src.num_class != K:
            log.fatal(
                f"init_model has {src.num_class} models per iteration, "
                f"training config has {K}"
            )
        if gb.config.boosting in ("dart", "rf"):
            # DART drop bookkeeping and RF's running-average score have
            # no stored state for the loaded trees — refuse rather than
            # silently corrupt (reference keeps full state in-process)
            log.fatal(
                f"init_model with boosting={gb.config.boosting} is not "
                "supported yet; use boosting=gbdt for continued training"
            )
        models = list(src.models)
        gb._models = list(models)
        gb.iter_ = len(models) // K
        gb._init_iters = gb.iter_  # iteration origin for truncate/snapshot
        for mi, t in enumerate(models):
            arrays = tree_to_arrays(t, gb.train_set)
            gb.device_trees.append((arrays, None))
            k = mi % K
            for ss in [gb.train] + gb.valids:
                dev = gb._dev_of(ss.dataset)
                if t.num_leaves > 1:
                    leaf = gb._traverse(arrays, dev["bins"], dev["nan_bin"], dev.get("bundle"))
                    ss.score = ss.score.at[k].add(arrays.leaf_value[leaf])
                else:
                    ss.score = ss.score.at[k].add(float(t.leaf_value[0]))

    def update(self, train_set: Optional[Dataset] = None, fobj=None) -> bool:
        """One boosting iteration (basic.py:4052). Returns True if
        training stopped (cannot split any more)."""
        if train_set is not None and train_set is not self.train_set:
            raise LightGBMError("Resetting train_set is not supported")
        if fobj is None:
            return self._gbdt.train_one_iter()
        # DART applies its dropout lazily before the score is read
        # (reference GetTrainingScore, dart.hpp:80)
        if hasattr(self._gbdt, "before_gradients"):
            self._gbdt.before_gradients()
        grad, hess = fobj(self.__inner_predict_raw(0), self.train_set)
        return self._gbdt.train_one_iter(np.asarray(grad), np.asarray(hess))

    def rollback_one_iter(self) -> "Booster":
        self._gbdt.rollback_one_iter()
        return self

    def current_iteration(self) -> int:
        return self._gbdt.current_iteration()

    def num_trees(self) -> int:
        return self._gbdt.num_trees()

    def num_model_per_iteration(self) -> int:
        return self._gbdt.num_class

    def reset_parameter(self, params: Dict[str, Any]) -> "Booster":
        self.params.update(params)
        self.config.update(params)
        self._gbdt.shrinkage_rate = self.config.learning_rate
        self._gbdt.params = None  # force re-derive
        from .learner import make_split_params

        self._gbdt.params = make_split_params(self.config)
        return self

    # ------------------------------------------------------------------
    def __inner_predict_raw(self, data_idx: int) -> np.ndarray:
        g = self._gbdt
        ss = g.train if data_idx == 0 else g.valids[data_idx - 1]
        score = g.get_score(ss)
        return score if g.num_class > 1 else score[0]

    def eval(self, data: Dataset, name: str, feval=None):
        raise NotImplementedError("use eval_train/eval_valid")

    def eval_train(self, feval=None) -> List[Tuple[str, str, float, bool]]:
        out = self._gbdt.eval_train()
        out = [(self._train_data_name, n, v, hb) for (_dn, n, v, hb) in out]
        if feval is not None:
            out.extend(self._run_feval(feval, 0, self._train_data_name))
        return out

    def eval_valid(self, feval=None) -> List[Tuple[str, str, float, bool]]:
        out = self._gbdt.eval_valid()
        if feval is not None:
            for i, name in enumerate(self._name_valid_sets):
                out.extend(self._run_feval(feval, i + 1, name))
        return out

    def _run_feval(self, feval, data_idx: int, name: str):
        ds = self.train_set if data_idx == 0 else self._valid_sets[data_idx - 1]
        preds = self.__inner_predict_raw(data_idx)
        # the reference converts scores before handing them to feval
        # (GetPredictAt -> ConvertOutput, gbdt.cpp:709); custom-objective
        # training has objective none -> identity
        if self._gbdt.objective is not None:
            preds = self._gbdt.objective.convert_output(preds)
        fevals = feval if isinstance(feval, (list, tuple)) else [feval]
        results = []
        for f in fevals:
            res = f(preds, ds)
            results.extend(res if isinstance(res, list) else [res])
        return [(name, rn, rv, rhb) for rn, rv, rhb in results]

    # ------------------------------------------------------------------
    def predict(
        self,
        data: Any,
        start_iteration: int = 0,
        num_iteration: Optional[int] = None,
        raw_score: bool = False,
        pred_leaf: bool = False,
        pred_contrib: bool = False,
        validate_features: bool = False,
        device: Optional[str] = None,
        **kwargs: Any,
    ) -> np.ndarray:
        arr, _ = _to_2d_numpy(data)
        if num_iteration is None:
            num_iteration = self.best_iteration if self.best_iteration > 0 else -1
        if device not in (None, "", "cpu", "host"):
            # TPU-resident scoring (serving.TensorForest): the forest is
            # packed to device tables and traversed rows x trees under
            # jit. Tables are rebuilt per call (same posture as
            # _packed_model: models mutate in place through refit /
            # set_leaf_output and packing is ~ms); the jitted traversal
            # itself is shared module-level, so no recompile per call.
            if pred_contrib:
                log.warning(
                    "pred_contrib has no device implementation; using "
                    "the host SHAP path"
                )
            elif kwargs.get("pred_early_stop",
                            self.params.get("pred_early_stop", False)):
                log.warning(
                    "pred_early_stop has no device implementation; "
                    "using the host predictor"
                )
            else:
                from .serving import TensorForest

                forest = TensorForest.from_booster(self)
                if pred_leaf:
                    return forest.predict_leaf(
                        arr, start_iteration, num_iteration
                    )
                raw = forest.predict_raw(arr, start_iteration, num_iteration)
                g = self._gbdt
                if not raw_score and g.objective is not None:
                    raw = g.objective.convert_output(raw)
                return raw[0] if g.num_class == 1 else raw.T
        if pred_leaf:
            return self._gbdt.predict_leaf_index(arr, start_iteration, num_iteration)
        if pred_contrib:
            if any(t.is_linear for t in self._gbdt.models):
                from . import log

                log.fatal(
                    "pred_contrib (SHAP) is not supported for models "
                    "with linear trees"
                )
            return self._gbdt.predict_contrib(arr, start_iteration, num_iteration)
        # prediction early stop (reference c_api predict parameter
        # parsing; kwargs mirror the parameter names)
        early_stop = None
        if kwargs.get("pred_early_stop", self.params.get("pred_early_stop", False)):
            # classification only (reference Predictor picks CreateNone
            # for everything else, prediction_early_stop.cpp:18)
            is_cls = self._gbdt.num_class > 1 or getattr(
                self.config, "objective", ""
            ) in ("binary", "cross_entropy", "cross_entropy_lambda")
            if is_cls:
                early_stop = (
                    int(kwargs.get("pred_early_stop_freq",
                                   self.params.get("pred_early_stop_freq", 10))),
                    float(kwargs.get("pred_early_stop_margin",
                                     self.params.get("pred_early_stop_margin", 10.0))),
                )
            else:
                log.warning(
                    "pred_early_stop only applies to classification; ignored"
                )
        return self._gbdt.predict(arr, start_iteration, num_iteration,
                                  raw_score=raw_score, early_stop=early_stop)

    # ------------------------------------------------------------------
    def model_to_string(
        self, num_iteration: Optional[int] = None, start_iteration: int = 0,
        importance_type: str = "split",
    ) -> str:
        from .model_io import save_model_string

        ni = num_iteration
        if ni is None:
            ni = self.best_iteration if self.best_iteration > 0 else -1
        return save_model_string(self._gbdt, self.config, ni, start_iteration)

    def save_model(
        self, filename: Union[str, Path], num_iteration: Optional[int] = None,
        start_iteration: int = 0, importance_type: str = "split",
    ) -> "Booster":
        Path(filename).write_text(
            self.model_to_string(num_iteration, start_iteration, importance_type)
        )
        return self

    def dump_model(
        self, num_iteration: Optional[int] = None, start_iteration: int = 0,
        importance_type: str = "split", object_hook=None,
    ) -> Dict[str, Any]:
        """JSON model representation (LGBM_BoosterDumpModel)."""
        from .model_io import dump_model_dict

        ni = num_iteration
        if ni is None:
            ni = self.best_iteration if self.best_iteration > 0 else -1
        d = dump_model_dict(
            self._gbdt, self.config, ni, start_iteration, importance_type
        )
        if object_hook is not None:
            # apply like json.loads(..., object_hook=...): bottom-up over
            # every dict in the structure
            import json

            d = json.loads(json.dumps(d), object_hook=object_hook)
        return d

    def refit(
        self, data: Any, label: Any, decay_rate: float = 0.9, **kwargs: Any
    ) -> "Booster":
        """Refit existing tree structures on new data
        (Booster.refit / LGBM_BoosterRefit)."""
        import copy

        arr, _ = _to_2d_numpy(data)
        new_booster = copy.copy(self)
        # shallow-copy the GBDT: refit only rewrites host tree leaf values
        # and replaces device_trees entries, so sharing the (possibly
        # device-resident) dataset buffers avoids doubling memory
        new_booster._gbdt = copy.copy(self._gbdt)
        new_booster._gbdt.models = [copy.deepcopy(t) for t in self._gbdt.models]
        new_booster._gbdt.device_trees = list(self._gbdt.device_trees)
        # un-alias the remaining mutable members so future mutations on the
        # refitted booster can never corrupt the source booster (the score
        # arrays themselves are immutable jax arrays — the _ScoreSet
        # containers and valids list are what must not be shared)
        import dataclasses as _dc

        if hasattr(self._gbdt, "train"):
            new_booster._gbdt.train = _dc.replace(self._gbdt.train)
            new_booster._gbdt.valids = [
                _dc.replace(v) for v in self._gbdt.valids
            ]
        new_params = dict(self.config.explicit_params())
        new_params["refit_decay_rate"] = decay_rate
        new_booster.config = Config(new_params)
        new_booster._gbdt.config = new_booster.config
        new_booster._gbdt.refit(
            arr, _to_1d(label), weight=kwargs.get("weight"),
            group=kwargs.get("group"),
        )
        return new_booster

    def get_split_value_histogram(
        self,
        feature,
        bins=None,
        xgboost_style: bool = False,
    ):
        """Histogram of the numeric split thresholds the model chose for
        one feature (reference basic.py:5065). Returns
        ``numpy.histogram``-style ``(hist, bin_edges)``, or the XGBoost
        matrix/DataFrame form when ``xgboost_style=True``."""
        from .plotting import _split_values

        values = _split_values(self, feature)
        n_unique = len(set(values))
        if bins is None or (
            isinstance(bins, int) and xgboost_style and bins > n_unique
        ):
            bins = max(n_unique, 1)
        hist, edges = np.histogram(np.asarray(values, dtype=np.float64),
                                   bins=bins)
        if not xgboost_style:
            return hist, edges
        keep = hist != 0
        out = np.column_stack((edges[1:][keep], hist[keep]))
        try:
            import pandas as pd

            return pd.DataFrame(out, columns=["SplitValue", "Count"])
        except ImportError:
            return out

    def feature_importance(self, importance_type: str = "split", iteration=None) -> np.ndarray:
        return self._gbdt.feature_importance(importance_type)

    def feature_name(self) -> List[str]:
        if self.train_set is not None:
            return self.train_set.get_feature_name()
        return list(self._gbdt.feature_names)

    def num_feature(self) -> int:
        if self._gbdt.train_set is not None:
            return self._gbdt.train_set.num_total_features
        return len(self._gbdt.feature_names)

    def free_dataset(self) -> "Booster":
        self.train_set = None
        return self

    def set_train_data_name(self, name: str) -> "Booster":
        """Name used for the training set in eval output (reference
        basic.py Booster.set_train_data_name)."""
        self._train_data_name = name
        return self

    def model_from_string(self, model_str: str) -> "Booster":
        """Load a model from its text-format string in place
        (reference basic.py Booster.model_from_string)."""
        from .model_io import load_model_string

        self.config, self._gbdt = load_model_string(model_str)
        self.train_set = None
        self._valid_sets = []
        self._name_valid_sets = []
        return self

    def get_leaf_output(self, tree_id: int, leaf_id: int) -> float:
        """Output value of one leaf (LGBM_BoosterGetLeafValue)."""
        return float(self._gbdt.models[tree_id].leaf_value[leaf_id])

    def set_leaf_output(self, tree_id: int, leaf_id: int,
                        value: float) -> "Booster":
        """Overwrite one leaf's output value (LGBM_BoosterSetLeafValue;
        Tree::SetLeafOutput). Updates the device-resident copy used by
        fused validation scoring as well as the host tree; like the
        reference, already-accumulated train/valid scores are not
        retroactively adjusted."""
        t = self._gbdt.models[tree_id]
        t.leaf_value[leaf_id] = float(value)
        if tree_id < len(self._gbdt.device_trees):
            arrays, aux = self._gbdt.device_trees[tree_id]
            if arrays is not None:
                arrays = arrays._replace(
                    leaf_value=arrays.leaf_value.at[leaf_id].set(
                        float(value)
                    )
                )
                self._gbdt.device_trees[tree_id] = (arrays, aux)
        return self

    def lower_bound(self) -> float:
        """Lower bound of the raw score over all possible inputs
        (LGBM_BoosterGetLowerBoundValue: sum of per-tree minima)."""
        return float(sum(
            float(np.min(t.leaf_value[: t.num_leaves]))
            for t in self._gbdt.models
        ))

    def upper_bound(self) -> float:
        """Upper bound of the raw score (LGBM_BoosterGetUpperBoundValue)."""
        return float(sum(
            float(np.max(t.leaf_value[: t.num_leaves]))
            for t in self._gbdt.models
        ))

    def shuffle_models(self, start_iteration: int = 0,
                       end_iteration: int = -1) -> "Booster":
        """Randomly permute the tree order in [start, end) iterations
        (LGBM_BoosterShuffleModels; predictions are order-invariant)."""
        K = self.num_model_per_iteration()
        n_iter = self._gbdt.num_trees() // K
        end = n_iter if end_iteration < 0 else min(end_iteration, n_iter)
        idx = np.arange(start_iteration, end)
        np.random.shuffle(idx)
        order = np.concatenate([
            np.arange(start_iteration),
            idx,
            np.arange(end, n_iter),
        ])
        models, dev = self._gbdt.models, self._gbdt.device_trees
        self._gbdt.models = [
            models[i * K + k] for i in order for k in range(K)
        ]
        if len(dev) == len(models):
            self._gbdt.device_trees = [
                dev[i * K + k] for i in order for k in range(K)
            ]
        return self

    def trees_to_dataframe(self):
        """All trees flattened to one pandas DataFrame, one row per
        node/leaf (reference basic.py Booster.trees_to_dataframe —
        same column set)."""
        import pandas as pd

        if self._gbdt.num_trees() == 0:
            raise LightGBMError(
                "There are no trees in this Booster and thus nothing "
                "to parse"
            )

        rows: List[Dict[str, Any]] = []

        def node_ix(tree_index: int, node: Dict[str, Any]) -> str:
            if "split_index" in node:
                return f"{tree_index}-S{node['split_index']}"
            return f"{tree_index}-L{node.get('leaf_index', 0)}"

        model = self.dump_model()
        for t in model["tree_info"]:
            tree_index = t["tree_index"]
            # explicit preorder stack: chain-shaped deep trees must not
            # hit the interpreter recursion limit
            stack = [(t["tree_structure"], 1, None)]
            while stack:
                node, depth, parent = stack.pop()
                ix = node_ix(tree_index, node)
                is_split = "split_index" in node
                left = node.get("left_child")
                right = node.get("right_child")
                rows.append({
                    "tree_index": tree_index,
                    "node_depth": depth,
                    "node_index": ix,
                    "left_child": (
                        node_ix(tree_index, left) if left else None
                    ),
                    "right_child": (
                        node_ix(tree_index, right) if right else None
                    ),
                    "parent_index": parent,
                    "split_feature": (
                        self._feature_display_name(node["split_feature"])
                        if is_split else None
                    ),
                    "split_gain": node.get("split_gain"),
                    "threshold": node.get("threshold"),
                    "decision_type": node.get("decision_type"),
                    "missing_direction": (
                        ("left" if node.get("default_left") else "right")
                        if is_split else None
                    ),
                    "missing_type": node.get("missing_type"),
                    "value": node.get("internal_value",
                                      node.get("leaf_value")),
                    "weight": node.get("internal_weight",
                                       node.get("leaf_weight")),
                    "count": node.get("internal_count",
                                      node.get("leaf_count")),
                })
                if is_split:
                    stack.append((right, depth + 1, ix))
                    stack.append((left, depth + 1, ix))
        return pd.DataFrame(rows)

    def _feature_display_name(self, fidx: int) -> str:
        names = self.feature_name()
        return names[fidx] if fidx < len(names) else f"Column_{fidx}"

    def set_network(
        self,
        machines: Any,
        local_listen_port: int = 12400,
        listen_time_out: int = 120,
        num_machines: int = 1,
    ) -> "Booster":
        """Join a multi-host cluster from an existing Booster (reference
        basic.py Booster.set_network; module-level set_network applies)."""
        set_network(machines, local_listen_port, listen_time_out,
                    num_machines)
        self._network = True
        return self

    def free_network(self) -> "Booster":
        return self
