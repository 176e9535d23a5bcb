"""Dataset and Booster — the primary user-facing objects.

Reference: python-package/lightgbm/basic.py (Dataset :1692, Booster :3495). The reference
binds a C++ core over ctypes; here the "core" is the JAX engine in-process, so Dataset
directly owns the host binning result and the device bin matrix, and Booster owns the
boosting engine. Public method surface mirrors the reference so existing LightGBM user
code ports by changing the import.
"""
from __future__ import annotations

import abc
import copy
import os
from pathlib import Path
from typing import Any, Dict, Iterable, List, Optional, Sequence, Tuple, Union

import numpy as np

from .binning import BinnedData, construct_binned, find_bin_mappers, find_feature_groups
from .config import Config, resolve_aliases
from .device_data import DeviceData, device_view, to_device
from .metrics import create_metrics
from .objectives import create_objective
from .telemetry import boundary as _boundary
from .utils.log import LightGBMError, log_info, log_warning, set_verbosity

_LABEL_FIELDS = ("label", "weight", "group", "init_score", "position")


def _mappers_compatible(a, b) -> bool:
    """True when two bin-mapper lists bin identically (CheckAlign analog)."""
    if a is b:
        return True
    if len(a) != len(b):
        return False
    for ma, mb in zip(a, b):
        if ma.bin_type != mb.bin_type:
            return False
        ua, ub = np.asarray(ma.upper_bounds), np.asarray(mb.upper_bounds)
        if ua.shape != ub.shape or not np.array_equal(ua, ub):
            return False
    return True


def _to_2d_float(data, align_categories=None
                 ) -> Tuple[np.ndarray, Optional[List[str]], List[int],
                            Optional[List[list]]]:
    """Coerce supported data containers to float64 ndarray; returns
    (array, feature_names or None, pandas_categorical_indices,
    pandas_categorical_lists or None).

    Accepts ndarray/DataFrame, a LIST of row chunks (the reference's
    ChunkedArray streaming-push ingestion, include/LightGBM/c_api.h
    LGBM_DatasetCreateFromMats), and pyarrow Table/RecordBatch
    (include/LightGBM/arrow.h).

    align_categories: the TRAINING data's per-categorical-column category
    lists (by categorical-column order) — predict-time DataFrames remap
    their categories through them so codes agree with training even when
    a frame's category order differs; unseen categories become NaN
    (reference: python-package basic.py _data_from_pandas +
    pandas_categorical in the model file)."""
    feature_names = None
    cat_idx: List[int] = []
    if isinstance(data, (list, tuple)) and data and all(
            (getattr(c, "ndim", 0) == 2) or hasattr(c, "columns")
            for c in data):
        # chunked 2-D row blocks (list-of-1-D stays the plain ndarray path);
        # chunks 1.. align their categorical codes to chunk 0's category
        # lists, or a chunk whose local category order differs would code
        # the same value differently
        first = _to_2d_float(data[0], align_categories)
        names0, cats0, lists0 = first[1], first[2], first[3]
        align_rest = align_categories if align_categories is not None \
            else lists0
        converted = [first] + [_to_2d_float(c, align_rest)
                               for c in data[1:]]
        return np.vstack([c[0] for c in converted]), names0, cats0, lists0
    t_name = type(data).__module__
    if t_name.startswith("pyarrow"):
        import pyarrow as pa
        if isinstance(data, pa.RecordBatch):
            data = pa.Table.from_batches([data])
        if isinstance(data, pa.Table):
            feature_names = [str(c) for c in data.column_names]
            cols = [np.asarray(data.column(i).to_numpy(zero_copy_only=False),
                               np.float64) for i in range(data.num_columns)]
            return np.column_stack(cols), feature_names, [], None
    if hasattr(data, "dtypes") and hasattr(data, "columns"):  # pandas DataFrame
        import pandas as pd
        feature_names = [str(c) for c in data.columns]
        df = data.copy()
        cat_lists: List[list] = []
        for i, col in enumerate(df.columns):
            if isinstance(df[col].dtype, pd.CategoricalDtype):
                if align_categories is not None \
                        and len(cat_lists) < len(align_categories):
                    train_cats = align_categories[len(cat_lists)]
                    frame_cats = list(df[col].cat.categories)
                    strs = [str(c) for c in frame_cats]
                    if (train_cats and frame_cats
                            and all(isinstance(t, str) for t in train_cats)
                            and not set(train_cats) & set(frame_cats)
                            and len(set(strs)) == len(strs)):
                        # model-file round trip stringifies non-JSON-native
                        # categories (datetimes); match them by str() —
                        # unless stringification collides, in which case
                        # the values are simply unseen (-> missing)
                        df[col] = df[col].cat.rename_categories(strs)
                    df[col] = df[col].cat.set_categories(train_cats)
                cat_lists.append(list(df[col].cat.categories))
                codes = df[col].cat.codes.astype(np.float64)
                df[col] = codes.where(codes >= 0, np.nan)  # unseen -> NaN
                cat_idx.append(i)
            elif df[col].dtype == object:
                raise LightGBMError(f"DataFrame column {col!r} has object dtype; "
                                    "convert to numeric or categorical first")
        if align_categories is not None \
                and len(cat_lists) != len(align_categories):
            # silent positional mis-alignment would produce wrong codes
            # (stock: "train and valid dataset categorical_feature do not
            # match")
            raise LightGBMError(
                f"DataFrame has {len(cat_lists)} categorical columns but "
                f"the training data had {len(align_categories)}; "
                "categorical columns must match training")
        arr = df.to_numpy(dtype=np.float64, na_value=np.nan)
        # cat_lists may be EMPTY — "DataFrame trained with zero categorical
        # columns" must stay distinguishable from "not a DataFrame" so the
        # column-count check above still fires for categorical predict frames
        return arr, feature_names, cat_idx, cat_lists
    if isinstance(data, np.ndarray) and data.dtype == np.float32 \
            and data.ndim == 2:
        # a float32 table is held as it came, not as a float64 copy twice
        # its size (6.4 GB beside a 400,000 x 2,000 table's 3.2 GB): every
        # reader widens the piece it takes - a column, a row sample, a
        # chunk - and float32 -> float64 is exact, so bins, thresholds and
        # models are those of the widened copy
        return data, feature_names, cat_idx, None
    arr = np.asarray(data, dtype=np.float64)
    if arr.ndim == 1:
        arr = arr.reshape(-1, 1)
    return arr, feature_names, cat_idx, None


def _is_scipy_sparse(data) -> bool:
    try:
        import scipy.sparse as sp
        return sp.issparse(data)
    except ImportError:
        return False


class Sequence(abc.ABC):
    """Generic batched random-access data interface for STREAMING dataset
    construction (reference: python-package basic.py:841 Sequence +
    LGBM_DatasetCreateFromSampledColumn / DatasetPushRows, c_api.h).

    Subclasses implement `__len__` and `__getitem__` (int -> (F,) row,
    slice -> (n, F) block). Construction makes two passes: random-access
    row sampling finds the bin mappers, then batches of `batch_size` rows
    stream through binning into the uint8 bin matrix — the float64 feature
    matrix is NEVER materialized (8x less peak memory than dense ingest).
    """

    batch_size = 4096

    @abc.abstractmethod
    def __getitem__(self, idx):
        raise NotImplementedError

    @abc.abstractmethod
    def __len__(self) -> int:
        raise NotImplementedError


class Dataset:
    """Training/validation dataset with lazy binning (reference: basic.py:1692)."""

    def __init__(self, data, label=None, reference: Optional["Dataset"] = None,
                 weight=None, group=None, init_score=None,
                 feature_name: Union[str, List[str]] = "auto",
                 categorical_feature: Union[str, List] = "auto",
                 params: Optional[Dict[str, Any]] = None,
                 free_raw_data: Optional[bool] = None, position=None):
        self.params = dict(params or {})
        self.reference = reference
        # None = auto: file-loaded datasets free the raw matrix after
        # construct() (nothing re-reads it and it is the largest host
        # allocation — stock frees file data too); in-memory containers
        # stay referenced unless the caller opts in
        self.free_raw_data = free_raw_data
        self._from_file = isinstance(data, (str, Path))
        self._feature_name_arg = feature_name
        self._categorical_feature_arg = categorical_feature
        self._predictor = None
        self._dist = None
        self._stream = None              # streaming-ingest source info
        self._streamed = False
        self.ingest_stats = None
        self.pandas_categorical = None   # training category lists (DataFrames)
        self._raw_container = None       # original user container (get_data)
        self.raw_seq = None
        self.raw_arrow = None

        if isinstance(data, (str, Path)) and self._is_binary_file(data):
            if reference is not None:
                raise LightGBMError(
                    "a binary dataset file carries its own bin mappers; "
                    "reference= cannot be combined with it")
            self.raw_data = None
            self.raw_sparse = None
            self._pandas_names = None
            self._pandas_cat_idx = []
            self.binned = None
            self._device = None
            self._resolved_feature_names = None
            self.label = self.weight = self.init_score = None
            self.position = self.group = None
            self._load_binary(str(data))
            # explicit constructor arguments override the stored metadata,
            # matching the non-binary path's semantics
            if label is not None:
                self.label = np.asarray(label, np.float64).reshape(-1)
            if weight is not None:
                self.weight = np.asarray(weight, np.float64).reshape(-1)
            if init_score is not None:
                self.init_score = np.asarray(init_score, np.float64)
            if position is not None:
                self.position = np.asarray(position, np.int32).reshape(-1)
            if group is not None:
                self.group = np.asarray(group, np.int64).reshape(-1)
            if isinstance(feature_name, list):
                self._resolved_feature_names = [str(x) for x in feature_name]
            return
        if isinstance(data, (str, Path)):
            from .ingest import resolve_ingest_mode
            if resolve_ingest_mode(self.params, str(data)) == "stream":
                from .dataset_io import detect_file_format
                if detect_file_format(str(data)) != "libsvm":
                    # defer ALL parsing to construct(): the streaming
                    # two-pass loader (docs/INGEST.md) reads the file in
                    # O(ingest_chunk_rows) chunks — num_data/num_feature
                    # are unknown until pass 1 runs
                    from .parallel.dist_data import dist_context
                    dist = None
                    if not self.params.get("pre_partition", False):
                        dist = dist_context()
                    self._stream = {"kind": "file", "path": str(data),
                                    "dist": dist}
                    if dist is not None:
                        self._dist = {"rank": dist[0], "nproc": dist[1]}
                    self.raw_data = None
                    self.raw_sparse = None
                    self._pandas_names = None
                    self._pandas_cat_idx = []
                    self.num_data_ = -1
                    self.num_feature_ = -1
                    self.label = None if label is None else \
                        np.asarray(label, np.float64).reshape(-1)
                    self.weight = None if weight is None else \
                        np.asarray(weight, np.float64).reshape(-1)
                    self.init_score = None if init_score is None else \
                        np.asarray(init_score, np.float64)
                    self.position = None if position is None else \
                        np.asarray(position, np.int32).reshape(-1)
                    self.group = None if group is None else \
                        np.asarray(group, np.int64).reshape(-1)
                    self.binned = None
                    self._device = None
                    self._resolved_feature_names = None
                    return
                log_info("ingest_mode=stream: LibSVM input falls back to "
                         "the in-memory loader")
        if isinstance(data, (str, Path)):
            from .dataset_io import load_data_file
            from .parallel.dist_data import dist_context
            dist = dist_context()
            if (dist is not None
                    and not self.params.get("pre_partition", False)):
                # distributed load: this process parses ONLY its row shard
                # (reference: DatasetLoader::LoadFromFile rank sharding,
                # dataset_loader.cpp:211); mappers sync in construct().
                # With reference= set (validation data) the shard is binned
                # with the TRAINING dataset's mappers instead
                # (LoadFromFileAlignWithOtherDataset, dataset_loader.cpp:307)
                rank, nproc = dist
                data, label_file, extras = load_data_file(
                    str(data), self.params, rank=rank, num_machines=nproc)
                self._dist = {"rank": rank, "nproc": nproc}
            else:
                data, label_file, extras = load_data_file(str(data),
                                                          self.params)
            if label is None:
                label = label_file
            if weight is None:
                weight = extras.get("weight")
            if group is None:
                group = extras.get("group")
            if position is None:
                position = extras.get("position")
            if init_score is None:
                init_score = extras.get("init_score")
        self.raw_sparse = None
        self.raw_seq = None
        self.raw_arrow = None
        if type(data).__module__.startswith("pyarrow"):
            import pyarrow as pa
            if isinstance(data, pa.RecordBatch):
                data = pa.Table.from_batches([data])
            if isinstance(data, pa.Table):
                # columnar ingestion: each column bins straight from the
                # Arrow buffers (zero-copy numpy views where the chunk
                # layout allows) — the (N, F) float64 matrix is never
                # materialized (reference: include/LightGBM/arrow.h
                # chunked-array C-stream ingestion)
                self.raw_arrow = data
                self.raw_data = None
                self._pandas_names = [str(c) for c in data.column_names]
                pandas_cat = []
                self._pandas_cat_idx = []
                self.num_data_ = int(data.num_rows)
                self.num_feature_ = int(data.num_columns)
                self.label = (None if label is None
                              else np.asarray(label, np.float64).reshape(-1))
                self.weight = (None if weight is None
                               else np.asarray(weight, np.float64).reshape(-1))
                self.init_score = (None if init_score is None
                                   else np.asarray(init_score, np.float64))
                self.position = (None if position is None else
                                 np.asarray(position, np.int32).reshape(-1))
                self.group = (None if group is None else
                              np.asarray(group, np.int64).reshape(-1))
                self.binned = None
                self._device = None
                self._resolved_feature_names = None
                return
        if isinstance(data, Sequence) or (
                isinstance(data, (list, tuple)) and data
                and all(isinstance(c, Sequence) for c in data)):
            seqs = [data] if isinstance(data, Sequence) else list(data)
            self.raw_seq = seqs
            self.raw_data = None
            self._pandas_names, pandas_cat = None, []
            self.num_data_ = int(sum(len(q) for q in seqs))
            first = np.asarray(seqs[0][0], np.float64).reshape(-1)
            self.num_feature_ = int(first.shape[0])
        elif _is_scipy_sparse(data):
            # CSR/CSC kept sparse end-to-end: bin mappers from sampled
            # non-zeros + implicit-zero counts, EFB from CSC structure,
            # binned matrix scattered in O(nnz) — the dense X is never
            # materialized (reference: src/io/sparse_bin.hpp, bin.h:482)
            self.raw_sparse = data.tocsr()
            self.raw_data = None
            self._pandas_names, pandas_cat = None, []
            self.num_data_, self.num_feature_ = self.raw_sparse.shape
        else:
            # validation frames align their categorical codes to the
            # TRAINING data's category lists (reference: pandas_categorical)
            align = (self.reference.pandas_categorical
                     if self.reference is not None else None)
            (self.raw_data, self._pandas_names, pandas_cat,
             self.pandas_categorical) = _to_2d_float(data, align)
            if self._pandas_names is not None:
                # keep the user's frame (a reference, not a copy) so
                # get_data() can return the ORIGINAL like stock does
                self._raw_container = data
            self.num_data_, self.num_feature_ = self.raw_data.shape
        self._pandas_cat_idx = pandas_cat

        self.label = None if label is None else np.asarray(label, np.float64).reshape(-1)
        self.weight = None if weight is None else np.asarray(weight, np.float64).reshape(-1)
        self.init_score = None if init_score is None else np.asarray(init_score, np.float64)
        self.position = None if position is None else np.asarray(position, np.int32).reshape(-1)
        self.group = None
        if group is not None:
            g = np.asarray(group, np.int64).reshape(-1)
            self.group = g

        self.binned: Optional[BinnedData] = None
        self._device: Optional[DeviceData] = None
        self._resolved_feature_names: Optional[List[str]] = None
        if self._dist is not None:
            self._finalize_distributed()

    def _finalize_distributed(self) -> None:
        """Fix the global shard-padded row layout and allgather the per-row
        metadata (O(N) scalars; the O(N*F) features stay shard-local).
        Pad rows carry weight 0 + true-mask 0 (see parallel/dist_data.py)."""
        from .parallel.dist_data import (allgather_np, check_uniform_features,
                                         gather_padded, shard_pad_base)
        if self.group is not None and int(self.group.sum()) != self.num_data_:
            raise LightGBMError(
                f"sum of group sizes ({int(self.group.sum())}) does not match "
                f"this rank's row count ({self.num_data_}); distributed "
                "ranking data must be pre-partitioned on query boundaries")
        fg = check_uniform_features(self.num_feature_)
        if fg != self.num_feature_:
            if self.raw_data is not None:
                self.raw_data = np.pad(self.raw_data,
                                       ((0, 0), (0, fg - self.num_feature_)))
            self.num_feature_ = fg
        n_local = self.num_data_
        base = shard_pad_base()
        counts = allgather_np(np.asarray([n_local], np.int64)).reshape(-1)
        n_shard = -(-int(counts.max()) // base) * base
        self._dist.update(n_local=n_local, n_shard=n_shard,
                          counts=counts, num_data_true=int(counts.sum()))
        mask = np.zeros(n_local, np.float32) + 1.0
        self._true_mask = gather_padded(mask, n_shard)
        self.label = gather_padded(self.label, n_shard)
        # pad rows must carry zero weight so weighted stats/metrics see only
        # true rows; without user weights the mask itself is the weight
        w = self.weight if self.weight is not None else mask.astype(np.float64)
        self.weight = gather_padded(np.asarray(w, np.float64), n_shard)
        self.position = gather_padded(self.position, n_shard)
        if self.init_score is not None:
            self.init_score = gather_padded(self.init_score, n_shard)
        if self.group is not None:
            # global query spans (start, size) in the shard-padded row space:
            # whole queries stay on their rank (the reference's distributed
            # ranking contract — queries never straddle machines,
            # dataset_loader.cpp partition_fun keeps groups together); pad
            # rows between shards belong to no query
            g = self.group
            rank, nproc = self._dist["rank"], self._dist["nproc"]
            starts_local = np.concatenate([[0], np.cumsum(g)[:-1]])
            nq_all = allgather_np(np.asarray([len(g)], np.int64)).reshape(-1)
            nq_max = int(nq_all.max())
            pad_s = np.zeros(nq_max, np.int64)
            pad_s[:len(g)] = starts_local
            pad_z = np.zeros(nq_max, np.int64)
            pad_z[:len(g)] = g
            s_all = allgather_np(pad_s)                  # (P, nq_max)
            z_all = allgather_np(pad_z)
            spans = []
            for r in range(nproc):
                kq = int(nq_all[r])
                spans.append(np.stack(
                    [s_all[r, :kq] + r * n_shard, z_all[r, :kq]], axis=1))
            self._query_spans = np.concatenate(spans, axis=0)   # (NQ, 2)
        self.num_data_ = int(n_shard * self._dist["nproc"])

    def get_true_row_mask(self, n: int) -> np.ndarray:
        """Row-validity mask of the padded global row space. Single-process
        layouts are a true-row prefix; distributed shard-padded layouts are
        not, so the engine must use this instead of a prefix slice."""
        out = np.zeros(n, np.float32)
        if self._dist is not None:
            out[:len(self._true_mask)] = self._true_mask
        else:
            out[:self.num_data_] = 1.0
        return out

    @classmethod
    def _is_binary_file(cls, path) -> bool:
        try:
            with open(path, "rb") as f:
                magic = f.read(len(cls._BINARY_MAGIC))
                return magic in (cls._BINARY_MAGIC, cls._BINARY_MAGIC_V1)
        except OSError:
            return False

    # ------------------------------------------------------------------
    def _resolve_categorical(self) -> List[int]:
        arg = self._categorical_feature_arg
        names = self.feature_name()
        cats = list(self._pandas_cat_idx)
        if arg == "auto" or arg is None or arg == "":
            return cats
        for c in (arg if isinstance(arg, (list, tuple)) else [arg]):
            if isinstance(c, str):
                if c in names:
                    cats.append(names.index(c))
                else:
                    log_warning(f"categorical_feature {c!r} not found in features")
            else:
                cats.append(int(c))
        return sorted(set(cats))

    def get_feature_name(self) -> List[str]:
        """Alias of feature_name() (reference: Dataset.get_feature_name)."""
        return self.feature_name()

    def set_reference(self, reference: "Dataset") -> "Dataset":
        """Bin this dataset with `reference`'s mappers (reference:
        Dataset.set_reference — which also adopts the reference's feature
        names and categorical spec; must happen before construct())."""
        if self.binned is not None and reference is not self.reference:
            raise LightGBMError(
                "Cannot set reference after the Dataset has been "
                "constructed; build a new Dataset instead")
        if self.raw_arrow is not None or self.raw_seq is not None:
            raise LightGBMError(
                "set_reference is not supported for arrow/Sequence "
                "datasets; pass reference= at construction from the same "
                "source type instead")
        self.reference = reference
        # stock adopts the reference's names/categorical spec
        self._feature_name_arg = "auto"
        self._resolved_feature_names = None
        if reference._resolved_feature_names is not None or \
                isinstance(reference._feature_name_arg, list):
            self._resolved_feature_names = list(reference.feature_name())
        self._categorical_feature_arg = reference._categorical_feature_arg
        # DataFrame categorical codes were baked at __init__ without this
        # reference's category lists — rebuild them from the ORIGINAL frame
        if (self._raw_container is not None
                and getattr(reference, "pandas_categorical", None)):
            (self.raw_data, self._pandas_names, self._pandas_cat_idx,
             self.pandas_categorical) = _to_2d_float(
                self._raw_container, reference.pandas_categorical)
        return self

    def get_data(self):
        """The raw data this Dataset was built from — the ORIGINAL
        container for DataFrames (reference: Dataset.get_data; raises
        after free_raw_data)."""
        for attr in ("_raw_container", "raw_data", "raw_sparse",
                     "raw_arrow", "raw_seq"):
            v = getattr(self, attr, None)
            if v is not None:
                return v
        raise LightGBMError(
            "Cannot access raw data: it was freed (free_raw_data=True) or "
            "the Dataset was loaded from a file/binary")

    def set_categorical_feature(self, categorical_feature) -> "Dataset":
        """Replace the categorical feature spec (reference:
        Dataset.set_categorical_feature; must happen before construct())."""
        if self.binned is not None and \
                categorical_feature != self._categorical_feature_arg:
            raise LightGBMError(
                "Cannot change categorical_feature after the Dataset has "
                "been constructed; build a new Dataset instead")
        self._categorical_feature_arg = categorical_feature
        return self

    def get_ref_chain(self, ref_limit: int = 100):
        """The chain of reference Datasets reachable from this one
        (reference: Dataset.get_ref_chain)."""
        head = self
        chain = set()
        while head is not None and len(chain) < ref_limit:
            if head in chain:
                break
            chain.add(head)
            head = head.reference
        return chain

    def feature_name(self) -> List[str]:
        if self._resolved_feature_names is not None:
            return self._resolved_feature_names
        arg = self._feature_name_arg
        if isinstance(arg, list):
            names = [str(x) for x in arg]
        elif self._pandas_names is not None:
            names = self._pandas_names
        else:
            if self.num_feature_ < 0:
                # deferred streaming ingest: width unknown until pass 1 —
                # don't cache an empty list
                return []
            names = [f"Column_{i}" for i in range(self.num_feature_)]
        self._resolved_feature_names = names
        return names

    # ------------------------------------------------------------------
    def _should_free_raw(self) -> bool:
        """Explicit free_raw_data only; the file-source auto-free is
        deferred to the training path (_free_raw_after_train) because
        construct() cannot know whether subset() (lgb.cv folds) or the
        linear-tree fitter will still need the raw matrix."""
        if self.free_raw_data is not None:
            return bool(self.free_raw_data)
        return self._streamed and self._from_file

    def _free_raw_after_train(self, cfg) -> None:
        """Auto-free for file-loaded datasets once a Booster owns the
        binned data: nothing re-reads the raw matrix on the training
        path and it is the largest host allocation.  linear_tree keeps
        it (the leaf fitter reads raw feature values); an explicit
        free_raw_data=False always wins."""
        if self.free_raw_data is None and self._from_file \
                and not cfg.linear_tree:
            self.raw_data = None
            self.raw_sparse = None
            self._raw_container = None

    def _eagerize_stream_file(self) -> None:
        """Replace the deferred streaming file source with the eager
        in-memory load (same parse + sidecars as __init__'s file path).
        linear_tree needs this: its leaf fitter reads raw feature
        values, which streaming ingest never materializes."""
        from .dataset_io import load_data_file
        info = self._stream
        dist = info.get("dist")
        if dist is not None:
            rank, nproc = dist
            data, label_file, extras = load_data_file(
                info["path"], self.params, rank=rank, num_machines=nproc)
        else:
            data, label_file, extras = load_data_file(info["path"],
                                                      self.params)
        if self.label is None and label_file is not None:
            self.label = np.asarray(label_file, np.float64).reshape(-1)
        if self.weight is None and extras.get("weight") is not None:
            self.weight = np.asarray(extras["weight"],
                                     np.float64).reshape(-1)
        if self.group is None and extras.get("group") is not None:
            self.group = np.asarray(extras["group"], np.int64).reshape(-1)
        if self.position is None and extras.get("position") is not None:
            self.position = np.asarray(extras["position"],
                                       np.int32).reshape(-1)
        if self.init_score is None and extras.get("init_score") is not None:
            self.init_score = np.asarray(extras["init_score"], np.float64)
        self.raw_data = np.asarray(data, np.float64)
        self.num_data_, self.num_feature_ = self.raw_data.shape
        self._stream = None
        if self._dist is not None:
            self._finalize_distributed()

    def construct(self) -> "Dataset":
        if self.binned is not None:
            return self
        cfg = Config.from_params(self.params)
        if self._stream is not None and cfg.linear_tree:
            log_warning(
                "linear_tree needs the raw feature matrix, which "
                "streaming ingest never materializes — falling back to "
                "the in-memory loader")
            self._eagerize_stream_file()
        if self._stream is not None or (
                str(cfg.ingest_mode).lower() == "stream"
                and not self._from_file
                and self.raw_sparse is None
                and (self.raw_data is not None or self.raw_seq is not None
                     or self.raw_arrow is not None)):
            # streaming two-pass ingest (docs/INGEST.md): deferred file
            # sources always route here; in-memory containers route here
            # when ingest_mode=stream is explicit (sketch-based mappers,
            # chunked bin fill, optional memory-mapped cache)
            from .ingest import stream_construct
            stream_construct(self, cfg)
            self._streamed = True
            if self._should_free_raw():
                self.raw_data = None
                self.raw_seq = None
                self.raw_arrow = None
                self._raw_container = None
            return self
        if self.num_data_ == 0:
            raise LightGBMError("Cannot construct Dataset: it has no rows")
        if self._dist is not None:
            return self._construct_distributed(cfg)
        if self.raw_seq is not None:
            return self._construct_streaming(cfg)
        if self.raw_arrow is not None:
            return self._construct_arrow(cfg)
        sparse = self.raw_sparse is not None
        if self.reference is not None:
            ref = self.reference.construct()
            mappers = ref.binned.bin_mappers
            groups = ref.binned.group_features
            with _boundary("Dataset::Bin", rows=self.num_data_):
                if sparse:
                    from .binning import construct_binned_sparse
                    self.binned = construct_binned_sparse(self.raw_sparse,
                                                          mappers, groups)
                else:
                    self.binned = construct_binned(self.raw_data, mappers,
                                                   groups)
        else:
            cats = self._resolve_categorical()
            from .binning import load_forced_bins
            mapper_kw = dict(
                max_bin=cfg.max_bin, min_data_in_bin=cfg.min_data_in_bin,
                categorical_features=cats,
                use_missing=cfg.use_missing, zero_as_missing=cfg.zero_as_missing,
                sample_cnt=cfg.bin_construct_sample_cnt,
                seed=cfg.data_random_seed,
                max_bin_by_feature=cfg.max_bin_by_feature,
                forced_bins=load_forced_bins(cfg.forcedbins_filename,
                                             self.num_feature_, cats))
            # find-bins = bin mappers + EFB groups from the row sample;
            # bin = the full (N, G) fill
            if sparse:
                from .binning import (construct_binned_sparse,
                                      find_bin_mappers_sparse,
                                      sample_sparse_csc, sparse_nz_masks)
                with _boundary("Dataset::FindBins", rows=self.num_data_):
                    mappers = find_bin_mappers_sparse(self.raw_sparse,
                                                      **mapper_kw)
                    groups = None
                    if cfg.enable_bundle:
                        # SAME sample rows as the dense path (same
                        # seed/draw), so bundling — and therefore the model
                        # — is identical to Dataset(X.todense()); transient
                        # cost is the F boolean masks,
                        # ~F * min(N, sample_cnt) bytes
                        Xc, n_sample = sample_sparse_csc(
                            self.raw_sparse, cfg.bin_construct_sample_cnt,
                            cfg.data_random_seed)
                        masks = sparse_nz_masks(Xc, n_sample, mappers)
                        del Xc
                        groups = find_feature_groups(None, mappers,
                                                     enable_bundle=True,
                                                     nz_masks=masks)
                        del masks
                with _boundary("Dataset::Bin", rows=self.num_data_):
                    self.binned = construct_binned_sparse(self.raw_sparse,
                                                          mappers, groups)
            else:
                with _boundary("Dataset::FindBins", rows=self.num_data_):
                    mappers = find_bin_mappers(self.raw_data, **mapper_kw)
                    groups = None
                    if cfg.enable_bundle:
                        sample_n = min(self.num_data_,
                                       cfg.bin_construct_sample_cnt)
                        rng = np.random.RandomState(cfg.data_random_seed)
                        idx = (np.arange(self.num_data_)
                               if self.num_data_ <= sample_n else
                               np.sort(rng.choice(self.num_data_, sample_n,
                                                  replace=False)))
                        sample_bins = [
                            mappers[f].transform(self.raw_data[idx, f])
                            for f in range(self.num_feature_)]
                        groups = find_feature_groups(sample_bins, mappers,
                                                     enable_bundle=True)
                        # the sampled per-feature bin pool is dead the
                        # moment groups exist — free it BEFORE the full bin
                        # fill allocates the (N, G) matrix (peak-memory
                        # moment)
                        del sample_bins
                with _boundary("Dataset::Bin", rows=self.num_data_):
                    self.binned = construct_binned(self.raw_data, mappers,
                                                   groups)
        if self._should_free_raw():
            self.raw_data = None
            self.raw_sparse = None
            self._raw_container = None
        return self

    def _arrow_col_chunks(self, f: int):
        """(start_row, values) per PRODUCER chunk — zero-copy numpy views
        where the chunk's layout allows, one chunk-sized copy otherwise;
        the full column is never coalesced (reference: arrow.h
        ArrowChunkedArray)."""
        start = 0
        for ch in self.raw_arrow.column(f).chunks:
            try:
                vals = ch.to_numpy(zero_copy_only=True)
            except Exception:
                vals = np.asarray(ch.to_numpy(zero_copy_only=False),
                                  np.float64)
            yield start, vals
            start += len(ch)

    def _construct_arrow(self, cfg) -> "Dataset":
        """Columnar construction from a pyarrow Table: sampling, bin-mapper
        search, EFB grouping and binning all read one column at a time from
        the Arrow buffers (reference: arrow.h ArrowChunkedArray ingestion —
        the dense matrix is never built)."""
        from .binning import (BinMapper, construct_binned_columns,
                              load_forced_bins)
        n, F = self.num_data_, self.num_feature_
        cats = set(self._resolve_categorical())
        rng = np.random.RandomState(cfg.data_random_seed)
        sample_n = min(n, cfg.bin_construct_sample_cnt)
        idx = (np.arange(n) if n <= sample_n
               else np.sort(rng.choice(n, sample_n, replace=False)))
        forced = load_forced_bins(cfg.forcedbins_filename, F,
                                  sorted(cats)) or [None] * F
        mbf = cfg.max_bin_by_feature
        mappers = []
        samples = []
        for f in range(F):
            # sample gather per producer chunk: transient is O(chunk), the
            # full column is never materialized
            parts = []
            for start, vals in self._arrow_col_chunks(f):
                lo = np.searchsorted(idx, start)
                hi = np.searchsorted(idx, start + len(vals))
                parts.append(np.asarray(vals, np.float64)[idx[lo:hi] - start])
            sc = np.concatenate(parts) if parts else np.zeros(0, np.float64)
            samples.append(sc)
            mb = cfg.max_bin if mbf is None else int(mbf[f])
            if f in cats:
                mappers.append(BinMapper.find_categorical(
                    sc, mb, cfg.min_data_in_bin, cfg.use_missing))
            else:
                mappers.append(BinMapper.find_numerical(
                    sc, mb, cfg.min_data_in_bin, cfg.use_missing,
                    cfg.zero_as_missing, forced_bounds=forced[f]))
        groups = None
        if cfg.enable_bundle:
            sample_bins = [mappers[f].transform(samples[f]) for f in range(F)]
            groups = find_feature_groups(sample_bins, mappers,
                                         enable_bundle=True)
            del sample_bins
        del samples
        self.binned = construct_binned_columns(
            None, n, F, mappers, groups,
            get_col_chunks=lambda f: (
                (s, np.asarray(v, np.float64))
                for s, v in self._arrow_col_chunks(f)))
        if self._should_free_raw():
            self.raw_arrow = None
        return self

    def _construct_streaming(self, cfg) -> "Dataset":
        """Two-pass streaming construction from Sequence sources: sampled
        random access finds bin mappers + EFB groups, then rows stream
        through binning batch by batch into the uint8 matrix (reference:
        two-round sampling + push-rows, dataset_loader.cpp:258 /
        DatasetPushRows)."""
        from .binning import load_forced_bins
        seqs = self.raw_seq
        n = self.num_data_
        cats = self._resolve_categorical()
        rng = np.random.RandomState(cfg.data_random_seed)
        sample_n = min(n, cfg.bin_construct_sample_cnt)
        idx = (np.arange(n) if n <= sample_n
               else np.sort(rng.choice(n, sample_n, replace=False)))
        # map global indices to (sequence, local) and fetch via slices of
        # contiguous runs (reference Sequence contract: int + slice access)
        bounds = np.concatenate([[0], np.cumsum([len(q) for q in seqs])])
        sample = np.empty((len(idx), self.num_feature_), np.float64)
        pos = 0
        for qi, q in enumerate(seqs):
            loc = idx[(idx >= bounds[qi]) & (idx < bounds[qi + 1])] - bounds[qi]
            for i in loc:
                sample[pos] = np.asarray(q[int(i)], np.float64).reshape(-1)
                pos += 1
        mappers = find_bin_mappers(
            sample, max_bin=cfg.max_bin, min_data_in_bin=cfg.min_data_in_bin,
            categorical_features=cats, use_missing=cfg.use_missing,
            zero_as_missing=cfg.zero_as_missing, sample_cnt=len(sample) + 1,
            seed=cfg.data_random_seed,
            max_bin_by_feature=cfg.max_bin_by_feature,
            forced_bins=load_forced_bins(cfg.forcedbins_filename,
                                         self.num_feature_, cats))
        groups = None
        if cfg.enable_bundle:
            sample_bins = [mappers[f].transform(sample[:, f])
                           for f in range(self.num_feature_)]
            groups = find_feature_groups(sample_bins, mappers,
                                         enable_bundle=True)
            del sample_bins
        # the sample pool is dead once mappers + groups exist — free it
        # BEFORE allocating the full (N, G) bin matrix
        del sample
        # stream batches straight into ONE preallocated bin matrix: each
        # chunk's rows bin in place (binning.bin_rows_into), no per-chunk
        # BinnedData/array allocation
        from .binning import BinnedData, bin_rows_into, binned_layout
        (groups, group_bin_counts, group_offsets, feature_offsets,
         feature_num_bins, dtype) = binned_layout(mappers, groups)
        bins = np.empty((n, len(groups)), dtype)
        row = 0
        for q in seqs:
            bs = max(int(getattr(q, "batch_size", 4096) or 4096), 1)
            for s_ in range(0, len(q), bs):
                chunk = np.asarray(q[s_:min(s_ + bs, len(q))], np.float64)
                if chunk.ndim == 1:
                    chunk = chunk.reshape(1, -1)
                bin_rows_into(chunk, mappers, groups, bins, row)
                row += len(chunk)
        self.binned = BinnedData(
            bins=bins, group_features=groups,
            group_offsets=np.asarray(group_offsets, np.int32),
            group_bin_counts=np.asarray(group_bin_counts, np.int32),
            feature_offsets=np.asarray(feature_offsets, np.int32),
            feature_num_bins=np.asarray(feature_num_bins, np.int32),
            bin_mappers=mappers, num_data=n,
            num_features=self.num_feature_)
        if self._should_free_raw():
            self.raw_seq = None
        return self

    def _construct_distributed(self, cfg) -> "Dataset":
        """Bin this rank's shard with GLOBALLY-synchronized mappers: per-rank
        samples are allgathered and every process runs the deterministic
        mapper + EFB computation on the identical gathered sample
        (reference: ConstructBinMappersFromTextData + mapper Allgather,
        dataset_loader.cpp:733-741)."""
        from dataclasses import replace
        from .parallel.dist_data import gather_sample
        d = self._dist
        if self.reference is not None:
            # validation data aligns with the TRAINING dataset's mappers and
            # EFB layout (reference: LoadFromFileAlignWithOtherDataset,
            # dataset_loader.cpp:307)
            ref = self.reference.construct()
            local = construct_binned(self.raw_data, ref.binned.bin_mappers,
                                     ref.binned.group_features)
            n_shard = d["n_shard"]
            bins = np.pad(local.bins, ((0, n_shard - local.bins.shape[0]),
                                       (0, 0)))
            self.binned = replace(local, bins=bins, num_data=n_shard)
            if self.free_raw_data:
                self.raw_data = None
            return self
        per_rank = max(1, cfg.bin_construct_sample_cnt // d["nproc"])
        rng = np.random.RandomState(cfg.data_random_seed + d["rank"])
        if d["n_local"] > per_rank:
            idx = np.sort(rng.choice(d["n_local"], per_rank, replace=False))
            sample_local = self.raw_data[idx]
        else:
            sample_local = self.raw_data
        sample = gather_sample(sample_local)
        cats = self._resolve_categorical()
        from .binning import load_forced_bins
        mappers = find_bin_mappers(
            sample, max_bin=cfg.max_bin,
            min_data_in_bin=cfg.min_data_in_bin, categorical_features=cats,
            use_missing=cfg.use_missing, zero_as_missing=cfg.zero_as_missing,
            sample_cnt=len(sample) + 1, seed=cfg.data_random_seed,
            max_bin_by_feature=cfg.max_bin_by_feature,
            forced_bins=load_forced_bins(cfg.forcedbins_filename,
                                         self.num_feature_, cats))
        groups = None
        if cfg.enable_bundle:
            sample_bins = [mappers[f].transform(sample[:, f])
                           for f in range(self.num_feature_)]
            groups = find_feature_groups(sample_bins, mappers,
                                         enable_bundle=True)
            del sample_bins
        del sample
        local = construct_binned(self.raw_data, mappers, groups)
        n_shard = d["n_shard"]
        bins = np.pad(local.bins, ((0, n_shard - local.bins.shape[0]),
                                   (0, 0)))
        self.binned = replace(local, bins=bins, num_data=n_shard)
        if self._should_free_raw():
            self.raw_data = None
        return self

    def device_view(self) -> DeviceData:
        """The layouts and dimensions of the binned table, without the
        table (``bins`` is None)."""
        self.construct()
        return device_view(self.binned)

    def device_data(self, sharding=None, pad_rows_to: int = 256,
                    pad_groups_to: int = 1, view=None) -> DeviceData:
        """The binned table on the device, shipped once and cached.  With
        ``sharding`` (an engine about to train over an in-process mesh) the
        table goes straight from the host to the mesh, a shard to a device,
        padded as asked (``view``: the engine's ``device_view()``); that
        copy is the engine's and is not cached here, and a cached
        single-device copy is dropped for it (a full N x G matrix on device
        0 for the whole run: +56 MiB at 2.1M rows on four v5e chips,
        chip_smoke.py, PR 22)."""
        if sharding is not None:
            self.construct()
            self._device = None
            return to_device(self.binned, pad_rows_to=pad_rows_to,
                             sharding=sharding, pad_groups_to=pad_groups_to,
                             view=view)
        if self._device is None:
            self.construct()
            ship = None
            if self._streamed and self.ingest_stats:
                # streamed datasets ship chunk by chunk into a donated
                # device buffer where the backend supports it, so the
                # host never stages a padded full-size copy
                ship = self.ingest_stats.get("chunk_rows")
            self._device = to_device(self.binned, ship_chunk_rows=ship)
        return self._device

    def bin_mappers(self):
        self.construct()
        return self.binned.bin_mappers

    # ------------------------------------------------------------------
    def num_data(self) -> int:
        return self.num_data_

    def num_feature(self) -> int:
        return self.num_feature_

    def get_label(self) -> Optional[np.ndarray]:
        return self.label

    def get_weight(self) -> Optional[np.ndarray]:
        return self.weight

    def get_group(self) -> Optional[np.ndarray]:
        return self.group

    def get_init_score(self) -> Optional[np.ndarray]:
        return self.init_score

    def get_position(self) -> Optional[np.ndarray]:
        return self.position

    def set_label(self, label) -> "Dataset":
        self.label = None if label is None else np.asarray(label, np.float64).reshape(-1)
        return self

    def set_weight(self, weight) -> "Dataset":
        self.weight = (None if weight is None
                       else np.asarray(weight, np.float64).reshape(-1))
        return self

    def set_group(self, group) -> "Dataset":
        self.group = None if group is None else np.asarray(group, np.int64).reshape(-1)
        return self

    def set_init_score(self, init_score) -> "Dataset":
        self.init_score = (None if init_score is None
                           else np.asarray(init_score, np.float64))
        return self

    def set_position(self, position) -> "Dataset":
        self.position = (None if position is None
                         else np.asarray(position, np.int32).reshape(-1))
        return self

    def get_field(self, field_name: str):
        if field_name not in _LABEL_FIELDS:
            raise LightGBMError(f"Unknown field {field_name}")
        return getattr(self, field_name if field_name != "group" else "group")

    def set_field(self, field_name: str, data) -> "Dataset":
        if field_name == "label":
            return self.set_label(data)
        if field_name == "weight":
            return self.set_weight(data)
        if field_name == "group":
            return self.set_group(data)
        if field_name == "init_score":
            return self.set_init_score(data)
        if field_name == "position":
            return self.set_position(data)
        raise LightGBMError(f"Unknown field {field_name}")

    # -- helpers used by the boosting engine ---------------------------
    def get_query_boundaries(self) -> Optional[np.ndarray]:
        """1-D (nq+1,) cumulative boundaries for contiguous layouts, or
        (nq, 2) [start, size] spans for the shard-padded distributed layout
        (pad rows between shards belong to no query)."""
        if self.group is None:
            return None
        if self._dist is not None:
            return self._query_spans
        return np.concatenate([[0], np.cumsum(self.group)]).astype(np.int64)

    def get_label_padded(self, n: int) -> Optional[np.ndarray]:
        if self.label is None:
            return None
        out = np.zeros(n, np.float64)
        out[:len(self.label)] = self.label
        return out

    def get_init_score_padded(self, n: int, k: int) -> Optional[np.ndarray]:
        if self.init_score is None:
            return None
        s = self.init_score
        if k == 1:
            out = np.zeros(n, np.float32)
            out[:len(s)] = s.reshape(-1)
        else:
            s2 = s.reshape(self.num_data_, k) if s.ndim == 1 and s.size == self.num_data_ * k \
                else s.reshape(-1, k) if s.ndim == 2 else np.tile(s.reshape(-1, 1), (1, k))
            out = np.zeros((n, k), np.float32)
            out[:s2.shape[0]] = s2
        return out

    def create_valid(self, data, label=None, weight=None, group=None,
                     init_score=None, params=None, position=None) -> "Dataset":
        return Dataset(data, label=label, reference=self, weight=weight, group=group,
                       init_score=init_score, params=params or self.params,
                       position=position)

    def subset(self, used_indices: Sequence[int], params=None) -> "Dataset":
        if self._dist is not None:
            raise LightGBMError(
                "cannot subset a distributed-loaded dataset: features are "
                "rank-local while metadata is global")
        if self.raw_data is None and self.raw_sparse is None:
            raise LightGBMError("cannot subset after raw data was freed")
        idx = np.asarray(used_indices, np.int64)
        # group propagation: when the indices are query-aligned (as cv()'s
        # group-aware folds guarantee), recompute the subset's query sizes
        group_sub = None
        if self.group is not None and len(idx) and np.all(np.diff(idx) > 0):
            bounds = np.concatenate([[0], np.cumsum(self.group)]).astype(np.int64)
            q_of = np.searchsorted(bounds, idx, side="right") - 1
            sel_q, counts = np.unique(q_of, return_counts=True)
            if np.array_equal(counts, bounds[sel_q + 1] - bounds[sel_q]):
                group_sub = counts
        sub = Dataset(
            (self.raw_data if self.raw_data is not None
             else self.raw_sparse)[idx],
            label=None if self.label is None else self.label[idx],
            weight=None if self.weight is None else self.weight[idx],
            group=group_sub,
            init_score=None if self.init_score is None else
            (self.init_score[idx] if self.init_score.ndim == 1
             else self.init_score[idx, :]),
            reference=self if self.binned is not None else self.reference or self,
            feature_name=self._feature_name_arg,
            categorical_feature=self._categorical_feature_arg,
            params=params or self.params)
        return sub

    _BINARY_MAGIC = b"LGBTPU.BIN.v2\n"
    _BINARY_MAGIC_V1 = b"LGBTPU.BIN.v1\n"

    def save_binary(self, filename: str) -> "Dataset":
        """Serialize the binned dataset (reference: Dataset::SaveBinaryFile);
        load it back by passing the file path to Dataset().

        The format is non-executing — a JSON header plus an npz archive of
        plain arrays (loaded with allow_pickle=False), like the reference's
        binary format. NOT portable across releases or to stock LightGBM."""
        import json
        import struct
        self.construct()
        b = self.binned
        mappers = b.bin_mappers
        arrays = {
            "bins": b.bins,
            "group_offsets": np.asarray(b.group_offsets, np.int64),
            "group_bin_counts": np.asarray(b.group_bin_counts, np.int64),
            "feature_offsets": np.asarray(b.feature_offsets, np.int64),
            "feature_num_bins": np.asarray(b.feature_num_bins, np.int64),
            "mapper_ub": (np.concatenate(
                [np.asarray(m.upper_bounds, np.float64).reshape(-1)
                 for m in mappers]) if mappers else np.zeros(0)),
            "mapper_ub_len": np.asarray(
                [np.asarray(m.upper_bounds).size for m in mappers], np.int64),
            "mapper_cats": (np.concatenate(
                [np.asarray(m.categories, np.int64).reshape(-1)
                 for m in mappers]) if mappers else np.zeros(0, np.int64)),
            "mapper_cats_len": np.asarray(
                [np.asarray(m.categories).size for m in mappers], np.int64),
        }
        for field in ("label", "weight", "group", "position", "init_score"):
            v = getattr(self, field)
            if v is not None:
                arrays[field] = np.asarray(v)
        meta = {
            "num_data": int(self.num_data_),
            "num_feature": int(self.num_feature_),
            "feature_names": self.feature_name(),
            "group_features": [list(map(int, g)) for g in b.group_features],
            "mappers": [[int(m.bin_type), int(m.missing_type),
                         int(m.num_bins), int(m.default_bin),
                         int(m.most_freq_bin), float(m.min_val),
                         float(m.max_val)] for m in mappers],
        }
        meta_b = json.dumps(meta).encode()
        from .robustness.checkpoint import atomic_open
        with atomic_open(filename, "wb") as f:
            f.write(self._BINARY_MAGIC)
            f.write(struct.pack("<Q", len(meta_b)))
            f.write(meta_b)
            np.savez(f, **arrays)
        return self

    def _load_binary(self, path: str) -> None:
        """Restore a save_binary file (reference: DatasetLoader::
        LoadFromBinFile) — the raw matrix is NOT stored; prediction-time
        rebinning is unavailable, training works as usual."""
        import json
        import struct
        from .binning import BinMapper, BinnedData
        try:
            file_size = os.path.getsize(path)
            with open(path, "rb") as f:
                magic = f.read(len(self._BINARY_MAGIC))
                if magic == self._BINARY_MAGIC_V1:
                    raise LightGBMError(
                        "this binary dataset uses the deprecated v1 pickle "
                        "format, which is unsafe to load; re-save it with "
                        "Dataset.save_binary() from this release")
                header = f.read(8)
                if len(header) != 8:
                    raise LightGBMError(f"truncated binary dataset: {path}")
                (meta_len,) = struct.unpack("<Q", header)
                if meta_len > file_size:
                    raise LightGBMError(f"corrupt binary dataset: {path}")
                meta = json.loads(f.read(meta_len).decode())
                blob = np.load(f, allow_pickle=False)
                blob = {k: blob[k] for k in blob.files}
        except LightGBMError:
            raise
        except Exception as exc:  # struct/json/zipfile errors → one clear type
            raise LightGBMError(
                f"failed to load binary dataset {path}: {exc}") from exc
        mappers = []
        ub_off = cat_off = 0
        for i, ms in enumerate(meta["mappers"]):
            bt, mt, nb, db, mfb = ms[:5]
            mn, mx = (ms[5], ms[6]) if len(ms) > 6 else (0.0, 0.0)
            ub_n = int(blob["mapper_ub_len"][i])
            cat_n = int(blob["mapper_cats_len"][i])
            mappers.append(BinMapper(
                upper_bounds=blob["mapper_ub"][ub_off:ub_off + ub_n],
                bin_type=bt, missing_type=mt,
                categories=blob["mapper_cats"][cat_off:cat_off + cat_n],
                num_bins=nb, default_bin=db, most_freq_bin=mfb,
                min_val=mn, max_val=mx))
            ub_off += ub_n
            cat_off += cat_n
        self.binned = BinnedData(
            bins=blob["bins"],
            group_features=meta["group_features"],
            group_offsets=blob["group_offsets"],
            group_bin_counts=blob["group_bin_counts"],
            feature_offsets=blob["feature_offsets"],
            feature_num_bins=blob["feature_num_bins"],
            bin_mappers=mappers,
            num_data=meta["num_data"], num_features=meta["num_feature"])
        for field in ("label", "weight", "group", "position", "init_score"):
            setattr(self, field, blob.get(field))
        self.num_data_ = meta["num_data"]
        self.num_feature_ = meta["num_feature"]
        self._resolved_feature_names = meta["feature_names"]
        self.raw_data = None

    def add_features_from(self, other: "Dataset") -> "Dataset":
        if self.raw_data is None or other.raw_data is None:
            raise LightGBMError("add_features_from requires raw data")
        self.raw_data = np.hstack([self.raw_data, other.raw_data])
        self.num_feature_ = self.raw_data.shape[1]
        self.binned = None
        self._device = None
        self._resolved_feature_names = None
        return self


class Booster:
    """Booster (reference: basic.py:3495). Wraps the boosting engine."""

    def __init__(self, params: Optional[Dict[str, Any]] = None,
                 train_set: Optional[Dataset] = None,
                 model_file: Optional[str] = None,
                 model_str: Optional[str] = None):
        params = dict(params or {})
        self.best_iteration = -1
        self.best_score: Dict[str, Dict[str, float]] = {}
        self._engine = None
        self._loaded_trees = None

        if train_set is not None:
            if not isinstance(train_set, Dataset):
                raise TypeError("train_set must be a Dataset")
            self.params = resolve_aliases(params)
            cfg = Config.from_params(params)
            set_verbosity(cfg.verbosity)
            from . import telemetry as _tel
            if cfg.telemetry or cfg.telemetry_out or cfg.trace_out:
                # sinks imply the switch: a trace_out without telemetry=True
                # would export an empty span buffer. Param-driven telemetry
                # is per-model, so drop any previous model's spans/records
                # before this one starts collecting
                _tel.reset()
                _tel.configure(
                    enabled=True,
                    metrics_out=cfg.telemetry_out or None,
                    trace_out=cfg.trace_out or None,
                    recompile_threshold=cfg.telemetry_recompile_threshold,
                    cost_capture=cfg.telemetry_cost,
                    _source="params")
            elif _tel.enabled() and _tel.enabled_source() == "params":
                # a previous model's param-driven telemetry must not leak
                # into this one (its JSONL sink, its per-iteration sync);
                # an explicit telemetry.enable()/configure() by user code
                # ("api" source) stays on
                _tel.configure(enabled=False, metrics_out="", trace_out="")
            # merge dataset params (dataset params win for binning keys)
            train_set.params = {**params, **train_set.params}
            train_set.construct()
            # a Booster owns the binned data now — drop a file-loaded
            # dataset's raw matrix (largest host allocation; kept for
            # linear_tree and under explicit free_raw_data=False)
            train_set._free_raw_after_train(cfg)
            objective = create_objective(cfg)
            if objective is not None:
                n = train_set.num_data()
                if train_set.get_label() is None:
                    raise LightGBMError("training requires labels")
                objective.init(train_set.get_label(), train_set.get_weight(),
                               query_boundaries=train_set.get_query_boundaries(),
                               position=train_set.get_position(), n=n)
            metrics = create_metrics(cfg, objective.name if objective else "none")
            for m in metrics:
                m.init(train_set.get_label() if train_set.get_label() is not None
                       else np.zeros(train_set.num_data()),
                       train_set.get_weight(), train_set.get_query_boundaries())
            from .models.gbdt import create_boosting
            self._engine = create_boosting(cfg, train_set, objective, metrics)
            self.config = cfg
            self.train_set = train_set
        elif model_file is not None or model_str is not None:
            from .model_io import load_model_string
            if model_file is not None:
                model_str = Path(model_file).read_text()
            loaded = load_model_string(model_str)
            self._loaded_trees = loaded
            self.params = params
            self.config = Config.from_params(params)
        else:
            raise LightGBMError("need train_set or model_file/model_str")

    # ------------------------------------------------------------------
    @property
    def engine(self):
        if self._engine is None:
            raise LightGBMError("Booster was loaded from a model file; "
                                "training operations unavailable")
        return self._engine

    def update(self, train_set: Optional[Dataset] = None, fobj=None) -> bool:
        """One boosting iteration; returns True if training should stop
        (reference: Booster.update, basic.py:4005)."""
        if train_set is not None and train_set is not getattr(self, "train_set", None):
            raise LightGBMError("changing train_set after construction is not supported")
        if fobj is not None:
            score = self.engine._unpad_score()
            grad, hess = fobj(np.asarray(score), self.train_set)
            return self.engine.train_one_iter(np.asarray(grad, np.float32),
                                              np.asarray(hess, np.float32))
        return self.engine.train_one_iter()

    def rollback_one_iter(self) -> "Booster":
        self.engine.rollback_one_iter()
        return self

    def current_iteration(self) -> int:
        return self.engine.iter_ if self._engine else \
            len(self._loaded_trees.trees) // max(self._loaded_trees.num_tree_per_iteration, 1)

    def num_trees(self) -> int:
        if self._engine:
            return len(self.engine.models)
        return len(self._loaded_trees.trees)

    def num_model_per_iteration(self) -> int:
        if self._engine:
            return self.engine.num_tree_per_iteration
        return self._loaded_trees.num_tree_per_iteration

    def add_valid(self, data: Dataset, name: str) -> "Booster":
        if not isinstance(data, Dataset):
            raise TypeError("Validation data should be a Dataset instance, "
                            f"met {type(data).__name__}")
        if data is not self.train_set:
            if data.binned is None and data.reference is None:
                # bin with the training mappers, like passing reference=train
                data.reference = self.train_set
            data.construct()
            # reference behavior: GBDT::AddValidDataset fatals on mismatched
            # bin mappers (src/boosting/gbdt.cpp CheckAlign); equality (not
            # just identity) matters for datasets reloaded from binary files
            if not _mappers_compatible(data.binned.bin_mappers,
                                       self.train_set.binned.bin_mappers):
                raise LightGBMError(
                    "cannot add validation data, since it has different bin "
                    "mappers with training data (construct it with "
                    "reference=train_set)")
        metrics = create_metrics(
            self.config,
            self.engine.objective.name if self.engine.objective else "none")
        for m in metrics:
            m.init(data.get_label() if data.get_label() is not None
                   else np.zeros(data.num_data()),
                   data.get_weight(), data.get_query_boundaries())
        self.engine.add_valid(data, name, metrics)
        return self

    # ------------------------------------------------------------------
    def eval_train(self, feval=None) -> List:
        out = [(n, m, v, hb) for (n, m, v, hb) in self.engine.eval_train()]
        out.extend(self._run_feval(
            feval, "training", self.engine.train_data,
            self.engine._score_to_host(self.engine.score,
                                       self.engine.num_data)))
        return out

    def eval_valid(self, feval=None) -> List:
        out = [(n, m, v, hb) for (n, m, v, hb) in self.engine.eval_valid()]
        for vi, vset in enumerate(self.engine.valid_sets):
            n = vset.num_data()
            score = self.engine._score_to_host(
                self.engine._valid_scores[vi], n)
            out.extend(self._run_feval(feval, self.engine.valid_names[vi], vset, score))
        return out

    def eval(self, data: Dataset, name: str, feval=None) -> List:
        for vi, vset in enumerate(self.engine.valid_sets):
            if vset is data:
                n = vset.num_data()
                score = self.engine._score_to_host(
                    self.engine._valid_scores[vi], n)
                out = []
                conv = (self.engine.objective.convert_output
                        if self.engine.objective else (lambda x: x))
                for m in self.engine.valid_metrics[vi]:
                    for (mn, v, hb) in m.evaluate(score, conv):
                        out.append((name, mn, v, hb))
                out.extend(self._run_feval(feval, name, vset, score))
                return out
        raise LightGBMError("eval() requires the dataset to be added via add_valid")

    def _run_feval(self, feval, name, dset, raw_score) -> List:
        if feval is None:
            return []
        fevals = feval if isinstance(feval, list) else [feval]
        out = []
        for f in fevals:
            res = f(raw_score, dset)
            if isinstance(res, tuple):
                res = [res]
            for (mn, v, hb) in res:
                out.append((name, mn, float(v), bool(hb)))
        return out

    # ------------------------------------------------------------------
    def _all_trees(self):
        if self._engine is not None:
            return self.engine.models
        return self._loaded_trees.trees

    def predict(self, data, start_iteration: int = 0, num_iteration: Optional[int] = None,
                raw_score: bool = False, pred_leaf: bool = False,
                pred_contrib: bool = False, validate_features: bool = False,
                **kwargs) -> np.ndarray:
        """Predict (reference: Booster.predict, basic.py:4625)."""
        if isinstance(data, Dataset):
            raise LightGBMError("predict() takes raw data, not a Dataset")
        if _is_scipy_sparse(data):
            # chunked densify: prediction walks real-valued thresholds, so
            # rows are materialized a bounded slab at a time (~256 MB)
            Xr = data.tocsr()
            nrows = Xr.shape[0]
            chunk = max(1, (1 << 25) // max(1, Xr.shape[1]))
            starts = range(0, nrows, chunk) if nrows else [0]
            outs = [self.predict(
                np.asarray(Xr[s:s + chunk].todense(), np.float64),
                start_iteration, num_iteration, raw_score, pred_leaf,
                pred_contrib, validate_features, **kwargs)
                for s in starts]
            return np.concatenate(outs, axis=0)
        X, _, _, _ = _to_2d_float(data, self._pandas_categorical())
        expected = self.num_feature()
        if expected and X.shape[1] != expected:
            raise LightGBMError(
                f"The number of features in data ({X.shape[1]}) is not the same "
                f"as it was in training data ({expected})")
        use, k, start_iteration, end_iteration = self._resolve_tree_slice(
            start_iteration, num_iteration)

        if pred_leaf:
            out = np.zeros((X.shape[0], len(use)), np.int32)
            for i, t in enumerate(use):
                out[:, i] = t.predict_leaf_raw(X)
            return out
        if pred_contrib:
            from .shap import predict_contrib
            return predict_contrib(use, X, k)

        n = X.shape[0]
        early_stop = bool(kwargs.get("pred_early_stop", False))
        # freq < 1 would never fire (and 0 would crash the modulo); clamp
        es_freq = max(int(kwargs.get("pred_early_stop_freq", 10)), 1)
        es_margin = float(kwargs.get("pred_early_stop_margin", 10.0))
        # init scores are folded into tree 0 at training time (AddBias), so a plain
        # sum over trees is the complete raw score
        score = None
        if n == 1 and not early_stop:
            # serving path: pre-bound single-row C tree walk, cached per
            # (model, iteration slice) — no device dispatch, no per-tree
            # NumPy overhead (reference: c_api.h:1399 SingleRowFast)
            fp = self._single_row_fast_cached(use, start_iteration,
                                              end_iteration, k)
            raw = fp.raw_predict(X[0])
            score = raw[:1] if k == 1 else raw.reshape(1, k)
            self.last_predict_path = "host (single-row native walk)"
        if score is None:
            score = self._predict_batch(X, use, k, early_stop, es_freq,
                                        es_margin)
        if self._average_output() and len(use):
            score = score / max(len(use) // max(k, 1), 1)
        if raw_score:
            return score
        conv = self._convert_output_fn()
        return np.asarray(conv(score))

    def _predict_batch(self, X, use, k, early_stop, es_freq, es_margin):
        """Raw scores of a batch under the ``Predict`` boundary span: the
        device walk where it applies (its steps are the span's children),
        else the host walk; ``path`` and ``reason`` on the record say
        which way the batch went (``last_predict_path``, split)."""
        with _boundary("Predict", rows=X.shape[0], trees=len(use)) as sp:
            # pred_early_stop composes with the device batch walk (k == 1):
            # the kernel freezes cleared rows every es_freq trees, exactly
            # the host loop's bookkeeping (the reference's early stop is a
            # latency optimization; forcing the host loop would pessimize
            # wide batches)
            es = (es_freq, es_margin) if early_stop else None
            score = self._try_device_predict(X, use, k, es=es)
            if score is None:
                with _boundary("Predict::HostWalk"):
                    score = self._host_walk(X, use, k, early_stop, es_freq,
                                            es_margin)
            path, _, reason = self.last_predict_path.partition(" (")
            sp.set(path=path, reason=reason[:-1])
        return score

    @staticmethod
    def _host_walk(X, use, k, early_stop, es_freq, es_margin):
        """Tree-by-tree NumPy walk of the real-valued thresholds."""
        n = X.shape[0]
        if k == 1:
            score = np.zeros(n, np.float64)
            active = np.ones(n, bool)
            all_active = True
            for i, t in enumerate(use):
                if early_stop and not all_active:
                    score[active] += t.predict_raw(X[active])
                else:
                    score += t.predict_raw(X)
                if early_stop and (i + 1) % es_freq == 0:
                    # reference: prediction_early_stop.cpp CreateBinary —
                    # rows whose margin 2|score| clears the threshold stop
                    # accumulating further trees
                    active &= ~(2.0 * np.abs(score) > es_margin)
                    all_active = bool(active.all())
                    if not active.any():
                        break
        else:
            score = np.zeros((n, k), np.float64)
            active = np.ones(n, bool)
            all_active = True
            for i, t in enumerate(use):
                if early_stop and not all_active:
                    score[active, i % k] += t.predict_raw(X[active])
                else:
                    score[:, i % k] += t.predict_raw(X)
                if early_stop and (i + 1) % (es_freq * k) == 0:
                    # CreateMulticlass: top-1 minus top-2 margin
                    part = np.partition(score, -2, axis=1)
                    margin = part[:, -1] - part[:, -2]
                    active &= ~(margin > es_margin)
                    all_active = bool(active.all())
                    if not active.any():
                        break
        return score

    def _resolve_tree_slice(self, start_iteration: int,
                            num_iteration: Optional[int]):
        """Iteration-window resolution shared by every predict entry point
        (best_iteration fallback + end clamp); returns (trees, k, start,
        end)."""
        trees = self._all_trees()
        k = self.num_model_per_iteration()
        n_total = len(trees) // max(k, 1)
        if num_iteration is None or num_iteration <= 0:
            num_iteration = (self.best_iteration
                             if self.best_iteration
                             and self.best_iteration > 0 else n_total)
        end = min(start_iteration + num_iteration, n_total)
        return trees[start_iteration * k:end * k], k, start_iteration, end

    def predict_single_row_fast_init(self, start_iteration: int = 0,
                                     num_iteration: Optional[int] = None,
                                     raw_score: bool = False):
        """FastConfig-style pre-bound single-row predictor (reference:
        include/LightGBM/c_api.h:1399-1428
        LGBM_BoosterPredictForMatSingleRowFastInit / ...Fast).  Returns a
        callable: ``fast(row) -> float`` (or (num_class,) array), walking
        the pre-packed trees in native code with no device dispatch (the
        output transform is the objective's NumPy twin)."""
        from .predict_fast import SingleRowFastPredictor
        use, k, start, end = self._resolve_tree_slice(start_iteration,
                                                      num_iteration)
        avg = (1.0 / max(len(use) // max(k, 1), 1)
               if self._average_output() and len(use) else 1.0)
        conv = None if raw_score else self._convert_output_np_fn()
        # the resolved window (best_iteration fallback applied) forwards to
        # the predictor, which owns the slicing — one implementation
        return SingleRowFastPredictor(self._all_trees(), k,
                                      self.num_feature(), avg, conv,
                                      start_iteration=start,
                                      num_iteration=end - start)

    def _single_row_fast_cached(self, use, start_iteration, end_iteration, k):
        """Internal predict() fast path: averaging/conversion stay in the
        generic tail, so the packed predictor is raw with factor 1.  The
        cache holds STRONG references to every tree's leaf_value array and
        compares with ``is``: model mutation (DART drop-rescale calls
        tree.shrink, which REBINDS leaf_value) must invalidate the packed
        arrays, and identity keyed on id() alone could false-hit when a
        dropped array's address is recycled for a rebound one."""
        key = (start_iteration, end_iteration, k)
        vals = [t.leaf_value for t in use]
        cached = getattr(self, "_fast1_cache", None)
        if (cached is None or cached[0] != key
                or len(cached[1]) != len(vals)
                or any(a is not b for a, b in zip(cached[1], vals))):
            from .predict_fast import SingleRowFastPredictor
            cached = (key, vals,
                      SingleRowFastPredictor(use, k, self.num_feature()))
            self._fast1_cache = cached
        return cached[2]

    _DEVICE_PREDICT_MIN_ROWS = 20_000
    # off the chip the kernel would run interpreted, so the host walk is
    # the path there; tests set this to exercise the kernel on the CPU
    _DEVICE_PREDICT_OFF_CHIP = False
    # which path the last predict() batch took: "device", or
    # "host (<reason>)" — never silent (chip_smoke.py asserts on it)
    last_predict_path = ""

    def _predict_on_host(self, reason: str, expected: bool = False):
        """Record why a batch left the device path.  ``expected`` reasons
        (small batch, model loaded from a file, not on the chip) are the
        design; any other means a large batch on the chip is about to take
        the host walk, and that is said out loud."""
        self.last_predict_path = f"host ({reason})"
        if not expected:
            log_warning(f"predict: device path refused — {reason}; "
                        "walking the trees on the host")
        return None

    def _try_device_predict(self, X, use, k, es=None):
        """Batched on-device prediction (pallas/predict_kernel.py): bin the
        raw matrix with the training mappers and walk all trees on-chip —
        numeric, zero-as-missing, and categorical splits included (cat
        left-sets ride a per-tree bin-domain bitset side table).  Returns
        None, with the reason in ``last_predict_path``, when the fast path
        does not apply (small batch, no engine, linear trees, bundled
        categorical features, not on the chip) —
        reference analog: predictor.hpp picks per-row vs batch paths.
        es=(freq, margin) composes prediction early stopping with the
        device walk (k == 1 only; multiclass margins couple classes, so
        they stay host-side)."""
        import jax
        from .runtime import on_tpu
        host = self._predict_on_host
        if self._engine is None or not use:
            return host("no training engine: model loaded from a file",
                        expected=True)
        if X.shape[0] < self._DEVICE_PREDICT_MIN_ROWS:
            return host(f"{X.shape[0]} rows < "
                        f"{self._DEVICE_PREDICT_MIN_ROWS}", expected=True)
        if not (on_tpu() or self._DEVICE_PREDICT_OFF_CHIP):
            return host("not on a TPU", expected=True)
        if es is not None and k != 1:
            return host("pred_early_stop with num_class > 1")
        L = max(max(t.num_leaves for t in use), 2)
        if L > 2048:
            return host(f"{L} leaves > 2048")
        # the whole per-class table must stay VMEM-resident (~16 MB/core)
        from .pallas.predict_kernel import ROWS_PER_TREE
        per_class = -(-len(use) // max(k, 1))
        if per_class * ROWS_PER_TREE * L * 4 > 10 * 2 ** 20:
            return host(f"{per_class} trees x {L} leaves: node tables "
                        "exceed the 10 MiB VMEM budget")
        cat_feats = set()
        for t in use:
            if t.is_linear:
                return host("linear-leaf trees")
            ni = max(t.num_leaves - 1, 0)
            if ni:
                dt = np.asarray(t.decision_type[:ni]).astype(np.int64)
                for f in np.asarray(t.split_feature[:ni])[(dt & 1) > 0]:
                    cat_feats.add(int(f))
        from .binning import construct_binned
        from .pallas.predict_kernel import CAT_DIGITS as \
            predict_kernel_CAT_DIGITS
        from .pallas.predict_kernel import (build_predict_tables,
                                            predict_stream, tree_max_depth)
        from .pallas.stream_kernel import pack_bins_T
        import jax.numpy as jnp
        eng = self.engine
        tb = eng.train_data.binned
        r = eng.dd.routing
        with _boundary("Predict::RoutingTables"):
            routing_np = {name: np.asarray(getattr(r, name))
                          for name in ("feat_group", "span_start",
                                       "default_bin", "bundled", "nan_bin",
                                       "num_bins", "mzero_bin")}
        for f in sorted(cat_feats):
            # the NaN/unseen sentinel re-bin below needs the cat feature
            # alone in its group, and the sentinel bin num_bins must fit
            # the uint8 storage — bundled or near-full ladders stay host
            if routing_np["bundled"][f] or tb.bin_mappers[f].num_bins >= 255:
                return host(f"categorical feature {f} is EFB-bundled or "
                            "fills the uint8 bin ladder")
        with _boundary("Predict::Rebin"):
            binned = construct_binned(np.asarray(X, np.float64),
                                      tb.bin_mappers, tb.group_features)
            bins = np.asarray(binned.bins)
            if bins.dtype != np.uint8:
                # the kernel's words hold four 8-bit bins
                return host("a feature group of more than 256 bins")
            if cat_feats:
                # the host walk routes NaN / unseen / negative categories
                # RIGHT (bit absent from the bitset); the mapper bins them
                # to bin 0 (the most frequent category) — re-bin those rows
                # to the sentinel bin one past the span, whose bitset bit
                # is always zero by construction (build_predict_tables)
                Xf = np.asarray(X, np.float64)
                for f in sorted(cat_feats):
                    m = tb.bin_mappers[f]
                    v = Xf[:, f]
                    ivc = np.where(np.isnan(v), -1.0, v)
                    ivc = np.clip(ivc, -1.0, float(2 ** 62)).astype(np.int64)
                    ok = (ivc >= 0) & np.isin(ivc,
                                              m.categories.astype(np.int64))
                    bins[~ok, int(routing_np["feat_group"][f])] = m.num_bins
        with _boundary("Predict::PackShip"):
            # the words are packed on the host (bins is NumPy): packed on
            # the device op by op, the int32 copy of the batch and its four
            # byte planes stay alive together for as long as the device
            # lags the host - up to 2.8 GB beside the 0.2 GB bins of a
            # 100,000 x 2,000 batch, another amount every call
            bins_T = jnp.asarray(pack_bins_T(bins).bins_T)
        maxd = max(max(tree_max_depth(t) for t in use), 1)
        n = X.shape[0]
        es_freq, es_margin = (int(es[0]), float(es[1])) if es else (0, 0.0)
        outs = []
        for c in range(k):
            trees_c = [t for i, t in enumerate(use) if i % k == c]
            with _boundary("Predict::NodeTables", trees=len(trees_c)):
                tabs, cat_tab = build_predict_tables(
                    trees_c, routing_np, L, bin_mappers=tb.bin_mappers)
            if cat_tab.shape[1] > 2048:
                return host("categorical bitset side table wider than "
                            "2048 words")
            if not cat_feats:
                # numeric-only: a minimal dummy keeps the unread cat
                # input out of VMEM (the kernel never touches it)
                cat_tab = cat_tab[:predict_kernel_CAT_DIGITS]
            with _boundary("Predict::Walk"):
                s = predict_stream(bins_T, jnp.asarray(tabs),
                                   jnp.asarray(cat_tab), L, len(trees_c),
                                   maxd, has_cat=bool(cat_feats),
                                   es_freq=es_freq, es_margin=es_margin)
            outs.append(s)
        with _boundary("Predict::Readback"):
            got = jax.device_get(outs)
        self.last_predict_path = "device"
        if k == 1:
            return np.asarray(got[0][:n], np.float64)
        return np.stack([h[:n] for h in got], axis=1).astype(np.float64)

    def _average_output(self) -> bool:
        if self._engine is not None:
            return self.engine._average_output
        if self._loaded_trees is not None:
            return self._loaded_trees.average_output
        return False

    def _convert_output_fn(self):
        if self._engine is not None and self.engine.objective is not None:
            return self.engine.objective.convert_output
        if self._loaded_trees is not None:
            return self._loaded_trees.convert_output
        return lambda x: x

    def _pandas_categorical(self):
        """Training DataFrame category lists for predict-time code
        alignment (reference: pandas_categorical in the model file)."""
        if self._engine is not None:
            return getattr(self.engine.train_data, "pandas_categorical", None)
        if self._loaded_trees is not None:
            return self._loaded_trees.pandas_categorical
        return None

    def _convert_output_np_fn(self):
        """NumPy output transform for host serving paths — a per-call jax
        dispatch would dominate single-row latency."""
        if self._engine is not None and self.engine.objective is not None:
            return self.engine.objective.convert_output_np
        if self._loaded_trees is not None:
            return self._loaded_trees.convert_output_np
        return lambda x: x

    # ------------------------------------------------------------------
    def save_model(self, filename: str, num_iteration: Optional[int] = None,
                   start_iteration: int = 0, importance_type: str = "split") -> "Booster":
        # tmp + os.replace: the serving registry hot-reloads model files by
        # path, so a torn write must never be observable (lgbtlint LGB005)
        from .robustness.checkpoint import atomic_write_text
        text = self.model_to_string(num_iteration, start_iteration,
                                    importance_type)
        atomic_write_text(str(filename), text)
        self._write_quality_sidecar(str(filename), text)
        return self

    def _write_quality_sidecar(self, filename: str, text: str) -> None:
        """Best-effort ``<model>.quality.json`` reference profile next to
        a trained model (docs/OBSERVABILITY.md "Data & model quality").
        Loaded boosters have no binned matrix, so only a training-side
        save emits one; a sidecar failure never fails the model save."""
        if self._engine is None or self.train_set is None \
                or getattr(self.train_set, "binned", None) is None:
            return
        cfg = getattr(self, "config", None)
        if cfg is not None and not getattr(cfg, "quality_profile", True):
            return
        try:
            from .telemetry.quality import QualityProfile
            QualityProfile.from_booster(self, text).save(filename)
        except Exception as exc:
            from .utils.log import log_warning
            log_warning(f"quality: sidecar write failed for {filename}: "
                        f"{exc}")

    def model_to_string(self, num_iteration: Optional[int] = None,
                        start_iteration: int = 0,
                        importance_type: str = "split") -> str:
        from .model_io import save_model_string
        return save_model_string(self, num_iteration, start_iteration, importance_type)

    def checkpoint(self, output_model: str, iteration: Optional[int] = None,
                   keep: int = -1) -> str:
        """Write a crash-consistent checkpoint resumable via
        ``lgb.train(..., resume_from=...)``: model text + engine state
        (score vector, RNG streams) + a sealed JSON manifest, all via
        tmp-file + ``os.replace``, pruned to the ``keep`` newest
        (docs/ROBUSTNESS.md).  Returns the snapshot path.  Multi-process:
        every rank must call this at the same iteration (the state capture
        is collective); only rank 0 writes."""
        from .robustness.checkpoint import write_checkpoint
        it = int(iteration) if iteration is not None else self.current_iteration()
        # a configured fleet dir pins promoted snapshots against pruning
        fleet_dir = str(getattr(getattr(self, "config", None),
                                "serve_fleet_dir", "") or "")
        return write_checkpoint(self, str(output_model), it, keep=keep,
                                fleet_dir=fleet_dir)

    def dump_model(self, num_iteration: Optional[int] = None, start_iteration: int = 0,
                   importance_type: str = "split") -> Dict:
        from .model_io import dump_model_dict
        return dump_model_dict(self, num_iteration, start_iteration, importance_type)

    def model_from_string(self, model_str: str) -> "Booster":
        """Replace this booster's model with one parsed from `model_str`
        (reference: basic.py:4445 — in-place load, returns self)."""
        from .model_io import load_model_string
        self._loaded_trees = load_model_string(model_str)
        self._engine = None
        self._fast1_cache = None
        self.best_iteration = -1
        return self

    def set_train_data_name(self, name: str) -> "Booster":
        """Name used for the training set in eval outputs (reference:
        basic.py set_train_data_name)."""
        self._train_data_name = name
        return self

    def set_network(self, machines, local_listen_port: int = 12400,
                    listen_time_out: int = 120,
                    num_machines: int = 1) -> "Booster":
        """Connect this process to a multi-machine job (reference:
        Booster.set_network / LGBM_NetworkInit — here the socket linker is
        jax.distributed; see also lgb.init_distributed and the CLI's
        machines= wiring)."""
        from .cli import _maybe_init_network
        if isinstance(machines, (list, tuple, set)):
            machines = ",".join(str(m) for m in machines)
        _maybe_init_network({"num_machines": num_machines,
                             "machines": str(machines),
                             "local_listen_port": local_listen_port})
        return self

    def trees_to_dataframe(self):
        """Parsed model as a pandas DataFrame, one row per node, with the
        reference's exact column set (reference: basic.py:3775)."""
        try:
            import pandas as pd
        except ImportError as exc:
            raise LightGBMError(
                "trees_to_dataframe requires pandas") from exc
        if self.num_trees() == 0:
            raise LightGBMError(
                "There are no trees in this Booster and thus nothing to parse")
        model = self.dump_model()
        feat_names = model["feature_names"]
        rows: List[Dict[str, Any]] = []

        def node_index(node, ti):
            if "split_index" in node:
                return f"{ti}-S{node['split_index']}"
            return f"{ti}-L{node.get('leaf_index', 0)}"

        def walk(node, ti, depth, parent):
            idx = node_index(node, ti)
            if "split_index" in node:
                f = node["split_feature"]
                rows.append({
                    "tree_index": ti, "node_depth": depth, "node_index": idx,
                    "left_child": node_index(node["left_child"], ti),
                    "right_child": node_index(node["right_child"], ti),
                    "parent_index": parent,
                    "split_feature": (feat_names[f]
                                      if f < len(feat_names) else str(f)),
                    "split_gain": node["split_gain"],
                    "threshold": node["threshold"],
                    "decision_type": node["decision_type"],
                    "missing_direction": ("left" if node.get("default_left")
                                          else "right"),
                    "missing_type": node.get("missing_type"),
                    "value": node["internal_value"],
                    "weight": node["internal_weight"],
                    "count": node["internal_count"]})
                walk(node["left_child"], ti, depth + 1, idx)
                walk(node["right_child"], ti, depth + 1, idx)
            else:
                rows.append({
                    "tree_index": ti, "node_depth": depth, "node_index": idx,
                    "left_child": None, "right_child": None,
                    "parent_index": parent, "split_feature": None,
                    "split_gain": np.nan, "threshold": np.nan,
                    "decision_type": None, "missing_direction": None,
                    "missing_type": None,
                    "value": node["leaf_value"],
                    "weight": node.get("leaf_weight"),
                    "count": node.get("leaf_count")})

        for ti, tree in enumerate(model["tree_info"]):
            walk(tree["tree_structure"], ti, 1, None)
        return pd.DataFrame(rows)

    def get_leaf_output(self, tree_id: int, leaf_id: int) -> float:
        """Value of one leaf (reference: basic.py:4883)."""
        return float(self._all_trees()[tree_id].leaf_value[leaf_id])

    def set_leaf_output(self, tree_id: int, leaf_id: int,
                        value: float) -> "Booster":
        """Overwrite one leaf's value (reference: Tree::SetLeafOutput via
        LGBM_BoosterSetLeafValue).  Invalidates cached predictors; under a
        live engine the device score vectors keep their history — like the
        reference, continued training after manual leaf edits reflects the
        edit only in new predictions."""
        t = self._all_trees()[tree_id]
        lv = np.asarray(t.leaf_value, np.float64).copy()
        lv[leaf_id] = value
        t.leaf_value = lv           # rebind: predictor caches key on identity
        self._fast1_cache = None
        return self

    def lower_bound(self) -> float:
        """Lower bound of raw scores: per-tree minimum leaf values summed
        (reference: GBDT::GetLowerBoundValue)."""
        return float(sum(float(np.min(t.leaf_value))
                         for t in self._all_trees()) or 0.0)

    def upper_bound(self) -> float:
        """Upper bound of raw scores (reference: GBDT::GetUpperBoundValue)."""
        return float(sum(float(np.max(t.leaf_value))
                         for t in self._all_trees()) or 0.0)

    def shuffle_models(self, start_iteration: int = 0,
                       end_iteration: int = -1) -> "Booster":
        """Randomly permute tree order in [start, end) iterations
        (reference: GBDT::ShuffleModels; used before refit).  Uses a LOCAL
        RNG seeded from data_random_seed so refit pipelines are
        reproducible and the global numpy RNG state stays untouched."""
        trees = self._all_trees()
        k = self.num_model_per_iteration()
        n_iter = len(trees) // max(k, 1)
        end = n_iter if end_iteration <= 0 else min(end_iteration, n_iter)
        seed = int((getattr(self, "params", None) or {})
                   .get("data_random_seed", 1) or 1)
        rng = np.random.RandomState((seed * 65539 + start_iteration * 9973
                                     + max(end, 0)) % (2 ** 31 - 1))
        idx = np.arange(start_iteration, end)
        rng.shuffle(idx)
        order = list(range(n_iter))
        order[start_iteration:end] = [int(i) for i in idx]
        new_trees = []
        for it in order:
            new_trees.extend(trees[it * k:(it + 1) * k])
        trees[:] = new_trees
        self._fast1_cache = None
        return self

    def feature_importance(self, importance_type: str = "split",
                           iteration: Optional[int] = None) -> np.ndarray:
        trees = self._all_trees()
        if iteration is not None and iteration > 0:
            trees = trees[:iteration * self.num_model_per_iteration()]
        nf = self.num_feature()
        imp = np.zeros(nf, np.float64)
        for t in trees:
            for i in range(t.num_leaves - 1):
                f = int(t.split_feature[i])
                if importance_type == "split":
                    imp[f] += 1.0
                else:
                    imp[f] += float(t.split_gain[i])
        if importance_type == "split":
            return imp.astype(np.int32)
        return imp

    def num_feature(self) -> int:
        if self._engine is not None:
            return self.train_set.num_feature()
        return self._loaded_trees.max_feature_idx + 1

    def feature_name(self) -> List[str]:
        if self._engine is not None:
            return self.train_set.feature_name()
        return self._loaded_trees.feature_names

    def free_dataset(self) -> "Booster":
        return self

    def free_network(self) -> "Booster":
        return self

    def reset_parameter(self, params: Dict[str, Any]) -> "Booster":
        resolved = resolve_aliases(params)
        self.engine.config.update(resolved)
        self.params.update(resolved)
        # learning-rate etc. take effect next iteration; tree-shape params
        # require new grow params
        self.engine._grow_params = self.engine._make_grow_params()
        import functools
        from .ops.grow import grow_tree as _gt
        from .telemetry import watched_jit
        # same (name, owner) as the engine's original jit: the rebuild
        # counts as a retrace of the same entry point, so the recompile
        # watchdog sees a mid-training parameter reset for what it is
        self.engine._grow_fn = watched_jit(functools.partial(
            _gt, layout=self.engine.dd.layout, routing=self.engine.dd.routing,
            params=self.engine._grow_params),
            name="grow_tree", owner=self.engine)
        return self

    def telemetry_summary(self) -> Dict[str, Any]:
        """Aggregated telemetry for this process: counters/gauges/time
        histograms, span phase totals, recompile-watchdog rollup, memory,
        and (when trained with telemetry on) per-iteration statistics.
        See docs/OBSERVABILITY.md."""
        stored = getattr(self, "telemetry_summary_", None)
        if stored:
            # a rollup shipped from another process (train_distributed rank
            # 0) answers for this booster; the local registry is empty
            return stored
        from . import telemetry as _tel
        out = _tel.summary()
        recs = [r for r in _tel.global_registry.records
                if r.get("event") == "iteration"]
        if self._engine is not None and recs:
            walls = np.asarray([r["wall_s"] for r in recs], np.float64)
            out["train"] = {
                "iterations_recorded": len(recs),
                "total_s": round(float(walls.sum()), 6),
                "mean_iter_s": round(float(walls.mean()), 6),
                "p50_iter_s": round(float(np.percentile(walls, 50)), 6),
                "p95_iter_s": round(float(np.percentile(walls, 95)), 6),
                "last_iter_s": round(float(walls[-1]), 6),
            }
            stragglers = [r for r in _tel.global_registry.records
                          if r.get("event") == "straggler_report"]
            if stragglers:
                out["straggler"] = stragglers[-1]
        return out

    def refit(self, data, label, decay_rate: float = 0.9, **kwargs) -> "Booster":
        from .model_io import refit_model
        return refit_model(self, data, label, decay_rate, **kwargs)

    def __copy__(self):
        return self

    def __deepcopy__(self, memo):
        model_str = self.model_to_string()
        return Booster(model_str=model_str)
