"""Binned dataset + metadata for lightgbm_tpu.

TPU-native analogue of the reference's ``Dataset``/``Metadata``
(reference: include/LightGBM/dataset.h:426,46; src/io/dataset.cpp,
src/io/metadata.cpp). Where the reference keeps per-feature ``Bin`` columns
(dense/sparse, 4/8/16-bit, src/io/dense_bin.hpp) optimized for CPU cache and
histogram prefetch, the TPU build keeps ONE dense row-major uint8/uint16 bin
matrix padded for HBM tiling — the analogue of the CUDA backend's row-wise
``CUDARowData`` (reference: include/LightGBM/cuda/cuda_row_data.hpp:31-89) —
because XLA histogramming wants a single contiguous [rows, features] tensor.

Construction pipeline (reference: DatasetLoader::ConstructFromSampleData,
src/io/dataset_loader.cpp:593):
  sample rows -> BinMapper.find_bin per feature -> value_to_bin over the full
  column -> drop trivial features -> pack.
"""
from __future__ import annotations

from typing import Any, Dict, List, Optional, Sequence, Union

import numpy as np

from ..config import Config
from ..obs import events as obs_events
from ..obs.registry import registry as obs
from ..utils import log
from .binning import BinMapper, BinType, MissingType


class Metadata:
    """Labels / weights / query boundaries / init score
    (reference: include/LightGBM/dataset.h:46, src/io/metadata.cpp:26)."""

    def __init__(self, num_data: int):
        self.num_data = num_data
        self.label = np.zeros(num_data, dtype=np.float32)
        self.weights: Optional[np.ndarray] = None
        self.query_boundaries: Optional[np.ndarray] = None
        self.init_score: Optional[np.ndarray] = None

    def set_label(self, label: Sequence[float]) -> None:
        label = np.asarray(label, dtype=np.float32).reshape(-1)
        if len(label) != self.num_data:
            log.fatal("Length of label (%d) != num_data (%d)"
                      % (len(label), self.num_data))
        self.label = label

    def set_weights(self, weights: Optional[Sequence[float]]) -> None:
        if weights is None:
            self.weights = None
            return
        weights = np.asarray(weights, dtype=np.float32).reshape(-1)
        if len(weights) != self.num_data:
            log.fatal("Length of weights (%d) != num_data (%d)"
                      % (len(weights), self.num_data))
        self.weights = weights

    def set_group(self, group: Optional[Sequence[int]]) -> None:
        """Group sizes -> query boundaries
        (reference: Metadata::SetQuery, src/io/metadata.cpp:456)."""
        if group is None:
            self.query_boundaries = None
            return
        group = np.asarray(group, dtype=np.int64).reshape(-1)
        if group.sum() != self.num_data:
            log.fatal("Sum of group sizes (%d) != num_data (%d)"
                      % (int(group.sum()), self.num_data))
        self.query_boundaries = np.concatenate(
            [[0], np.cumsum(group)]).astype(np.int32)

    def set_init_score(self, init_score: Optional[Sequence[float]]) -> None:
        if init_score is None:
            self.init_score = None
            return
        init_score = np.asarray(init_score, dtype=np.float64).reshape(-1)
        if len(init_score) % max(self.num_data, 1) != 0:
            # len == num_data or num_class * num_data
            # (reference: Metadata::SetInitScore, src/io/metadata.cpp)
            log.fatal("Length of init_score (%d) must be a multiple of "
                      "num_data (%d)" % (len(init_score), self.num_data))
        self.init_score = init_score

    @property
    def num_queries(self) -> int:
        return 0 if self.query_boundaries is None else len(self.query_boundaries) - 1


def _count_bundles(layout, conflict_rows: int) -> None:
    """``efb/groups``: the bundled matrix's columns; ``efb/features_bundled``:
    the features that share a column with another; ``efb/conflict_rows``:
    the (row, bundle) pairs in which a member was dropped for another
    (io/efb.py ``bundle_columns``). Counted once, when a training set is
    bundled."""
    obs.inc("efb/groups", layout.num_groups)
    obs.inc("efb/features_bundled",
            sum(len(g) for g in layout.groups if len(g) > 1))
    obs.inc("efb/conflict_rows", conflict_rows)


class BinnedDataset:
    """Quantized training data (reference: include/LightGBM/dataset.h:426).

    Attributes
    ----------
    bins : np.ndarray [num_data, num_used_features] uint8/uint16
        Row-major bin matrix; the HBM-resident training payload.
    bin_mappers : list[BinMapper]  (one per *used* feature)
    used_feature_map : original column index per used feature
    num_bin_per_feature / max_num_bin : histogram sizing
    """

    def __init__(self) -> None:
        self.bins: np.ndarray = np.zeros((0, 0), dtype=np.uint8)
        self.bin_mappers: List[BinMapper] = []
        self.used_feature_map: List[int] = []
        self.num_total_features: int = 0
        self.feature_names: List[str] = []
        self.metadata: Metadata = Metadata(0)
        self.max_num_bin: int = 0
        self.num_bin_per_feature: np.ndarray = np.zeros(0, dtype=np.int32)
        self.monotone_constraints: Optional[np.ndarray] = None
        self.feature_penalty: Optional[np.ndarray] = None
        self.raw_data: Optional[np.ndarray] = None  # kept for linear trees
        # EFB: when set, ``bins`` is the bundled [N, G] matrix (io/efb.py)
        self.bundle = None
        self._raw_dev = None

    # ------------------------------------------------------------------
    @classmethod
    def from_matrix(cls, data: np.ndarray, config: Config,
                    label: Optional[Sequence[float]] = None,
                    weights: Optional[Sequence[float]] = None,
                    group: Optional[Sequence[int]] = None,
                    init_score: Optional[Sequence[float]] = None,
                    feature_names: Optional[List[str]] = None,
                    categorical_feature: Optional[Sequence[Union[int, str]]] = None,
                    reference: Optional["BinnedDataset"] = None,
                    keep_raw_data: bool = False) -> "BinnedDataset":
        """Build from a dense float matrix (reference:
        DatasetLoader::ConstructFromSampleData, src/io/dataset_loader.cpp:593,
        for the sample pass; Dataset::PushRow + FinishLoad for the full pass)."""
        is_sparse = hasattr(data, "tocsc")
        if is_sparse:
            # scipy input stays sparse end-to-end: every per-column pass
            # is O(nnz), never materializing a dense value column
            # (reference analogue: SparseBin, src/io/sparse_bin.hpp —
            # delta-encoded pushes; here CSC slices feed the binner and
            # EFB bundles the exclusive columns)
            data = data.tocsc()
            if not data.has_canonical_format:
                # duplicate (row, col) entries must SUM (dense semantics);
                # copy first — tocsc() may alias the caller's matrix
                data = data.copy()
                data.sum_duplicates()
            if keep_raw_data:
                log.fatal("keep_raw_data/linear_tree requires dense input")
        else:
            data = np.asarray(data)
            if data.dtype not in (np.float32, np.float64):
                data = data.astype(np.float64)
            if data.ndim != 2:
                log.fatal("Training data must be 2-dimensional")
        n, num_total_features = data.shape

        def col_nonzero(f: int):
            """Sparse column f as (row_indices, values) — O(nnz)."""
            sl = slice(int(data.indptr[f]), int(data.indptr[f + 1]))
            return data.indices[sl], np.asarray(data.data[sl],
                                                dtype=np.float64)

        def full_col(f: int) -> np.ndarray:
            return data[:, f]   # dense paths only; sparse uses col_nonzero

        self = cls()
        self.num_total_features = num_total_features
        self.feature_names = list(feature_names) if feature_names else [
            f"Column_{i}" for i in range(num_total_features)]

        if categorical_feature is None and config.categorical_feature:
            categorical_feature = config.categorical_feature
        cat_set = _resolve_categorical(categorical_feature, self.feature_names)

        if reference is not None:
            # validation set aligned with the training set's bin mappers
            # (reference: DatasetLoader::LoadFromFileAlignWithOtherDataset,
            # src/io/dataset_loader.cpp:299)
            self.bin_mappers = reference.bin_mappers
            self.used_feature_map = reference.used_feature_map
            self.num_bin_per_feature = reference.num_bin_per_feature
            self.max_num_bin = reference.max_num_bin
            self.monotone_constraints = reference.monotone_constraints
            self.feature_penalty = reference.feature_penalty
            self.bundle = reference.bundle
        else:
            # --- sampling pass (bin_construct_sample_cnt, config.h:641) ---
            sample_cnt = min(config.bin_construct_sample_cnt, n)
            rng = np.random.RandomState(config.data_random_seed)
            if sample_cnt < n:
                sample_idx = np.sort(rng.choice(n, sample_cnt, replace=False))
            else:
                sample_idx = None
            max_bin_by_feature = validate_max_bin_by_feature(
                config, num_total_features)
            forced_bounds = load_forced_bounds(config)
            mappers: List[BinMapper] = []
            sample_bin_cols: List[np.ndarray] = []
            sample_cnt_eff = sample_cnt if sample_idx is not None else n
            with obs.scope("io::find_bins"):
                for f in range(num_total_features):
                    if is_sparse:
                        # feed the binner only the sampled NON-ZERO values;
                        # total_sample_cnt accounts the zeros (the reference
                        # samples exactly this way —
                        # DatasetLoader::SampleTextData keeps non-zeros +
                        # the global sample count, dataset_loader.cpp:593)
                        rows, vals = col_nonzero(f)
                        if sample_idx is not None:
                            pos = np.searchsorted(sample_idx, rows)
                            pos_ok = pos < len(sample_idx)
                            pos_ok[pos_ok] &= (sample_idx[pos[pos_ok]]
                                               == rows[pos_ok])
                            sample_col = vals[pos_ok]
                            sample_rows = pos[pos_ok]
                        else:
                            sample_col = vals
                            sample_rows = rows
                    else:
                        col = full_col(f)
                        sample_col = (col if sample_idx is None
                                      else col[sample_idx])
                    bm = find_bin_for_feature(
                        f, sample_col, sample_cnt_eff, config, cat_set,
                        forced_bounds, max_bin_by_feature)
                    mappers.append(bm)
                    if not bm.is_trivial:
                        if is_sparse:
                            sb = np.full(sample_cnt_eff, bm.default_bin,
                                         dtype=np.int32)
                            sb[sample_rows] = bm.value_to_bin(sample_col)
                            sample_bin_cols.append(sb)
                        else:
                            sample_bin_cols.append(
                                bm.value_to_bin(sample_col).astype(np.int32))
            self.bin_mappers = [m for m in mappers if not m.is_trivial]
            self.used_feature_map = [i for i, m in enumerate(mappers)
                                     if not m.is_trivial]
            if not self.bin_mappers:
                log.warning("There are no meaningful features which satisfy "
                            "the provided configuration. Decreasing "
                            "Dataset parameters min_data_in_bin or min_data_in_leaf "
                            "and re-constructing Dataset might resolve this warning.")
            self.num_bin_per_feature = np.asarray(
                [m.num_bin for m in self.bin_mappers], dtype=np.int32)
            self.max_num_bin = int(self.num_bin_per_feature.max()) if len(
                self.num_bin_per_feature) else 1
            self._set_constraints(config)
            if config.enable_bundle and len(self.bin_mappers) > 1:
                with obs.scope("io::efb_bundle"):
                    self._find_bundles(sample_bin_cols, config)

        # --- full binning pass (O(nnz) per column on sparse input) ---
        def binned_col(j: int) -> np.ndarray:
            f, bm = self.used_feature_map[j], self.bin_mappers[j]
            if is_sparse:
                rows, vals = col_nonzero(f)
                out = np.full(n, bm.default_bin, dtype=np.int32)
                out[rows] = bm.value_to_bin(vals)
                return out
            return bm.value_to_bin(full_col(f))

        with obs.scope("io::apply_bins"):
            if self.bundle is not None:
                from .efb import bundle_columns
                dtype = (np.uint8 if self.bundle.num_bundled_bins <= 256
                         else np.uint16)
                zero_bins = np.asarray(
                    [m.default_bin for m in self.bin_mappers],
                    dtype=np.int32)
                self.bins, conflicts = bundle_columns(
                    binned_col, self.bundle, zero_bins, n, dtype)
                if reference is None:
                    _count_bundles(self.bundle, conflicts)
            else:
                dtype = np.uint8 if self.max_num_bin <= 256 else np.uint16
                bins = np.empty((n, len(self.bin_mappers)), dtype=dtype)
                for j in range(len(self.bin_mappers)):
                    bins[:, j] = binned_col(j).astype(dtype)
                self.bins = bins
        if keep_raw_data:
            self.raw_data = data

        self.metadata = Metadata(n)
        if label is not None:
            self.metadata.set_label(label)
        self.metadata.set_weights(weights)
        self.metadata.set_group(group)
        self.metadata.set_init_score(init_score)
        obs_events.emit(
            "dataset", num_data=n, num_features=self.num_features,
            num_total_features=num_total_features,
            max_num_bin=self.max_num_bin,
            bundled=self.bundle is not None,
            aligned_to_reference=reference is not None)
        return self

    # ------------------------------------------------------------------
    def _set_constraints(self, config: Config) -> None:
        if config.monotone_constraints:
            mc = np.zeros(len(self.bin_mappers), dtype=np.int8)
            for j, f in enumerate(self.used_feature_map):
                if f < len(config.monotone_constraints):
                    mc[j] = config.monotone_constraints[f]
            self.monotone_constraints = mc
        if config.feature_contri:
            fp = np.ones(len(self.bin_mappers), dtype=np.float64)
            for j, f in enumerate(self.used_feature_map):
                if f < len(config.feature_contri):
                    fp[j] = config.feature_contri[f]
            self.feature_penalty = fp

    # ------------------------------------------------------------------
    def _find_bundles(self, sample_bin_cols: List[np.ndarray],
                      config: Config) -> None:
        """Greedy EFB over the sampled binned columns (reference:
        Dataset::FindGroups, src/io/dataset.cpp:107). Only numerical,
        non-NaN-missing, mostly-zero features are candidates."""
        from .efb import build_layout, find_groups
        F = len(self.bin_mappers)
        if not sample_bin_cols or F < 2:
            return
        sample_cnt = len(sample_bin_cols[0])
        zero_bins = np.asarray([m.default_bin for m in self.bin_mappers],
                               dtype=np.int32)
        masks: List[Optional[np.ndarray]] = []
        for j, m in enumerate(self.bin_mappers):
            if (m.bin_type == BinType.CATEGORICAL
                    or m.missing_type == MissingType.NAN
                    or m.num_bin < 2):
                masks.append(None)
                continue
            nz = sample_bin_cols[j] != zero_bins[j]
            # bundling only pays off on sparse columns (reference:
            # kSparseThreshold, include/LightGBM/bin.h:39)
            masks.append(nz if nz.mean() <= 0.3 else None)
        if all(mk is None for mk in masks):
            return
        max_bundle_bins = max(self.max_num_bin, min(config.max_bin + 1, 256))
        groups = find_groups(masks, self.num_bin_per_feature, sample_cnt,
                             max_bundle_bins)
        if all(len(g) == 1 for g in groups):
            return
        self.bundle = build_layout(groups, self.num_bin_per_feature)
        log.info("EFB: bundled %d features into %d columns"
                 % (F, self.bundle.num_groups))

    def feature_bin_column(self, j: int) -> np.ndarray:
        """Per-feature bin column, unbundling if needed (host)."""
        if self.bundle is None:
            return self.bins[:, j]
        from .efb import member_bin
        lay = self.bundle
        col = self.bins[:, int(lay.group_of[j])].astype(np.int32)
        return member_bin(col, lay.first_bin[j], lay.num_bins[j],
                          self.bin_mappers[j].default_bin,
                          lay.needs_zero_fix[j]).astype(self.bins.dtype)

    def raw_device(self):
        """The raw values on the device, float32 as the reference keeps
        them (linear leaves read them beside the bins), uploaded once;
        None where the dataset keeps none."""
        if getattr(self, "_raw_dev", None) is None \
                and self.raw_data is not None:
            import jax.numpy as jnp
            with obs.scope("io::stage_raw_device"):
                self._raw_dev = jnp.asarray(
                    np.asarray(self.raw_data, dtype=np.float32))
        return self._raw_dev

    def feature_bins(self) -> np.ndarray:
        """[N, F] per-feature bin matrix; materializes when bundled
        (memory-heavy on wide sparse data — only host traversal paths
        need it)."""
        if self.bundle is None:
            return self.bins
        out = np.empty((self.bins.shape[0], len(self.bin_mappers)),
                       dtype=self.bins.dtype)
        for j in range(len(self.bin_mappers)):
            out[:, j] = self.feature_bin_column(j)
        return out

    # ------------------------------------------------------------------
    @property
    def num_data(self) -> int:
        return self.bins.shape[0]

    @property
    def num_features(self) -> int:
        return len(self.bin_mappers)

    def real_threshold(self, feature: int, bin_idx: int) -> float:
        """Bin index -> real-valued split threshold for model storage
        (reference: Tree::Split records RealThreshold via BinToValue)."""
        return self.bin_mappers[feature].bin_to_value(bin_idx)

    def real_feature_index(self, inner_feature: int) -> int:
        return self.used_feature_map[inner_feature]

    def inner_feature_index(self, real_feature: int) -> int:
        try:
            return self.used_feature_map.index(real_feature)
        except ValueError:
            return -1

    def feature_infos(self) -> List[str]:
        infos = ["none"] * self.num_total_features
        for f, bm in zip(self.used_feature_map, self.bin_mappers):
            infos[f] = bm.feature_info()
        return infos


def validate_max_bin_by_feature(config, num_total_features: int) -> list:
    """``max_bin_by_feature`` checks (reference:
    src/io/dataset_loader.cpp:614-616 CHECK_EQ/CHECK_GT); returns the
    (possibly empty) per-feature list. Shared by ``from_matrix`` and
    the sharded builder (io/shards.py)."""
    max_bin_by_feature = config.max_bin_by_feature
    if max_bin_by_feature:
        if len(max_bin_by_feature) != num_total_features:
            log.fatal("Length of max_bin_by_feature (%d) != number of "
                      "features (%d)" % (len(max_bin_by_feature),
                                         num_total_features))
        if min(max_bin_by_feature) <= 1:
            log.fatal("Each entry of max_bin_by_feature must be > 1")
    return max_bin_by_feature or []


def find_bin_for_feature(f: int, sample_col: np.ndarray,
                         total_sample_cnt: int, config: Config,
                         cat_set: set, forced_bounds: dict,
                         max_bin_by_feature: list) -> BinMapper:
    """THE per-feature ``find_bin`` knob set — one definition shared by
    ``from_matrix`` and the sharded out-of-core builder (io/shards.py),
    so the two construction paths cannot drift apart: identical mappers
    over an identical sample are the sharded path's bit-parity
    contract."""
    bm = BinMapper()
    max_bin_f = (max_bin_by_feature[f] if f < len(max_bin_by_feature)
                 else config.max_bin)
    bm.find_bin(
        sample_col, total_sample_cnt=total_sample_cnt,
        max_bin=max_bin_f,
        min_data_in_bin=config.min_data_in_bin,
        min_split_data=config.min_data_in_leaf,
        pre_filter=config.feature_pre_filter,
        bin_type=(BinType.CATEGORICAL if f in cat_set
                  else BinType.NUMERICAL),
        use_missing=config.use_missing,
        zero_as_missing=config.zero_as_missing,
        forced_upper_bounds=forced_bounds.get(f))
    return bm


def load_forced_bounds(config) -> dict:
    """forcedbins_filename (config.h:740): JSON list of
    {"feature": i, "bin_upper_bound": [...]} entries
    (reference: DatasetLoader reads it into forced_bins then
    BinMapper::FindBin applies FindBinWithPredefinedBin). Shared by the
    in-memory construction above and the out-of-core sharded builder
    (io/shards.py)."""
    forced_bounds: dict = {}
    if getattr(config, "forcedbins_filename", ""):
        import json
        try:
            with open(config.forcedbins_filename) as fh:
                for entry in json.load(fh):
                    forced_bounds[int(entry["feature"])] = [
                        float(v) for v in entry["bin_upper_bound"]]
        except (OSError, ValueError, KeyError, TypeError) as e:
            log.warning("Cannot load forced bins from %s: %s"
                        % (config.forcedbins_filename, e))
    return forced_bounds


def _resolve_categorical(categorical_feature, feature_names) -> set:
    cats: set = set()
    if categorical_feature is None or categorical_feature == "auto":
        return cats
    if isinstance(categorical_feature, str):
        categorical_feature = [c for c in categorical_feature.split(",") if c]
    for c in categorical_feature:
        if isinstance(c, str) and not c.lstrip("-").isdigit():
            if c in feature_names:
                cats.add(feature_names.index(c))
            else:
                log.warning("Unknown categorical feature name: %s", c)
        else:
            cats.add(int(c))
    return cats
