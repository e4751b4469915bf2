"""Feature-parallel tree learner: features sharded over the mesh axis.

TPU-native equivalent of the reference's ``FeatureParallelTreeLearner``
(reference: src/treelearner/feature_parallel_tree_learner.cpp: every rank
holds all rows but owns a feature subset; after finding its local best
split, ranks agree via ``SyncUpGlobalBestSplit`` — an Allreduce with a
max-gain reducer, parallel_tree_learner.h:190). Here the bin matrix is
sharded [rows, FEATURES→mesh] so each device histograms and scans only its
feature block; the winning (gain, feature) argmax is a replicated scalar
reduction XLA lowers to the same max-Allreduce; the partition update reads
one feature column (a one-column all-gather, the analogue of every rank
splitting locally since all ranks hold all data).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..io.dataset import BinnedDataset
from ..obs.registry import registry as obs
from ..ops.split import FeatureMeta
from ..treelearner.grow import stage_bins_by_feature
from .data_parallel import DataParallelTreeLearner


class FeatureParallelTreeLearner(DataParallelTreeLearner):
    """Same host loop and step dataflow as the data-parallel learner, but
    sharded over features instead of rows. Rows are replicated (the
    reference's "all ranks hold all data"), so the partition update is
    fully local and the histogram needs no cross-device reduction at all —
    only the best-split argmax crosses devices.

    EFB bundles are unpacked here: features are the sharded axis, and
    bundle columns would couple features across shards (the histogram
    never crosses devices in this learner, so bundling buys no comm)."""

    _supports_bundles = False

    def __init__(self, config, dataset: BinnedDataset, mesh: Mesh,
                 axis: str = "data"):
        # pad the FEATURE axis to a devices multiple before sharding
        super().__init__(config, dataset, mesh, axis)
        n_dev = mesh.devices.size
        bins_full = (dataset.feature_bins() if dataset.bundle is not None
                     else dataset.bins)
        N, F = bins_full.shape
        Fp = -(-F // n_dev) * n_dev
        pad = np.zeros((N, Fp - F), dtype=bins_full.dtype)
        bins_host = np.concatenate([bins_full, pad], axis=1)
        # rows replicated, features sharded
        self.R = N
        self.F_pad = Fp
        with obs.scope("io::stage_bins_device"):
            self.bins = jax.device_put(
                bins_host, NamedSharding(mesh, P(None, self.axis)))
        self.bins_fm = stage_bins_by_feature(self.bins)
        self.row_sharding = NamedSharding(mesh, P())  # rows replicated
        # feature metadata padded to Fp: padded features are trivial
        # (num_bin 1 → never valid thresholds)
        meta = FeatureMeta.from_dataset(dataset,
                                        int(config.max_cat_to_onehot))
        padF = Fp - F

        def padv(a, fill):
            return jnp.concatenate(
                [a, jnp.full((padF,), fill, dtype=a.dtype)])

        self.meta = FeatureMeta(
            num_bin=padv(meta.num_bin, 1),
            missing_type=padv(meta.missing_type, 0),
            zero_bin=padv(meta.zero_bin, 0),
            is_categorical=padv(meta.is_categorical, False),
            use_onehot=padv(meta.use_onehot, False),
            monotone=padv(meta.monotone, 0),
        )
        self.meta = jax.device_put(self.meta, self.rep_sharding)
        self.F = Fp
        self.Fp = Fp
        # keep histograms feature-sharded; only the argmax crosses devices
        self.hist_sharding = NamedSharding(mesh, P(self.axis, None, None))
        self.gh_sharding = NamedSharding(mesh, P(None, None))  # replicated
        # the base __init__ sized the CEGB/monotone vectors before the
        # feature-axis repadding above — rebuild them at [Fp]
        self._init_cegb(config)
        self._init_monotone(config)

    def _make_cegb_fetched(self, rows: int) -> jnp.ndarray:
        # rows are replicated in this learner
        # jaxlint: disable=JLT003 -- one-shot replicated-zeros
        # allocation at CEGB setup (out_shardings is the point), never
        # dispatched again
        return jax.jit(lambda: jnp.zeros((rows, self.Fp),
                                         dtype=jnp.float32),
                       out_shardings=self.rep_sharding)()

    # the column mask is the mixin's draw over the dataset's real
    # features at this learner's padded width (``Fp`` = ``F_pad``),
    # replicated by the mesh learner's ``_place_feature_mask``
