"""Multi-process (multi-host) distributed training.

TPU-native replacement for the reference's network bootstrap + distributed
loading (reference: machine-list/TCP full-mesh connect
src/network/linkers_socket.cpp:166; rank-sharded BinMapper construction
with Allgather, src/io/dataset_loader.cpp:1070-1240; per-rank
pre-partitioned loading, dataset_loader.cpp:203-260):

- bootstrap: ``jax.distributed.initialize`` (gRPC coordinator ≙ the
  reference's machine list; ICI/DCN collectives ≙ its TCP/MPI links)
- distributed binning: every process samples its LOCAL rows, the samples
  are allgathered host-side, and every process runs the same BinMapper
  construction on the identical gathered sample — same outcome as the
  reference's "shard features, bin, allgather mappers" with one hop less
  serialization
- training: the mesh learners (data_parallel.py) run unchanged over a
  global mesh; each process feeds its row shard via
  ``jax.make_array_from_process_local_data``. Every process executes the
  same host loop (SPMD discipline); split records are replicated, so all
  processes build identical trees — the reference reaches the same state
  via SyncUpGlobalBestSplit.
"""
from __future__ import annotations

from typing import Optional, Sequence

import numpy as np

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..config import Config
from ..io.dataset import BinnedDataset
from ..obs.registry import registry as obs
from ..treelearner.grow import stage_bins_by_feature
from ..utils import log
from .data_parallel import DataParallelTreeLearner


def initialize(coordinator_address: Optional[str] = None,
               num_processes: Optional[int] = None,
               process_id: Optional[int] = None) -> None:
    """Bootstrap the process group (reference: Network::Init,
    src/network/network.cpp:30 — machine list + listen port become the
    coordinator address + process id)."""
    jax.distributed.initialize(coordinator_address=coordinator_address,
                               num_processes=num_processes,
                               process_id=process_id)


def global_mesh(axis: str = "data") -> Mesh:
    """1-D mesh over every device of every process."""
    return Mesh(np.array(jax.devices()), (axis,))


def distributed_binned_dataset(local_X: np.ndarray, config: Config,
                               label: Optional[Sequence[float]] = None,
                               **kw) -> BinnedDataset:
    """Distributed binning (reference:
    DatasetLoader::ConstructBinMappersFromTextData,
    src/io/dataset_loader.cpp:1070): sample locally, allgather the
    samples, build identical mappers everywhere, bin only local rows."""
    from jax.experimental import multihost_utils

    local_X = np.asarray(local_X, dtype=np.float64)
    n_local = local_X.shape[0]
    n_proc = jax.process_count()
    per_proc = max(1, config.bin_construct_sample_cnt // max(n_proc, 1))
    rng = np.random.RandomState(config.data_random_seed
                                + jax.process_index())
    take = min(per_proc, n_local)
    idx = np.sort(rng.choice(n_local, take, replace=False)) \
        if take < n_local else np.arange(n_local)
    sample = local_X[idx]
    # pad to a common per-process shape for the allgather; padding rows
    # are trimmed back out via the gathered count vector (a zeros row
    # covers the empty-shard case)
    # process_allgather adds NO leading process axis when n_proc == 1;
    # reshape(n_proc, ...) normalizes both layouts
    counts = np.asarray(multihost_utils.process_allgather(
        np.asarray([take], dtype=np.int64))).reshape(n_proc, -1)[:, 0]
    max_take = int(counts.max())
    if take < max_take:
        pad_row = sample[:1] if take > 0 else np.zeros(
            (1, local_X.shape[1]), dtype=local_X.dtype)
        pad = np.repeat(pad_row, max_take - take, axis=0)
        sample = np.concatenate([sample, pad], axis=0)
    # allgather as int32 bit patterns: process_allgather canonicalizes
    # float64 -> float32 (and int64 -> int32) when x64 is off, which
    # would round the bin boundaries; two int32 words per double
    # round-trip exactly
    bits = np.ascontiguousarray(sample).view(np.int32)
    gathered_bits = np.asarray(multihost_utils.process_allgather(bits))
    gathered = np.ascontiguousarray(gathered_bits).view(np.float64) \
        .reshape(n_proc, max_take, local_X.shape[1])
    parts = [gathered[p][:int(counts[p])] for p in range(n_proc)]
    full_sample = np.concatenate(parts, axis=0)

    # every process now builds mappers from the identical global sample,
    # then bins only its local rows
    cfg2 = Config.from_params(dict(config.raw_params,
                                   bin_construct_sample_cnt=len(
                                       full_sample)))
    template = BinnedDataset.from_matrix(full_sample, cfg2)
    ds = BinnedDataset.from_matrix(local_X, config, label=label,
                                   reference=template, **kw)
    ds.num_total_features = template.num_total_features
    return ds


class DistributedDataParallelLearner(DataParallelTreeLearner):
    """Data-parallel learner over a multi-process global mesh: each
    process contributes its local row shard; the device mesh spans all
    processes and XLA's collectives ride ICI/DCN (reference analogue:
    DataParallelTreeLearner over MPI ranks)."""

    def supports_train_many(self) -> bool:
        """The batched scan hardcodes the single-process tail-pad gh
        layout (_make_gh_traced) and the [:N] partition slice; this
        learner's per-process interleaved pad blocks need their own
        staging, so the batched path stays off multi-process meshes."""
        return False

    def __init__(self, config, local_dataset: BinnedDataset, mesh: Mesh,
                 axis: str = "data"):
        from jax.experimental import multihost_utils

        bins_local = self._init_mesh_common(config, local_dataset, mesh,
                                            axis)
        if self._quantized:
            # the per-iteration scale is a GLOBAL max and the stochastic
            # draw is per-global-row; the host-side per-process staging
            # has neither without an extra allgather round — quantized
            # mode stays off the multi-process learner for now
            from ..ops.histogram import _warn_once
            _warn_once("use_quantized_grad is not supported by the "
                       "multi-process distributed learner; training "
                       "falls back to exact f32 histograms",
                       component="parallel.distributed")
            self._quantized = False
            self._hist_impl = self._hist_impl[:2] + (0,)
            self._qscale = self._qs_ones
        n_local, C = bins_local.shape
        if self.F == 0:
            log.fatal("Cannot train without features")
        n_proc = jax.process_count()
        dev_per_proc = len(mesh.devices.flatten()) // max(n_proc, 1)
        counts = np.asarray(multihost_utils.process_allgather(
            np.asarray([n_local], dtype=np.int64))).reshape(-1)
        self.N = int(counts.sum())
        # per-process padded block, equal across processes so the global
        # row axis splits evenly over devices
        block = -(-int(counts.max()) // max(dev_per_proc, 1)) \
            * max(dev_per_proc, 1)
        self.R = block * n_proc
        self._block = block
        self._n_local = n_local

        local_bins = np.zeros((block, C), dtype=bins_local.dtype)
        local_bins[:n_local] = bins_local
        with obs.scope("io::stage_bins_device"):
            self.bins = jax.make_array_from_process_local_data(
                NamedSharding(mesh, P(self.axis, None)), local_bins)
        self.bins_fm = stage_bins_by_feature(self.bins)
        self._init_cegb(config)
        self._init_monotone(config)

    def _make_gh(self, grad, hess, bag) -> jnp.ndarray:
        """Local [n_local] numpy grad/hess shard → global padded sharded
        [R, 4] gh matrix (overrides the single-process device path)."""
        n = self._n_local
        ind = np.ones(n, dtype=np.float32) if bag is None \
            else np.asarray(bag, dtype=np.float32)
        gh_local = np.zeros((self._block, 4), dtype=np.float32)
        gh_local[:n, 0] = np.asarray(grad, np.float32) * ind
        gh_local[:n, 1] = np.asarray(hess, np.float32) * ind
        gh_local[:n, 2] = ind
        gh_local[:n, 3] = 1.0
        return jax.make_array_from_process_local_data(
            self.gh_sharding, gh_local)

    # kept as the public name used by callers/tests
    make_global_gh = _make_gh

    def _initial_partition(self, gh):
        # each process's local pad rows are interleaved per-process, not
        # a single tail: rows with total-count channel 0 are padding
        leaf_of_row = jnp.where(gh[:, 3] > 0.0, 0, -1).astype(jnp.int32)
        return jax.lax.with_sharding_constraint(
            leaf_of_row, self.row_sharding)

    def _finalize_partition(self, leaf_of_row):
        # keep the global sharded vector; local_leaf_assignment slices it
        return leaf_of_row

    def local_leaf_assignment(self, leaf_of_row) -> np.ndarray:
        """This process's [n_local] slice of the global partition."""
        shards = [s for s in leaf_of_row.addressable_shards]
        shards.sort(key=lambda s: s.index[0].start)
        local = np.concatenate([np.asarray(s.data) for s in shards])
        return local[:self._n_local]
