"""Tree-learner factory.

Equivalent of the reference's ``TreeLearner::CreateTreeLearner``
(reference: src/treelearner/tree_learner.cpp:15-55 — keyed on
``tree_learner`` ∈ serial/feature/data/voting × ``device_type``). On TPU
the device dimension collapses: every learner runs on the accelerator;
the parallel variants differ only in how they shard over the mesh.
"""
from __future__ import annotations

from ..utils import log
from .serial import SerialTreeLearner


def create_tree_learner(config, dataset, mesh=None):
    name = getattr(config, "tree_learner", "serial")
    from ..io.shards import ShardedBinnedDataset
    if isinstance(dataset, ShardedBinnedDataset):
        # out-of-core datasets have exactly one engine: the shard-sweep
        # learner (treelearner/sharded.py). Its trees are pinned
        # bit-identical to serial, so the promotion is silent for the
        # default and a Warning for an explicit mesh-learner ask.
        if name not in ("serial",):
            log.warning("tree_learner=%s requested but the dataset is "
                        "sharded (out-of-core); using the sharded "
                        "shard-sweep learner" % name)
        from .sharded import ShardedTreeLearner
        return ShardedTreeLearner(config, dataset)
    if name in ("serial",):
        # On an accelerator the DEFAULT is the 1-device-mesh data
        # learner: the learner the benchmark's cells measure. Both run
        # grow.py's whole-tree loop and split step and grow the same
        # trees (tests/test_fused_growth.py TestSerialVsMeshParity);
        # the serial learner pads rows and features to shared shapes,
        # which the CPU tests want and the chip has not measured. An
        # explicitly requested serial learner is honored, as are forced
        # splits (serial only). One learner class is ROADMAP D1.
        explicit = any(k in getattr(config, "raw_params", {})
                       for k in ("tree_learner", "tree", "tree_type",
                                 "tree_learner_type"))
        import jax
        if (not explicit and jax.default_backend() != "cpu"
                and not config.forcedsplits_filename):
            from ..parallel import DataParallelTreeLearner, make_mesh
            log.info("tree_learner=serial on an accelerator: using the "
                     "1-device-mesh whole-tree learner (identical "
                     "trees)")
            return DataParallelTreeLearner(config, dataset, make_mesh(1))
        return SerialTreeLearner(config, dataset)
    import jax
    from ..parallel import (DataParallelTreeLearner,
                            FeatureParallelTreeLearner,
                            VotingParallelTreeLearner, make_mesh)
    if mesh is None:
        if len(jax.devices()) < 2:
            # still honor the request on a 1-device mesh
            log.info("tree_learner=%s on a single device: using a "
                     "1-device mesh" % name)
        # mesh_shape (e.g. "data=8") bounds the device count; the
        # 1-D GBDT learners use the first axis extent
        n_dev = None
        shape = str(getattr(config, "mesh_shape", "") or "")
        if shape:
            try:
                n_dev = int(shape.split(",")[0].split("=")[1])
            except (IndexError, ValueError):
                log.warning("cannot parse mesh_shape=%r; using all "
                            "devices" % shape)
        mesh = make_mesh(n_dev)
    if name in ("data", "data_parallel"):
        return DataParallelTreeLearner(config, dataset, mesh)
    if name in ("feature", "feature_parallel"):
        return FeatureParallelTreeLearner(config, dataset, mesh)
    if name in ("voting", "voting_parallel"):
        return VotingParallelTreeLearner(config, dataset, mesh)
    log.fatal("Unknown tree learner type %s" % name)


__all__ = ["SerialTreeLearner", "create_tree_learner"]
