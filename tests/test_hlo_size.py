"""The compiled learner program must not scale with the dataset.

Round-2 regression (VERDICT round 2, Weak #1): the jitted learner closed
over the binned matrix, so JAX embedded the whole dataset into the HLO as
a literal — ~300 MB of program at Higgs scale, blowing the remote-compile
size limit. The binned matrix must be a traced argument; this test lowers
the learner's jitted functions at N = 1M rows via ShapeDtypeStructs (no
data materialized) and asserts the serialized HLO stays small.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from lightgbm_tpu.config import Config
from lightgbm_tpu.io.dataset import BinnedDataset
from lightgbm_tpu.treelearner.grow import bins_by_feature
from lightgbm_tpu.treelearner.serial import SerialTreeLearner

N_BIG = 1_000_000
MAX_HLO_BYTES = 10 * 1024 * 1024


@pytest.fixture(scope="module")
def learner():
    rng = np.random.RandomState(0)
    # tiny real dataset to build mappers; shapes are then overridden with
    # ShapeDtypeStructs at N_BIG for lowering
    X = rng.randn(512, 16)
    cfg = Config.from_params({"num_leaves": 31, "max_bin": 63,
                              "verbosity": -1})
    ds = BinnedDataset.from_matrix(X, cfg)
    lrn = SerialTreeLearner(cfg, ds)
    # pretend the dataset is 1M rows: rebuild shape-dependent attributes
    lrn.N = N_BIG
    return lrn


def _sds(shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype)


def _root_args(lrn):
    R = -(-(lrn.N + 1) // 4096) * 4096
    return (
        _sds((R, lrn.Fp), lrn.bins.dtype),
        _sds((R, 4), jnp.float32),
        _sds((R,), jnp.int32),
        _sds((lrn.Fp,), jnp.bool_),
        _sds((), jnp.bool_),
        _sds((), jnp.int32),
        _sds((2,), jnp.float32),
        lrn.meta,
        lrn.params,
        lrn._btab,
    )


def _fm_sds(bins_sds):
    return jax.eval_shape(bins_by_feature, bins_sds)


def _hlo_bytes(lowered) -> int:
    return len(lowered.compiler_ir("hlo").as_serialized_hlo_module_proto())


def test_root_hlo_small(learner):
    n = _hlo_bytes(learner._root_fn.lower(*_root_args(learner)))
    assert n < MAX_HLO_BYTES, f"root HLO is {n} bytes"


def test_fused_tree_hlo_small(learner):
    args = _root_args(learner)
    state_sds, _ = jax.eval_shape(learner._root_fn, *args)
    lowered = learner._fused_fn().lower(
        args[0], _fm_sds(args[0]), state_sds, _sds((), jnp.int32),
        _sds((), jnp.int32),
        args[3], _sds((), jnp.int32), _sds((2,), jnp.float32),
        learner.meta, learner.params, learner._btab)
    n = _hlo_bytes(lowered)
    assert n < MAX_HLO_BYTES, f"whole-tree HLO is {n} bytes"


def test_stepwise_hlo_small(learner):
    args = _root_args(learner)
    state_sds, _ = jax.eval_shape(learner._root_fn, *args)
    fn = learner._step_fn()
    lowered = fn.lower(args[0], _fm_sds(args[0]), state_sds,
                       _sds((), jnp.int32),
                       _sds((), jnp.int32), _sds((), jnp.bool_),
                       args[3], args[3], _sds((), jnp.int32),
                       _sds((2,), jnp.float32),
                       learner.meta, learner.params, learner._btab)
    n = _hlo_bytes(lowered)
    assert n < MAX_HLO_BYTES, f"stepwise HLO is {n} bytes"
