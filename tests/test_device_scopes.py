"""The grower's stages are named on the device clock.

Every stage of the device programs sits in a ``jax.named_scope`` named
``obs_<stage>`` (docs/OBSERVABILITY.md "Device traces"); the benchmark's
``benchmark/trace/scopes.py`` reads them back from a profiler trace. A
refactor that drops a scope, a reader that loses time, a compile that is
not accounted and a bucket counter that drifts from the trees all fail
here, on the CPU.
"""
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.trace import scopes, work, xplane
from lightgbm_tpu.config import Config
from lightgbm_tpu.io.dataset import BinnedDataset
from lightgbm_tpu.obs import compile as obs_compile
from lightgbm_tpu.obs.registry import registry
from lightgbm_tpu.ops import histogram
from lightgbm_tpu.parallel.data_parallel import (DataParallelTreeLearner,
                                                 make_mesh)
from lightgbm_tpu.treelearner.grow import bins_by_feature
from lightgbm_tpu.treelearner.serial import SerialTreeLearner
from lightgbm_tpu.utils import next_pow2

SMALL_TRACE = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmark", "tests", "data",
    "small.xplane.pb")


# --- (a) every scope of the table is in the lowered programs -------------

def _dataset(hist_backend):
    rng = np.random.RandomState(0)
    cfg = Config.from_params({"num_leaves": 31, "max_bin": 63,
                              "verbosity": -1,
                              "hist_backend": hist_backend})
    return cfg, BinnedDataset.from_matrix(rng.randn(40000, 16), cfg)


def _mesh_programs(hist_backend):
    cfg, ds = _dataset(hist_backend)
    lrn = DataParallelTreeLearner(cfg, ds, make_mesh(1))
    lrn._ensure_compiled()
    args = (lrn.bins, jax.ShapeDtypeStruct((lrn.R, 4), jnp.float32),
            lrn._sample_features(), jnp.int32(1), lrn._qs_ones)
    state, _ = jax.eval_shape(lrn._root_fn, *args)
    tree = lrn._tree_fn.lower(lrn.bins, lrn.bins_fm, state, *args[2:])
    return (lrn._root_fn.lower(*args).as_text(debug_info=True),
            tree.as_text(debug_info=True))


@pytest.fixture(scope="module")
def lowered():
    root, tree = _mesh_programs("auto")
    _, tree_onehot = _mesh_programs("onehot")
    cfg, ds = _dataset("auto")
    serial = SerialTreeLearner(cfg, ds)
    r = -(-(serial.N + 1) // 4096) * 4096
    sds = jax.ShapeDtypeStruct
    root_args = (sds((r, serial.Fp), serial.bins.dtype),
                 sds((r, 4), jnp.float32), sds((r,), jnp.int32),
                 sds((serial.Fp,), jnp.bool_), sds((), jnp.bool_),
                 sds((), jnp.int32), sds((2,), jnp.float32), serial.meta,
                 serial.params, serial._btab)
    state, _ = jax.eval_shape(serial._root_fn, *root_args)
    serial_tree = serial._fused_fn().lower(
        root_args[0], jax.eval_shape(bins_by_feature, root_args[0]), state,
        sds((), jnp.int32), sds((), jnp.int32),
        root_args[3], sds((), jnp.int32), sds((2,), jnp.float32),
        serial.meta, serial.params, serial._btab)
    return {"root": root, "tree": tree, "tree_onehot": tree_onehot,
            "serial_tree": serial_tree.as_text(debug_info=True)}


@pytest.mark.parametrize("program,scope", [
    ("root", "obs_hist_scatter"),
    ("root", "obs_split_scan"),
    ("tree", "obs_pick_leaf"),
    ("tree", "obs_partition"),
    ("tree", "obs_compact"),
    ("tree", "obs_hist_scatter"),
    ("tree", "obs_hist_subtract"),
    ("tree", "obs_hist_store"),
    ("tree", "obs_split_scan"),
    ("tree", "obs_psum_histogram"),
    ("tree_onehot", "obs_hist_einsum"),
    ("serial_tree", "obs_pick_leaf"),
    ("serial_tree", "obs_partition"),
    ("serial_tree", "obs_compact"),
    ("serial_tree", "obs_hist_subtract"),
    ("serial_tree", "obs_hist_store"),
    ("serial_tree", "obs_split_scan"),
])
def test_scope_is_in_the_lowered_program(lowered, program, scope):
    """``mesh.root``/``mesh.tree`` (``_root_impl``, ``_tree_impl``) on one
    device, and the serial learner's whole-tree program
    (``serial.fused_tree``), which run the same scoped functions of
    ``treelearner/grow.py``."""
    assert re.search(r'[/"]%s[/"]' % scope, lowered[program]), (
        "%s has no operation under %s" % (program, scope))


@pytest.mark.parametrize("program", ["tree", "serial_tree"])
def test_tile_loop_is_tagged_inside_the_compaction(lowered, program):
    """The smaller child's tiles are one loop inside the whole-tree
    loop's body, under the compaction's scope, with the tile's gathers
    there and the histogram's own scope inside; no branch by size is
    left. Both learners reach the one ``_grow_tree`` and the one
    ``_compact_child_hist``."""
    text = lowered[program]
    inner = r"while/body/obs_compact/while/body/"
    assert re.search(inner + r"gather", text)
    assert re.search(inner + r"(\w+/)*obs_hist_scatter/", text)
    assert "obs_bucket_" not in text
    assert not re.search(r"obs_compact/cond/branch_\d+_fun/(\w+/)*obs_hist_",
                         text)


def test_pallas_path_is_scoped_and_the_kernel_named(monkeypatch):
    """The path ``build_histogram`` takes on a TPU, lowered for one."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    bins = jax.ShapeDtypeStruct((2 * histogram.PALLAS_ROW_TILE, 28),
                                jnp.uint8)
    gh = jax.ShapeDtypeStruct((2 * histogram.PALLAS_ROW_TILE, 4),
                              jnp.float32)
    text = jax.jit(lambda b, g: histogram.build_histogram(b, g, 255)) \
        .trace(bins, gh).lower(lowering_platforms=("tpu",)) \
        .as_text(debug_info=True)
    assert "/obs_hist_pallas/jit(_pallas_histogram_body)" in text
    assert '"hist_kernel/pallas_call"' in text
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("features", [28, 2000],
                         ids=["one-block", "three-blocks"])
def test_tile_loop_keeps_the_kernel_under_its_scope_and_name(monkeypatch,
                                                             features):
    """What a tile of the loop lowers to on a TPU: the kernel under
    ``obs_hist_pallas``, by its name, continuing its third operand in
    place, whether the features go through in one block or several."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    sds = jax.ShapeDtypeStruct
    rows = 4 * histogram.PALLAS_ROW_TILE
    tiles = histogram.histogram_tiles(sds((rows, features), jnp.uint8),
                                      sds((rows, 4), jnp.float32), 255)
    tile = histogram.PALLAS_ROW_TILE
    text = jax.jit(lambda a, b, g: tiles.result(tiles.add(a, b, g))) \
        .trace(jax.eval_shape(tiles.zeros),
               sds((tile, features), jnp.uint8),
               sds((tile, 4), jnp.float32)) \
        .lower(lowering_platforms=("tpu",)).as_text(debug_info=True)
    assert '/obs_hist_pallas/hist_kernel/pallas_call"' in text
    assert "tpu_custom_call" in text
    assert re.search(r"output_operand_aliases? = \[.*operand_index = 2",
                     text)


def _kernel_call(features, gh_dtype, tile):
    """The ``pallas_call`` equation of one tile's ``_pallas_accumulate``."""
    sds = jax.ShapeDtypeStruct
    acc = jax.eval_shape(
        lambda: histogram._kernel_zeros(features, 255, 4, gh_dtype))
    jaxpr = jax.make_jaxpr(
        lambda a, b, g: histogram._pallas_accumulate(a, b, g, tile))(
            acc, sds((3 * tile, features), jnp.uint8),
            sds((3 * tile, 4), gh_dtype))
    (call,) = [e for e in jaxpr.jaxpr.eqns
               if e.primitive.name == "pallas_call"]
    return call.params["grid_mapping"]


def test_one_block_of_features_is_the_kernel_of_before():
    """F = 968 in float32 (``bosch-train`` and its two sampled cells):
    the chooser returns F, the grid runs over the row tiles alone and
    the accumulator operand is left in HBM (``pl.ANY``), copied in by
    the first step: the program those cells ran before the kernel had
    an axis over feature blocks."""
    tile = histogram.PALLAS_ROW_TILE
    assert histogram._pallas_feature_block(968, 255, 4, tile, 4, 1) == 968
    mapping = _kernel_call(968, jnp.float32, tile)
    assert mapping.grid == (3,)
    bins, gh, acc, out = mapping.block_mappings
    assert str(acc.transformed_block_aval.memory_space) == "any"
    assert [b.transformed_block_aval.shape for b in (bins, gh, out)] \
        == [(968, tile), (4, tile), (968, 16, 64)]


@pytest.mark.parametrize("features,gh_dtype,tile,hi_rows", [
    (2000, jnp.float32, histogram.PALLAS_ROW_TILE, 16),
    (968, jnp.int8, histogram.PALLAS_ROW_TILE_INT, 32)],
    ids=["epsilon-f32", "bosch-int8"])
def test_several_blocks_of_features_put_their_axis_outside_the_rows(
        features, gh_dtype, tile, hi_rows):
    """Where all of F passes the VMEM bound the grid gains a leading
    axis over blocks of features, and the accumulator comes in as a
    block with the output's index map."""
    block = histogram._pallas_feature_block(
        features, 255, 4, tile, jnp.dtype(gh_dtype).itemsize, 1)
    mapping = _kernel_call(features, gh_dtype, tile)
    assert mapping.grid == (-(-features // block), 3)
    bins, gh, acc, out = mapping.block_mappings
    assert acc.transformed_block_aval.memory_space is None
    assert [b.transformed_block_aval.shape for b in (bins, gh, acc, out)] \
        == [(block, tile), (4, tile), (block, hi_rows, 64),
            (block, hi_rows, 64)]
    assert str(acc.index_map_jaxpr) == str(out.index_map_jaxpr)


# --- (b) the reader ------------------------------------------------------

def test_reader_finds_the_name_stack_in_the_event_metadata():
    ops = scopes.load_ops(SMALL_TRACE)
    by_name = {}
    for name, tf_op in zip(ops.line.names, ops.tf_op):
        by_name.setdefault(xplane.short_name(name).split()[0],
                           set()).add(tf_op)
    assert by_name["%fusion.8"] == {
        "jit(bench_small_loop)/while/body/closed_call/dot_general:"}
    # an operation XLA added itself has no name stack
    assert by_name["%copy.11"] == {""}


def test_reader_agrees_with_profile_data_and_loses_no_time():
    ops = scopes.load_ops(SMALL_TRACE)
    trace = xplane.load(SMALL_TRACE)
    assert ops.line.names == trace.ops().names
    np.testing.assert_array_equal(ops.line.start, trace.ops().start)
    np.testing.assert_array_equal(ops.line.dur, trace.ops().dur)
    assert ops.modules.names == trace.modules().names
    times = scopes.stage_times(ops)
    assert set(times.stages) == {scopes.UNSCOPED}
    assert times.total_s == pytest.approx(xplane.union_s(trace.ops()),
                                          rel=1e-9)
    # clipped to one program, what lies between its operations included
    clipped = scopes.stage_times(ops, r"^jit_bench_small_loop")
    assert clipped.total_s == pytest.approx(xplane.union_s(
        trace.modules().matching(r"^jit_bench_small_loop")), rel=1e-9)
    assert clipped.unscoped_ops[0][0].startswith("%fusion.8")


def _hand_made():
    """Two runs of ``jit__tree_impl`` and one of another program. Times in
    ns; ``while`` [0, 1000) holds the body's operations."""
    p = "jit(_tree_impl)/while/body/"
    events = [
        ("%while.1 = while()", 0, 1000, ""),
        ("%fusion.1 = f32[8]{0} fusion()", 0, 100, p + "obs_pick_leaf/argmax"),
        ("%fusion.2 = pred[8]{0} fusion()", 100, 200, p + "obs_partition/le"),
        ("%fusion.3 = s32[8]{0} fusion()", 300, 100,
         p + "obs_compact/cumsum"),
        ("%conditional.1 = conditional()", 400, 400, p + "obs_compact/cond"),
        ("%fusion.4 = u8[8]{0} fusion()", 400, 100,
         p + "obs_compact/cond/branch_1_fun/obs_bucket_5000/gather"),
        ("%fusion.5 = f32[8]{0} fusion()", 500, 250,
         p + "obs_compact/cond/branch_1_fun/obs_bucket_5000/"
             "obs_hist_einsum/obs_psum_histogram/dot_general:"),
        ("%copy.1 = f32[8]{0} copy()", 800, 100, ""),
        ("%fusion.6 = f32[8]{0} fusion()", 900, 50,
         p + "obs_split_scan/obs_made_up/reduce_max"),
        # the second run: [2000, 2100)
        ("%fusion.7 = f32[8]{0} fusion()", 2000, 80,
         "jit(_tree_impl)/obs_hist_store/dynamic_update_slice"),
        # another program's operation, [3000, 3500)
        ("%fusion.8 = f32[8]{0} fusion()", 3000, 500,
         "jit(other)/obs_partition/le"),
    ]
    line = xplane.Line([e[0] for e in events],
                       np.asarray([e[1] for e in events], np.int64),
                       np.asarray([e[2] for e in events], np.int64))
    modules = xplane.Line(
        ["jit__tree_impl(1)", "jit__tree_impl(1)", "jit_other(2)"],
        np.asarray([0, 2000, 3000], np.int64),
        np.asarray([1000, 100, 500], np.int64))
    return scopes.Ops(line, [e[3] for e in events], modules)


def test_innermost_stage_wins_and_the_stages_add_up():
    times = scopes.stage_times(_hand_made(), r"^jit__tree_impl")
    ns = {k: round(v * 1e9) for k, v in times.stages.items()}
    assert ns == {
        "obs_pick_leaf": 100, "obs_partition": 200,
        # the cumsum, the gather, and the conditional's own 50 ns
        "obs_compact": 100 + 100 + 50,
        # under obs_compact and obs_psum_histogram, but a stage of its own
        "obs_hist_einsum": 250,
        "obs_split_scan": 50, "obs_hist_store": 80,
        # the copy, the while's own 50 ns, and the second run's last 20 ns
        scopes.UNSCOPED: 100 + 50 + 20,
    }
    assert round(times.total_s * 1e9) == 1000 + 100
    assert {b: {k: round(v * 1e9) for k, v in per.items()}
            for b, per in times.buckets.items()} == {
        5000: {"obs_compact": 100, "obs_hist_einsum": 250}}
    assert [[n, round(s * 1e9)] for n, s in times.unscoped_ops] == [
        ["%copy.1 f32[8] copy", 100], ["%while.1 = while()", 50],
        [scopes.BETWEEN_OPS, 20]]


@pytest.mark.parametrize("tf_op,expected", [
    ("", (None, None)),
    ("jit(f)/while/body/add:", (None, None)),
    ("jit(f)/obs_psum_histogram/all-reduce", (None, None)),
    ("jit(f)/obs_compact/cond/branch_0_fun/obs_bucket_12500/gather",
     ("obs_compact", 12500)),
    ("jit(f)/obs_compact/obs_bucket_7/obs_hist_pallas/jit(g)/hist_kernel:",
     ("obs_hist_pallas", 7)),
    ("jit(f)/obs_split_scan", ("obs_split_scan", None)),
])
def test_stage_of_a_name_stack(tf_op, expected):
    assert scopes.stage_of(tf_op) == expected


# --- (c) compile accounting ----------------------------------------------

def test_a_compile_is_accounted_once_under_jaxs_name(timer_on):
    def scopes_fresh_fn(x):
        return x * 3 + 1

    fn = obs_compile.instrument_jit("test.scopes_fresh", scopes_fresh_fn)
    names = ["jit_lower_s/jit(scopes_fresh_fn)",
             "jit_backend_compile_s/jit(scopes_fresh_fn)"]
    assert not any(n in timer_on.stats() for n in names)
    fn(jnp.ones(7)).block_until_ready()
    first = timer_on.stats()
    assert all(first[n][0] > 0 and first[n][1] == 1 for n in names)
    fn(jnp.ones(7)).block_until_ready()
    second = timer_on.stats()
    assert all(second[n][:2] == first[n][:2] for n in names)
    # none of it under the prefix the retrace budget counts
    assert not [k for k in registry.counters
                if k.startswith("jit_trace/jit")]


def test_compiles_are_not_accounted_while_the_timer_is_off():
    assert not registry.timer.enabled

    def scopes_quiet_fn(x):
        return x - 2

    fn = obs_compile.instrument_jit("test.scopes_quiet", scopes_quiet_fn)
    fn(jnp.ones(5)).block_until_ready()
    assert not [k for k in registry.timer.stats() if "scopes_quiet_fn" in k]


_CACHE_CHILD = """
import sys
import jax, jax.numpy as jnp
jax.config.update("jax_compilation_cache_dir", sys.argv[1])
jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
from lightgbm_tpu.obs import compile as obs_compile
from lightgbm_tpu.obs.registry import registry
counted = []
jax.monitoring.register_event_listener(lambda e, **kw: counted.append(e))
if sys.argv[2] == "profiled":
    registry.timer.enable()
def cache_probe_fn(x):
    with jax.named_scope("obs_probe"):
        return jnp.sin(x) * 2
obs_compile.instrument_jit("probe", cache_probe_fn)(jnp.ones(16))
registry.timer.disable()
print(sum(e.endswith("/cache_hits") for e in counted),
      sum(e.endswith("/cache_misses") for e in counted),
      registry.count("jit_cache_hits"), registry.count("jit_cache_misses"))
"""


def test_a_profiled_run_reads_no_cache_entry_of_an_unprofiled_one(tmp_path):
    """jax's cache key leaves metadata out, so an entry compiled before a
    scope would come back without it; with the stage timer on the key
    holds the metadata. Fresh interpreters: the key is per process."""
    import subprocess
    import sys
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

    def child(mode):
        out = subprocess.run(
            [sys.executable, "-c", _CACHE_CHILD, str(tmp_path), mode],
            env=dict(os.environ, JAX_PLATFORMS="cpu"), cwd=repo,
            capture_output=True, text=True, timeout=120)
        assert out.returncode == 0, out.stderr[-2000:]
        return [int(v) for v in out.stdout.split()[-4:]]

    hits, misses, counted_hits, counted_misses = child("plain")
    assert hits == 0 and misses >= 1
    # counted only while the timer is on
    assert counted_hits == counted_misses == 0
    assert child("plain")[:2] == [misses, 0]
    assert child("profiled") == [0, misses, 0, misses]
    assert child("profiled") == [misses, 0, misses, 0]


# --- (d) the bucket counters ---------------------------------------------

def _train_data_learner():
    import lightgbm_tpu as lgb
    rng = np.random.RandomState(3)
    X = rng.randn(40000, 8)
    y = (X[:, 0] * X[:, 1] + X[:, 2] > 0).astype(float)
    return lgb.train({"objective": "binary", "verbose": -1,
                      "num_leaves": 31, "max_bin": 63,
                      "tree_learner": "data", "num_machines": 1},
                     lgb.Dataset(X, label=y), num_boost_round=3)


def _hist_rows():
    return (registry.count("grow/hist_rows_needed"),
            registry.count("grow/hist_rows_bucketed"))


def test_bucket_counters_follow_the_trees(timer_on):
    needed0, bucketed0 = _hist_rows()
    bst = _train_data_learner()
    assert type(bst.inner.learner).__name__ == "DataParallelTreeLearner"
    needed, bucketed = _hist_rows()
    counts = work.tree_counts_from_model_text(bst.model_to_string())
    assert len(counts) == 3
    assert needed - needed0 == sum(sum(smaller) for _, smaller in counts)
    # a mesh of several devices passes over every row for every split
    splits = sum(len(smaller) for _, smaller in counts)
    assert bucketed - bucketed0 == 40000 * splits


def _smaller_children(tree) -> list:
    """Rows of the smaller child of each of a grown tree's splits."""
    def rows(child):
        return (tree.internal_count[child] if child >= 0
                else tree.leaf_count[~child])
    return [int(min(rows(tree.left_child[k]), rows(tree.right_child[k])))
            for k in range(tree.num_leaves - 1)]


def test_bucket_counters_count_whole_tiles_where_the_learner_compacts(
        timer_on):
    """One device: every split's pass visits whole tiles, its smaller
    child's rows and less than a tile besides."""
    needed0, bucketed0 = _hist_rows()
    grown = _grow_trees_on_one_device()
    needed, bucketed = _hist_rows()
    smaller = [rows for t in grown for rows in _smaller_children(t)]
    tile = histogram.DEFAULT_ROW_TILE
    assert needed - needed0 == sum(smaller)
    assert bucketed - bucketed0 == sum(-(-rows // tile) * tile
                                       for rows in smaller)
    assert (bucketed - bucketed0) % tile == 0
    assert needed - needed0 <= bucketed - bucketed0 \
        < needed - needed0 + tile * len(smaller)


def test_bucket_counters_stay_still_while_the_timer_is_off():
    assert not registry.timer.enabled
    before = _hist_rows()
    _train_data_learner()
    assert _hist_rows() == before


# --- (e) the rows a split reorders ----------------------------------------

def _partition_rows():
    return (registry.count("grow/partition_parent_rows"),
            registry.count("grow/partition_window_rows"))


def _grow_trees_on_one_device(trees: int = 2):
    """Trees of a learner that compacts: a one-device mesh, whatever the
    number of devices the process has (``_train_data_learner`` spreads
    over all of them)."""
    rng = np.random.RandomState(3)
    X = rng.randn(40000, 8)
    y = (X[:, 0] * X[:, 1] + X[:, 2] > 0).astype(np.float32)
    cfg = Config.from_params({"objective": "binary", "num_leaves": 31,
                              "max_bin": 63, "verbosity": -1})
    lrn = DataParallelTreeLearner(
        cfg, BinnedDataset.from_matrix(X, cfg, label=y), make_mesh(1))
    hess = jnp.full(len(y), 0.25, dtype=jnp.float32)
    return [lrn.train(jnp.asarray(0.5 - y) * (1 + k), hess)[0]
            for k in range(trees)]


def test_partition_row_counters_follow_the_trees(timer_on):
    parents0, windows0 = _partition_rows()
    grown = _grow_trees_on_one_device()
    parents, windows = _partition_rows()
    splits = sum(t.num_leaves - 1 for t in grown)
    assert splits >= 2 * 20
    assert parents - parents0 == sum(
        int(t.internal_count[:t.num_leaves - 1].sum()) for t in grown)
    # every parent is padded to a window of 16,384 x 2^k entries, the
    # roots' 40,000 rows to 65,536
    assert windows - windows0 >= max(parents - parents0, 16384 * splits,
                                     65536 * len(grown))
    assert (windows - windows0) % 16384 == 0


def test_partition_row_counters_stay_still_while_the_timer_is_off():
    assert not registry.timer.enabled
    before = _partition_rows()
    _grow_trees_on_one_device(1)
    assert _partition_rows() == before


# --- (f) the rest of the iteration: program names, hops, spans ----------

def _module_name(fn, *args) -> str:
    return re.search(r"module @(\S+)", fn.lower(*args).as_text()).group(1)


def _score_program(name):
    """A jitted program of ``boosting/gbdt.py``'s score plumbing or of the
    validation walk with abstract arguments to lower it by."""
    from lightgbm_tpu.boosting import gbdt
    from lightgbm_tpu.ops import predict
    from lightgbm_tpu.utils.scalars import dev_i32
    sds = jax.ShapeDtypeStruct
    score, col = sds((100, 1), jnp.float32), sds((100,), jnp.float32)
    rows_i32, k = sds((100,), jnp.int32), dev_i32(0)
    if name == "predict.traverse":
        inner = _small_booster(np.random.RandomState(0).randn(300, 4)).inner
        dtree = predict.build_device_tree(inner.models[0], inner._bin_meta,
                                          64)
        return predict._traverse, (jnp.asarray(inner.train_data.bins),
                                   dtree, 4)
    return {
        "gbdt.take_col": (gbdt._take_col, (score, k)),
        "gbdt.score_delta": (gbdt._apply_leaf_delta,
                             (score, sds((15,), jnp.float32), rows_i32, k)),
        "gbdt.score_add_col": (gbdt._add_score_col, (score, col, k)),
        "gbdt.valid_score_add": (gbdt._add_valid_score_col,
                                 (score, col, k)),
        "gbdt.score_set_col": (gbdt._set_score_col, (score, col, k)),
        "predict.gather_leaf": (predict._gather_leaf_values,
                                (sds((16,), jnp.float32), rows_i32)),
    }[name]


def _small_booster(X, rounds=1, **kw):
    import lightgbm_tpu as lgb
    y = (X[:, 0] * X[:, 1] + X[:, 2] > 0).astype(float)
    return lgb.train({"objective": "binary", "verbose": -1,
                      "num_leaves": 15, "max_bin": 63, "metric": "auc"},
                     lgb.Dataset(X, label=y), num_boost_round=rounds, **kw)


def _gradient_program():
    obj = _small_booster(np.random.RandomState(1).randn(300, 4)) \
        .inner.objective
    return type(obj)._grads, (obj, jax.ShapeDtypeStruct((300,), jnp.float32),
                              obj.label_sign, obj.label_weight, obj.weights)


@pytest.mark.parametrize("name", [
    "gbdt.take_col", "gbdt.score_delta", "gbdt.score_add_col",
    "gbdt.valid_score_add", "gbdt.score_set_col"])
def test_a_score_program_runs_under_its_registered_name(name):
    """jax names a program after its function, and the device trace shows
    nothing else of it: as lambdas all five ran as ``jit__lambda``."""
    fn, args = _score_program(name)
    assert _module_name(fn, *args) == "jit_" + name.replace(".", "_")


def test_the_mesh_learner_s_monotone_root_runs_under_its_registered_name():
    cfg, ds = _dataset("auto")
    lrn = DataParallelTreeLearner(cfg, ds, make_mesh(1))
    gh = jnp.zeros((lrn.R, 4), jnp.float32)
    lrn._mono_root(gh, lrn._sample_features(), 1)
    assert _module_name(lrn._mono_root_fn, lrn.bins, gh,
                        lrn._sample_features(), jnp.int32(1),
                        lrn._qs_ones) == "jit_mesh_mono_root"


@pytest.mark.parametrize("name,module", [
    ("predict.traverse", "jit__traverse_body"),
    ("predict.gather_leaf", "jit__gather_leaf_values_body"),
    ("obj.binary.grads", "jit__grads"),
    ("test.named", "jit_scopes_named_fn")])
def test_a_named_function_keeps_its_own_name(name, module):
    """The names the benchmark's readers and the ledger know."""
    if name == "test.named":
        def scopes_named_fn(x):
            return x + 1
        fn = obs_compile.instrument_jit(name, scopes_named_fn)
        args = (jax.ShapeDtypeStruct((3,), jnp.float32),)
    elif name == "obj.binary.grads":
        fn, args = _gradient_program()
    else:
        fn, args = _score_program(name)
    assert _module_name(fn, *args) == module


def test_no_instrument_jit_site_is_a_lambda():
    """One rule names every program: its function's name. A lambda would
    run as ``jit__lambda`` beside every other one."""
    import ast
    import pathlib
    import lightgbm_tpu
    sites = []
    for path in pathlib.Path(lightgbm_tpu.__file__).parent.rglob("*.py"):
        for node in ast.walk(ast.parse(path.read_text())):
            if isinstance(node, ast.Call) and getattr(
                    node.func, "attr", getattr(node.func, "id", None)) in (
                    "instrument_jit", "instrument_jit_method"):
                sites.append((path.name, node.lineno, any(
                    isinstance(arg, ast.Lambda) for arg in node.args)))
    assert len(sites) > 50      # the scan finds the package's sites
    assert [site for site in sites if site[2]] == []


VALID_COUNTERS = ("valid/walk_hops_run", "valid/walk_hops_needed",
                  "valid/trees_walked", "valid/trees_all_nodes",
                  "valid/node_decisions")
VALID_SPANS = ("gbdt::eval_fetch", "gbdt::eval_compute")


def _train_with_validation(rounds=3, categorical=False):
    """``categorical``: column 0 holds six categories that decide the
    label with the others, so every tree has a categorical node and
    walks."""
    import lightgbm_tpu as lgb
    rng = np.random.RandomState(5)
    X, Xv = rng.randn(6000, 6), rng.randn(1500, 6)
    kw = {}
    if categorical:
        X[:, 0], Xv[:, 0] = rng.randint(0, 6, 6000), rng.randint(0, 6, 1500)
        kw = {"categorical_feature": [0]}

    def label(A):
        head = np.isin(A[:, 0], [1, 4]) if categorical else A[:, 0] > 0
        return (head * A[:, 1] + A[:, 2] > 0).astype(float)
    train = lgb.Dataset(X, label=label(X), **kw)
    valid = lgb.Dataset(Xv, label=label(Xv), reference=train)
    return lgb.train({"objective": "binary", "verbose": -1, "metric": "auc",
                      "num_leaves": 31, "max_bin": 63,
                      "min_data_per_group": 5, "cat_smooth": 1}, train,
                     num_boost_round=rounds, valid_sets=[valid])


def _valid_counters():
    return np.asarray([registry.count(name) for name in VALID_COUNTERS])


def _spy_traversals(monkeypatch):
    from lightgbm_tpu.ops import predict
    walks, traverse = [], predict._traverse

    def spy(bins, dtree, trips):
        walks.append((bins.shape[0], trips))
        return traverse(bins, dtree, trips)
    monkeypatch.setattr(predict, "_traverse", spy)
    return walks


def test_walk_counters_follow_the_trees(timer_on, monkeypatch):
    """``walk_hops_run`` is what the lockstep walk was handed as its
    static ``trips``, for the validation rows, whatever rule chose them:
    trees with a categorical node walk."""
    walks = _spy_traversals(monkeypatch)
    before = _valid_counters()
    trees = _train_with_validation(categorical=True).inner.models
    assert len(trees) == 3 and all(t.cat_bin_masks for t in trees)
    assert [rows for rows, _ in walks] == [1500] * 3
    hops_run, hops_needed, walked, all_nodes, decisions = \
        _valid_counters() - before
    assert (walked, all_nodes, decisions) == (3, 0, 0)
    assert hops_run == sum(rows * trips for rows, trips in walks)
    assert hops_needed == sum(int(round(1500 * float(
        (t.leaf_depth[:t.num_leaves] * t.leaf_count[:t.num_leaves]).sum()
        / t.leaf_count[:t.num_leaves].sum()))) for t in trees)
    # no row needs more hops than the deepest leaf, which the loop covers
    assert 1500 * 3 < hops_needed <= hops_run


def test_numeric_trees_count_their_node_decisions(timer_on, monkeypatch):
    """Trees of numerical nodes are scored all nodes at once: no hops run,
    every row decides every (padded) node of the tree."""
    walks = _spy_traversals(monkeypatch)
    before = _valid_counters()
    trees = _train_with_validation().inner.models
    assert len(trees) == 3 and walks == [(1500, None)] * 3
    hops_run, hops_needed, walked, all_nodes, decisions = \
        _valid_counters() - before
    assert (hops_run, hops_needed, walked, all_nodes) == (0, 0, 0, 3)
    assert decisions == sum(1500 * next_pow2(t.num_internal) for t in trees)


def test_walk_counters_stay_still_while_the_timer_is_off():
    assert not registry.timer.enabled
    before = _valid_counters()
    _train_with_validation(1)
    assert (_valid_counters() == before).all()


def test_walk_counters_stay_still_under_a_walk_of_no_iteration(timer_on):
    """The benchmark warms the walk's programs through ``_tree_outputs``
    at every hop count: not a tree of the run."""
    bst = _train_with_validation(1)
    before = _valid_counters()
    inner = bst.inner
    out = inner.valid_data[0]._tree_outputs(inner.models[-1],
                                            inner._bin_meta)
    assert out.shape == (1500,)
    assert (_valid_counters() == before).all()


@pytest.mark.parametrize("span", VALID_SPANS)
def test_span_is_recorded_once_an_iteration(timer_on, span):
    calls0 = {s: timer_on.counts.get(s, 0)
              for s in VALID_SPANS + ("gbdt::eval_metrics",)}
    _train_with_validation(3)
    assert timer_on.counts[span] - calls0[span] == 3
    # the two parts lie inside the whole, which is entered as often
    assert timer_on.counts["gbdt::eval_metrics"] \
        - calls0["gbdt::eval_metrics"] == 3
