"""The per-leaf histogram store is updated in place.

A split step reads the two old slices of ``GrowState.hists`` once,
before its first write (``treelearner/grow.py _split_hist_store``). A
read of the old store ordered after a write makes XLA keep the carried
buffer alive across that write: on the v5e the whole ``f32[L,F,B,4]``
store was then copied twice per split (ISSUE 28). Two guards:

- the whole-tree program of the data-parallel learner, compiled here for
  a described (not attached) v5e chip, holds no ``copy`` of the store's
  shape inside its ``while`` body;
- on the CPU, the helper writes exactly the two children's slices, and
  nothing at all when the step is not valid.

``store_copies_on_v5e`` is also the host-side check of
``.claude/skills/verify/SKILL.md`` at the benchmark cells' sizes.

The same compiled program guards the rows ordered by leaf (ISSUE 34,
``grow.py _partition_order``): a split step scatters its parent's window
and nothing longer, and never copies ``GrowState.order``
(``order_faults``); and the tile loop of the smaller child's histogram
(ISSUE 36, ``grow.py _compact_child_hist``): its body gathers a tile's
rows and continues the accumulator in place, and copies none of the
arrays it reads a tile of (``whole_copies_in_loops``).
"""
import re
from unittest import mock

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from lightgbm_tpu.config import Config
from lightgbm_tpu.io.dataset import BinnedDataset
from lightgbm_tpu.parallel import DataParallelTreeLearner, make_mesh
from lightgbm_tpu.treelearner.grow import (_split_hist_store,
                                           _window_sizes, bins_by_feature)


def describe_v5e():
    """Compile-only TPU v5e devices (needs libtpu; raises without)."""
    from jax.experimental import topologies
    return topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")


def compile_tree_program(topo, features: int, leaves: int,
                         rows: int = 65536, max_bin: int = 255,
                         quantized: bool = False) -> tuple:
    """``(optimised HLO, the store's shape)`` of
    ``DataParallelTreeLearner._tree_impl`` compiled for one described
    v5e chip at ``rows`` x ``features``, with abstract arguments. The
    learner is built on the CPU from a few rows (it only
    lends its metadata and static sizes), then pointed at the described
    chip; ``jax.default_backend`` is steered so that the histogram takes
    the path it takes on the chip. ``quantized``: int8 gradient rows
    (``use_quantized_grad``), as ``bosch-train-quant`` has them."""
    rng = np.random.RandomState(0)
    cfg = Config.from_params({"num_leaves": leaves, "max_bin": max_bin,
                              "min_data_in_leaf": 1, "verbosity": -1,
                              "use_quantized_grad": quantized})
    ds = BinnedDataset.from_matrix(rng.randn(1024, features), cfg)
    learner = DataParallelTreeLearner(cfg, ds, make_mesh(1))
    mesh = Mesh(np.array(topo.devices[:1]), (learner.axis,))
    rep = NamedSharding(mesh, P())
    row = NamedSharding(mesh, P(learner.axis))
    learner.mesh, learner.N, learner.R = mesh, rows, rows
    learner.rep_sharding = learner.hist_sharding = rep
    learner.row_sharding = row
    learner.gh_sharding = NamedSharding(mesh, P(learner.axis, None))

    def spec(shape, dtype, sharding=rep):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)

    bins = spec((rows, features), ds.bins.dtype, learner.gh_sharding)
    gh = spec((rows, 4), jnp.int8 if quantized else jnp.float32,
              learner.gh_sharding)
    fmask = spec((features,), jnp.bool_)
    seed = spec((), jnp.int32)
    qscale = spec((2,), jnp.float32)
    with mock.patch.object(jax, "default_backend", lambda: "tpu"):
        state, _ = jax.eval_shape(learner._root_impl, bins, gh, fmask,
                                  seed, qscale)
        state = jax.tree_util.tree_map(
            lambda a: spec(a.shape, a.dtype,
                           row if a.shape == (rows,) else
                           learner.gh_sharding if a.shape == (rows, 4)
                           else rep), state)
        fm = jax.eval_shape(bins_by_feature, bins)
        lowered = jax.jit(learner._tree_impl, donate_argnums=(2,)).lower(
            bins, spec(fm.shape, fm.dtype), state, fmask, seed, qscale)
        return lowered.compile().as_text(), state.hists.shape


def store_copies(hlo: str, store_shape) -> dict:
    """``{computation name: [copy instructions whose result has the
    store's shape]}`` over every computation of an optimised HLO
    module, the ``while`` bodies among them (``s32`` is the store of
    quantized rows)."""
    shape = r"[fs]32\[%s\]" % ",".join(str(d) for d in store_shape)
    found, name = {}, None
    for line in hlo.splitlines():
        head = re.match(r"^(?:ENTRY\s+)?(%?[\w.\-]+)\s.*\{\s*$", line)
        if head and not line.startswith(" "):
            name = head.group(1)
        elif re.search(r"=\s*%s(\{[^}]*\})?\s+copy\(" % shape, line):
            found.setdefault(name, []).append(line.strip())
    return found


def while_bodies(hlo: str) -> set:
    return set(re.findall(r"body=(%?[\w.\-]+)", hlo))


def store_copies_on_v5e(features: int, leaves: int, rows: int = 65536,
                        max_bin: int = 255, topo=None):
    """(copies inside a ``while`` body, copies outside), each a list of
    HLO lines, for the tree program at this size on a described v5e."""
    topo = topo or describe_v5e()
    hlo, store = compile_tree_program(topo, features, leaves, rows,
                                      max_bin)
    assert "f32[%s]" % ",".join(map(str, store)) in hlo, (
        "the program holds no array of the store's shape: helper stale")
    copies = store_copies(hlo, store)
    bodies = while_bodies(hlo)
    assert bodies, "the tree program lost its while loop"
    inside = [c for k, v in copies.items() if k in bodies for c in v]
    outside = [c for k, v in copies.items() if k not in bodies for c in v]
    return inside, outside


def order_faults(hlo: str, rows: int) -> list:
    """What a split step may not do to the rows ordered by leaf, as
    lines of an optimised HLO module of the tree program at ``rows``
    rows: a ``scatter`` that is not a window's own (``obs_window_<W>``
    in its ``op_name``, W updates; the scatter of every row number into
    a bucket's ``idx`` was 5.9 ns a row a split), and a ``copy`` of
    ``order``'s shape, which a branch that returns the updated ``order``
    brings (``copy-start`` is the compiler moving it between memories
    whole, the same traffic)."""
    order_shape = r"s32\[%d\]" % (rows + _window_sizes(rows)[0])
    faults = []
    for line in hlo.splitlines():
        if re.search(r"=\s*\(?%s[^=]*\s(copy|copy-start)\(" % order_shape,
                     line):
            faults.append(line.strip())
        got = re.search(r"=\s*\w+\[(\d+)\]\S*\s+scatter\(", line)
        if " scatter(" in line and not (
                got and "/obs_window_%s/scatter" % got.group(1) in line):
            faults.append(line.strip())
    return faults


def whole_copies_in_loops(hlo: str, shapes) -> list:
    """The ``copy`` / ``copy-start`` instructions inside any ``while``
    body of an optimised HLO module whose result has one of ``shapes``
    (``"u8[65536,64]"``): a loop body that copies a whole array it
    reads a tile of pays the array per trip."""
    found, name, bodies = [], None, while_bodies(hlo)
    shape = "|".join(re.escape(s) for s in shapes)
    for line in hlo.splitlines():
        head = re.match(r"^(?:ENTRY\s+)?(%?[\w.\-]+)\s.*\{\s*$", line)
        if head and not line.startswith(" "):
            name = head.group(1)
        elif name in bodies and re.search(
                r"=\s*\(?(%s)[^=]*\s(copy|copy-start)\(" % shape, line):
            found.append(line.strip())
    return found


@pytest.fixture(scope="module")
def topo():
    try:
        return describe_v5e()
    except Exception as e:  # no libtpu, or its lock is held elsewhere
        pytest.skip("no v5e:2x2 topology can be described here: %s" % e)


def test_no_store_copy_inside_the_loop_on_v5e(topo):
    inside, _ = store_copies_on_v5e(64, 31, topo=topo)
    assert inside == [], (
        "the while body copies the whole histogram store: a read of "
        "state.hists is ordered after a write\n" + "\n".join(inside))


def test_a_split_touches_its_parents_window_of_the_order_on_v5e(topo):
    rows = 65536
    hlo, _ = compile_tree_program(topo, 64, 31, rows)
    assert "s32[%d]" % (rows + _window_sizes(rows)[0]) in hlo, (
        "the program holds no array of order's shape: helper stale")
    windows = set(re.findall(r"/obs_window_(\d+)/scatter", hlo))
    assert windows == {str(w) for w in _window_sizes(rows)}
    faults = order_faults(hlo, rows)
    assert faults == [], (
        "a split step scatters more than its parent's window, or "
        "copies the rows ordered by leaf\n" + "\n".join(faults))


@pytest.mark.parametrize("quantized", [False, True],
                         ids=["float32", "int8"])
def test_the_tile_loop_copies_no_whole_array_on_v5e(topo, quantized):
    """The smaller child's tile loop, inside the whole-tree loop: one
    ``hist_kernel`` call in the tree program (the ladder had one a
    bucket), continuing its accumulator in place, and no copy of the
    bin matrix, of its feature-major copy, of the rows ordered by leaf
    or of the store in a loop body."""
    rows, features = 65536, 64
    hlo, store = compile_tree_program(topo, features, 31, rows,
                                      quantized=quantized)
    kernels = [line for line in hlo.splitlines()
               if re.search(r"=.* custom-call\(.*hist_kernel", line)]
    assert len(kernels) == 1
    assert "output_to_operand_aliasing={{}: (2, {})}" in kernels[0]
    assert "obs_compact/while/body/obs_hist_pallas/hist_kernel" \
        in kernels[0]
    assert "obs_bucket_" not in hlo
    shapes = ["u8[%d,%d]" % (rows, features),
              "u8[%d,1,%d,128]" % (features, rows // 128),
              "s32[%d]" % (rows + _window_sizes(rows)[0]),
              "%s[%s]" % ("s32" if quantized else "f32",
                          ",".join(str(d) for d in store))]
    assert all(s in hlo for s in shapes), "helper stale: %s" % shapes
    faults = whole_copies_in_loops(hlo, shapes)
    assert faults == [], "\n".join(faults)


@pytest.mark.parametrize("features,gh_dtype", [
    (2000, jnp.float32), (968, jnp.int8), (968, jnp.float32)],
    ids=["epsilon-f32", "bosch-int8", "bosch-f32"])
def test_the_kernel_compiles_through_mosaic_at_the_cells_widths(
        topo, features, gh_dtype):
    """``_pallas_accumulate`` through Mosaic for a described v5e at the
    benchmark cells' widths and row tiles, feature blocks as the
    chooser sets them: what Mosaic refuses (a block that is neither a
    multiple of 8 nor all of F, an HBM slice off the 128-lane tiling,
    more VMEM than the limit) is refused here, in a second, and not on
    the chip."""
    from lightgbm_tpu.ops import histogram
    one_chip = NamedSharding(Mesh(np.array(topo.devices[:1]), ("x",)), P())
    tile = histogram._pallas_row_tile(gh_dtype)

    def spec(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    acc = jax.eval_shape(
        lambda: histogram._kernel_zeros(features, 255, 4, gh_dtype))
    compiled = jax.jit(
        lambda a, b, g: histogram._pallas_accumulate(a, b, g, tile),
        donate_argnums=0).lower(
            spec(acc.shape, acc.dtype), spec((2 * tile, features), jnp.uint8),
            spec((2 * tile, 4), gh_dtype)).compile()
    assert "hist_kernel" in compiled.as_text()


def test_whole_copies_in_loops_reads_bodies_alone():
    hlo = "\n".join([
        "%body.1 (p: (s32[], u8[8,4])) -> (s32[], u8[8,4]) {",
        "  %copy.1 = u8[8,4]{1,0:T(8,128)(4,1)} copy(%gte.1)",
        "  %copy-start.2 = (s32[40]{0:T(1024)S(1)}, s32[40]{0:T(1024)}, "
        "u32[]{:S(2)}) copy-start(%gte.2)",
        "  %copy.3 = u8[2,4]{0,1:T(8,128)(4,1)} copy(%fusion.3)",
        "}",
        "ENTRY %main.2 (a: u8[8,4]) -> u8[8,4] {",
        "  %copy.4 = u8[8,4]{1,0:T(8,128)(4,1)} copy(%a)",
        "  %while.5 = (s32[], u8[8,4]) while(%t), condition=%cond.1, "
        "body=%body.1",
        "}"])
    assert [line.split()[0] for line in whole_copies_in_loops(
        hlo, ["u8[8,4]", "s32[40]"])] == ["%copy.1", "%copy-start.2"]


def test_order_faults_sees_the_scatter_of_every_row_and_a_copy():
    """The reader on lines of the parent commit's program (ISSUE 34) and
    of a branch that returned the updated ``order``."""
    old = ('  ROOT %scatter.9 = s32[7813]{0:T(1024)} scatter(%p.1, %t.2, '
           '%t.3), metadata={op_name="jit(_tree_impl)/while/body/'
           'obs_compact/cond/branch_3_fun/obs_bucket_7813/scatter"}')
    copied = ('  %copy.221 = s32[2048576]{0:T(1024)} '
              'copy(%get-tuple-element.64)')
    moved = ('  %copy-start.5 = (s32[2048576]{0:T(1024)}, s32[2048576]'
             '{0:T(1024)S(1)}, u32[]{:S(2)}) copy-start(%dus.1)')
    own = ('  ROOT %scatter.34 = s32[16384]{0:T(1024)S(1)} scatter(%p.4, '
           '%t.7, %t.8), metadata={op_name="jit(_tree_impl)/while/body/'
           'obs_compact/cond/branch_6_fun/obs_window_16384/scatter"}')
    assert order_faults("\n".join([old, copied, moved, own]), 1_000_000) \
        == [old.strip(), copied.strip(), moved.strip()]


def _numpy_hist(bins, gh, rows, B):
    """[F, B, 4] histogram of ``rows``; exact for the dyadic gh below."""
    F = bins.shape[1]
    h = np.zeros((F, B, 4), dtype=np.float32)
    for f in range(F):
        np.add.at(h[f], bins[rows, f], gh[rows])
    return h


def _serial_helper_step(valid: bool):
    """The shared helper alone, on a store full of noise."""
    rng = np.random.RandomState(0)
    hists = rng.randn(7, 5, 8, 4).astype(np.float32)
    small = rng.randn(5, 8, 4).astype(np.float32)
    leaf, new_leaf = 2, 5
    after, hist_left, hist_right = jax.jit(_split_hist_store)(
        jnp.asarray(hists), jnp.int32(leaf), jnp.int32(new_leaf),
        jnp.asarray(small), jnp.asarray(False), jnp.asarray(valid))
    np.testing.assert_array_equal(np.asarray(hist_left),
                                  hists[leaf] - small)
    np.testing.assert_array_equal(np.asarray(hist_right), small)
    return hists, np.asarray(after), leaf, new_leaf, hists[leaf] - small, \
        small


def _data_parallel_step(valid: bool):
    """One whole split step of the data-parallel learner
    (``_mesh_split_body``) at the root of a small tree, every other
    slot of the store filled with noise. Grad and hess are dyadic, so
    numpy's sums equal the device's whatever their order."""
    rng = np.random.RandomState(1)
    X = rng.randn(600, 5)
    grad = np.where(X[:, 0] + X[:, 1] ** 2 > 0.3, -0.5, 0.5)
    hess = np.full(600, 0.25)
    cfg = Config.from_params({"num_leaves": 7, "max_bin": 15,
                              "min_data_in_leaf": 5, "verbosity": -1})
    ds = BinnedDataset.from_matrix(X, cfg)
    learner = DataParallelTreeLearner(cfg, ds, make_mesh(1))
    gh = learner._make_gh(jnp.asarray(grad, jnp.float32),
                          jnp.asarray(hess, jnp.float32), None)
    fmask = learner._sample_features()
    state, rec = learner._root_impl(learner.bins, gh, fmask, jnp.int32(0),
                                    learner._qscale)
    assert bool(np.isfinite(rec.gain)) and float(rec.gain) > 0
    noise = rng.randn(*state.hists.shape[1:]).astype(np.float32)
    state = state._replace(hists=state.hists.at[1:].set(noise))
    before = np.asarray(state.hists)
    leaf, new_leaf = 0, 1
    out = jax.jit(learner._mesh_split_body)(
        learner.bins, learner.bins_fm, state, rec, jnp.int32(leaf),
        jnp.int32(new_leaf),
        jnp.asarray(valid), fmask, fmask, qscale=learner._qscale)
    part = np.asarray(out.leaf_of_row)
    bins, gh = np.asarray(learner.bins), np.asarray(gh)
    want = [_numpy_hist(bins, gh, np.flatnonzero(part == k), learner.B)
            for k in (leaf, new_leaf)]
    if valid:
        assert 0 < (part == new_leaf).sum() < len(part)
    return before, np.asarray(out.hists), leaf, new_leaf, want[0], want[1]


@pytest.mark.parametrize("step", [_serial_helper_step, _data_parallel_step],
                         ids=["serial_helper", "data_parallel"])
class TestSplitStepStore:
    def test_invalid_step_leaves_store_bit_identical(self, step):
        before, after, *_ = step(False)
        assert after.tobytes() == before.tobytes()

    def test_valid_step_writes_the_two_children_only(self, step):
        before, after, leaf, new_leaf, want_left, want_right = step(True)
        np.testing.assert_array_equal(after[leaf], want_left)
        np.testing.assert_array_equal(after[new_leaf], want_right)
        rest = [i for i in range(len(after)) if i not in (leaf, new_leaf)]
        assert after[rest].tobytes() == before[rest].tobytes()
