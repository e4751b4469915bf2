"""One run of a training cell: set-up, the measured window, the check.

Set-up builds one booster, drives it through its first ``checked_steps``
iterations with ``Booster.update`` (the first compiles) and keeps the
training rows' scores after each; the window goes on with that same booster
and the same call (``drive_window``: by the clock, or in a traced run for
``TRACED_ITERATIONS``). Once the window has closed and the peak memory is
read, the booster is freed and the plain reference follows those first steps
from the same table.
"""
from __future__ import annotations

import contextlib
import gc
import os
import shutil
import sys
import time

from . import check, program, program_obs
from .spec import CHECKOUT

LOWERING_EVENT = "/jax/core/compile/jaxpr_to_mlir_module_duration"
TRACE_DIR = os.path.join(CHECKOUT, ".bench_trace")
# variants of a run that the driver never asks for: the controls and the
# faults of "How correct is decided", run by hand and by the tests
VARIANTS = ("quantized", "ref-bf16", "ref-half", "ref-frozen")
SPANS = r"^(bench|tree|gbdt|io|obj|jit)::"
# a traced run's window: this many iterations, so that its trace holds the
# same trees, and takes as long to stop and read, whatever the program's speed
TRACED_ITERATIONS = 6


class Run:
    """What a run measured, as the per-layer metric readers get it."""

    def __init__(self, rows: int, features: int, peaks: dict):
        self.rows, self.features = rows, features
        self.peaks = peaks          # the chip's row of trace/peaks.json
        self.phases = {}            # name -> seconds, host clock
        self.end_to_end = {}        # metric name -> value
        self.window_s = None
        self.iterations = 0
        self.trace = None           # trace.xplane.Trace in a traced run
        self.busy_s = None
        self.tree_counts = []       # per tree grown in the window

    @contextlib.contextmanager
    def phase(self, name: str):
        t0 = time.perf_counter()
        yield
        self.phases[name] = time.perf_counter() - t0
        print("phase %-28s %9.3f s" % (name, self.phases[name]), flush=True)


class CompileCounter:
    """Programs lowered while it is listening: jax's own event, whichever
    module asked for the compilation."""

    def __init__(self):
        self.count = 0
        self.listening = False

    def __call__(self, event: str, duration: float, **kw) -> None:
        if self.listening and event == LOWERING_EVENT:
            self.count += 1


def start_trace(name: str) -> str:
    import jax
    log_dir = os.path.join(TRACE_DIR, name)
    shutil.rmtree(log_dir, ignore_errors=True)
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    options.host_tracer_level = 2
    jax.profiler.start_trace(log_dir, profiler_options=options)
    return log_dir


def drive_window(prog, seconds: float, trace: bool) -> tuple:
    """``(updates issued, seconds)`` of the window: ``prog.update()`` under
    ``bench::iteration`` until ``seconds`` have passed, and in a traced run
    no more than ``TRACED_ITERATIONS`` times; the clock stops when the
    scores of the last update are ready."""
    import jax
    attempted = 0
    t0 = time.perf_counter()
    if trace:
        while (attempted < TRACED_ITERATIONS
               and time.perf_counter() - t0 < seconds):
            with jax.profiler.TraceAnnotation("bench::iteration"):
                prog.update()
            attempted += 1
    else:
        while time.perf_counter() - t0 < seconds:
            with jax.profiler.TraceAnnotation("bench::iteration"):
                prog.update()
            attempted += 1
    prog.wait()
    return attempted, time.perf_counter() - t0


def _reference_scores(reference, variant, X, y, params, steps, X_hold):
    import jax.numpy as jnp
    ref = reference.Reference(
        X, y, reference.Params.from_dict(params),
        gh_dtype=jnp.bfloat16 if variant == "ref-bf16" else jnp.float32,
        drop_odd_rows=variant == "ref-half",
        freeze_scores=variant == "ref-frozen")
    scores = [ref.step() for _ in range(steps)]
    return scores, ref.predict_raw(X_hold), ref


def run(cell: dict, seed: int, seconds: float, trace: bool, device,
        peaks: dict, t_process: float, variant=None) -> tuple:
    """``(Run, result, compared)``: ``result`` lacks its metrics, which the
    caller picks by ``BENCHMARK.json``'s lists from ``Run.end_to_end`` or
    the per-layer readers, and ``compared``, which goes last on the line."""
    import jax
    from ..trace import work, xplane

    cfg, mix = cell["config"], cell["traffic"]
    rows, features = int(cfg["rows"]), int(cfg["features"])
    hold = int(cfg["valid_rows"])
    steps = int(mix["checked_steps"])
    params = dict(cfg["params"], **mix.get("extra_params", {}))
    if variant == "quantized":
        params["use_quantized_grad"] = True
    ref_params = dict(cfg["defaults_in_force"], **cfg["params"])
    reference = cell["spec"].reference(cell)
    make_table = cell["spec"].make_table(mix["data"])

    this = Run(rows, features, peaks)
    compiles = CompileCounter()
    jax.monitoring.register_event_duration_secs_listener(compiles)
    print("compile cache: %s" % program.enable_compile_cache(), flush=True)
    if trace:
        program.enable_spans()

    with this.phase("generate"):
        X_all, y_all, dataset_kw = make_table(rows + hold, features, seed,
                                              mix["data"])
        X, y, X_hold, y_hold = (X_all[:rows], y_all[:rows], X_all[rows:],
                                y_all[rows:])
    init = reference.init_score(y)

    got_scores = []
    prog = None
    if variant in (None, "quantized"):
        prog = program.Program(params)
        with this.phase("bin"):
            if mix["validate"]:
                prog.bin(X, y, X_hold, y_hold, **dataset_kw)
            else:
                prog.bin(X, y, **dataset_kw)
        with this.phase("upload"):
            prog.build()
        with this.phase("compile + first step"):
            prog.update()
            got_scores.append(prog.scores())
        for k in range(2, steps + 1):
            with this.phase("steady warm step %d" % k):
                prog.update()
                got_scores.append(prog.scores())
        with this.phase("warm validation walk"):
            hops = prog.warm_validation_walk()
        print("learner: %s; validation walk warmed at %s hops" % (
            prog.learner_name(), hops), flush=True)
    this.end_to_end["setup_s"] = time.perf_counter() - t_process

    counts0 = program.trace_counts()
    # set-up's trees are counted too: a reader of the counters takes what
    # they were when the window opened from them
    this.counters_at_window = program_obs.counters()
    attempted = 0
    log_dir = None
    if prog is not None and seconds > 0:
        if trace:
            log_dir = start_trace(cell["name"])
        compiles.listening = True
        attempted, this.window_s = drive_window(prog, seconds, trace)
        compiles.listening = False
        if trace:
            jax.profiler.stop_trace()
            program.disable_spans()
        this.iterations = prog.iterations() - steps
        if this.iterations:
            this.end_to_end["train_iter_s"] = this.window_s / this.iterations
    retraced = {k: v - counts0.get(k, 0)
                for k, v in program.trace_counts().items()
                if v != counts0.get(k, 0)}
    stats = device.memory_stats() or {}
    memory_peak = int(stats.get("peak_bytes_in_use", 0))
    print("window: %d iterations in %r s; lowered in window: %d; program's "
          "jit_trace counts that moved: %s; peak %d bytes" % (
              this.iterations, this.window_s, compiles.count, retraced,
              memory_peak), flush=True)

    if prog is not None:
        with this.phase("held-out predict"):
            got_hold = prog.predict_raw(X_hold, steps)
        if trace and this.iterations:
            this.tree_counts = work.tree_counts_from_model_text(
                prog.model_text())[steps:]
        prog.free()
        prog = None
        gc.collect()

    if log_dir is not None:
        with this.phase("read trace"):
            this.trace = xplane.load(xplane.find_xplane(log_dir))
            this.busy_s = xplane.busy_s(this.trace)

    with this.phase("reference"):
        ref_scores, ref_hold, ref = _reference_scores(
            reference, None, X, y, ref_params, steps, X_hold)
    print("reference: %s leaves per tree, %s rows histogrammed per tree; "
          "seconds %s" % ([len(t.leaf) + 1 for t in ref.trees],
                          [int(sum(t.smaller_rows)) for t in ref.trees],
                          ref.seconds), flush=True)
    def gaps():
        return check.compare(reference.loss, y, init, got_scores, ref_scores,
                             y_hold, got_hold, ref_hold)

    for v in (variant or "").split(","):
        if v.startswith("ref-"):
            with this.phase("reference as " + v):
                got_scores, got_hold, _ = _reference_scores(
                    reference, v, X, y, ref_params, steps, X_hold)
            print("readings of %s: %s" % (v, gaps()), flush=True)

    numbers = gaps()
    numbers["window_compiles"] = compiles.count + sum(retraced.values())
    correct, compared = check.judge(numbers, cell["limits"])
    failed = attempted - this.iterations
    dev = {"platform": device.platform, "kind": device.device_kind,
           "count": 1, "memory_peak_bytes": memory_peak}
    if trace and this.window_s:
        dev["busy_s"] = this.busy_s
        dev["window_s"] = this.window_s
    result = {"correct": bool(correct and failed == 0), "attempted": attempted,
              "failed": failed, "metrics": {}, "device": dev}
    check.report(compared, sys.stderr)
    return this, result, compared
