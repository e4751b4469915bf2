"""One run of a training cell whose checked steps come after a warm-up.

A configuration that samples rows by their gradients leaves its first
iterations unsampled, so what the cell measures begins after them. Set-up
builds one booster and drives it with ``Booster.update`` through the mix's
``warm_steps`` iterations, which are not compared (the plain step is
another cell's to guard), and then through ``checked_steps`` more, keeping
the training rows' scores before the first and after each of those; the
window goes on with that same booster and the same call, so every timed
iteration comes after the warm-up. Once the window has closed and the peak memory is read,
the booster is freed and the plain reference follows the checked steps from
the program's own scores at the end of the warm-up (training rows and
held-out rows), from the same table.

The reference a cell of this kind names offers what ``reference/__init__.py``
lists for kind ``train``, and ``Reference(..., start_scores=,
start_iteration=)`` with ``predict_raw(X, start=)``; its own controls are
keywords of ``Reference`` (``CONTROLS``).
"""
from __future__ import annotations

import gc
import sys
import time

from . import check, program, program_obs
from .train import SPANS  # noqa: F401  (the runner's, as kind train's)
from .train import CompileCounter, Run, drive_window, start_trace

# variants of a run that the driver never asks for: a control or a fault in
# the program's place over the checked steps, as (parameters changed,
# keywords of Reference)
CONTROLS = {
    "ref-plain": ({}, {"sampling": "none"}),
    "ref-noamp": ({}, {"amplify": False}),
    "ref-uniform": ({}, {"sampling": "uniform"}),
    "ref-top19": ({"top_rate": 0.19}, {}),
    "ref-bf16": ({}, {"gh_dtype": "bfloat16"}),
    "ref-half": ({}, {"drop_odd_rows": True}),
    "ref-frozen": ({}, {"freeze_scores": True}),
}
VARIANTS = tuple(CONTROLS)


def _reference_scores(reference, variant, X, y, params, steps, X_hold,
                      start_scores, start_hold, start_iteration):
    changed, keywords = CONTROLS.get(variant, ({}, {}))
    ref = reference.Reference(
        X, y, reference.Params.from_dict(dict(params, **changed)),
        start_scores=start_scores, start_iteration=start_iteration,
        **keywords)
    scores = [ref.step() for _ in range(steps)]
    return scores, ref.predict_raw(X_hold, start=start_hold), ref


def run(cell: dict, seed: int, seconds: float, trace: bool, device,
        peaks: dict, t_process: float, variant=None) -> tuple:
    """``(Run, result, compared)``, as ``train.run`` returns them."""
    import jax
    from ..trace import work, xplane

    cfg, mix = cell["config"], cell["traffic"]
    rows, features = int(cfg["rows"]), int(cfg["features"])
    hold = int(cfg["valid_rows"])
    warm, steps = int(mix["warm_steps"]), int(mix["checked_steps"])
    params = dict(cfg["params"], **mix.get("extra_params", {}))
    ref_params = dict(cfg["defaults_in_force"], **cfg["params"])
    reference = cell["spec"].reference(cell)
    make_table = cell["spec"].make_table(mix["data"])

    this = Run(rows, features, peaks)
    this.warm_steps = warm      # for the reader of the compile's share
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
    with this.phase("warm steps 2 to %d" % warm):
        for _ in range(warm - 1):
            prog.update()
        start_scores = prog.scores()
    got_scores = []
    for k in range(1, steps + 1):
        with this.phase("checked step %d" % k):
            prog.update()
            got_scores.append(prog.scores())
    with this.phase("warm validation walk"):
        hops = prog.warm_validation_walk()
    print("learner: %s; validation walk warmed at %s hops" % (
        prog.learner_name(), hops), flush=True)
    this.end_to_end["setup_s"] = time.perf_counter() - t_process

    counts0 = program.trace_counts()
    # the warm-up's trees are unsampled ones: a reader of this cell's
    # counters takes what they were when the window opened from them
    this.counters_at_window = program_obs.counters()
    attempted = 0
    log_dir = None
    if seconds > 0:
        if trace:
            log_dir = start_trace(cell["name"])
        compiles.listening = True
        attempted, this.window_s = drive_window(prog, seconds, trace)
        compiles.listening = False
        if trace:
            jax.profiler.stop_trace()
            program.disable_spans()
        this.iterations = prog.iterations() - warm - steps
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

    with this.phase("held-out predict"):
        start_hold = prog.predict_raw(X_hold, warm)
        got_hold = prog.predict_raw(X_hold, warm + steps)
    tree_counts = work.tree_counts_from_model_text(prog.model_text())[warm:]
    this.tree_counts = tree_counts[steps:] if trace else []
    print("program: rows at the root of each tree after the warm-up %s, "
          "rows histogrammed %s; counters moved in the window by %s" % (
              [c[0] for c in tree_counts],
              [work.histogram_rows(c) for c in tree_counts],
              {name: n - this.counters_at_window.get(name, 0)
               for name, n in sorted(program_obs.counters().items())
               if n != this.counters_at_window.get(name, 0)}),
          flush=True)
    prog.free()
    prog = None
    gc.collect()

    if log_dir is not None:
        with this.phase("read trace"):
            this.trace = xplane.load(xplane.find_xplane(log_dir))
            this.busy_s = xplane.busy_s(this.trace)

    def follow(v):
        return _reference_scores(reference, v, X, y, ref_params, steps,
                                 X_hold, start_scores, start_hold, warm)

    with this.phase("reference"):
        ref_scores, ref_hold, ref = follow(None)
    print("reference: %s leaves per tree, %s rows at the root, %s rows "
          "histogrammed per tree; seconds %s" % (
              [len(t.leaf) + 1 for t in ref.trees],
              [t.smaller_rows[0] for t in ref.trees],
              [int(sum(t.smaller_rows)) for t in ref.trees], ref.seconds),
          flush=True)

    def gaps():
        return check.compare(reference.loss, y, start_scores, got_scores,
                             ref_scores, y_hold, got_hold, ref_hold)

    def verdict(who):
        """The compared numbers of whatever stands in the program's place,
        judged by the cell's limits, and said on a line of its own."""
        numbers = gaps()
        numbers["window_compiles"] = compiles.count + sum(retraced.values())
        correct, compared = check.judge(numbers, cell["limits"])
        print("verdict on %s: correct %s; over its limit: %s; readings %s" % (
            who, correct,
            [n for n, c in compared.items()
             if c["value"] is None or not c["value"] <= c["limit"]],
            numbers), flush=True)
        return correct, compared

    # every control of a comma list is judged; the result line carries the
    # verdict on the last one
    correct, compared = verdict("the program")
    for v in (variant or "").split(","):
        if v:
            with this.phase("reference as " + v):
                got_scores, got_hold, _ = follow(v)
            correct, compared = verdict(v)
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
