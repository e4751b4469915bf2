"""One run of a scoring cell: set-up, the measured window, the check.

Set-up makes the mix's blocks of rows and its model text from the seed,
loads the text as a user does (``program.Scorer``), and sends every
client's block through the server once (the first compiles the one bucket).
In the window each client, a thread, submits its block whole, waits for the
answer and submits it again; at ``--seconds`` the clients stop resubmitting,
and the window closes when the blocks in flight are answered. Once it has
closed, the peak memory is read and the server is stopped and freed, the
plain reference walks a sample of each block's rows, drawn from the seed,
and every answer the window was given is compared with it on those rows.
"""
from __future__ import annotations

import dataclasses
import gc
import sys
import threading
import time

import numpy as np

from . import check, program, train

# the control (leaf values rounded to bfloat16) and the fault (the last
# tenth of the trees left out), each the reference put in the program's place
VARIANTS = ("ref-bf16", "ref-short")
SPANS = r"^(bench|serve)::"


@dataclasses.dataclass
class Block:
    """One submission of a client's block, times on the window's clock."""
    client: int
    t_submit: float
    t_answer: float
    rows: int
    answer: object = None       # the scores, or None where it failed
    error: str = ""


class Run(train.Run):
    """What a scoring run measured, as the per-layer readers get it."""

    def __init__(self, rows: int, features: int, peaks: dict):
        super().__init__(rows, features, peaks)
        self.blocks = []            # Block of every submission of the window
        self.trees = self.leaves = 0
        self.hops_per_row = None    # nodes a row visits in the whole forest

    def answered(self) -> list:
        return [b for b in self.blocks if b.answer is not None]


def _client(k: int, block, scorer, start, clock: dict, seconds: float,
            out: list) -> None:
    import jax
    start.wait()
    while time.perf_counter() - clock["t0"] < seconds:
        t_submit = time.perf_counter()
        answer, error = None, ""
        with jax.profiler.TraceAnnotation("bench::block"):
            try:
                answer = scorer.submit(block).result()
            except Exception as e:    # a block that raises or is shed has
                error = repr(e)       # failed; the client goes on
        out.append(Block(k, t_submit - clock["t0"],
                         time.perf_counter() - clock["t0"], len(block),
                         answer, error))


def _window(scorer, blocks: list, seconds: float) -> list:
    """The closed loop: one thread per client; the clock starts when all of
    them are at the barrier."""
    clock, out = {}, []
    start = threading.Barrier(
        len(blocks), action=lambda: clock.update(t0=time.perf_counter()))
    threads = [threading.Thread(target=_client, name="bench-client-%d" % k,
                                args=(k, b, scorer, start, clock, seconds,
                                      out))
               for k, b in enumerate(blocks)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    return sorted(out, key=lambda b: b.t_submit)


def _well_formed(answer, rows: int) -> bool:
    a = np.asarray(answer)
    return a.size == rows and a.shape[0] == rows and bool(
        np.all(np.isfinite(a)))


def run(cell: dict, seed: int, seconds: float, trace: bool, device,
        peaks: dict, t_process: float, variant=None) -> tuple:
    """``(Run, result, compared)``, as ``train.run`` gives them."""
    import jax
    import jax.numpy as jnp
    from ..trace import xplane

    cfg, mix = cell["config"], cell["traffic"]
    features = int(cfg["features"])
    clients, rows = int(mix["clients"]), int(mix["block_rows"])
    checked = min(int(mix["checked_rows_per_block"]), rows)
    reference = cell["spec"].reference(cell)

    this = Run(rows, features, peaks)
    compiles = train.CompileCounter()
    jax.monitoring.register_event_duration_secs_listener(compiles)
    print("compile cache: %s" % program.enable_compile_cache(), flush=True)
    if trace:
        program.enable_spans()

    with this.phase("generate"):
        X_all, _, _ = cell["spec"].make_table(mix["data"])(
            clients * rows, features, seed, mix["data"])
        blocks = [X_all[k * rows:(k + 1) * rows] for k in range(clients)]
    with this.phase("model text"):
        text = cell["spec"].generator(mix["model"]).make_model_text(
            features, int(cfg["rows"]), seed, mix["model"])
    with this.phase("load model"):
        scorer = program.Scorer(text, mix["server"])
    for k, block in enumerate(blocks):
        with this.phase("warm block %d" % k + (" + compile" if not k else "")):
            scorer.submit(block).result()
    this.end_to_end["setup_s"] = time.perf_counter() - t_process

    counts0 = program.trace_counts()
    log_dir = None
    if seconds > 0:
        if trace:
            log_dir = train.start_trace(cell["name"])
        compiles.listening = True
        this.blocks = _window(scorer, blocks, seconds)
        this.window_s = max(b.t_answer for b in this.blocks)
        compiles.listening = False
        if trace:
            jax.profiler.stop_trace()
            program.disable_spans()
        answered_rows = sum(b.rows for b in this.answered())
        if answered_rows:
            this.end_to_end["score_rows_per_s"] = (answered_rows
                                                   / this.window_s)
    retraced = {k: v - counts0.get(k, 0)
                for k, v in program.trace_counts().items()
                if v != counts0.get(k, 0)}
    stats = device.memory_stats() or {}
    memory_peak = int(stats.get("peak_bytes_in_use", 0))
    attempted = len(this.blocks)
    failed = attempted - len(this.answered())
    print("window: %d blocks of %d rows answered, %d failed%s, in %r s; "
          "lowered in window: %d; program's jit_trace counts that moved: "
          "%s; peak %d bytes" % (
              attempted - failed, rows, failed,
              " (%s)" % "; ".join(b.error for b in this.blocks if b.error)[
                  :400] if failed else "", this.window_s, compiles.count,
              retraced, memory_peak), flush=True)
    scorer.stop()
    scorer = None
    gc.collect()

    if log_dir is not None:
        with this.phase("read trace"):
            this.trace = xplane.load(xplane.find_xplane(log_dir))
            this.busy_s = xplane.busy_s(this.trace)

    with this.phase("reference"):
        draw = [np.sort(np.random.Generator(np.random.Philox(
            np.random.SeedSequence([int(seed), k]))).choice(
                rows, checked, replace=False)) for k in range(clients)]
        sample = [blocks[k][draw[k]] for k in range(clients)]
        forest = reference.Forest.from_model_text(text)
        ref, hops = [], 0
        for rows_k in sample:
            ref.append(forest.predict_raw(rows_k))
            hops += forest.hops
    this.trees, this.leaves = forest.trees, forest.leaves
    this.hops_per_row = hops / float(clients * checked)
    print("reference: %d trees of %d leaves, %d rows of each block, %.2f "
          "nodes visited per row and tree" % (
              forest.trees, forest.leaves, checked,
              this.hops_per_row / forest.trees), flush=True)

    well_formed = [b for b in this.answered() if _well_formed(b.answer, rows)]
    got = [(b.client, np.asarray(b.answer).reshape(-1)[draw[b.client]])
           for b in well_formed]
    if variant:
        readings = check.compare_scores(got, ref)
        print("readings of the program: %s, within their limits: %s" % (
            readings, check.judge(readings, {
                k: cell["limits"][k] for k in readings})[0]), flush=True)
    for v in (variant or "").split(","):
        if v.startswith("ref-"):
            with this.phase("reference as " + v):
                control = reference.Forest.from_model_text(
                    text,
                    leaf_dtype=jnp.bfloat16 if v == "ref-bf16" else None,
                    drop_last_trees=(forest.trees // 10
                                     if v == "ref-short" else 0))
                stood_in = [control.predict_raw(rows_k) for rows_k in sample]
            # in the place of every answer of the window, or once a block
            got = [(k, stood_in[k]) for k, _ in got] or list(
                enumerate(stood_in))
            print("readings of %s: %s" % (v, check.compare_scores(got, ref)),
                  flush=True)

    numbers = check.compare_scores(got, ref)
    numbers["answers_malformed"] = len(this.answered()) - len(well_formed)
    numbers["window_compiles"] = compiles.count + sum(retraced.values())
    correct, compared = check.judge(numbers, cell["limits"])
    dev = {"platform": device.platform, "kind": device.device_kind,
           "count": 1, "memory_peak_bytes": memory_peak}
    if trace and this.window_s:
        dev["busy_s"] = this.busy_s
        dev["window_s"] = this.window_s
    result = {"correct": bool(correct and failed == 0),
              "attempted": attempted, "failed": failed, "metrics": {},
              "device": dev}
    check.report(compared, sys.stderr)
    return this, result, compared
