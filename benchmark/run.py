"""One cell of the benchmark, once.

    python3 benchmark/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Builds the cell's inputs from ``--seed``, warms exactly the cell's own
shapes (set-up), measures for ``--seconds`` seconds, checks what the timed
object produced against the plain reference, and prints phase lines and
then, as the last line of its standard output, one JSON object. With
``--trace 0`` its metrics are the cell's end-to-end metrics, with
``--trace 1`` its per-layer metrics, read from a profiler trace of the
window, which in a traced run of a training cell is the first
``harness/train.py`` ``TRACED_ITERATIONS`` (6) iterations after set-up, or
fewer where ``--seconds`` runs out first: the trace then holds the same
trees, and takes as long to stop and read, whatever the program's speed.
Without a TPU that ``trace/peaks.json`` knows it exits 3 and prints
no result line.

A cell is run by the runner of its traffic mix's ``kind``,
``harness/<kind>.py``: a training cell (``bosch-train``, kind ``train``)
drives ``Booster.update`` and reports ``train_iter_s``; a scoring cell
(kind ``score``; ``bosch-score-bulk``, whose entries wait in
``queued/bosch-score-bulk.json`` until ``BENCHMARK.json`` lists it) drives
``PredictServer.submit`` from the mix's clients and reports
``score_rows_per_s``. Both take the same arguments. ``--variant`` puts a control or a fault in the program's place
(the runner's ``VARIANTS``: ``ref-bf16`` in both kinds, ``ref-half`` and
``ref-short`` in one each), which ``correct`` has to refuse. The
benchmark's own tests run on the CPU: ``python -m pytest benchmark/tests -q``.
"""
from __future__ import annotations

import time

T_PROCESS = time.perf_counter()

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main(argv=None) -> int:
    from benchmark.harness import device, spec
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--variant", default=None,
                    help="a control or a fault instead of the program as "
                         "configured, one of the VARIANTS of the cell's "
                         "runner; several ref-* with commas (never asked "
                         "for by the driver)")
    args = ap.parse_args(argv)
    try:
        bench = spec.Spec()
        cell = bench.cell(args.workload)
        runner = bench.runner(cell["traffic"].get("kind"))
        if args.variant is not None and not set(
                args.variant.split(",")) <= set(runner.VARIANTS):
            raise spec.SpecError("variant %r, the runner has %s" % (
                args.variant, ", ".join(runner.VARIANTS)))
        import jax
        devices, peaks = device.require_chips(jax.devices(), cell["chips"])
    except (spec.SpecError, device.NoChip) as e:
        print("benchmark: %s" % e, file=sys.stderr)
        return 3
    print("device: %s x%d; workload %s seed %d" % (
        devices[0].device_kind, len(devices), args.workload, args.seed),
        flush=True)
    this, result, compared = runner.run(
        cell, args.seed, args.seconds, bool(args.trace), devices[0], peaks,
        T_PROCESS, args.variant)
    if args.trace:
        values = {}
        for name in bench.per_layer(args.workload):
            value = bench.reader(name)(this)
            if value is not None:
                values[name] = value
        if this.trace is not None:
            from benchmark.trace import xplane
            result["breakdown"] = {
                "device_ops": xplane.top(xplane.self_times(
                    this.trace.ops(), xplane.short_name)),
                "idle_gaps": xplane.idle_gaps(this.trace, runner.SPANS),
            }
    else:
        values = {n: this.end_to_end[n]
                  for n in bench.end_to_end(args.workload)
                  if n in this.end_to_end}
    result["metrics"] = {n: {"value": v, "unit": bench.unit(n)}
                         for n, v in values.items()}
    result["compared"] = compared
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
