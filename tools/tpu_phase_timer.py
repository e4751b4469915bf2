"""Per-phase wall-time breakdown of one boosting iteration.

Answers "where does the tree-build time go" on real hardware: gradient
computation, gh staging, root dispatch, whole-tree dispatch, record
read-back, score update — each fenced with block_until_ready so
async dispatch can't smear phases together. The phases are
recorded through the telemetry registry (lightgbm_tpu/obs) — the same
stage timer the trainer itself uses — so this tool is the registry's
hardware consumer, not a parallel hand-rolled timer. The reference's
equivalent is its per-tree timer dump (src/treelearner/
serial_tree_learner.cpp Global timer); here the phases map to the
mesh learner's actual dispatch structure (parallel/data_parallel.py
train()).

Usage:  python tools/tpu_phase_timer.py [rows] [n_trees]
Prints one JSON line per tree plus a summary (registry snapshot).

Fleet mode:  python tools/tpu_phase_timer.py --from-metrics DUMP|URL
Instead of running anything, read a metrics-gateway dump (a file, or a
gateway URL to scrape — see lightgbm_tpu/obs/gateway.py) and print the
per-rank phase table the fleet already reported: one JSON line per
rank with its ``stage_seconds_total``/``stage_calls_total`` breakdown,
plus a fleet summary (sources, push ages, run ids). This path parses
OpenMetrics with the stdlib-pure ``obs/openmetrics.py`` loaded by file
path and never imports jax.
"""
from __future__ import annotations

import json
import sys

sys.path.insert(0, __import__("os").path.join(
    __import__("os").path.dirname(__import__("os").path.abspath(__file__)),
    ".."))


def _from_metrics(src: str) -> None:
    """Per-rank stage table from a gateway metrics dump — must run
    BEFORE any jax import (the whole point of reading the dump is not
    needing the hardware this tool normally drives)."""
    import os
    sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
    import trace_report
    om = trace_report._openmetrics()
    text = trace_report.fetch_metrics_text(src)
    parsed = om.parse_openmetrics(text)
    pfx = om.kPrefix
    per_rank: dict = {}
    ages: dict = {}
    run_ids = set()
    for (name, labels), v in sorted(parsed.items()):
        ld = dict(labels)
        rank = str(ld.get("rank", "?"))
        if name == pfx + "stage_seconds_total":
            stage = per_rank.setdefault(rank, {}).setdefault(
                str(ld.get("stage", "?")), {"s": 0.0, "calls": 0})
            stage["s"] = round(stage["s"] + v, 4)
        elif name == pfx + "stage_calls_total":
            stage = per_rank.setdefault(rank, {}).setdefault(
                str(ld.get("stage", "?")), {"s": 0.0, "calls": 0})
            stage["calls"] = int(stage["calls"] + v)
        elif name == pfx + "gateway_push_age_seconds":
            ages["%s/%s" % (rank, ld.get("process", "?"))] = v
        elif name == pfx + "run_info" and ld.get("run_id"):
            run_ids.add(ld["run_id"])
    for rank in sorted(per_rank):
        print(json.dumps({"rank": rank, "phases": per_rank[rank]}),
              flush=True)
    print(json.dumps({"phase": "fleet", "source": src,
                      "ranks": len(per_rank),
                      "push_age_s": ages,
                      "run_ids": sorted(run_ids)}), flush=True)


def main() -> None:
    if len(sys.argv) > 1 and sys.argv[1] == "--from-metrics":
        if len(sys.argv) != 3:
            print("usage: tpu_phase_timer.py --from-metrics DUMP|URL",
                  file=sys.stderr)
            raise SystemExit(2)
        _from_metrics(sys.argv[2])
        return
    rows = int(sys.argv[1]) if len(sys.argv) > 1 else 1_000_000
    n_trees = int(sys.argv[2]) if len(sys.argv) > 2 else 3

    import jax
    import jax.numpy as jnp

    from bench import make_higgs_like
    from lightgbm_tpu.config import Config
    from lightgbm_tpu.io.dataset import BinnedDataset
    from lightgbm_tpu.boosting import create_boosting
    from lightgbm_tpu.obs import health as obs_health
    from lightgbm_tpu.obs.registry import registry
    from lightgbm_tpu.utils.compile_cache import enable_compile_cache

    enable_compile_cache()
    registry.enable()
    obs_health.record_backend(source="tpu_phase_timer")
    print(json.dumps({"phase": "devices",
                      "platform": jax.devices()[0].platform}), flush=True)

    X, y = make_higgs_like(rows)
    cfg = Config.from_params({
        "objective": "binary", "num_leaves": 255, "max_bin": 255,
        "learning_rate": 0.1, "verbosity": -1, "min_data_in_leaf": 100,
        "tree_learner": "data", "mesh_shape": "data=1",
    })
    with registry.scope("phase::binned"):
        ds = BinnedDataset.from_matrix(X, cfg, label=y)
    print(json.dumps(
        {"phase": "binned",
         "s": round(registry.timer.totals["phase::binned"], 2)}),
        flush=True)
    del X

    booster = create_boosting(cfg, ds)
    learner = booster.learner
    objective = booster.objective

    # one full warmup iteration compiles everything
    with registry.scope("phase::warmup_iter"):
        booster.train_one_iter()
        jax.block_until_ready(booster.train_score)
    print(json.dumps(
        {"phase": "warmup_iter",
         "s": round(registry.timer.totals["phase::warmup_iter"], 2)}),
        flush=True)

    def fenced(name, fn):
        """Run fn under a registry stage scope with a device fence so
        the async dispatch cost lands in ITS stage."""
        with registry.scope(name):
            out = fn()
            jax.block_until_ready(out)
        return out

    PHASES = ("phase::grad", "phase::stage_gh", "phase::root_fn",
              "phase::tree_fn", "phase::readback")
    for k in range(n_trees):
        before = {p: registry.timer.totals.get(p, 0.0) for p in PHASES}
        # same call shape as GBDT.train_one_iter (boosting/gbdt.py)
        grad, hess = fenced("phase::grad", lambda: objective.get_gradients(
            booster.train_score[:, 0]))
        gh = fenced("phase::stage_gh",
                    lambda: learner._make_gh(grad, hess, None))
        feature_mask = learner._sample_features()
        state, root_rec = fenced("phase::root_fn", lambda: learner._root_fn(
            learner.bins, gh, feature_mask, jnp.int32(k + 1),
            learner._qscale))
        state, recs = fenced("phase::tree_fn", lambda: learner._tree_fn(
            learner.bins, state, feature_mask, jnp.int32(k + 1),
            learner._qscale))
        with registry.scope("phase::readback"):
            jax.device_get(recs)

        rec = {p.split("::", 1)[1]:
               round(registry.timer.totals.get(p, 0.0) - before[p], 4)
               for p in PHASES}
        rec["tree"] = k
        print(json.dumps(rec), flush=True)

    summary = {p.split("::", 1)[1]:
               round(registry.timer.totals.get(p, 0.0) / n_trees, 4)
               for p in PHASES}
    summary["phase"] = "mean_per_tree"
    summary["rows"] = rows
    summary["registry"] = registry.snapshot()
    print(json.dumps(summary), flush=True)


if __name__ == "__main__":
    main()
