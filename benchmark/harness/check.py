"""The comparison that decides ``correct``.

Each number is a gap between what the timed object produced in its first
steps and what the plain reference produces from the same table: the
training loss after each checked step, the norm of the scores' change after
the first step and after the last (the gap between the two norms, not the
norm of the difference), and the loss on held-out rows of the model cut to
the checked steps. A scoring cell compares the raw scores of the answers
its window was given with the reference's, row by row
(``compare_scores``). Every number has a limit of its own in
``limits/<cell>.json``.
"""
from __future__ import annotations

import sys

import numpy as np


def _rel(a: float, b: float) -> float:
    return abs(a - b) / abs(b)


def _norm(v: np.ndarray) -> float:
    return float(np.sqrt(np.sum(np.square(v, dtype=np.float64))))


def compare(loss_fn, y, init, got_scores, ref_scores, y_hold, got_hold,
            ref_hold) -> dict:
    """The compared numbers by name; ``*_scores`` are lists of the training
    rows' raw scores after step 1, 2, ... of the program and the reference."""
    out = {}
    for k, (g, r) in enumerate(zip(got_scores, ref_scores), start=1):
        out["loss%d" % k] = _rel(loss_fn(g, y), loss_fn(r, y))
    out["step1_norm"] = _rel(_norm(got_scores[0] - np.float32(init)),
                             _norm(ref_scores[0] - np.float32(init)))
    last = len(ref_scores) - 1
    out["change%d_norm" % (last + 1)] = _rel(
        _norm(got_scores[last] - np.float32(init)),
        _norm(ref_scores[last] - np.float32(init)))
    out["holdout_loss%d" % (last + 1)] = _rel(loss_fn(got_hold, y_hold),
                                             loss_fn(ref_hold, y_hold))
    return out


def compare_scores(got: list, ref: list) -> dict:
    """A scoring cell's numbers. ``got`` holds ``(block, scores)`` of every
    answer compared, on that block's checked rows, and ``ref[block]`` the
    reference's raw scores of the same rows: the largest and the
    root-mean-square gap between them over all the answers, relative to the
    root-mean-square of the reference's scores."""
    if not got:
        return {}
    scale = _norm(np.concatenate(ref)) / np.sqrt(sum(len(r) for r in ref))
    gaps = np.concatenate([np.asarray(g, dtype=np.float64) - ref[b]
                           for b, g in got])
    return {"score_max_gap": float(np.max(np.abs(gaps)) / scale),
            "score_rms_gap": float(_norm(gaps) / np.sqrt(len(gaps)) / scale)}


def judge(numbers: dict, limits: dict) -> tuple:
    """``(correct, {name: {"value", "limit"}})``: every limit has to have
    its number, and every number has to lie within its limit."""
    compared = {}
    correct = True
    for name, limit in limits.items():
        value = numbers.get(name)
        compared[name] = {"value": value, "limit": limit}
        if value is None or not np.isfinite(value) or value > limit:
            correct = False
    return correct, compared


def report(compared: dict, stream=sys.stderr) -> None:
    """Each number compared beside its limit, as a run's last lines there."""
    for name, c in compared.items():
        ok = c["value"] is not None and c["value"] <= c["limit"]
        print("compared %-16s %-24r limit %-10r %s" % (
            name, c["value"], c["limit"], "ok" if ok else "OVER"),
            file=stream)
    stream.flush()
