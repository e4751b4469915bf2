"""Device time of the grower's stages, from the scope names in a trace.

The program wraps each stage of its device programs in a
``jax.named_scope`` named ``obs_<stage>``. XLA keeps the name stack of
every operation it compiles in the operation's metadata, and the profiler
writes it into the ``.xplane.pb`` as the ``tf_op`` stat of the event's
*metadata* (``jit(_tree_impl)/while/body/obs_compact/cond/...``), through
``while`` and ``conditional`` bodies. ``jax.profiler.ProfileData``, which
``xplane.py`` reads with, shows an event's own stats and nothing of its
metadata, so this module reads the file's wire format itself (protocol
buffers: ``XSpace`` of tsl's ``xplane.proto``; the few fields below) and
needs nothing the run does not already have.

An operation's stage is the innermost segment of its ``tf_op`` that names
one (``STAGES``); ``obs_bucket_<S>`` tags the branch of the compaction
ladder and is summed apart; any other segment, ``obs_psum_*`` among them,
leaves the operation with the stage around it. Operations XLA adds itself
(the copies of a loop's carried buffers) carry no ``tf_op``: they, and
operations under no stage, are ``unscoped``, reported with their largest
members and never dropped. Times are self times (``xplane.self_times``), so
a loop and the operations of its body are not counted twice.
"""
from __future__ import annotations

import dataclasses
import re

import numpy as np

from . import xplane

STAGES = ("obs_pick_leaf", "obs_partition", "obs_compact", "obs_hist_pallas",
          "obs_hist_einsum", "obs_hist_scatter", "obs_hist_subtract",
          "obs_hist_store", "obs_split_scan")
BUCKET = re.compile(r"^obs_bucket_(\d+)$")
UNSCOPED = "unscoped"
# the part of a program's interval in which none of its operations ran
BETWEEN_OPS = "(between operations)"


# --- the wire format -----------------------------------------------------

def _varint(buf, i: int) -> tuple:
    value = shift = 0
    while True:
        byte = buf[i]
        i += 1
        value |= (byte & 0x7F) << shift
        if byte < 0x80:
            return value, i
        shift += 7


def _fields(buf):
    """``(field number, value)`` of one message: an int for a varint, a
    ``memoryview`` for a length-delimited field, None for a fixed one."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        wire = key & 7
        if wire == 0:
            value, i = _varint(buf, i)
        elif wire == 2:
            size, i = _varint(buf, i)
            value = buf[i:i + size]
            i += size
        elif wire == 1:
            value, i = None, i + 8
        elif wire == 5:
            value, i = None, i + 4
        else:
            raise ValueError("wire type %d in an .xplane.pb" % wire)
        yield key >> 3, value


def _signed(value: int) -> int:
    return value - (1 << 64) if value >= 1 << 63 else value


def _text(view) -> str:
    return bytes(view).decode("utf-8", "replace")


def _map_entry(buf) -> tuple:
    key = value = None
    for no, v in _fields(buf):
        if no == 1:
            key = v
        elif no == 2:
            value = v
    return key, value


def _stat(buf) -> tuple:
    """``(stat metadata id, value)`` of an XStat; a string value as text,
    a reference as ``("ref", id)``."""
    key = value = None
    for no, v in _fields(buf):
        if no == 1:
            key = v
        elif no == 5:
            value = _text(v)
        elif no == 7:
            value = ("ref", v)
        elif v is not None and not isinstance(v, memoryview):
            value = v
    return key, value


def _events(events: list) -> tuple:
    """``(metadata ids, offsets in ps, durations in ps)`` of a line's
    XEvents. One is written in the order of its field numbers, so its
    stats (field 4), which are most of its bytes, are not walked."""
    ids, offsets, durations = [], [], []
    for event in events:
        meta = offset = duration = 0
        for field, v in _fields(event):
            if field == 1:
                meta = v
            elif field == 2:
                offset = v
            elif field == 3:
                duration = v
            else:
                break
        ids.append(meta)
        offsets.append(_signed(offset))
        durations.append(_signed(duration))
    return ids, offsets, durations


def _line(buf, names: dict) -> tuple:
    """``(line name, metadata ids, xplane.Line)``, on ``ProfileData``'s
    clock: the line's timestamp plus the event's offset, nanoseconds."""
    name, timestamp_ns, events = "", 0, []
    for no, v in _fields(buf):
        if no == 2:
            name = _text(v)
        elif no == 3:
            timestamp_ns = _signed(v)
        elif no == 4:
            events.append(v)
    ids, offsets, durations = _events(events)
    start = timestamp_ns + np.asarray(offsets, np.float64) / 1e3
    dur = np.asarray(durations, np.float64) / 1e3
    return name, ids, xplane.Line([names.get(i, "") for i in ids],
                                  start.astype(np.int64),
                                  dur.astype(np.int64))


@dataclasses.dataclass
class Ops:
    """A device's ``XLA Ops`` events with the ``tf_op`` of each one's
    metadata (``""`` where it has none), and its ``XLA Modules`` line."""
    line: xplane.Line
    tf_op: list
    modules: xplane.Line


def load_ops(path: str, ordinal: int = 0) -> Ops:
    with open(path, "rb") as f:
        space = memoryview(f.read())
    for no, plane in _fields(space):
        if no != 1:
            continue
        name = next((_text(v) for n, v in _fields(plane) if n == 2), "")
        m = xplane.DEVICE_PLANE.match(name)
        if m and int(m.group(2)) == ordinal:
            return _device_ops(plane)
    return Ops(xplane.EMPTY, [], xplane.EMPTY)


def _device_ops(plane) -> Ops:
    stat_names, event_names, event_stats, lines = {}, {}, {}, []
    for no, v in _fields(plane):
        if no == 3:
            lines.append(v)
        elif no == 5:
            key, meta = _map_entry(v)
            stat_names[key] = next(
                (_text(x) for n, x in _fields(meta) if n == 2), "")
        elif no == 4:
            key, meta = _map_entry(v)
            stats = []
            for n, x in _fields(meta):
                if n == 2:
                    event_names[key] = _text(x)
                elif n == 5:
                    stats.append(_stat(x))
            event_stats[key] = stats
    tf_op_ids = {k for k, name in stat_names.items() if name == "tf_op"}
    tf_ops = {}
    for key, stats in event_stats.items():
        for stat_id, value in stats:
            if stat_id in tf_op_ids:
                if isinstance(value, tuple):
                    value = stat_names.get(value[1], "")
                tf_ops[key] = value if isinstance(value, str) else ""
    found = {}
    for raw in lines:
        name, ids, line = _line(raw, event_names)
        found[name] = (ids, line)
    ids, ops = found.get(xplane.OPS_LINE, ([], xplane.EMPTY))
    _, modules = found.get(xplane.MODULES_LINE, ([], xplane.EMPTY))
    return Ops(ops, [tf_ops.get(i, "") for i in ids], modules)


# --- stages --------------------------------------------------------------

def stage_of(tf_op: str) -> tuple:
    """``(stage, bucket)``: the innermost stage segment of a name stack
    (None where it has none) and the size of its ``obs_bucket_<S>`` tag
    (None where it has none)."""
    stage = bucket = None
    for segment in tf_op.rstrip(":").split("/"):
        if segment in STAGES:
            stage = segment
        else:
            m = BUCKET.match(segment)
            if m:
                bucket = int(m.group(1))
    return stage, bucket


@dataclasses.dataclass
class StageTimes:
    stages: dict        # stage or UNSCOPED -> seconds (self time)
    buckets: dict       # bucket size -> {stage: seconds}
    unscoped_ops: list  # [[short name, seconds]], the ten largest
    total_s: float      # the programs' own time: the stages add up to it


def stage_times(ops: Ops, programs: str = None) -> StageTimes:
    """Self time per stage of the operations that ran inside the programs
    whose ``XLA Modules`` name matches ``programs`` (all operations where
    None). With ``programs``, what of the programs' intervals no operation
    covers is ``unscoped`` too, so the stages add up to the programs'
    time."""
    line, tf_op = ops.line, ops.tf_op
    between = 0.0
    if programs is not None:
        spans = xplane._merged(ops.modules.matching(programs))
        starts = np.asarray([a for a, _ in spans], np.int64)
        ends = np.asarray([b for _, b in spans], np.int64)
        at = np.searchsorted(starts, line.start, side="right") - 1
        inside = (at >= 0) & (line.start < ends[np.maximum(at, 0)]) \
            if len(spans) else np.zeros(len(line), bool)
        keep = np.flatnonzero(inside)
        line = xplane.Line([line.names[i] for i in keep], line.start[keep],
                           line.dur[keep])
        tf_op = [tf_op[i] for i in keep]
        between = max(float((ends - starts).sum()) * 1e-9
                      - xplane.union_s(line), 0.0)
    keys = []
    for name, path in zip(line.names, tf_op):
        stage, bucket = stage_of(path)
        keys.append((stage, bucket,
                     xplane.short_name(name) if stage is None else None))
    own = xplane.self_times(xplane.Line(keys, line.start, line.dur))
    stages = {UNSCOPED: between}
    buckets, unscoped = {}, {BETWEEN_OPS: between} if between else {}
    for (stage, bucket, short), seconds in own.items():
        name = stage or UNSCOPED
        stages[name] = stages.get(name, 0.0) + seconds
        if bucket is not None:
            per = buckets.setdefault(bucket, {})
            per[name] = per.get(name, 0.0) + seconds
        if stage is None:
            unscoped[short] = unscoped.get(short, 0.0) + seconds
    return StageTimes(stages, buckets, xplane.top(unscoped),
                      sum(stages.values()))
