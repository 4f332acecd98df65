#!/usr/bin/env python3
"""Device time per phase of the FiGaRo pipeline, and the serving spans.

The program runs each phase of its traced pipeline under a
``jax.named_scope`` (``figaro.counts``, ``figaro.heads_tails``, ...), which
reaches every device operation's name stack, the ``tf_op`` stat of the
operation's event metadata in the profiler trace. `jax.profiler.ProfileData`
exposes only the events' own stats, so this module reads the ``.xplane.pb``
itself, with message classes built from a descriptor written here (no
TensorFlow import in a process that holds the chip).

Each instant of device time inside the ``bench.window`` annotation goes to
the innermost operation running then, so a ``while`` and the body it
contains count once, and the operation's group is the first ``figaro.*``
component of its name stack. Time in a pipeline program with no such scope
is ``figaro.unscoped``; time in any other program (the server's slicing of
results) is ``outside_pipeline``. The groups sum to the busy time. Where no
operation of a pipeline program carries a ``figaro.`` scope, which is what a
program loaded from a compilation cache written before the scopes existed
looks like, ``scopes_seen`` is false and every phase reads None.

The serving layer's ``figaro.serve.*`` annotations (`AsyncFigaroServer`)
name the device's idle gaps, ahead of the benchmark's own ``bench.*`` ones.

    python3 bench/phases.py [trace.xplane.pb[.gz]]

prints the reduction of a trace as JSON, by default of the newest one under
``.bench_trace/`` (what ``bench/run.py --trace 1`` leaves there).
"""

from __future__ import annotations

import bisect
import collections
import functools
import gzip
import json
import re
import sys
from pathlib import Path

import numpy as np

if __package__ in (None, ""):  # run as a script: python3 bench/phases.py
    sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

from bench import tracing  # noqa: E402

PHASES = ("figaro.counts", "figaro.heads_tails", "figaro.join_children",
          "figaro.project", "figaro.assemble", "figaro.postprocess",
          "figaro.downstream")
NODE_PASSES = ("figaro.heads_tails", "figaro.join_children", "figaro.project")
UNSCOPED = "figaro.unscoped"
OUTSIDE = "outside_pipeline"
SERVE = "figaro.serve."
# Waiting states: they name an idle gap only where nothing else does.
SERVE_WAITS = ("figaro.serve.depth_wait", "figaro.serve.ready")
PIPELINE = re.compile(r"jit__\w+_impl\b")  # the engine's programs
SCOPE = re.compile(r"(?:^|[/(])(figaro\.[a-z_]+)")
TRACE_DIR = Path(__file__).resolve().parent.parent / ".bench_trace"

_INT64, _UINT64, _DOUBLE, _STRING, _MESSAGE = 3, 4, 1, 9, 11
_OPTIONAL, _REPEATED = 1, 3
# The fields of tensorflow/tsl/profiler/protobuf/xplane.proto this module
# reads: (name, number, type, label, message type).
_XPLANE = {
    "XSpace": [("planes", 1, _MESSAGE, _REPEATED, "XPlane")],
    "XPlane": [("name", 2, _STRING, _OPTIONAL, None),
               ("lines", 3, _MESSAGE, _REPEATED, "XLine"),
               ("event_metadata", 4, _MESSAGE, _REPEATED,
                "XPlane.EventMetadataEntry"),
               ("stat_metadata", 5, _MESSAGE, _REPEATED,
                "XPlane.StatMetadataEntry")],
    "XLine": [("name", 2, _STRING, _OPTIONAL, None),
              ("timestamp_ns", 3, _INT64, _OPTIONAL, None),
              ("events", 4, _MESSAGE, _REPEATED, "XEvent")],
    "XEvent": [("metadata_id", 1, _INT64, _OPTIONAL, None),
               ("offset_ps", 2, _INT64, _OPTIONAL, None),
               ("duration_ps", 3, _INT64, _OPTIONAL, None),
               ("stats", 4, _MESSAGE, _REPEATED, "XStat")],
    "XStat": [("metadata_id", 1, _INT64, _OPTIONAL, None),
              ("double_value", 2, _DOUBLE, _OPTIONAL, None),
              ("uint64_value", 3, _UINT64, _OPTIONAL, None),
              ("int64_value", 4, _INT64, _OPTIONAL, None),
              ("str_value", 5, _STRING, _OPTIONAL, None)],
    "XEventMetadata": [("id", 1, _INT64, _OPTIONAL, None),
                       ("name", 2, _STRING, _OPTIONAL, None),
                       ("stats", 5, _MESSAGE, _REPEATED, "XStat")],
    "XStatMetadata": [("id", 1, _INT64, _OPTIONAL, None),
                      ("name", 2, _STRING, _OPTIONAL, None)],
}
_MAPS = {"EventMetadataEntry": "XEventMetadata",
         "StatMetadataEntry": "XStatMetadata"}


@functools.cache
def _xspace_class():
    from google.protobuf import (descriptor_pb2, descriptor_pool,
                                 message_factory)

    package = "bench.xplane"
    fd = descriptor_pb2.FileDescriptorProto(name="bench_xplane.proto",
                                            package=package, syntax="proto3")

    def add(msg, name, number, kind, label, type_name):
        f = msg.field.add(name=name, number=number, type=kind, label=label)
        if type_name:
            f.type_name = f".{package}.{type_name}"

    for name, fields in _XPLANE.items():
        msg = fd.message_type.add(name=name)
        for field in fields:
            add(msg, *field)
        if name == "XPlane":
            for entry, value in _MAPS.items():
                sub = msg.nested_type.add(name=entry)
                sub.options.map_entry = True
                add(sub, "key", 1, _INT64, _OPTIONAL, None)
                add(sub, "value", 2, _MESSAGE, _OPTIONAL, value)
        if name == "XStat":
            msg.oneof_decl.add(name="value")
            for f in msg.field[1:]:
                f.oneof_index = 0
    pool = descriptor_pool.DescriptorPool()
    pool.Add(fd)
    return message_factory.GetMessageClass(
        pool.FindMessageTypeByName(f"{package}.XSpace"))


def load(source):
    """An XSpace from a path (``.xplane.pb``, gzipped or not) or bytes."""
    if not isinstance(source, bytes):
        with open(source, "rb") as f:
            source = f.read()
    if source[:2] == b"\x1f\x8b":
        source = gzip.decompress(source)
    space = _xspace_class()()
    space.ParseFromString(source)
    return space


def latest(trace_dir: Path = TRACE_DIR) -> Path:
    found = sorted(Path(trace_dir).glob("**/*.xplane.pb"),
                   key=lambda p: p.stat().st_mtime)
    if not found:
        raise FileNotFoundError(f"no trace under {trace_dir}")
    return found[-1]


def _stat(stat):
    kind = stat.WhichOneof("value")
    return getattr(stat, kind) if kind else None


def _events(plane, line):
    """``(name, start_ns, end_ns, {stat: value})`` of a line's events."""
    stat_names = {k: v.name for k, v in plane.stat_metadata.items()}
    meta = plane.event_metadata
    base = line.timestamp_ns * 1000
    return [(meta[e.metadata_id].name,
             (base + e.offset_ps) * 1e-3,
             (base + e.offset_ps + e.duration_ps) * 1e-3,
             {stat_names.get(s.metadata_id): _stat(s) for s in e.stats})
            for e in line.events]


def _name_stacks(plane) -> dict:
    """Event metadata id → the ``tf_op`` stat (the JAX name stack)."""
    ids = [k for k, v in plane.stat_metadata.items() if v.name == "tf_op"]
    if not ids:
        return {}
    tf_op = ids[0]
    return {k: s.str_value for k, m in plane.event_metadata.items()
            for s in m.stats if s.metadata_id == tf_op}


def _group(name_stack: str | None, in_pipeline: bool) -> str:
    if not in_pipeline:
        return OUTSIDE
    found = SCOPE.search(name_stack or "")
    return found.group(1) if found else UNSCOPED


def _innermost(ops, lo: float, hi: float) -> dict:
    """Seconds per group inside ``[lo, hi]``, each instant given to the
    latest-started operation still running. ``ops`` are ``(start, end,
    group)`` sorted by start, longest first among equal starts."""
    time: dict[str, float] = collections.defaultdict(float)

    def give(group, a, b):
        d = min(b, hi) - max(a, lo)
        if d > 0:
            time[group] += d * 1e-9

    t = lo
    stack: list[tuple[float, str]] = []  # (end, group), innermost last
    for s, e, group in ops:
        while stack and stack[-1][0] <= s:
            end, g = stack.pop()
            give(g, t, end)
            t = max(t, end)
        if stack:
            give(stack[-1][1], t, s)
        t = max(t, s)
        stack.append((e, group))
    while stack:
        end, g = stack.pop()
        give(g, t, end)
        t = max(t, end)
    return time


def reduce(space) -> dict:
    """Device time by phase group, the serving spans and the idle gaps of
    the ``bench.window`` of ``space`` (an XSpace, see `load`).

    Returns ``window`` (host-clock ns), ``window_s``, ``busy_s`` and
    ``groups`` (seconds, averaged over the devices that ran anything),
    ``scopes_seen``, ``serve`` (the ``figaro.serve.*`` spans as ``(name,
    start_ns, end_ns, batch, requests)``) and ``idle_gaps`` (``[name,
    seconds]``, longest first, at most ten)."""
    host, serve, devices = [], [], []
    for plane in space.planes:
        if tracing.DEVICE_PLANE.fullmatch(plane.name):
            devices.append(plane)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for name, s, e, stats in _events(plane, line):
                    if name.startswith("bench."):
                        host.append((name, s, e))
                    elif name.startswith(SERVE):
                        serve.append((name, s, e, stats.get("batch"),
                                      stats.get("requests")))
    windows = [e for e in host if e[0] == tracing.WINDOW]
    if not windows:
        raise ValueError(f"no {tracing.WINDOW!r} annotation in the trace")
    lo, hi = windows[0][1], windows[0][2]
    marks = [e for e in host if e[0] != tracing.WINDOW]
    per_device, busy, gaps, scoped = [], [], [], False
    for plane in devices:
        lines = {line.name: line for line in plane.lines}
        if tracing.OPS_LINE not in lines:
            continue
        stacks = _name_stacks(plane)
        modules = sorted(
            (s, e, bool(PIPELINE.search(name))) for name, s, e, _ in
            _events(plane, lines[tracing.MODULES_LINE])
        ) if tracing.MODULES_LINE in lines else []
        starts = [m[0] for m in modules]
        ops = []
        base = lines[tracing.OPS_LINE].timestamp_ns * 1000
        for ev in lines[tracing.OPS_LINE].events:
            s = (base + ev.offset_ps) * 1e-3
            e = s + ev.duration_ps * 1e-3
            k = bisect.bisect_right(starts, s) - 1
            in_pipeline = k >= 0 and s < modules[k][1] and modules[k][2]
            group = _group(stacks.get(ev.metadata_id), in_pipeline)
            scoped |= group not in (UNSCOPED, OUTSIDE)
            ops.append((s, e, group))
        if not ops:
            continue
        ops.sort(key=lambda o: (o[0], -o[1]))
        groups = _innermost(ops, lo, hi)
        per_device.append(groups)
        busy.append(sum(groups.values()))
        iv = tracing._clip(np.array([(s, e) for s, e, _ in ops]), lo, hi)
        merged = tracing._union(iv)
        edges = np.concatenate([[lo], merged.ravel(), [hi]]).reshape(-1, 2)
        gaps += [(float(a), float(b)) for a, b in edges if b > a]
    n = len(per_device)
    names = sorted({g for d in per_device for g in d})
    top = sorted(gaps, key=lambda g: g[0] - g[1])[:tracing.TOP]
    return {
        "window": (lo, hi),
        "window_s": (hi - lo) * 1e-9,
        "devices": n,
        "busy_s": sum(busy) / n if n else 0.0,
        "groups": {g: sum(d.get(g, 0.0) for d in per_device) / n
                   for g in names},
        "scopes_seen": scoped,
        "serve": serve,
        "idle_gaps": [[gap_name(a, b, serve, marks), (b - a) * 1e-9]
                      for a, b in top],
    }


def gap_name(lo: float, hi: float, serve, marks) -> str:
    """The ``figaro.serve.*`` span that overlaps ``[lo, hi]`` the most, the
    waiting ones (``depth_wait``, ``ready``) only where no other does; else
    the benchmark's own annotation (`tracing.gap_name`)."""
    overlap: dict[str, float] = collections.defaultdict(float)
    for name, s, e, *_ in serve:
        d = min(e, hi) - max(s, lo)
        if d > 0:
            overlap[name] += d
    busy = {k: v for k, v in overlap.items() if k not in SERVE_WAITS}
    if busy or overlap:
        return max((busy or overlap).items(), key=lambda kv: kv[1])[0]
    return tracing.gap_name(lo, hi, marks)


def phases(trace: dict) -> dict | None:
    """Seconds per phase group, or None where the trace shows no scope."""
    if not trace["devices"] or not trace["scopes_seen"]:
        return None
    return trace["groups"]


def resolved_in_window(trace: dict) -> int:
    """Requests whose batch the server resolved inside the window."""
    lo, hi = trace["window"]
    return sum(r or 0 for name, s, e, _, r in trace["serve"]
               if name == SERVE + "resolve" and lo <= e <= hi)


def ms_per_request(groups: dict | None, names, requests: int) -> float | None:
    """Device milliseconds of the ``names`` groups per request; None where
    the phases are unknown or no request completed."""
    if groups is None or not requests:
        return None
    return 1e3 * sum(groups.get(n, 0.0) for n in names) / requests


@functools.lru_cache(maxsize=1)
def _reduced(path: Path, mtime: float) -> dict:
    return reduce(load(path))


def read_ms_per_request(run, names, trace_dir: Path = TRACE_DIR):
    """A ``<phase>_ms_per_request`` metric's reader: device milliseconds of
    the ``names`` groups in the newest trace under ``trace_dir`` per request
    completed in the traced span of ``run`` (the harness's run record, as
    ``device_ms_per_request`` counts them); None in an untraced run."""
    if not run["trace"]:
        return None
    path = latest(trace_dir)
    trace = _reduced(path, path.stat().st_mtime)
    done = tracing.completed_in(run["result"]["requests"], run["trace_span"])
    return ms_per_request(phases(trace), names, done)


def serving(before: dict, after: dict) -> dict:
    """The serving metrics over a span, from two `AsyncFigaroServer.stats`
    snapshots: requests per dispatch, queue wait in ms per request and the
    dispatch thread's own ms per batch. None where nothing was dispatched."""
    d = {k: after[k] - before[k] for k in
         ("dispatches", "dispatched_requests", "queue_wait_s",
          "dispatch_host_s")}
    if not d["dispatches"]:
        return dict.fromkeys(("requests_per_dispatch", "queue_wait_ms",
                              "dispatch_host_ms_per_batch"))
    batches, requests = d["dispatches"], d["dispatched_requests"]
    return {"requests_per_dispatch": requests / batches,
            "queue_wait_ms": 1e3 * d["queue_wait_s"] / requests,
            "dispatch_host_ms_per_batch": 1e3 * d["dispatch_host_s"]
            / batches}


def summary(trace: dict) -> dict:
    """What the command line prints: the phase split per request resolved
    in the window, and the gaps."""
    done = resolved_in_window(trace)
    groups = phases(trace)
    return {
        "window_s": trace["window_s"], "busy_s": trace["busy_s"],
        "scopes_seen": trace["scopes_seen"], "resolved_requests": done,
        "device_phases_s": trace["groups"],
        "device_phases_ms_per_request": None if groups is None else {
            g: ms_per_request(groups, (g,), done) for g in groups},
        "idle_gaps": trace["idle_gaps"],
    }


def main(argv=None) -> int:
    args = sys.argv[1:] if argv is None else argv
    path = Path(args[0]) if args else latest()
    print(json.dumps(summary(reduce(load(path)))))
    return 0


if __name__ == "__main__":
    sys.exit(main())
