"""Reduction of a profiler trace to the numbers the per-layer metrics read.

A trace is the ``.xplane.pb`` that ``jax.profiler`` writes. Its device
planes (``/device:TPU:<n>``) carry one event per executed operation on the
``XLA Ops`` line and one per executed program on the ``XLA Modules`` line;
the host plane carries the benchmark's own annotations (``bench.*``) on the
threads that made them. Device and host events share one clock.

The window is the span of the ``bench.window`` annotation, which the
harness opens around the traced part of the run.
"""

from __future__ import annotations

import collections
import re

import numpy as np

DEVICE_PLANE = re.compile(r"/device:TPU:\d+")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
WINDOW = "bench.window"
WAIT = "bench.wait"
TOP = 10


def _union(intervals: np.ndarray) -> np.ndarray:
    """Merge ``[start, end]`` rows into disjoint, sorted intervals."""
    if not len(intervals):
        return intervals.reshape(0, 2)
    iv = intervals[np.argsort(intervals[:, 0], kind="stable")]
    ends = np.maximum.accumulate(iv[:, 1])
    new = np.concatenate([[True], iv[1:, 0] > ends[:-1]])
    starts = iv[new, 0]
    last = np.concatenate([np.flatnonzero(new)[1:] - 1, [len(iv) - 1]])
    return np.stack([starts, ends[last]], axis=1)


def _clip(intervals: np.ndarray, lo: float, hi: float) -> np.ndarray:
    iv = np.clip(intervals, lo, hi)
    return iv[iv[:, 1] > iv[:, 0]]


def _events(line):
    return [(e.name, float(e.start_ns), float(e.start_ns + e.duration_ns))
            for e in line.events]


def op_name(name: str) -> str:
    """``%fusion.42 = f32[4,2097152]{1,0:T(4,128)} fusion(...)`` →
    ``fusion.42 f32[4,2097152]``: the operation and its result type."""
    head, eq, rest = name.partition(" = ")
    if not eq:
        return name[:120]
    kind = rest.split(" ", 1)[0].split("{", 1)[0]
    return f"{head.lstrip('%')} {'tuple' if kind.startswith('(') else kind}"


def reduce(profile) -> dict:
    """Busy time, program executions, top operations and idle gaps.

    ``profile`` is a `jax.profiler.ProfileData`. Returns ``busy_s`` (the
    union of operation intervals inside the window, averaged over the
    devices that ran anything), ``window_s``, ``modules`` (executions per
    program name), ``device_ops`` and ``idle_gaps`` (``[name, seconds]``,
    longest first, at most ten each)."""
    host, devices = [], []
    for plane in profile.planes:
        if DEVICE_PLANE.fullmatch(plane.name):
            lines = {line.name: _events(line) for line in plane.lines}
            devices.append(lines)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                host += [e for e in _events(line) if e[0].startswith("bench.")]
    windows = [e for e in host if e[0] == WINDOW]
    if not windows:
        raise ValueError(f"no {WINDOW!r} annotation in the trace")
    lo, hi = windows[0][1], windows[0][2]
    marks = [e for e in host if e[0] != WINDOW]
    busy, modules, op_time, gaps = [], collections.Counter(), {}, []
    for lines in devices:
        ops = lines.get(OPS_LINE, [])
        if not ops:
            continue
        iv = _clip(np.array([(s, e) for _, s, e in ops]), lo, hi)
        merged = _union(iv)
        busy.append(float((merged[:, 1] - merged[:, 0]).sum()))
        for name, s, e in ops:
            d = min(e, hi) - max(s, lo)
            if d > 0:
                key = op_name(name)
                op_time[key] = op_time.get(key, 0.0) + d
        for name, s, e in lines.get(MODULES_LINE, []):
            if lo <= s < hi:
                modules[name] += 1
        edges = np.concatenate([[lo], merged.ravel(), [hi]]).reshape(-1, 2)
        gaps += [(float(a), float(b)) for a, b in edges if b > a]
    top_ops = sorted(op_time.items(), key=lambda kv: -kv[1])[:TOP]
    top_gaps = sorted(gaps, key=lambda g: g[0] - g[1])[:TOP]
    return {
        "busy_s": float(np.mean(busy)) * 1e-9 if busy else 0.0,
        "window_s": (hi - lo) * 1e-9,
        "devices": len(busy),
        "modules": dict(modules),
        "device_ops": [[n, t * 1e-9] for n, t in top_ops],
        "idle_gaps": [[gap_name(a, b, marks), (b - a) * 1e-9]
                      for a, b in top_gaps],
    }


def gap_name(lo: float, hi: float, marks) -> str:
    """The host annotation that overlaps ``[lo, hi]`` the most. Waiting on
    futures is the benchmark's background state, so ``bench.wait`` names a
    gap only where nothing else overlaps it; ``host.unannotated`` where
    nothing does."""
    overlap: dict[str, float] = {}
    for name, s, e in marks:
        d = min(e, hi) - max(s, lo)
        if d > 0:
            overlap[name] = overlap.get(name, 0.0) + d
    if not overlap:
        return "host.unannotated"
    busy = {k: v for k, v in overlap.items() if k != WAIT} or overlap
    return max(busy.items(), key=lambda kv: kv[1])[0]


def idle_pct(trace) -> float | None:
    """100 x (1 - busy / window); None without a trace of a device."""
    if not trace or not trace["devices"]:
        return None
    return 100.0 * (1.0 - trace["busy_s"] / trace["window_s"])


def completed_in(requests, span) -> int:
    """Requests whose answer came inside the host-clock ``span``."""
    if not span:
        return 0
    lo, hi = span
    return sum(1 for r in requests if r.done is not None and lo <= r.done <= hi)


def executions(modules: dict, program: str) -> int:
    """Executions of programs whose name contains ``program``."""
    return sum(n for name, n in modules.items() if program in name)
