"""Load generators and the arithmetic of the measured window.

Two arrival models drive ``AsyncFigaroServer.submit()``:

* closed loop: ``outstanding`` requests are pre-loaded into a paused server
  so they coalesce into full batches, and every completion submits one new
  request from the completion callback. The window starts at the first
  completion and ends at the first batch boundary (a completion whose index
  is a multiple of the batch size) at least ``seconds`` later, so whole
  batches are counted and a stall stays inside the window.
* open loop: requests are due on a fixed schedule whatever the server does.
  The gaps are the exponential distribution's quantiles in one shuffled
  order, which each seed starts at another point: every seed offers the same
  set of gaps in another order. Latency is taken from when a request was due
  to when its future resolved.

The generator's own host work is wrapped in ``jax.profiler``
annotations (``bench.submit``, ``bench.sleep``, ``bench.wait``), which the
trace reduction uses to name the device's idle gaps.
"""

from __future__ import annotations

import threading
import time

import numpy as np

WAIT_PAST_CLOSE_S = 60.0
# The open loop's gaps are shuffled once, the same way for every seed, and
# each seed starts the schedule at another point of it: how the gaps' order
# bunches arrivals is what sets the latency tail, and a fresh shuffle per
# seed made seeds differ by far more than two runs of one seed.
SCHEDULE_SEED = 20220401


class Request:
    __slots__ = ("payload", "due", "submitted", "done", "result", "error")

    def __init__(self, payload: int, due: float):
        self.payload, self.due = payload, due
        self.submitted = self.done = None
        self.result = self.error = None


def poisson_gaps(rate: float, seconds: float, rng) -> np.ndarray:
    """``round(rate * seconds)`` inter-arrival gaps: exponential quantiles at
    the midpoints of equal-probability bins, shuffled once in an order fixed
    for every seed, then rotated by an offset drawn from ``rng``."""
    n = max(1, round(rate * seconds))
    q = (np.arange(n) + 0.5) / n
    gaps = np.random.default_rng(SCHEDULE_SEED).permutation(
        -np.log1p(-q) / rate)
    return np.roll(gaps, -int(rng.integers(n)))


def percentile(values, q: float) -> float:
    """The ``q``-th percentile, linearly interpolated between order
    statistics (numpy's default)."""
    return float(np.percentile(np.asarray(values, np.float64), q))


def latencies(result: dict):
    """Seconds from due to answered for every open-loop request; None for a
    closed loop, or where a request never got its answer (a failed request
    misses every latency limit)."""
    if result["arrival"] != "open":
        return None
    reqs = result["requests"]
    if any(r.done is None or r.error is not None for r in reqs):
        return None
    return [r.done - r.due for r in reqs]


def batch_window(done_times, seconds: float, batch: int):
    """``(start, end, completed)`` of a closed-loop window over sorted
    completion times: from the first completion to the first completion at
    a batch boundary ``seconds`` or more later. None if it never closed."""
    t = np.asarray(done_times, np.float64)
    for c in range(batch, len(t), batch):
        if t[c] - t[0] >= seconds:
            return float(t[0]), float(t[c]), c
    return None


class _Tracker:
    """Completion bookkeeping shared by the callbacks and the waiter."""

    def __init__(self, clock):
        self.clock = clock
        self.lock = threading.Lock()
        self.done_times: list[float] = []
        self.first = threading.Event()
        self.closed = threading.Event()

    def record(self, req: Request, fut) -> int:
        req.done = self.clock()
        try:
            req.result = fut.result()
        except Exception as e:  # the request failed: counted, not raised
            req.error = e
        with self.lock:
            self.done_times.append(req.done)
            k = len(self.done_times) - 1
        self.first.set()
        return k


def closed_loop(server, payloads, order, *, outstanding: int, batch: int,
                seconds: float, annotate, clock=time.perf_counter,
                on_first=None, on_stop=None, trace_for=None) -> dict:
    """Keep ``outstanding`` requests in the server until the window closes.

    ``on_first`` runs in the calling thread once the first request has
    completed, and ``on_stop`` ``trace_for`` seconds later or when the
    window closes, whichever comes first (the traced run starts and stops
    its profiler there)."""
    track = _Tracker(clock)
    reqs: list[Request] = []

    def submit() -> None:
        with track.lock:
            req = Request(int(order[len(reqs) % len(order)]), clock())
            reqs.append(req)
        with annotate("bench.submit"):
            fut = server.submit(payloads[req.payload])
        req.submitted = clock()
        fut.add_done_callback(lambda f, r=req: completed(r, f))

    def completed(req: Request, fut) -> None:
        k = track.record(req, fut)
        if track.closed.is_set():
            return
        if k % batch == 0 and k and req.done - track.done_times[0] >= seconds:
            track.closed.set()
            return
        submit()

    server.pause()
    for _ in range(outstanding):
        submit()
    server.resume()
    limit = seconds + WAIT_PAST_CLOSE_S
    with annotate("bench.wait"):
        if not track.first.wait(limit):
            raise RuntimeError(f"no request completed in {limit} s")
    if on_first is not None:
        on_first()
    closed = False
    if on_stop is not None:
        with annotate("bench.wait"):
            closed = track.closed.wait(trace_for)
        on_stop()
    if not closed:
        with annotate("bench.wait"):
            closed = track.closed.wait(limit)
    _drain(reqs, clock() + WAIT_PAST_CLOSE_S, annotate, clock)
    with track.lock:
        times = sorted(track.done_times)
    return {"arrival": "closed", "requests": reqs,
            "window": batch_window(times, seconds, batch) if closed
            else None}


def open_loop(server, payloads, order, gaps, *, annotate,
              clock=time.perf_counter, on_start=None, on_stop=None,
              trace_for=None) -> dict:
    """Submit one request per gap on schedule; wait for all of them.

    ``on_start`` runs before the first request is due, and ``on_stop``
    once ``trace_for`` seconds of the schedule have passed or every request
    has its answer, whichever comes first."""
    track = _Tracker(clock)
    if on_start is not None:
        on_start()
    t0 = clock()
    stop = on_stop
    due = t0 + np.cumsum(gaps)
    reqs = []
    for i, t in enumerate(due):
        req = Request(int(order[i % len(order)]), float(t))
        reqs.append(req)
        if stop is not None and clock() - t0 >= trace_for:
            stop()
            stop = None
        pause = t - clock()
        if pause > 0:
            with annotate("bench.sleep"):
                time.sleep(pause)
        with annotate("bench.submit"):
            fut = server.submit(payloads[req.payload])
        req.submitted = clock()
        fut.add_done_callback(lambda f, r=req: track.record(r, f))
    _drain(reqs, clock() + WAIT_PAST_CLOSE_S, annotate, clock)
    if stop is not None:
        stop()
    return {"arrival": "open", "requests": reqs}


def _drain(reqs, deadline: float, annotate, clock) -> None:
    """Wait until every request has its answer, or until ``deadline``; one
    still unanswered then counts as failed."""
    with annotate("bench.wait"):
        while any(r.done is None for r in reqs) and clock() <= deadline:
            time.sleep(0.005)
