"""Async-first FiGaRo serving: request queue, futures, pipelined dispatch.

The paper's serving leverage — one cached Givens pipeline answering many
users' feature-sets over a fixed join structure — needs more than a blocking
callable: with one-shot synchronous dispatch, host-side request prep, H2D
transfer, executable launch, and result readback all serialize, and callers
must hand-assemble full batches themselves. `AsyncFigaroServer` turns the
serving layer into a small pipeline:

  * ``submit(request) -> FigaroFuture`` enqueues one request (per-node
    [m_i, n_i] leaves) or a sub-batch ([B, m_i, n_i] leaves, B=0 included)
    onto a micro-batching queue;
  * a dispatcher thread coalesces pending requests up to ``max_batch`` rows,
    pads the coalesced batch to its bucketed capacity
    (`launch.mesh.serving_batch_capacity` — powers of two, aligned to the
    serving mesh axis) and dispatches through the `FigaroEngine`. Because
    jax dispatch is asynchronous, with ``queue_depth >= 2`` the *next*
    batch's staging (`engine.stage` — H2D of donated input slabs) overlaps
    the in-flight executable: engine-level double buffering;
  * a completion thread blocks on readiness and resolves futures strictly in
    submission order. Exceptions propagate per-request: a request that fails
    validation resolves only its own future, and if a coalesced dispatch
    fails at run time, each batched request is re-dispatched alone so one
    poisoned request cannot fail its batchmates. Such a fallback runs a
    different program from the one asked for, so it is never silent: every
    isolated re-dispatch is counted in ``stats()``, the batch's exception is
    kept there, and a warning is logged;
  * every step of a batch runs under a `jax.profiler.TraceAnnotation` named
    ``figaro.serve.<step>`` that carries the batch's sequence number
    (``batch``) and live request count (``requests``): ``coalesce`` (taking
    the group and concatenating its payloads), ``depth_wait`` (waiting for a
    free ``queue_depth`` slot), ``stage``, ``launch`` on the dispatch thread;
    ``ready`` (waiting for the device) and ``resolve`` (slicing the results
    out, resolving the futures) on the completion thread. With the profiler
    off a span costs one annotation object. ``stats()`` counts at the same
    boundaries, always on;
  * ``append(node, rows)`` joins the same stream — it drains in-flight work,
    then refreshes the shared `plan_cache.PlanHolder` (zero retraces while
    live sizes stay within capacity), so the owning `JoinDataset`'s plan and
    ``stats()`` never fork from the server's.

The synchronous `FigaroServer` (`train.serve`) remains as a thin
``submit(...).result()`` wrapper over this machinery.
"""

from __future__ import annotations

import concurrent.futures
import functools
import itertools
import logging
import queue
import threading
import time
import weakref

import jax
import numpy as np

from repro.core.join_tree import FigaroPlan
from repro.core.plan_cache import PlanHolder, pad_data
from repro.sanitizer.locks import san_condition, san_lock
from repro.sanitizer.races import shared_state
from repro.sanitizer.threads import san_thread

__all__ = ["SERVE_KINDS", "validate_serve_kind", "FigaroFuture",
           "AsyncFigaroServer"]

_log = logging.getLogger(__name__)

#: The serving kinds every serving surface supports (`make_figaro_server`,
#: `Session.serve`, `JoinDataset.serve`) — validated eagerly, in one place.
SERVE_KINDS = ("qr", "svd", "pca", "lsq")


def validate_serve_kind(kind: str, *, label_col=None,
                        check_label: bool = False) -> None:
    """Eager serve-kind validation shared by every serving entry point.

    A bad ``kind`` must fail at construction with the full list of supported
    kinds — not at (or after) the first dispatch. ``check_label=True`` also
    enforces the lsq label requirement.
    """
    if kind not in SERVE_KINDS:
        raise ValueError(f"unknown serve kind {kind!r}; supported kinds: "
                         f"{', '.join(SERVE_KINDS)}")
    if check_label and kind == "lsq" and label_col is None:
        raise ValueError("kind='lsq' needs label_col")


class FigaroFuture(concurrent.futures.Future):
    """Result handle for one submitted request (or sub-batch).

    A thin `concurrent.futures.Future` (stdlib semantics for
    ``result(timeout)`` / ``exception(timeout)`` / ``done()`` /
    ``add_done_callback``), resolved by the server's completion thread in
    submission order. ``result()`` re-raises the request's own exception if
    it failed — validation errors and poisoned-dispatch errors are
    per-request, batchmates are unaffected.
    """

    def _resolve(self, value=None, error: BaseException | None = None):
        if error is not None:
            self.set_exception(error)
        else:
            self.set_result(value)


class _Request:
    """One queue entry: a validated (or failed-at-validation) request."""

    __slots__ = ("future", "arrays", "b", "single", "sig", "plan", "error",
                 "submitted")

    def __init__(self):
        self.future = FigaroFuture()
        self.submitted = time.perf_counter()
        self.arrays = None  # capacity-shaped [b, m_i, n_i] leaves
        self.b = 0
        self.single = False  # squeeze the leading axis on resolve
        self.sig = None  # coalescing-compatibility key
        self.plan: FigaroPlan | None = None
        self.error: BaseException | None = None

    def _fail(self, error: BaseException) -> None:
        if not self.future.done():
            self.future._resolve(error=error)


_SHUTDOWN = object()
_span = jax.profiler.TraceAnnotation
#: The always-on counters of `AsyncFigaroServer.stats` (class docstring).
_COUNTERS = ("dispatches", "dispatched_requests", "dispatched_rows",
             "capacity_rows", "queue_wait_s", "queue_wait_max_s",
             "dispatch_host_s", "dispatch_host_max_s")


def _slice_out(out, offset: int, b: int, single: bool):
    """This request's slice of a coalesced batch output."""
    if single:
        return jax.tree.map(lambda x: x[offset], out)
    return jax.tree.map(lambda x: x[offset:offset + b], out)


# The worker loops hold only a weakref to the server (plus its queues), so an
# abandoned server can be garbage-collected; its finalizer posts _SHUTDOWN and
# the threads exit instead of leaking for the life of the process.

def _wait_gate(server_ref):
    """Wait out a pause() hold WITHOUT keeping the server strongly
    referenced: a paused, abandoned server must stay collectable (its
    finalizer posts the shutdown sentinel) — blocking inside a server method
    would pin it alive, and its threads, forever. Returns the live server
    once the gate is open, or None if it was collected meanwhile."""
    while True:
        server = server_ref()
        if server is None:
            return None
        gate = server._run_gate
        del server
        if gate.wait(timeout=0.2):
            return server_ref()


def _dispatch_loop(server_ref, in_q, out_q):
    leftover = None
    while True:
        item = leftover if leftover is not None else in_q.get()
        leftover = None
        server = _wait_gate(server_ref) if item is not _SHUTDOWN else None
        if item is _SHUTDOWN or server is None:
            # Shut down on the queue handles, NOT through the server: when
            # the finalizer of a GC'd server posts _SHUTDOWN, the weakref is
            # already dead — the completion thread must still be released,
            # and any still-queued requests must fail rather than hang their
            # futures (close() drains first, so this only fires for GC).
            dead = RuntimeError("server closed or garbage-collected before "
                                "the request was dispatched")
            while True:
                if item is not _SHUTDOWN and item is not None:
                    item._fail(dead)
                try:
                    item = in_q.get_nowait()
                except queue.Empty:
                    break
            out_q.put(_SHUTDOWN)
            return
        try:
            leftover = server._dispatch_one(item)
        except BaseException as e:  # defensive: the loop must survive
            server._fail_item(item, e)
        del server


def _complete_loop(server_ref, out_q):
    while True:
        got = out_q.get()
        server = server_ref() if got is not _SHUTDOWN else None
        if got is _SHUTDOWN or server is None:
            # A dead weakref means the server was collected with groups
            # still in flight (nobody kept a server reference, only
            # futures): fail them — silently returning would leave those
            # futures unresolved forever. close() drains before shutdown,
            # so the sentinel path normally finds the queue empty.
            dead = RuntimeError("server closed or garbage-collected before "
                                "the request was answered")
            while True:
                if got is not _SHUTDOWN and got is not None:
                    for it in got[0]:
                        it._fail(dead)
                try:
                    got = out_q.get_nowait()
                except queue.Empty:
                    return
        try:
            server._resolve_group(*got)
        except BaseException as e:  # defensive: resolve rather than hang
            for it in got[0]:
                if not it.future.done():
                    it.future._resolve(error=e)
                    server._done_one()
            server._depth_sem.release()
        del server


@shared_state({"_outstanding": "_cond", "_isolated": "_cond",
               "_batch_error": "_cond", "_counters": "_cond",
               "_closed": "_close_lock", "_threads": "_thread_lock"})
class AsyncFigaroServer:
    """Pipelined micro-batching serving endpoint for one join structure.

    Construct through `make_figaro_server` / ``ds.serve(kind=...)`` — see
    the module docstring for the pipeline. The public surface:

    ``submit(request)``
        Enqueue per-node request leaves ([m_i, n_i] for one request,
        [B, m_i, n_i] for a sub-batch; rows at the live size are zero-padded
        to capacity, any other row count fails that request's future).
        Returns a `FigaroFuture`.
    ``server(data_batch)``
        Synchronous convenience: ``submit(data_batch).result()``.
    ``append(node, rows)``
        Drain in-flight work, then append ``rows = (key_columns,
        data_rows)`` to relation ``node`` through the shared `PlanHolder` —
        the owning `JoinDataset` (and every sibling server) sees the same
        refreshed plan. True = still within capacity (zero retraces).
    ``stats()``
        ``isolated_redispatches`` (requests re-dispatched alone after their
        coalesced batch failed; 0 on a healthy path) and ``batch_error``
        (the last failed batch's exception, or None). Since construction:
        ``dispatches`` (coalesced batches handed to the engine),
        ``dispatched_requests``, ``dispatched_rows`` and ``capacity_rows``
        (live request rows, and the padded bucket capacity they ran at);
        ``queue_wait_s`` summed over requests from `submit` to the start of
        their batch's launch (coalescing, the ``queue_depth`` wait and
        staging included) and ``queue_wait_max_s``; ``dispatch_host_s``, the
        dispatch thread's own time per batch (coalesce, stage and launch,
        not the ``queue_depth`` wait), summed, and ``dispatch_host_max_s``.
    ``flush()`` / ``close()`` / ``pause()`` / ``resume()``
        Drain outstanding requests; shut the worker threads down; hold /
        release the coalescer (pause + submit + resume dispatches one
        maximally-coalesced batch deterministically — useful for warm-up and
        for tests asserting coalesced-batch identities).
    """

    def __init__(self, holder: PlanHolder, dispatch_fn, *, engine=None,
                 axis_size: int = 1, max_batch: int = 32,
                 queue_depth: int = 2):
        if holder.plan is None:
            raise ValueError("AsyncFigaroServer needs a holder with a built "
                             "plan")
        if max_batch < 1:
            raise ValueError(f"max_batch must be >= 1, got {max_batch}")
        if queue_depth < 1:
            raise ValueError(f"queue_depth must be >= 1, got {queue_depth}")
        from repro.launch.mesh import serving_batch_capacity

        self._holder = holder
        self._dispatch_fn = dispatch_fn  # (plan, batch, batch_capacity) -> out
        self._capacity_for = functools.partial(serving_batch_capacity,
                                               axis_size=axis_size)
        # Stage (async H2D) only on the single-device path: under a mesh the
        # engine re-places the padded batch with the mesh sharding itself.
        self._engine_stage = (engine.stage if engine is not None
                              and axis_size == 1 else None)
        self.max_batch = max_batch
        self.queue_depth = queue_depth
        self._in_q: queue.Queue = queue.Queue()
        self._out_q: queue.Queue = queue.Queue()
        self._depth_sem = threading.Semaphore(queue_depth)
        self._run_gate = threading.Event()
        self._run_gate.set()
        # Sanitizer-aware locks (FIG007), created before the state they
        # guard so FIGARO_SAN=1 can resolve them mid-__init__.
        self._cond = san_condition("server._cond")
        self._close_lock = san_lock("server._close_lock")  # closed vs enqueue
        self._thread_lock = san_lock("server._thread_lock")
        self._outstanding = 0
        self._isolated = 0
        self._batch_error: BaseException | None = None
        self._counters = dict.fromkeys(_COUNTERS, 0)
        self._batch_seq = itertools.count()  # the dispatch thread's alone
        self._closed = False
        self._threads: list[threading.Thread] | None = None
        self._finalizer = weakref.finalize(self, self._in_q.put, _SHUTDOWN)

    # -- plan lifecycle (shared with the owning JoinDataset) -----------------

    @property
    def plan(self) -> FigaroPlan:
        """The currently-served plan — the shared holder's, never a fork.

        Every request captures this plan at *submit* time (``item.plan``),
        and dispatch uses the captured plan — so a holder-level swap (an
        append refresh, or an adaptive re-root via `PlanHolder.replace`)
        never changes the plan a pending future is answered with: the swap
        paths drain first, and anything submitted before the drain resolves
        bit-identically to the pre-swap plan."""
        return self._holder.plan

    def append(self, node: str, rows) -> bool:
        """Append ``rows = (key_columns, data_rows)`` to relation ``node``.

        Drains in-flight work first (queued requests were validated against
        the old capacities), then refreshes the shared plan holder. Returns
        True when the refresh stayed within the plan's capacities — the next
        dispatch reuses the cached executable, zero retraces. Appends through
        a dataset with adaptive re-rooting (``ds.append``) may additionally
        swap the orientation at the same drain point; requests submitted
        after the swap validate against — and are answered on — the new
        plan's layout."""
        return self._holder.refresh({node: rows})

    # -- submission ----------------------------------------------------------

    def submit(self, request) -> FigaroFuture:
        """Enqueue one request ([m_i, n_i] leaves) or a sub-batch
        ([B, m_i, n_i]); returns a `FigaroFuture` resolved in submission
        order. Validation failures resolve this future alone."""
        item = _Request()
        try:
            self._prepare(item, request)
        except Exception as e:
            item.error = e
        # The closed check and the enqueue are one atomic step against
        # close(): without the lock, a submit racing close() could enqueue
        # its item AFTER the shutdown sentinel and hang its future forever.
        with self._close_lock:
            if self._closed:
                raise RuntimeError("server is closed")
            with self._cond:
                self._outstanding += 1
            self._ensure_threads()
            self._in_q.put(item)
        return item.future

    def __call__(self, data_batch):
        """Synchronous dispatch: ``submit(data_batch).result()``."""
        return self.submit(data_batch).result()

    def _prepare(self, item: _Request, request) -> None:
        plan = self._holder.plan
        data = tuple(request)
        if len(data) != len(plan.spec.nodes):
            raise ValueError(
                f"expected one data leaf per relation "
                f"({len(plan.spec.nodes)}: {list(plan.spec.names)}), "
                f"got {len(data)}")
        ndims = {np.ndim(d) for d in data}
        if ndims == {2}:
            item.single = True
            data = tuple(np.asarray(d)[None] for d in data)
        elif ndims != {3}:
            raise ValueError(
                "request leaves must all be [rows_i, n_i] (one request) or "
                f"all [B, rows_i, n_i] (a sub-batch); got ndims {sorted(ndims)}")
        bs = {int(np.shape(d)[0]) for d in data}
        if len(bs) != 1:
            raise ValueError(f"request leaves disagree on the batch size: "
                             f"{sorted(bs)}")
        sizes = [(int(ix.row_mask.sum()) if ix.row_mask is not None else sp.m,
                  sp) for sp, ix in zip(plan.spec.nodes, plan.index)]
        if not all(np.shape(d)[-2] == sp.m for d, (_, sp) in zip(data, sizes)):
            for d, (live, sp) in zip(data, sizes):
                if np.shape(d)[-2] not in (live, sp.m):
                    raise ValueError(
                        f"{sp.name}: request batch has {np.shape(d)[-2]} "
                        f"rows; expected the live size ({live}) or the "
                        f"capacity ({sp.m}) — rebuild request buffers after "
                        f"append()")
            data = pad_data(data, plan.spec)
        item.arrays = data
        item.b = bs.pop()
        item.plan = plan
        item.sig = (id(plan), tuple(
            np.dtype(getattr(d, "dtype", None) or np.asarray(d).dtype).str
            for d in data))

    # -- worker plumbing -----------------------------------------------------

    def _ensure_threads(self) -> None:
        # No unlocked fast-path read: `_threads` is written under
        # `_thread_lock`, so the check must hold it too (the uncontended
        # acquire is cheap, and the lockset race detector would rightly flag
        # the bare read once a second thread has gone through here).
        with self._thread_lock:
            if self._threads is not None:
                return
            ref = weakref.ref(self)
            threads = [
                san_thread(_dispatch_loop,
                           args=(ref, self._in_q, self._out_q),
                           name="figaro-serve-dispatch", daemon=True),
                san_thread(_complete_loop, args=(ref, self._out_q),
                           name="figaro-serve-complete", daemon=True),
            ]
            for t in threads:
                t.start()
            self._threads = threads

    def _dispatch_one(self, first: _Request):
        """Coalesce a group starting at ``first``, dispatch it, hand it to
        the completion thread. Returns a popped-but-incompatible request to
        seed the next group (or _SHUTDOWN, passed through). The pause() gate
        was already waited out by the dispatch loop (without a strong server
        reference), so the queue behind ``first`` is fully drained here."""
        t_first = time.perf_counter()
        seq = next(self._batch_seq)
        with _span("figaro.serve.coalesce", batch=seq) as span:
            group = [first]
            live_sig = first.sig if first.error is None else None
            total_b = first.b if first.error is None else 0
            leftover = None
            while total_b < self.max_batch:
                try:
                    nxt = self._in_q.get_nowait()
                except queue.Empty:
                    break
                # Stop at a shutdown sentinel, an incompatible request, or a
                # sub-batch that would push the group past max_batch (a
                # single oversized submit still dispatches alone — it cannot
                # be split); the popped item seeds the next group, preserving
                # FIFO order.
                if nxt is _SHUTDOWN or (nxt.error is None and (
                        (live_sig is not None and nxt.sig != live_sig)
                        or total_b + nxt.b > self.max_batch)):
                    leftover = nxt
                    break
                group.append(nxt)
                if nxt.error is None:
                    live_sig = live_sig or nxt.sig
                    total_b += nxt.b
            live = [it for it in group if it.error is None]
            n = len(live)
            span.set_metadata(requests=n)
            payload = data = None
            try:
                if n == 1:
                    data = live[0].arrays
                elif n:
                    data = tuple(
                        np.concatenate([np.asarray(it.arrays[j])
                                        for it in live])
                        for j in range(len(live[0].arrays)))
            except Exception as e:
                payload = (None, e)
        t_coalesced = time.perf_counter()
        with _span("figaro.serve.depth_wait", batch=seq, requests=n):
            self._depth_sem.acquire()  # ≤ queue_depth batches in flight
        t_slot = t_launch = time.perf_counter()
        capacity = self._capacity_for(total_b)
        if live and payload is None:
            try:
                if self._engine_stage is not None:
                    with _span("figaro.serve.stage", batch=seq, requests=n):
                        data = self._engine_stage(data)
                t_launch = time.perf_counter()
                with _span("figaro.serve.launch", batch=seq, requests=n):
                    out = self._dispatch_fn(live[0].plan, data,
                                            capacity or None)
                payload = (out, None)
            except Exception as e:
                payload = (None, e)
        host = t_coalesced - t_first + time.perf_counter() - t_slot
        if live:
            waits = [t_launch - it.submitted for it in live]
            with self._cond:
                c = self._counters
                c["dispatches"] += 1
                c["dispatched_requests"] += n
                c["dispatched_rows"] += total_b
                c["capacity_rows"] += capacity
                c["queue_wait_s"] += sum(waits)
                c["queue_wait_max_s"] = max(c["queue_wait_max_s"], *waits)
                c["dispatch_host_s"] += host
                c["dispatch_host_max_s"] = max(c["dispatch_host_max_s"], host)
        self._out_q.put((group, live, payload, seq))
        return leftover

    def _resolve_group(self, group, live, payload, seq) -> None:
        out, err = payload if payload is not None else (None, None)
        n = len(live)
        if err is None and out is not None:
            try:
                with _span("figaro.serve.ready", batch=seq, requests=n):
                    jax.block_until_ready(out)
            except Exception as e:
                err, out = e, None
        with _span("figaro.serve.resolve", batch=seq, requests=n):
            self._resolve(group, live, out, err)
        self._depth_sem.release()

    def _resolve(self, group, live, out, err) -> None:
        results, errors = {}, {}
        if live and err is None and out is not None:
            offset = 0
            for it in live:
                results[id(it)] = _slice_out(out, offset, it.b, it.single)
                offset += it.b
        elif len(live) > 1:
            # A coalesced dispatch failed: isolate the poisoned request(s) by
            # re-dispatching each request alone — batchmates still succeed.
            # Counted and logged: a batch that cannot compile or run out of
            # device memory must not pass for a healthy one.
            _log.warning("coalesced batch of %d requests failed (%r); "
                         "re-dispatching each request alone", len(live), err)
            with self._cond:
                self._isolated += len(live)
                self._batch_error = err
            for it in live:
                try:
                    o = self._dispatch_fn(it.plan, it.arrays,
                                          self._capacity_for(it.b) or None)
                    jax.block_until_ready(o)
                    results[id(it)] = _slice_out(o, 0, it.b, it.single)
                except Exception as e:
                    errors[id(it)] = e
        elif live:
            errors[id(live[0])] = err
        for it in group:  # strictly submission order
            if it.error is not None:
                it.future._resolve(error=it.error)
            elif id(it) in results:
                it.future._resolve(value=results[id(it)])
            else:
                it.future._resolve(error=errors.get(id(it), err))
            self._done_one()

    def stats(self) -> dict:
        """Serving counters (see the class docstring)."""
        with self._cond:
            return dict(self._counters,
                        isolated_redispatches=self._isolated,
                        batch_error=self._batch_error)

    def _fail_item(self, item, error: BaseException) -> None:
        if isinstance(item, _Request) and not item.future.done():
            item.future._resolve(error=error)
            self._done_one()

    def _done_one(self) -> None:
        with self._cond:
            self._outstanding -= 1
            self._cond.notify_all()

    # -- flow control --------------------------------------------------------

    def flush(self) -> None:
        """Block until every submitted request has been answered.

        Releases a `pause` hold first: flush demands every queued request be
        answered, which a held coalescer could never do — without this,
        ``append`` (which drains every server attached to the plan holder,
        paused or not) would deadlock on a paused server's queued work."""
        self.resume()
        with self._cond:
            self._cond.wait_for(lambda: self._outstanding == 0)

    def pause(self) -> None:
        """Hold the coalescer: submitted requests queue up but do not
        dispatch until `resume` — pre-loading the queue this way yields one
        maximally-coalesced batch. `flush` / `append` / `close` release the
        hold (they require the queue to drain)."""
        self._run_gate.clear()

    def resume(self) -> None:
        self._run_gate.set()

    def close(self) -> None:
        """Drain outstanding work and stop the worker threads."""
        with self._close_lock:  # `_closed` is only ever read under the lock
            if self._closed:
                return
        self.flush()  # releases any pause() hold first
        threads = None
        with self._close_lock:
            if self._closed:
                return
            self._closed = True
            with self._thread_lock:
                threads = self._threads
            if threads is not None:
                self._in_q.put(_SHUTDOWN)
        if threads is not None:
            for t in threads:
                t.join(timeout=10.0)

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()
        return False
