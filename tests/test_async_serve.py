"""Async serving: futures in submission order, micro-batch coalescing
(bit-identical to the one-shot batched dispatch), per-request exception
isolation, interleaved submit/append streams with zero retraces, B=0/B=1
edges, and the shared plan holder between a JoinDataset and its servers."""

import numpy as np
import jax.numpy as jnp
import pytest

from repro import figaro
from repro.core.engine import FigaroEngine
from repro.core.join_tree import build_plan
from repro.core.plan_cache import PlanHolder, build_capacity_plan
from repro.launch.mesh import make_data_mesh, serving_batch_capacity
from repro.train.async_serve import FigaroFuture
from repro.train.serve import (AsyncFigaroServer, FigaroServer,
                               SERVE_KINDS, make_figaro_server)


def _star_tables(m_fact: int = 20):
    rng = np.random.default_rng(m_fact)
    return {
        "Orders": ({"cust": np.arange(m_fact) % 8,
                    "prod": np.arange(m_fact) % 4},
                   rng.normal(size=(m_fact, 2)), ["amount", "qty"]),
        "Customers": ({"cust": np.arange(8)},
                      rng.normal(size=(8, 2)), ["age", "income"]),
        "Products": ({"prod": np.arange(4)},
                     rng.normal(size=(4, 1)), ["price"]),
    }


_STAR_EDGES = [("Orders", "Customers"), ("Orders", "Products")]


def _star_ds(session, m_fact=20):
    return session.ingest(_star_tables(m_fact)).join("Orders", _STAR_EDGES)


def _requests(plan, rng, n):
    """n single requests (per-node [m_i, n_i] leaves) at capacity shapes."""
    return [tuple(rng.normal(size=np.asarray(d).shape) for d in plan.data)
            for _ in range(n)]


# -- capacity bucketing -------------------------------------------------------


def test_serving_batch_capacity_buckets():
    assert serving_batch_capacity(0) == 0
    assert serving_batch_capacity(1) == 1
    assert serving_batch_capacity(3) == 4
    assert serving_batch_capacity(8) == 8
    # aligned to a non-power-of-two mesh axis
    assert serving_batch_capacity(1, axis_size=3) == 3
    assert serving_batch_capacity(5, axis_size=3) == 9
    assert serving_batch_capacity(4, axis_size=2) == 4


def test_engine_batch_capacity_shares_executable_across_live_sizes(rng):
    """Partial batches padded to one bucket share one executable; the pad is
    sliced off the result."""
    plan = build_plan(_star_tree())
    engine = FigaroEngine(donate_data=False)
    b3 = _stack(_requests(plan, rng, 3))
    b5 = _stack(_requests(plan, rng, 5))
    r3 = np.asarray(engine.qr(plan, b3, batched=True, batch_capacity=8,
                              dtype=jnp.float64))
    assert r3.shape == (3, plan.num_cols, plan.num_cols)
    assert engine.trace_count("qr_batched") == 1
    r5 = np.asarray(engine.qr(plan, b5, batched=True, batch_capacity=8,
                              dtype=jnp.float64))
    assert r5.shape[0] == 5
    assert engine.trace_count("qr_batched") == 1, \
        "live sizes in one batch bucket must share the executable"
    with pytest.raises(ValueError, match="batch_capacity"):
        engine.qr(plan, b5, batched=True, batch_capacity=2,
                  dtype=jnp.float64)
    with pytest.raises(ValueError, match="batched"):
        engine.qr(plan, [d[0] for d in b3], batch_capacity=4,
                  dtype=jnp.float64)


def _star_tree():
    from repro.core.join_tree import JoinTree
    from repro.core.relation import Database, full_reduce

    db = full_reduce(Database.from_arrays(_star_tables()), _STAR_EDGES)
    return JoinTree.from_edges(db, "Orders", _STAR_EDGES)


def _stack(reqs):
    return tuple(np.stack([r[j] for r in reqs])
                 for j in range(len(reqs[0])))


# -- futures + coalescing -----------------------------------------------------


def test_coalesced_submit_bit_identical_to_sync_batched_dispatch(rng):
    """pause + submit×4 + resume dispatches ONE coalesced B=4 batch whose
    per-request results are bit-identical to the one-shot batched dispatch of
    the same batch (same executable: same engine, same signature)."""
    plan = build_plan(_star_tree())
    engine = FigaroEngine(donate_data=False)
    server = make_figaro_server(plan, kind="qr", dtype=jnp.float64,
                                engine=engine)
    reqs = _requests(plan, rng, 4)
    server.pause()
    futures = [server.submit(r) for r in reqs]
    server.resume()
    results = [np.asarray(f.result(timeout=60)) for f in futures]
    assert engine.trace_count("qr_batched") == 1, \
        "4 submits must coalesce into one dispatch"
    r_sync = np.asarray(engine.qr(plan, _stack(reqs), batched=True,
                                  dtype=jnp.float64))
    assert engine.trace_count("qr_batched") == 1  # same executable
    for i, r in enumerate(results):
        np.testing.assert_array_equal(r, r_sync[i], err_msg=f"request {i}")
    server.close()


def test_futures_resolve_in_submission_order(rng, monkeypatch):
    plan = build_plan(_star_tree())
    server = make_figaro_server(plan, kind="qr", dtype=jnp.float64,
                                engine=FigaroEngine(donate_data=False))
    order = []
    orig = FigaroFuture._resolve

    def spy(self, *a, **k):
        order.append(self)
        return orig(self, *a, **k)

    monkeypatch.setattr(FigaroFuture, "_resolve", spy)
    futures = [server.submit(r) for r in _requests(plan, np.random.
                                                   default_rng(0), 6)]
    server.flush()
    assert all(f.done() for f in futures)
    assert order == futures, "futures must resolve in submission order"
    server.close()


def test_submit_sub_batch_and_call_are_equivalent(rng):
    plan = build_plan(_star_tree())
    engine = FigaroEngine(donate_data=False)
    server = make_figaro_server(plan, kind="qr", dtype=jnp.float64,
                                engine=engine)
    batch = _stack(_requests(plan, rng, 3))
    via_future = np.asarray(server.submit(batch).result(timeout=60))
    via_call = np.asarray(server(batch))
    assert via_future.shape == (3, plan.num_cols, plan.num_cols)
    np.testing.assert_array_equal(via_future, via_call)
    server.close()


def test_edge_batches_b0_and_b1(rng):
    plan = build_plan(_star_tree())
    engine = FigaroEngine(donate_data=False)
    server = make_figaro_server(plan, kind="qr", dtype=jnp.float64,
                                engine=engine)
    n = plan.num_cols
    empty = tuple(np.zeros((0,) + np.asarray(d).shape) for d in plan.data)
    assert np.asarray(server.submit(empty).result(timeout=60)).shape \
        == (0, n, n)
    one = _stack(_requests(plan, rng, 1))
    r1 = np.asarray(server.submit(one).result(timeout=60))
    assert r1.shape == (1, n, n)
    # single-request submit: unbatched leaves in, unbatched result out
    single = server.submit(tuple(d[0] for d in one)).result(timeout=60)
    np.testing.assert_array_equal(np.asarray(single), r1[0])
    server.close()


# -- per-request exception isolation ------------------------------------------


def test_validation_error_fails_only_its_own_future(rng):
    plan = build_plan(_star_tree())
    server = make_figaro_server(plan, kind="qr", dtype=jnp.float64,
                                engine=FigaroEngine(donate_data=False))
    good = _requests(plan, rng, 2)
    bad = tuple(d[:-1] for d in good[0])  # wrong row counts everywhere
    server.pause()
    f_ok1 = server.submit(good[0])
    f_bad = server.submit(bad)
    f_ok2 = server.submit(good[1])
    server.resume()
    r1 = np.asarray(f_ok1.result(timeout=60))
    r2 = np.asarray(f_ok2.result(timeout=60))
    assert r1.shape == r2.shape == (plan.num_cols, plan.num_cols)
    with pytest.raises(ValueError, match="live size|rebuild request"):
        f_bad.result(timeout=60)
    assert isinstance(f_bad.exception(), ValueError)
    server.close()


def test_poisoned_dispatch_does_not_fail_coalesced_batchmates(rng):
    """If the coalesced dispatch itself blows up, each batched request is
    re-dispatched alone: batchmates succeed, only the poisoned request's
    future carries the exception."""
    plan = build_plan(_star_tree())
    engine = FigaroEngine(donate_data=False)
    server = make_figaro_server(plan, kind="qr", dtype=jnp.float64,
                                engine=engine)
    real = server._dispatch_fn

    def flaky(plan_, batch, cap):
        if any(np.isnan(np.asarray(d)).any() for d in batch):
            raise RuntimeError("poisoned request batch")
        return real(plan_, batch, cap)

    server._dispatch_fn = flaky
    good = _requests(plan, rng, 2)
    poisoned = tuple(np.asarray(d).copy() for d in good[0])
    poisoned[0][0, 0] = np.nan
    server.pause()
    f1 = server.submit(good[0])
    f2 = server.submit(poisoned)
    f3 = server.submit(good[1])
    server.resume()
    r1 = np.asarray(f1.result(timeout=60))
    r3 = np.asarray(f3.result(timeout=60))
    with pytest.raises(RuntimeError, match="poisoned"):
        f2.result(timeout=60)
    # batchmates got real answers (match a clean per-request dispatch)
    ref = FigaroEngine(donate_data=False)
    for r, req in ((r1, good[0]), (r3, good[1])):
        ri = np.asarray(ref.qr(plan, list(req), dtype=jnp.float64))
        np.testing.assert_allclose(r, ri,
                                   atol=1e-10 * max(np.abs(ri).max(), 1.0))
    server.close()


def test_isolated_redispatches_are_counted(rng):
    """A clean coalesced batch leaves the isolation counter at 0; a poisoned
    one counts each request re-dispatched alone and keeps the batch's
    exception in ``stats()``."""
    plan = build_plan(_star_tree())
    server = make_figaro_server(plan, kind="qr", dtype=jnp.float64,
                                engine=FigaroEngine(donate_data=False))
    real = server._dispatch_fn

    def flaky(plan_, batch, cap):
        if np.shape(batch[0])[0] > 1:
            raise RuntimeError("coalesced batch failed")
        return real(plan_, batch, cap)

    reqs = _requests(plan, rng, 3)
    server.pause()
    futures = [server.submit(r) for r in reqs]
    server.resume()
    for f in futures:
        f.result(timeout=60)
    stats = server.stats()
    assert (stats["isolated_redispatches"], stats["batch_error"]) == (0, None)

    server._dispatch_fn = flaky
    server.pause()
    futures = [server.submit(r) for r in reqs]
    server.resume()
    for f in futures:  # each request still answers, alone
        assert np.asarray(f.result(timeout=60)).shape \
            == (plan.num_cols, plan.num_cols)
    stats = server.stats()
    assert stats["isolated_redispatches"] == 3
    assert isinstance(stats["batch_error"], RuntimeError)
    assert "coalesced batch failed" in str(stats["batch_error"])
    server.close()


def test_stats_count_batches_rows_and_waits(rng):
    """Three requests held by pause() coalesce into one dispatch at bucket
    capacity 4; each request's wait from submit to launch is counted."""
    plan = build_plan(_star_tree())
    server = make_figaro_server(plan, kind="qr", dtype=jnp.float64,
                                engine=FigaroEngine(donate_data=False))
    server.pause()
    futures = [server.submit(r) for r in _requests(plan, rng, 3)]
    server.resume()
    for f in futures:
        f.result(timeout=60)
    stats = server.stats()
    assert stats["dispatches"] == 1
    assert stats["dispatched_requests"] == 3
    assert stats["dispatched_rows"] == 3
    assert stats["capacity_rows"] == 4
    assert 0 < stats["queue_wait_max_s"] <= stats["queue_wait_s"]
    assert 0 < stats["dispatch_host_max_s"] == stats["dispatch_host_s"]
    server.submit(_requests(plan, rng, 1)[0]).result(timeout=60)
    after = server.stats()
    assert (after["dispatches"], after["dispatched_requests"],
            after["capacity_rows"]) == (2, 4, 5)
    assert after["dispatch_host_s"] >= after["dispatch_host_max_s"]
    server.close()


# -- streaming submit/append with zero retraces -------------------------------


def test_interleaved_submit_append_zero_retraces_in_capacity(rng):
    sess = figaro.Session(headroom=16)
    ds = _star_ds(sess)
    server = ds.serve(kind="qr", dtype=jnp.float64)
    live = lambda: tuple(
        rng.normal(size=(ds.stats()["nodes"][nm]["live_rows"],
                         ds.tree.db[nm].num_data_cols))
        for nm in ds.tree.preorder())
    for step in range(3):
        r = server.submit(live()).result(timeout=60)
        assert np.asarray(r).shape == (ds.plan.num_cols, ds.plan.num_cols)
        in_cap = server.append("Orders", ({"cust": np.array([step]),
                                           "prod": np.array([step % 4])},
                                          np.ones((1, 2)) * step))
        assert in_cap, "append within headroom must keep the signature"
    server.submit(live()).result(timeout=60)
    st = ds.stats()
    assert st["traces"]["qr_batched"] == 1, \
        "streaming submit+append in capacity must be zero-retrace"
    assert st["appends"] == 3 and st["regrows"] == 0
    server.close()


def test_append_drains_in_flight_requests(rng):
    """append must answer queued requests (validated against the old
    capacities) before swapping the plan."""
    sess = figaro.Session(headroom=16)
    ds = _star_ds(sess)
    server = ds.serve(kind="qr", dtype=jnp.float64)
    reqs = _requests(ds.plan, rng, 3)
    server.pause()
    futures = [server.submit(r) for r in reqs]
    server.resume()
    server.append("Orders", ({"cust": np.array([0]), "prod": np.array([0])},
                             np.ones((1, 2))))
    assert all(f.done() for f in futures), "append must drain the queue"
    for f in futures:
        assert np.asarray(f.result()).shape \
            == (ds.plan.num_cols, ds.plan.num_cols)
    server.close()


# -- shared plan holder: no dataset/server fork -------------------------------


def test_server_append_keeps_dataset_in_sync_and_vice_versa():
    sess = figaro.Session(headroom=16)
    ds = _star_ds(sess)
    server = ds.serve(kind="qr", dtype=jnp.float64)
    live0 = ds.stats()["nodes"]["Orders"]["live_rows"]

    # server -> dataset
    assert server.append("Orders", ({"cust": np.array([0, 1]),
                                     "prod": np.array([0, 1])},
                                    np.ones((2, 2))))
    assert ds.stats()["nodes"]["Orders"]["live_rows"] == live0 + 2
    assert ds.plan is server.plan, "dataset and server plan state forked"
    assert ds.stats()["appends"] == 1

    # dataset -> server
    assert ds.append("Orders", {"cust": np.array([2]),
                                "prod": np.array([2])}, np.ones((1, 2)))
    assert server.plan is ds.plan
    rows = int(server.plan.source_tree.db["Orders"].num_rows)
    assert rows == live0 + 3
    assert ds.stats()["appends"] == 2

    # two servers over one dataset share the same holder too
    server2 = ds.serve(kind="svd", dtype=jnp.float64)
    assert server2.plan is server.plan
    server.close()
    server2.close()


# -- sharded async path (in-process 1-device mesh; multi-device in CI) --------


def test_async_server_over_data_mesh_matches_per_sample(rng):
    plan = build_plan(_star_tree())
    engine = FigaroEngine(donate_data=False)
    mesh = make_data_mesh()
    server = make_figaro_server(plan, kind="qr", dtype=jnp.float64,
                                engine=engine, mesh=mesh)
    reqs = _requests(plan, rng, 3)
    server.pause()
    futures = [server.submit(r) for r in reqs]
    server.resume()
    ref = FigaroEngine(donate_data=False)
    for f, req in zip(futures, reqs):
        ri = np.asarray(ref.qr(plan, list(req), dtype=jnp.float64))
        np.testing.assert_allclose(np.asarray(f.result(timeout=60)), ri,
                                   atol=1e-10 * max(np.abs(ri).max(), 1.0))
    assert engine.trace_count("qr_batched") == 1
    server.close()


# -- surface contracts --------------------------------------------------------


def test_serve_kinds_single_source_of_truth():
    assert figaro.SERVE_KINDS == SERVE_KINDS == ("qr", "svd", "pca", "lsq")
    from repro.api import SERVE_KINDS as api_kinds

    assert api_kinds is SERVE_KINDS
    # one validator, both surfaces
    ds = _star_ds(figaro.Session())
    with pytest.raises(ValueError, match="supported kinds: qr, svd, pca, lsq"):
        ds.serve(kind="cholesky")
    cap = build_capacity_plan(_star_tree())
    with pytest.raises(ValueError, match="supported kinds: qr, svd, pca, lsq"):
        make_figaro_server(cap, kind="cholesky")


def test_sync_server_is_async_server():
    cap = build_capacity_plan(_star_tree())
    server = make_figaro_server(cap, kind="qr", dtype=jnp.float64,
                                engine=FigaroEngine(donate_data=False))
    assert isinstance(server, FigaroServer)
    assert isinstance(server, AsyncFigaroServer)
    server.close()
    with pytest.raises(RuntimeError, match="closed"):
        server.submit(tuple(np.asarray(d) for d in cap.data))
    server.close()  # idempotent


def test_append_on_paused_server_does_not_deadlock(rng):
    """flush/append release a pause() hold: append drains every attached
    server, so a held coalescer with queued work must drain, not deadlock."""
    sess = figaro.Session(headroom=16)
    ds = _star_ds(sess)
    server = ds.serve(kind="qr", dtype=jnp.float64)
    server.pause()
    fut = server.submit(_requests(ds.plan, rng, 1)[0])
    # no resume(): append itself must release the hold and drain
    assert ds.append("Orders", {"cust": np.array([0]),
                                "prod": np.array([0])}, np.ones((1, 2)))
    assert fut.done()
    server.close()


def test_coalescer_respects_max_batch_for_sub_batches(rng):
    """Two B=3 sub-batches under max_batch=4 must dispatch as two groups
    (caps 4+4), never one coalesced B=6 group in a B=8 bucket."""
    plan = build_plan(_star_tree())
    server = make_figaro_server(plan, kind="qr", dtype=jnp.float64,
                                engine=FigaroEngine(donate_data=False),
                                max_batch=4)
    seen = []
    real = server._dispatch_fn

    def spy(plan_, batch, cap):
        seen.append((int(np.shape(batch[0])[0]), cap))
        return real(plan_, batch, cap)

    server._dispatch_fn = spy
    b3 = _stack(_requests(plan, rng, 3))
    server.pause()
    futures = [server.submit(b3), server.submit(b3)]
    server.resume()
    for f in futures:
        assert np.asarray(f.result(timeout=60)).shape[0] == 3
    assert seen == [(3, 4), (3, 4)], seen
    server.close()


def test_abandoned_server_threads_exit():
    """Dropping a server without close() must not leak its worker threads:
    the finalizer's shutdown reaches both loops even though the weakref is
    already dead."""
    import gc
    import time as _time

    cap = build_capacity_plan(_star_tree())
    server = make_figaro_server(cap, kind="qr", dtype=jnp.float64,
                                engine=FigaroEngine(donate_data=False))
    server(tuple(np.asarray(d) for d in cap.data))  # starts the threads
    threads = list(server._threads)
    assert all(t.is_alive() for t in threads)
    del server
    gc.collect()
    deadline = _time.time() + 10.0
    while any(t.is_alive() for t in threads) and _time.time() < deadline:
        _time.sleep(0.05)
    assert not any(t.is_alive() for t in threads), \
        "abandoned server leaked its dispatch/completion threads"


def test_complete_loop_fails_inflight_futures_when_server_dies():
    """A group already dispatched to the completion queue when the server is
    collected must fail its futures, not leave them unresolved forever."""
    import queue as _queue

    from repro.train import async_serve as asv

    item = asv._Request()
    later = asv._Request()
    out_q = _queue.Queue()
    out_q.put(([item], [item], None))
    out_q.put(([later], [later], None))
    asv._complete_loop(lambda: None, out_q)  # dead weakref from the start
    for it in (item, later):
        assert it.future.done()
        with pytest.raises(RuntimeError, match="garbage-collected"):
            it.future.result(timeout=0)


def test_constructor_validation():
    cap = build_capacity_plan(_star_tree())
    with pytest.raises(ValueError, match="max_batch"):
        make_figaro_server(cap, kind="qr", max_batch=0)
    with pytest.raises(ValueError, match="queue_depth"):
        make_figaro_server(cap, kind="qr", queue_depth=0)
    with pytest.raises(ValueError, match="built plan"):
        AsyncFigaroServer(PlanHolder(), lambda *a: None)
