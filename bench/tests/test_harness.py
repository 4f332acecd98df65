"""The benchmark's harness on the CPU: lookup by name, window arithmetic,
trace reduction, the reference, and the refusal to run without a TPU."""

import gzip
import json
import os
from pathlib import Path
from types import SimpleNamespace

import jax
import numpy as np
import pytest

from bench import load, reference, registry, tracing
from bench import run as run_cli

HERE = Path(__file__).resolve().parent
BENCH = registry.benchmark()
CELLS = [w["name"] for w in BENCH["workloads"]]
CONFIGS = [c["name"] for c in BENCH["configs"]]


# -- lookup by name ----------------------------------------------------------

@pytest.mark.parametrize("cell", CELLS)
def test_every_cell_finds_its_parts_by_name(cell):
    w = registry.workload(BENCH, cell)
    cfg = registry.config(w["config"])
    assert {"SIZES", "TINY", "ROOT", "EDGES", "relations", "SOURCE", "REDUCED",
            "ASSUMED"} <= set(dir(cfg))
    entry = next(c for c in BENCH["configs"] if c["name"] == w["config"])
    assert set(cfg.REDUCED) == set(entry["reduced"])
    assert cfg.SOURCE == entry["source"]
    mix = registry.traffic(w["traffic"])
    assert mix["arrival"] in ("closed", "poisson")
    assert callable(registry.check(mix["kind"]))
    assert np.dtype(mix["control_dtype"]).itemsize < \
        np.dtype(mix["dtype"]).itemsize
    assert registry.limits(cell)
    for traced in (False, True):
        entries = registry.metrics_for(BENCH, cell, traced)
        assert entries
        for m in entries:
            assert callable(registry.reader(m["name"]))


def test_every_part_is_used_by_a_cell():
    used = {"configs": CONFIGS, "limits": CELLS,
            "traffic": [w["traffic"] for w in BENCH["workloads"]],
            "checks": [registry.traffic(w["traffic"])["kind"]
                       for w in BENCH["workloads"]],
            "metrics": [m["name"] for k in ("end_to_end", "per_layer")
                        for m in BENCH[k]]}
    for kind, names in used.items():
        files = {p.stem for p in (registry.BENCH / kind).iterdir()
                 if p.suffix in (".py", ".json")}
        assert files == set(names), kind


def test_metrics_for_splits_end_to_end_and_per_layer():
    names = lambda cell, traced: {m["name"] for m in registry.metrics_for(
        BENCH, cell, traced)}
    assert names("favorita.qr.saturated", False) == {"requests_per_s",
                                                     "setup_s"}
    assert names("favorita.qr.saturated", True) == {
        "device_idle_pct.saturated", "device_ms_per_request.saturated",
        "engine_compiles.saturated"}


@pytest.mark.parametrize("lookup, name", [
    (registry.config, "no_such_config"), (registry.traffic, "no_such_mix"),
    (registry.limits, "no.such.cell"), (registry.reader, "no_such_metric"),
    (registry.check, "no_such_kind"),
    (lambda n: registry.workload(BENCH, n), "no.such.cell")])
def test_unknown_name_is_an_error(lookup, name):
    with pytest.raises(KeyError, match=name):
        lookup(name)


@pytest.mark.parametrize("name", ["../run", "a/b", "", "x y"])
def test_name_outside_the_alphabet_is_refused(name):
    with pytest.raises(ValueError):
        registry.traffic(name)


def test_peaks_table_holds_the_v5e():
    v5e = registry.peaks()["TPU v5 lite"]
    assert v5e["bf16_flops_per_s"] == 197e12
    assert v5e["hbm_bytes_per_s"] == 819e9
    assert v5e["hbm_bytes"] == 16e9


def test_a_run_without_a_tpu_fails_and_prints_no_result(capsys):
    assert jax.devices()[0].platform != "tpu"
    cache = (jax.config.jax_compilation_cache_dir,
             os.environ.get("JAX_COMPILATION_CACHE_DIR"))
    rc = run_cli.main(["--workload", CELLS[0], "--seed", "1",
                       "--seconds", "1"])
    out = capsys.readouterr()
    assert rc == 1
    assert "NoChip" in out.err
    assert not any(line.startswith("{") for line in out.out.splitlines())
    # Refused before the compilation cache is touched.
    assert (jax.config.jax_compilation_cache_dir,
            os.environ.get("JAX_COMPILATION_CACHE_DIR")) == cache


# -- window and percentile arithmetic on fixed timestamps --------------------

def test_batch_window_counts_whole_batches():
    # Batches of 4 complete every 0.5 s, the requests of one batch 1 ms apart.
    done = [b * 0.5 + k * 0.001 for b in range(12) for k in range(4)]
    start, end, completed = load.batch_window(done, 2.0, 4)
    assert (start, end, completed) == (0.0, 2.0, 16)
    rate = registry.reader("requests_per_s")(
        {"result": {"window": (start, end, completed)}})
    assert rate == 8.0
    assert load.batch_window(done, 6.0, 4) is None  # never closed


def test_batch_window_keeps_a_stall_inside():
    done = [0.0, 0.0, 0.0, 0.0, 1.0, 1.0, 1.0, 1.0, 4.0, 4.0, 4.0, 4.0]
    assert load.batch_window(done, 1.5, 4) == (0.0, 4.0, 8)


def _open(latencies_s):
    reqs = []
    for i, lat in enumerate(latencies_s):
        r = load.Request(0, due=float(i))
        r.submitted, r.done = float(i), float(i) + lat
        reqs.append(r)
    return {"result": {"arrival": "open", "requests": reqs}}


def test_latency_percentiles_on_fixed_values():
    lat = load.latencies(_open([0.1 * k for k in range(1, 11)])["result"])
    assert load.percentile(lat, 50) == pytest.approx(0.55)
    assert load.percentile(lat, 90) == pytest.approx(0.91)


def test_a_failed_request_leaves_no_latency():
    run = _open([0.1, 0.2])
    run["result"]["requests"][1].error = RuntimeError("lost")
    assert load.latencies(run["result"]) is None


def test_poisson_gaps_are_the_same_set_in_another_order():
    a = load.poisson_gaps(4.0, 30, np.random.default_rng(1))
    b = load.poisson_gaps(4.0, 30, np.random.default_rng(2 ** 31 + 7))
    assert len(a) == 120
    assert not np.array_equal(a, b)
    assert np.array_equal(np.sort(a), np.sort(b))
    assert a.sum() == pytest.approx(30, rel=0.05)


# -- trace reduction ---------------------------------------------------------

def _interval_trace(ops, window, marks=()):
    """A stand-in for `jax.profiler.ProfileData` with one device."""
    ev = lambda name, s, e: SimpleNamespace(name=name, start_ns=s,
                                            duration_ns=e - s)
    line = lambda name, evs: SimpleNamespace(name=name, events=evs)
    device = SimpleNamespace(name="/device:TPU:0", lines=[
        line(tracing.OPS_LINE, [ev(n, s, e) for n, s, e in ops]),
        line(tracing.MODULES_LINE, [ev("jit__qr_batched_impl", 100, 200),
                                    ev("jit__qr_batched_impl", 500, 900)])])
    host = SimpleNamespace(name="/host:CPU", lines=[line("main", [
        ev(tracing.WINDOW, *window)] + [ev(n, s, e) for n, s, e in marks])])
    return SimpleNamespace(planes=[host, device])


def test_reduce_takes_the_union_of_overlapping_ops():
    trace = tracing.reduce(_interval_trace(
        [("fusion", 100, 300), ("dot", 200, 400), ("copy", 600, 700),
         ("late", 900, 1200)], window=(0, 1000),
        marks=[("bench.wait", 0, 1000), ("bench.submit", 420, 580)]))
    # Busy 100-400, 600-700 and 900-1000 (the window ends at 1000).
    assert trace["busy_s"] == pytest.approx(500e-9)
    assert trace["window_s"] == pytest.approx(1000e-9)
    assert tracing.idle_pct(trace) == pytest.approx(50.0)
    assert tracing.executions(trace["modules"], "_batched_impl") == 2
    assert trace["device_ops"][0] == ["fusion", pytest.approx(200e-9)]
    # Gaps: 0-100 and 700-900 under waiting alone, 400-600 under a submit.
    assert [[n, round(s * 1e9)] for n, s in trace["idle_gaps"]] == [
        ["bench.submit", 200], ["bench.wait", 200], ["bench.wait", 100]]


def test_reduce_refuses_a_trace_without_the_window():
    bad = _interval_trace([("dot", 0, 10)], window=(0, 10))
    bad.planes[0].lines[0].events.pop(0)
    with pytest.raises(ValueError, match="bench.window"):
        tracing.reduce(bad)


def test_reduce_reads_a_trace_recorded_on_the_chip():
    with gzip.open(HERE / "data" / "v5e_qr_window.xplane.pb.gz") as f:
        profile = jax.profiler.ProfileData.from_serialized_xspace(f.read())
    with open(HERE / "data" / "v5e_qr_window.json") as f:
        want = json.load(f)
    trace = tracing.reduce(profile)
    assert trace["devices"] == 1
    assert 0 < trace["busy_s"] <= trace["window_s"]
    for key in ("busy_s", "window_s"):
        assert trace[key] == pytest.approx(want[key], rel=1e-12)
    assert tracing.executions(trace["modules"], "_batched_impl") == \
        want["batched_executions"]
    assert [n for n, _ in trace["device_ops"]] == want["top_ops"]
    assert len(trace["idle_gaps"]) <= tracing.TOP


# -- the reference -----------------------------------------------------------

@pytest.mark.parametrize("seed", [2 ** 31 + 11, 3_000_000_019])
@pytest.mark.parametrize("config", CONFIGS)
def test_reference_matches_the_materialized_join(config, seed):
    from repro.core.join_tree import JoinTree
    from repro.core.materialize import materialize_join
    from repro.core.relation import Database

    cfg = registry.config(config)
    tables = cfg.relations(np.random.default_rng(seed), cfg.TINY)
    tree = JoinTree.from_edges(Database.from_arrays(tables), cfg.ROOT,
                               list(cfg.EDGES))
    a = materialize_join(tree)
    order = reference.preorder(cfg.ROOT, cfg.EDGES)
    assert order == tree.preorder()
    join = reference.JoinReference({r: tables[r][0] for r in order},
                                   cfg.ROOT, cfg.EDGES, block_rows=5_000)
    got, = join.moments([{r: tables[r][1] for r in order}])
    assert got["rows"] == a.shape[0]
    np.testing.assert_allclose(got["gram"], a.T @ a, rtol=1e-12)
    r = reference.r_factor(got["gram"])
    np.testing.assert_allclose(r.T @ r, a.T @ a, rtol=1e-10)
    assert np.all(np.diag(r) > 0)


@pytest.mark.parametrize("config", CONFIGS)
def test_every_seed_gives_the_same_sizes(config):
    cfg = registry.config(config)
    shapes = [{n: (len(next(iter(k.values()))), v.shape)
               for n, (k, v, _) in cfg.relations(
                   np.random.default_rng(seed), cfg.TINY).items()}
              for seed in (0, 2 ** 31 + 5)]
    assert shapes[0] == shapes[1]
