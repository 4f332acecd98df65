"""Device time by phase and the serving spans, read from raw profiler traces:
two recorded on a v5e at the tiny size (one before the program had its
phase scopes, one after) and small synthetic ones."""

import gzip
import json
from pathlib import Path

import jax
import pytest

from bench import load, phases, tracing

DATA = Path(__file__).resolve().parent / "data"
SCOPED = DATA / "v5e_qr_phases.xplane.pb.gz"
SCOPELESS = DATA / "v5e_qr_window.xplane.pb.gz"
DEVICE_METRICS = {"counts": ("figaro.counts",),
                  "node_passes": phases.NODE_PASSES,
                  "assembly": ("figaro.assemble",),
                  "postprocess": ("figaro.postprocess",)}


@pytest.fixture(scope="module")
def scoped():
    return phases.reduce(phases.load(SCOPED))


@pytest.fixture(scope="module")
def scopeless():
    return phases.reduce(phases.load(SCOPELESS))


@pytest.mark.parametrize("path", [SCOPED, SCOPELESS], ids=["scoped",
                                                             "scopeless"])
def test_phase_groups_sum_to_the_busy_time(path):
    trace = phases.reduce(phases.load(path))
    assert trace["devices"] == 1
    assert sum(trace["groups"].values()) == pytest.approx(trace["busy_s"],
                                                          rel=1e-12)
    with gzip.open(path) as f:
        profile = jax.profiler.ProfileData.from_serialized_xspace(f.read())
    # The device's busy union as the accepted reduction reads it, from
    # timestamps rounded to whole nanoseconds.
    assert trace["busy_s"] == pytest.approx(
        tracing.reduce(profile)["busy_s"], rel=1e-3)


def test_the_chip_trace_reads_every_device_phase(scoped):
    with open(DATA / "v5e_qr_phases.json") as f:
        want = json.load(f)
    assert scoped["scopes_seen"]
    assert scoped["groups"] == pytest.approx(want["device_phases_s"],
                                             rel=1e-12)
    done = phases.resolved_in_window(scoped)
    assert done == want["resolved_requests"] > 0
    for names in DEVICE_METRICS.values():
        assert phases.ms_per_request(phases.phases(scoped), names, done) > 0
    assert set(scoped["groups"]) <= set(phases.PHASES) | {phases.UNSCOPED,
                                                          phases.OUTSIDE}


def test_a_trace_without_scopes_reads_no_phase(scopeless):
    assert not scopeless["scopes_seen"]
    assert set(scopeless["groups"]) == {phases.UNSCOPED, phases.OUTSIDE}
    assert phases.phases(scopeless) is None
    for names in DEVICE_METRICS.values():
        assert phases.ms_per_request(phases.phases(scopeless), names,
                                     42) is None


def test_idle_gaps_are_named_by_the_serving_spans(scoped, scopeless):
    assert any(n.startswith(phases.SERVE) for n, _ in scoped["idle_gaps"])
    assert len(scoped["idle_gaps"]) == tracing.TOP
    # Without serving spans the benchmark's own annotations name the gaps,
    # as the accepted reduction names them.
    with gzip.open(SCOPELESS) as f:
        profile = jax.profiler.ProfileData.from_serialized_xspace(f.read())
    assert [n for n, _ in scopeless["idle_gaps"]] == \
        [n for n, _ in tracing.reduce(profile)["idle_gaps"]]


def test_serving_spans_carry_batch_and_requests(scoped):
    steps = {n[len(phases.SERVE):] for n, *_ in scoped["serve"]}
    assert steps == {"coalesce", "depth_wait", "stage", "launch", "ready",
                     "resolve"}
    launches = [r for n, _, _, _, r in scoped["serve"]
                if n == phases.SERVE + "launch"]
    assert set(launches) == {4}  # the closed loop's full batches
    batches = [b for n, _, _, b, _ in scoped["serve"]
               if n == phases.SERVE + "coalesce"]
    assert batches == sorted(batches)


def test_gap_name_prefers_work_to_waiting():
    serve = [("figaro.serve.depth_wait", 0, 100, 1, 4),
             ("figaro.serve.coalesce", 40, 60, 2, 4)]
    marks = [("bench.wait", 0, 100), ("bench.submit", 10, 90)]
    assert phases.gap_name(0, 100, serve, marks) == "figaro.serve.coalesce"
    assert phases.gap_name(0, 30, serve, marks) == "figaro.serve.depth_wait"
    assert phases.gap_name(200, 300, serve, marks) == "host.unannotated"
    assert phases.gap_name(0, 30, [], marks) == "bench.submit"


def test_innermost_counts_a_loop_and_its_body_once():
    ops = [(0, 100, "figaro.postprocess"), (10, 20, "figaro.project"),
           (30, 60, "figaro.project"), (30, 40, "figaro.assemble"),
           (150, 250, "outside_pipeline")]
    time = phases._innermost(ops, 0, 200)
    assert time == pytest.approx({"figaro.postprocess": 60e-9,
                                  "figaro.project": 30e-9,
                                  "figaro.assemble": 10e-9,
                                  "outside_pipeline": 50e-9})


def test_serving_metrics_from_two_snapshots():
    before = {"dispatches": 10, "dispatched_requests": 40,
              "queue_wait_s": 30.0, "dispatch_host_s": 0.5}
    after = {"dispatches": 13, "dispatched_requests": 52,
             "queue_wait_s": 40.8, "dispatch_host_s": 0.62}
    got = phases.serving(before, after)
    assert got == pytest.approx({"requests_per_dispatch": 4.0,
                                 "queue_wait_ms": 900.0,
                                 "dispatch_host_ms_per_batch": 40.0})
    assert set(phases.serving(after, after).values()) == {None}


def test_the_reader_of_a_run_counts_requests_as_the_harness_does(tmp_path):
    with gzip.open(SCOPED) as f:
        (tmp_path / "run.xplane.pb").write_bytes(f.read())
    reqs = [load.Request(0, 0.0) for _ in range(5)]
    for i, r in enumerate(reqs):
        r.done = float(i)
    run = {"trace": {"devices": 1}, "trace_span": [0.5, 3.5],
           "result": {"requests": reqs}}
    trace = phases.reduce(phases.load(SCOPED))
    got = phases.read_ms_per_request(run, ("figaro.counts",), tmp_path)
    assert got == pytest.approx(1e3 * trace["groups"]["figaro.counts"] / 3)
    assert phases.read_ms_per_request(dict(run, trace=None),
                                      ("figaro.counts",), tmp_path) is None


def test_the_descriptor_reads_what_profile_data_reads():
    with gzip.open(SCOPELESS) as f:
        raw = f.read()
    ours = phases.load(raw)
    theirs = jax.profiler.ProfileData.from_serialized_xspace(raw)
    for a, b in zip(ours.planes, theirs.planes):
        assert a.name == b.name
        for la, lb in zip(a.lines, b.lines):
            assert la.name == lb.name
            ours_events = phases._events(a, la)
            assert [e[0] for e in ours_events] == [e.name for e in lb.events]
            assert [e[1] for e in ours_events] == pytest.approx(
                [e.start_ns for e in lb.events], abs=1.0)


def test_the_command_line_prints_the_summary(capsys):
    assert phases.main([str(SCOPED)]) == 0
    out = json.loads(capsys.readouterr().out)
    assert out["scopes_seen"]
    assert set(out["device_phases_ms_per_request"]) == set(
        out["device_phases_s"])
