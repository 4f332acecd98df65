"""Whole runs of every cell at its configuration's tiny size on the CPU.

A rehearsal skips the look for a chip and drives the rest of a run: set-up
through the façade, warm-up, the window's load, and the comparison with the
reference that decides ``correct``. It reports no metric. The sound program
must come out correct; its control (the same cell served in the precision
below the traffic's) and a served answer altered where it is produced must
not.
"""

import time

import jax
import pytest

from bench import harness, registry
from bench import run as run_cli
from repro.train import async_serve

CELLS = [w["name"] for w in registry.benchmark()["workloads"]]
SEED = 2 ** 31 + 101


def rehearse(cell, **kw):
    return harness.run(cell, SEED, 1.0, False, started=time.perf_counter(),
                       rehearse=True, say=lambda line: None, **kw)


@pytest.mark.parametrize("cell", CELLS)
def test_sound_program_is_correct_and_its_control_is_not(cell):
    out = rehearse(cell)
    assert out["correct"], out["checks"]
    assert "metrics" not in out and "device" not in out
    assert out["failed"] == 0 and out["attempted"] > 0
    ctl = rehearse(cell, control=True)
    assert not ctl["correct"]
    assert any(c["value"] > c["limit"] for c in ctl["checks"].values())


def _altered(slice_out):
    """The first request of every batch gets its answer scaled by 1.001."""
    def wrong(out, offset, b, single):
        got = slice_out(out, offset, b, single)
        return jax.tree.map(lambda x: x * 1.001, got) if offset == 0 else got
    return wrong


def _neighbour(slice_out):
    """Every request of a batch of two or more gets its neighbour's answer."""
    def wrong(out, offset, b, single):
        size = jax.tree.leaves(out)[0].shape[0]
        return slice_out(out, (offset + 1) % size, b, single)
    return wrong


# A batchmate's answer is a fault only where batches of two or more form:
# the closed loop's; the tiny open loop's requests are served one by one.
FAULTS = [(cell, fault) for cell in CELLS for fault in (_altered, _neighbour)
          if fault is _altered or registry.traffic(registry.workload(
              registry.benchmark(), cell)["traffic"])["arrival"] == "closed"]


@pytest.mark.parametrize("cell, fault", FAULTS)
def test_an_answer_altered_where_it_is_produced_is_caught(monkeypatch, cell,
                                                          fault):
    monkeypatch.setattr(async_serve, "_slice_out",
                        fault(async_serve._slice_out))
    out = rehearse(cell)
    assert not out["correct"]


def test_a_rehearsal_prints_no_result_line(capsys):
    rc = run_cli.main(["--workload", CELLS[0], "--seed", "7",
                       "--seconds", "1", "--rehearse"])
    out = capsys.readouterr()
    assert rc == 1
    assert "rehearsal, correct=True" in out.err
    assert out.err.rstrip().splitlines()[-2].startswith("check r_err ")
    assert not any(line.startswith("{") for line in out.out.splitlines())
