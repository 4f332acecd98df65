"""Finds a cell's parts by name: configuration, traffic, limits, checks,
metrics.

Every part lives in a file of its own under ``bench/``, named after the
entry in ``BENCHMARK.json`` that uses it, so a later cell or metric is added
as new files and entries without editing anything that is there.
"""

from __future__ import annotations

import importlib.util
import json
import re
from pathlib import Path

BENCH = Path(__file__).resolve().parent
REPO = BENCH.parent
NAME = re.compile(r"[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}")


def _file(kind: str, name: str, suffix: str) -> Path:
    if not NAME.fullmatch(name):
        raise ValueError(f"bad {kind} name {name!r}")
    path = BENCH / kind / f"{name}{suffix}"
    if not path.is_file():
        have = sorted(p.name[:-len(suffix)]
                      for p in (BENCH / kind).glob(f"*{suffix}"))
        raise KeyError(f"unknown {kind} entry {name!r}; have {have}")
    return path


def _module(path: Path, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def benchmark(path: Path = REPO / "BENCHMARK.json") -> dict:
    with open(path) as f:
        return json.load(f)


def peaks() -> dict:
    """Published peaks per ``device_kind`` (``peaks.json``)."""
    with open(BENCH / "peaks.json") as f:
        return json.load(f)["devices"]


def workload(bench: dict, name: str) -> dict:
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"unknown workload {name!r}; have {sorted(cells)}")
    return cells[name]


def config(name: str):
    """The deployment module ``configs/<name>.py``."""
    return _module(_file("configs", name, ".py"), f"bench_config_{name}")


def traffic(name: str) -> dict:
    with open(_file("traffic", name, ".json")) as f:
        return json.load(f)


def limits(cell: str) -> dict:
    """``{check: {"limit": x, ...}}`` for one cell."""
    with open(_file("limits", cell, ".json")) as f:
        return json.load(f)


def check(kind: str):
    """``compare(answer, moments) -> {check: error}`` of ``checks/<kind>.py``."""
    return _module(_file("checks", kind, ".py"), f"bench_check_{kind}").compare


def reader(metric: str):
    """``read(run) -> float | None`` of ``metrics/<metric>.py``."""
    return _module(_file("metrics", metric, ".py"),
                   "bench_metric_" + metric.replace(".", "_")).read


def metrics_for(bench: dict, cell: str, traced: bool) -> list[dict]:
    """The metric entries a run of ``cell`` reports: the end-to-end ones
    untraced, the per-layer ones traced. An entry without ``workloads`` is
    reported by every cell that reports the end-to-end metric it moves
    (per-layer) or by every cell (end-to-end)."""
    e2e = [m for m in bench["end_to_end"]
           if cell in m.get("workloads", [cell])]
    if not traced:
        return e2e
    reported = {m["name"] for m in e2e}
    return [m for m in bench["per_layer"]
            if (cell in m["workloads"] if "workloads" in m
                else m["moves"] in reported)]
