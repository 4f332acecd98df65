"""Test helpers: random acyclic databases + R-factor comparison."""

from __future__ import annotations

import re

import numpy as np

from repro.core.join_tree import JoinTree, build_plan
from repro.core.materialize import materialize_join
from repro.core.relation import Database, full_reduce

__all__ = ["random_acyclic_db", "r_close", "TOPOLOGIES", "hlo_instructions"]

# (name, edges, root) — relation names are S1..S4; key attrs named for edges.
TOPOLOGIES = {
    "chain2": ([("S1", "S2")], "S1"),
    "chain3": ([("S1", "S2"), ("S2", "S3")], "S1"),
    "star3": ([("S1", "S2"), ("S1", "S3")], "S1"),
    "snowflake4": ([("S1", "S2"), ("S2", "S3"), ("S2", "S4")], "S1"),
}


def random_acyclic_db(topology: str, rng: np.random.Generator, *,
                      max_rows: int = 9, max_cols: int = 3,
                      max_card: int = 4, cartesian: bool = False,
                      retries: int = 20):
    """Random database + join tree for a named topology.

    Key attribute ``e{i}`` is shared by the two endpoints of edge i. With
    ``cartesian=True`` all key columns are constant (join = Cartesian
    product) — exercises the degenerate grouping path. Redraws (up to
    ``retries``) when full reduction empties a relation out.
    """
    edges, root = TOPOLOGIES[topology]
    rel_attrs: dict[str, list[str]] = {}
    for i, (a, b) in enumerate(edges):
        rel_attrs.setdefault(a, []).append(f"e{i}")
        rel_attrs.setdefault(b, []).append(f"e{i}")
    last_err = None
    for _ in range(retries):
        tables = {}
        for name, attrs in rel_attrs.items():
            m = int(rng.integers(2, max_rows + 1))
            nd = int(rng.integers(1, max_cols + 1))
            keys = {a: (np.zeros(m, np.int64) if cartesian
                        else rng.integers(0, max_card, size=m))
                    for a in attrs}
            tables[name] = (keys, rng.normal(size=(m, nd)),
                            [f"{name.lower()}y{j}" for j in range(nd)])
        db = Database.from_arrays(tables)
        try:
            db = full_reduce(db, edges)
        except ValueError as e:  # some relation emptied out — redraw
            last_err = e
            continue
        tree = JoinTree.from_edges(db, root, edges)
        return db, tree, build_plan(tree)
    raise ValueError(f"no non-empty db after {retries} draws: {last_err}")


def r_close(r_a, r_b, *, rtol=1e-9) -> bool:
    r_a, r_b = np.asarray(r_a), np.asarray(r_b)
    scale = max(np.abs(r_b).max(), 1e-30)
    return np.abs(r_a - r_b).max() / scale < rtol


def materialized(tree: JoinTree) -> np.ndarray:
    return np.asarray(materialize_join(tree))


_SOURCE_TABLE = re.compile(
    r"(FileNames|FunctionNames|FileLocations|StackFrames)$|\d+ ")


def hlo_instructions(hlo: str) -> str:
    """An optimized HLO text without its metadata and its tables of source
    locations (which the CPU prints after the computations, the TPU before):
    what the compiler made, not where it came from."""
    body = "\n".join(line for line in hlo.splitlines()
                     if not _SOURCE_TABLE.match(line))
    return re.sub(r",? metadata=\{[^}]*\}", "", body)
