"""Experiment 2 (Fig. 6): domain-parallel scaling.

The paper splits each relation into contiguous blocks per thread. Here the
same freedom is exercised by ``partitioned_figaro_qr``: fact-table row
partitions, independent FiGaRo per partition, TSQR combine (the paper's
domain parallelism).

This container exposes ONE physical core, so wall-clock speedup is not
observable; the benchmark reports the *load balance* (max rows per worker,
which on real hardware bounds the parallel time) plus wall time for
reference, and asserts result invariance across partition counts.
"""

from __future__ import annotations

import jax.numpy as jnp
import numpy as np

from repro.core.distributed import partitioned_figaro_qr
from repro.core.join_tree import build_plan
from repro.core.qr import figaro_qr
from repro.data.relational import yelp_like

from ._util import Csv, timeit


def run(csv: Csv, *, fast: bool = False) -> None:
    tree = yelp_like(scale=200 if fast else 500)
    plan = build_plan(tree)
    r_ref = np.asarray(figaro_qr(plan, dtype=jnp.float64))
    fact_rows = plan.nodes[plan.root].data.shape[0]
    for parts in (1, 2, 4, 8):
        t = timeit(lambda: partitioned_figaro_qr(tree, parts), repeats=1)
        r_p = np.asarray(partitioned_figaro_qr(tree, parts))
        err = np.abs(np.abs(r_p) - np.abs(r_ref)).max() / np.abs(r_ref).max()
        case = f"parts{parts}"
        csv.add("scaling", case, "wall_s_1core", t)
        csv.add("scaling", case, "max_rows_per_worker",
                int(np.ceil(fact_rows / parts)))
        csv.add("scaling", case, "result_rel_err", float(err))


if __name__ == "__main__":
    c = Csv()
    c.header()
    run(c)
