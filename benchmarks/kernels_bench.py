"""Kernel-layer benchmark: FiGaRo inner loop (segmented head/tail) and the
fused node kernel.

On this CPU container the Pallas kernels execute in ``interpret=True`` mode
(Python emulation — NOT indicative of TPU speed); wall time is reported for
the XLA path that actually runs here, and the kernel path is checked for
agreement. On TPU the kernel path replaces the XLA scan with one fused
HBM→VMEM pass (see EXPERIMENTS.md §Perf for the roofline accounting).

Emits the standard ``BENCH_kernels.json`` (see `_util.write_bench_json`) so
the kernel-layer perf trajectory is tracked alongside the engine's.
"""

from __future__ import annotations

import jax.numpy as jnp
import numpy as np

from repro.core.heads_tails import segmented_head_tail
from repro.kernels.node_fused import fused_node_pass, fused_node_pass_ref

from ._util import Csv, timeit, write_bench_json


def _segments(rng, m):
    """Random contiguous segments over m rows in m // 16 slots, some empty:
    position within segment [m], each slot's last row and liveness [K]."""
    seg = np.sort(rng.integers(0, m // 16, size=m)).astype(np.int32)
    pos = np.zeros(m, np.int32)
    pos[1:] = np.where(seg[1:] == seg[:-1], 1, 0)
    pos = np.cumsum(pos) * (pos > 0)
    count = np.bincount(seg, minlength=m // 16)
    last = np.maximum(np.cumsum(count) - 1, 0).astype(np.int32)
    return jnp.array(pos), jnp.array(last), jnp.array(count > 0)


def run(csv: Csv, *, fast: bool = False) -> None:
    rows: list[dict] = []

    def add(case, metric, value):
        csv.add("kernels", case, metric, value)
        rows.append({"case": case, "metric": metric, "value": float(value)})

    rng = np.random.default_rng(0)
    sizes = [(4096, 64), (16384, 64)] if fast else \
        [(4096, 64), (16384, 64), (65536, 64)]
    for m, n in sizes:
        data = jnp.array(rng.normal(size=(m, n)), jnp.float32)
        w = jnp.array(rng.uniform(0.5, 2.0, size=m), jnp.float32)
        args = (data, w, *_segments(rng, m))
        t = timeit(lambda: segmented_head_tail(*args))
        case = f"headtail_{m}x{n}"
        add(case, "xla_path_s", t)
        add(case, "rows_per_s", m / t)
        if m <= 4096:  # interpret mode is slow; validate on the small size
            h1, t1, _ = segmented_head_tail(*args, use_kernel=False)
            h2, t2, _ = segmented_head_tail(*args, use_kernel=True)
            add(case, "kernel_max_abs_err", float(jnp.abs(t1 - t2).max()))

    # -- fused node pass: one-kernel mask+scan+scale+emit vs its XLA ref ----
    # The ref is the path figaro_r0(use_kernel=False) effectively runs; the
    # fused kernel replaces three-plus HBM round-trips per node with one.
    for m, n in [(4096, 64)] if fast else [(4096, 64), (16384, 64)]:
        data = jnp.array(rng.normal(size=(m, n)), jnp.float32)
        w = jnp.array(rng.uniform(0.5, 2.0, size=m), jnp.float32)
        pos, last, live = _segments(rng, m)
        emit = jnp.array(rng.uniform(0.5, 2.0, size=m), jnp.float32)
        f_args = (data, w, pos, emit, last, live)
        t_ref = timeit(lambda: fused_node_pass_ref(*f_args))
        case = f"node_fused_{m}x{n}"
        add(case, "xla_ref_s", t_ref)
        add(case, "rows_per_s", m / t_ref)
        if m <= 4096:  # interpret-mode check on the small size only
            s1, h1, nn1 = fused_node_pass_ref(*f_args)
            s2, h2, nn2 = fused_node_pass(*f_args)
            add(case, "kernel_slab_max_abs_err", float(jnp.abs(s1 - s2).max()))
            add(case, "kernel_head_max_abs_err", float(jnp.abs(h1 - h2).max()))

    write_bench_json("kernels", rows)


if __name__ == "__main__":
    c = Csv()
    c.header()
    run(c)
