"""Distributed FiGaRo: domain-parallel QR over a mesh (paper Exp. 2 / §7).

Demonstrates the two parallel layers on an 8-device host mesh:
  * partitioned FiGaRo — the fact table is split into row blocks; each worker
    runs FiGaRo independently; the partial R factors merge via TSQR (the
    paper's "domain parallelism", Fig. 6);
  * mesh-distributed THIN/TSQR post-processing of R0 via shard_map — the
    per-thread Givens scheme of §7 mapped onto jax.lax collectives.

Must run as its own process (device count locks at jax init):
  PYTHONPATH=src python examples/distributed_qr.py
"""

import os

os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")

import jax  # noqa: E402

jax.config.update("jax_enable_x64", True)

from repro.compile_cache import enable_compile_cache  # noqa: E402

enable_compile_cache()

import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.compat import AxisType, make_mesh  # noqa: E402
from repro.core.distributed import (distributed_postprocess_r0,  # noqa: E402
                                    partitioned_figaro_qr)
from repro.core.figaro import figaro_r0  # noqa: E402
from repro.core.join_tree import build_plan  # noqa: E402
from repro.core.postprocess import normalize_sign  # noqa: E402
from repro.data.relational import yelp_like  # noqa: E402

print(f"devices: {len(jax.devices())}")
mesh = make_mesh((len(jax.devices()),), ("data",),
                 axis_types=(AxisType.Auto,))

tree = yelp_like(scale=400)
plan = build_plan(tree)

# single-worker reference
r_ref = np.asarray(partitioned_figaro_qr(tree, 1))

# 1) domain parallelism: 8 fact-table partitions
r_part = np.asarray(partitioned_figaro_qr(tree, 8))
err1 = np.abs(np.abs(r_part) - np.abs(r_ref)).max() / np.abs(r_ref).max()
print(f"partitioned FiGaRo (8 workers) rel err: {err1:.2e}")

# 2) mesh TSQR post-processing of R0
r0 = figaro_r0(plan, dtype=jnp.float64)
r_mesh = np.asarray(distributed_postprocess_r0(r0, mesh, "data"))
err2 = np.abs(np.abs(r_mesh) - np.abs(r_ref)).max() / np.abs(r_ref).max()
print(f"mesh TSQR post-process         rel err: {err2:.2e}")

assert err1 < 1e-10 and err2 < 1e-10
print("OK — identical R under every parallel decomposition "
      "(the rotation-sequence freedom the paper exploits).")
