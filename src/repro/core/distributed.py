"""Distributed FiGaRo: THIN/TSQR on the mesh + fact-partitioned multi-pod QR.

Two levels, mirroring the paper's own structure (§7 THIN, §8 Exp 2):

1. **Mesh post-processing** (`distributed_postprocess_r0`): R₀'s rows are
   sharded over a mesh axis; each shard runs a local blocked-Householder QR,
   then a butterfly ``ppermute`` combine (log₂ P rounds of QR on stacked
   [2n × n] triangles) leaves every shard holding the identical final R.
   This is the paper's dominant cost parallelized with `shard_map` — the TPU
   version of THIN's per-thread Givens + parallel combine.

2. **Fact-table domain partitioning** (`partitioned_figaro_qr`): the join is a
   disjoint union over partitions of the fact (root) relation's rows (key
   groups kept whole; dimension relations replicated) — so
   ``A = vstack(A_1..A_P)`` and ``R = tsqr-combine(R_1..R_P)``. Each partition
   runs the full FiGaRo pipeline independently (in production: one partition
   per pod, SPMD; here: per-partition jit programs + the same combine). This
   is how FiGaRo scales past a single pod, and it is *elastic*: P is chosen at
   launch from the devices that exist.

Orthogonal-freedom note: any composition of orthogonal reductions yields the
same R up to row signs (tests pin signs via `normalize_sign` and check the
Gram invariant), which is exactly the freedom the paper exploits.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.compat import shard_map

from .figaro import figaro_r0
from .join_tree import JoinTree, build_plan
from .postprocess import blocked_qr_r, householder_qr_r, normalize_sign, tsqr_r
from .relation import Database, Relation

__all__ = [
    "butterfly_qr_combine",
    "distributed_postprocess_r0",
    "distributed_qr_r",
    "partition_fact_table",
    "partitioned_figaro_qr",
]


def butterfly_qr_combine(r_local: jnp.ndarray, axis_name: str,
                         axis_size: int, leaf_qr=householder_qr_r) -> jnp.ndarray:
    """Inside shard_map: combine per-shard R factors so all shards hold the
    final R.

    For a power-of-two ``axis_size``: log₂(P) butterfly rounds; round d stacks
    each shard's R with its distance-d partner's and re-triangularizes
    ([2n, n] QR). For any other P the pure butterfly is *invalid* — partner
    ``i ^ d`` can point past the axis (P=3 pairs shard 2 with nonexistent
    shard 3) and that shard would end the loop without the others'
    contributions. Instead the remainder shards [P₂, P) (P₂ = largest power of
    two ≤ P) are first folded into shards [0, P−P₂), the butterfly runs on the
    [0, P₂) core, and the combined R is broadcast back to the folded-away
    shards: 1 + log₂(P₂) + 1 rounds, every round a valid permutation.
    """
    axis_size = int(axis_size)
    if axis_size < 1:
        raise ValueError(f"axis_size must be a positive int, got {axis_size}")
    if axis_size == 1:
        return r_local
    r = r_local
    idx = jax.lax.axis_index(axis_name)
    core = 1 << (axis_size.bit_length() - 1)  # largest power of two <= P
    rem = axis_size - core
    if rem:  # fold shards [core, P) into [0, rem)
        r_in = jax.lax.ppermute(r, axis_name,
                                [(core + i, i) for i in range(rem)])
        r = jnp.where(idx < rem, leaf_qr(jnp.concatenate([r, r_in], axis=0)),
                      r)
    d = 1
    while d < core:
        perm = [(i, i ^ d) for i in range(core)]
        r_other = jax.lax.ppermute(r, axis_name, perm)
        # Stable stacking order (lower index first) keeps all core shards
        # bitwise identical after each round.
        lo = jnp.where(idx < (idx ^ d), r, r_other)
        hi = jnp.where(idx < (idx ^ d), r_other, r)
        r = jnp.where(idx < core, leaf_qr(jnp.concatenate([lo, hi], axis=0)),
                      r)
        d *= 2
    if rem:  # broadcast the combined R back to the folded-away shards
        r_bcast = jax.lax.ppermute(r, axis_name,
                                   [(i, core + i) for i in range(rem)])
        r = jnp.where(idx >= core, r_bcast, r)
    return r


def distributed_postprocess_r0(
    r0: jnp.ndarray,
    mesh: Mesh,
    axis: str = "data",
) -> jnp.ndarray:
    """R₀ (M×N) → R (N×N) with rows sharded over ``mesh[axis]`` (THIN on TPU)."""
    m, n = r0.shape
    p = mesh.shape[axis]
    mp = -(-m // p) * p
    if mp != m:
        r0 = jnp.concatenate([r0, jnp.zeros((mp - m, n), r0.dtype)], axis=0)
    # Pre-shard the rows over the mesh: inputs committed to a single device
    # (e.g. the stacked per-partition Rs) would otherwise be rejected by the
    # mesh-wide computation.
    r0 = jax.device_put(r0, NamedSharding(mesh, P(axis, None)))

    def shard_fn(block):  # [mp/p, n] per shard
        r_local = blocked_qr_r(block)
        return butterfly_qr_combine(r_local, axis, p, leaf_qr=householder_qr_r)

    fn = shard_map(
        shard_fn, mesh=mesh,
        in_specs=P(axis, None),
        out_specs=P(axis, None),  # each shard returns its (identical) R
    )
    out = fn(r0)  # [p*n, n] stacked identical copies
    return normalize_sign(out[:n])


def distributed_qr_r(a: jnp.ndarray, mesh: Mesh,
                     axis: str = "data") -> jnp.ndarray:
    """General tall-skinny distributed QR (used by optim.orthogonal too)."""
    return distributed_postprocess_r0(a, mesh, axis)


# ---------------------------------------------------------------------------
# Multi-pod scaling: fact-table domain partitioning.
# ---------------------------------------------------------------------------


def partition_fact_table(tree: JoinTree, num_parts: int) -> list[JoinTree]:
    """Split the root relation's rows into ``num_parts`` contiguous chunks
    (whole key groups; paper §8 Exp 2 'domain parallelism'), replicating the
    other relations. Empty chunks are dropped."""
    db = tree.db
    root = db[tree.root]
    # Root must be grouped by its sort order for contiguous whole groups;
    # sort exactly as build_plan would (no parent => canonical key order).
    root_sorted = root.sorted_by(root.key_attrs)
    m = root_sorted.num_rows
    if root.key_attrs:
        codes = np.zeros(m, dtype=np.int64)
        for a in root.key_attrs:
            codes = codes * (int(root_sorted.key_col(a).max()) + 1) + \
                root_sorted.key_col(a)
        boundaries = np.nonzero(np.r_[True, codes[1:] != codes[:-1]])[0]
    else:
        boundaries = np.arange(m)
    # Cut at group starts nearest to equal row counts.
    cuts = [0]
    for k in range(1, num_parts):
        target = k * m // num_parts
        j = int(boundaries[np.searchsorted(boundaries, target)]) \
            if target <= boundaries[-1] else m
        cuts.append(max(j, cuts[-1]))
    cuts.append(m)
    trees = []
    for lo, hi in zip(cuts[:-1], cuts[1:]):
        if hi <= lo:
            continue
        part = Relation(root.name, root.key_attrs, root.data_attrs,
                        root_sorted.keys[lo:hi], root_sorted.data[lo:hi])
        rels = dict(db.relations)
        rels[root.name] = part
        sub_db = Database(rels)
        # Dimension rows that no longer join with this fact chunk must be
        # dropped (full reduction per partition).
        from .relation import full_reduce
        sub_db = full_reduce(sub_db, tree.edges())
        trees.append(JoinTree(sub_db, dict(tree.parent)))
    return trees


def partitioned_figaro_qr(
    tree: JoinTree,
    num_parts: int,
    *,
    dtype=jnp.float64,
    use_kernel: bool = False,
    assembly: str = "padded",
    engine=None,
    mesh: Mesh | None = None,
    axis: str = "data",
) -> jnp.ndarray:
    """FiGaRo over ``num_parts`` fact partitions + TSQR combine.

    Per-partition programs are independent (different static shapes — in
    production each runs on its own pod). Each partition dispatches through
    the shared `FigaroEngine` (default: the `repro.api.default_session()`
    engine, so partitions share executables with the rest of the façade),
    whose executable cache keys on the partition's plan signature — repeat
    calls (elastic re-dispatch, refreshed data) reuse the compiled programs
    instead of re-tracing per call. `figaro.Session.partitioned_qr` is the
    façade form (session engine/mesh/dtype defaults).

    Without a ``mesh`` the partitions run (async) on the default device and
    the partial R factors are TSQR-combined locally. With a ``mesh`` each
    partition's program is placed on its own device slot (round-robin over the
    mesh — jit dispatch is async, so the per-partition programs execute
    concurrently) and the stacked partial Rs are combined on the mesh itself
    via `distributed_postprocess_r0`'s butterfly.
    """
    if engine is None:
        from repro.api import default_session

        engine = default_session().engine
    parts = partition_fact_table(tree, num_parts)
    if mesh is None:
        rs = [engine.qr(build_plan(t), dtype=dtype, use_kernel=use_kernel,
                        assembly=assembly)
              for t in parts]
        stacked = jnp.concatenate(rs, axis=0)
        return normalize_sign(tsqr_r(stacked, leaf_rows=max(
            r.shape[0] for r in rs)))
    slots = mesh.devices.reshape(-1)
    rs = []
    for i, t in enumerate(parts):
        with jax.default_device(slots[i % slots.size]):
            rs.append(engine.qr(build_plan(t), dtype=dtype,
                                use_kernel=use_kernel, assembly=assembly))
    # Colocate the per-slot Rs before stacking (cross-device concat is an
    # error), then THIN-combine the [P·N, N] stack over the mesh.
    stacked = jnp.concatenate(
        [jax.device_put(r, slots[0]) for r in rs], axis=0)
    return distributed_postprocess_r0(stacked, mesh, axis)
