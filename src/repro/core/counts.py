"""Batched group-by count queries over the join tree (paper §5, Algorithm 1).

Computes, for every node ``i`` of the join tree:

  Φ↓_i(x̄_p)  join size of S_i's subtree, grouped by the parent-shared key
  Φ↑_i(x̄_p)  join size of everything *outside* S_i's subtree
  Φ°_i(x̄_i)  join size of all relations except S_i, grouped by X̄_i

in two passes (bottom-up, then top-down), linear time. The paper's CPU version
uses atomics for concurrent accumulation; here every accumulation is a
`segment_sum` / gather over the static index structure in the `FigaroPlan`, so
the whole thing jits and differentiates away on TPU with zero synchronization.

Counts can exceed 2^31 quickly (they multiply along the tree), so they are
computed in floating point of a configurable dtype; sqrt of the counts is what
FiGaRo actually consumes. The default is float64: float32 is exact only up to
2^24, beyond which the full-join sizes round and ``phi_circ`` (= full / rpk)
silently corrupts the emission scaling. A numpy int64 reference lives in
`compute_counts_reference`, which also gives the façade its exact join size
(`JoinDataset.stats`) and the largest count a request will meet
(`check_counts_exact`).

Capacity-padded (masked) plans — see `repro.core.plan_cache` — carry group
slots with ``group_count == 0``; their counts are identically zero, and every
division below is guarded so 0/0 resolves to 0 instead of NaN. For exact plans
all denominators are >= 1, so the guards are value-neutral.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from .join_tree import FigaroPlan

__all__ = ["NodeCounts", "compute_counts", "compute_counts_reference",
           "largest_count", "check_counts_exact"]


class NodeCounts(dict):
    """Per-node aggregate bundle: keys rpk, theta_down, phi_down, full, phi_up, phi_circ."""


def _safe_div(num: jnp.ndarray, den: jnp.ndarray) -> jnp.ndarray:
    """``num / den`` with 0/0 -> 0 (dead capacity slots of masked plans)."""
    ok = den > 0
    return jnp.where(ok, num / jnp.where(ok, den, 1), jnp.zeros((), num.dtype))


def compute_counts(plan: FigaroPlan, dtype=jnp.float64) -> list[NodeCounts]:
    """Algorithm 1, jitted-friendly. Returns one `NodeCounts` per node index.

    Reads the static sizes off ``plan.spec`` and the (possibly traced) index
    arrays off ``plan.index``, so it composes with plans passed through jit as
    pytree arguments.
    """
    spec = plan.spec
    out: list[NodeCounts] = [NodeCounts() for _ in spec.nodes]

    # --- PASS 1 (bottom-up): ROWS_PER_KEY, Θ↓, Φ↓ -------------------------
    for idx in reversed(spec.preorder):
        sp, ix = spec.nodes[idx], plan.index[idx]
        rpk = jnp.asarray(ix.group_count, dtype=dtype)
        theta = rpk
        for ch in sp.children:
            phi_down_child = out[ch]["phi_down"]  # [P_child]
            lookup = jnp.asarray(ix.child_lookup[ch])
            theta = theta * phi_down_child[lookup]
        out[idx]["rpk"] = rpk
        out[idx]["theta_down"] = theta
        if sp.parent >= 0:
            out[idx]["phi_down"] = jax.ops.segment_sum(
                theta, jnp.asarray(ix.group_to_pgroup), num_segments=sp.P)

    # --- PASS 2 (top-down): FULL_JOIN_SIZE, Φ↑, Φ° ------------------------
    for idx in spec.preorder:
        sp, ix = spec.nodes[idx], plan.index[idx]
        if sp.parent >= 0:
            up = out[idx]["phi_up"]  # set by the parent below
            full = out[idx]["theta_down"] * up[jnp.asarray(ix.group_to_pgroup)]
        else:
            full = out[idx]["theta_down"]
        out[idx]["full"] = full
        out[idx]["phi_circ"] = _safe_div(full, out[idx]["rpk"])
        for ch in sp.children:
            lookup = jnp.asarray(ix.child_lookup[ch])
            full_ij = jax.ops.segment_sum(full, lookup,
                                          num_segments=spec.nodes[ch].P)
            out[ch]["phi_up"] = _safe_div(full_ij, out[ch]["phi_down"])

    return out


def _exact_div(num: np.ndarray, den: np.ndarray) -> np.ndarray:
    """``num // den`` in int64, asserted exact; 0 where ``den`` is 0 (dead
    capacity slots of masked plans)."""
    live = den > 0
    assert np.all(num[live] % den[live] == 0)
    out = np.zeros_like(num)
    out[live] = num[live] // den[live]
    return out


def compute_counts_reference(plan: FigaroPlan) -> list[dict[str, np.ndarray]]:
    """Same two-pass recurrences in numpy int64 (exact) — test oracle, and
    the host's exact counts. Capacity-padded plans give their dead slots 0."""
    nodes = plan.nodes
    out: list[dict[str, np.ndarray]] = [dict() for _ in nodes]
    for idx in reversed(plan.preorder):
        nd = nodes[idx]
        rpk = nd.group_count.astype(np.int64)
        theta = rpk.copy()
        for ch in nd.children:
            theta = theta * out[ch]["phi_down"][nd.child_lookup[ch]]
        out[idx]["rpk"] = rpk
        out[idx]["theta_down"] = theta
        if nd.parent >= 0:
            acc = np.zeros(nd.P, dtype=np.int64)
            np.add.at(acc, nd.group_to_pgroup, theta)
            out[idx]["phi_down"] = acc
    for idx in plan.preorder:
        nd = nodes[idx]
        if nd.parent >= 0:
            full = out[idx]["theta_down"] * out[idx]["phi_up"][nd.group_to_pgroup]
        else:
            full = out[idx]["theta_down"]
        out[idx]["full"] = full
        out[idx]["phi_circ"] = _exact_div(full, out[idx]["rpk"])
        for ch in nd.children:
            acc = np.zeros(nodes[ch].P, dtype=np.int64)
            np.add.at(acc, nd.child_lookup[ch], full)
            out[ch]["phi_up"] = _exact_div(acc, out[ch]["phi_down"])
    return out


def largest_count(counts: list[dict[str, np.ndarray]]) -> int:
    """The largest per-key count Algorithm 1 forms: every other count is a
    part of some node's ``full`` (the join rows a key takes part in)."""
    return max(int(c["full"].max(initial=0)) for c in counts)


def check_counts_exact(largest: int, dtype) -> None:
    """Refuse to run Algorithm 1 in ``dtype`` where a count passes the
    largest integer it holds exactly (2^24 in float32), which would corrupt
    the √Φ scalings without a sign. Types narrower than float32 (exact to
    256 or 2,048) only serve as precision controls and are not checked."""
    info = jnp.finfo(dtype)
    if info.bits < 32:
        return
    limit = 2 ** (info.nmant + 1)
    if largest > limit:
        raise ValueError(
            f"a join key takes part in {largest:,} join rows, more than "
            f"{jnp.dtype(dtype).name} counts exactly ({limit:,}); serve in "
            f"float64")
