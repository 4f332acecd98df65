"""FiGaRo (paper §6, Algorithm 2): pushing Givens rotations past the join.

Bottom-up over the join tree; per node:

  HEADS_AND_TAILS            per-join-key head/tail of the node's data columns;
                             tails scaled by √Φ° go to the output, heads into
                             the carried `Data` matrix (one row per key X̄_i).
  PROCESS_AND_JOIN_CHILDREN  gather children's carried heads through the key
                             lookup, apply the cross-subtree scale products
                             (lines 21–26 of Algorithm 2).
  PROJECT_AWAY_JOIN_ATTRS    generalized head/tail over `Data` weighted by the
                             carried scales; generalized tails scaled by √Φ↑ go
                             to the output, heads (one row per X̄_p) are carried
                             to the parent with scales √Φ↓.

The result ``R₀`` is almost upper-triangular with at most M non-zero rows and
satisfies ``A[:, Ȳ] = Q·[R₀; 0]`` for orthogonal Q (Theorem 6.1) — equivalently
``R₀ᵀR₀ == AᵀA``, the invariant the tests enforce.

Execution model (post plan-split): the `FigaroPlan` is a pytree — its static
`PlanSpec` (shapes, topology, R₀ row/column layout) is treedef metadata and the
`NodeIndex` arrays are leaves — so this function jits **with the plan as an
argument**. One compiled executable serves every plan with the same signature;
`repro.core.engine.FigaroEngine` owns that cache and the batched (vmapped)
dispatch over a leading data axis.

Two hot-path variants, both cache-keyed by the engine:

  * ``use_kernel=True`` routes each node's two head/tail passes through the
    fused `kernels/node_fused` Pallas kernel: live-row masking, the weighted
    segmented scan, the tail formula, segment-start zeroing and √Φ emission
    scaling collapse into one HBM round-trip per pass. ``use_kernel=False``
    (default) is the XLA path — `segmented_head_tail` per pass — which stays
    the CPU fallback. Both paths take the heads from the inclusive sums at
    each segment's last row, a gather of K indices, instead of a second
    [m, n] reduction. Post-processing does not read ``use_kernel``.

  * ``assembly`` picks how the emitted slabs become R₀. ``"padded"``
    (default) pads every slab to the full ``num_cols`` width and concatenates
    in emission order — every slab is written twice at full width. ``"band"``
    uses the band layout recorded in ``PlanSpec.bands``: each slab is
    slice-updated into a zeros [r0_rows, num_cols] buffer at its static
    (row0, col0) band, so beyond the single zero fill each slab moves only
    its own rowsᵢ·widthᵢ elements (`assembly_traffic` is the analytic model
    the benchmarks report). Both paths produce bit-identical layouts.

Capacity-padded plans (`repro.core.plan_cache`): when a node carries a
``row_mask``, the static shapes above are *capacities* and the mask is the
weight vector of every row-level Givens sequence — dead rows contribute
nothing (weight 0, data zeroed) and the corresponding R₀ rows are exactly
zero, so the same executable serves every live size up to capacity. The fused
kernel keeps this contract: the mask rides in as the kernel's ``data_scale``
so masked slab rows are exactly zero straight out of the kernel.

Each phase of the traced body runs under a `jax.named_scope`, so a device op's
name stack (the ``tf_op`` a profiler trace carries) says which phase it belongs
to: ``figaro.counts`` (Algorithm 1), ``figaro.heads_tails`` (lines 11-16),
``figaro.join_children`` (lines 17-26) and ``figaro.project`` (lines 27-34),
each with the node's name as a sub-scope, and ``figaro.assemble``; the
post-processing (``figaro.postprocess``) and the downstream reads
(``figaro.downstream``) are scoped where they are traced. Scopes are metadata
only: the compiled program is op for op the same.
"""

from __future__ import annotations

import functools
from typing import Sequence

import jax
import jax.numpy as jnp
import numpy as np

from .counts import compute_counts
from .heads_tails import segmented_head_tail
from .join_tree import FigaroPlan, PlanSpec

__all__ = ["figaro_r0", "figaro_r0_batched", "figaro_r0_fn",
           "assembly_traffic", "r0_nonzero_rows_bound"]

ASSEMBLIES = ("padded", "band")


def _pad_cols(block: jnp.ndarray, col0: int, num_cols: int) -> jnp.ndarray:
    """Embed ``block`` into columns [col0, col0+w) of an all-zero [rows, N] slab."""
    return jnp.pad(block, ((0, 0), (col0, num_cols - col0 - block.shape[1])))


def _assemble_padded(spec: PlanSpec, tail_slabs, out_slabs) -> jnp.ndarray:
    """Every slab padded to full width, concatenated in emission order."""
    slabs = []
    for idx in reversed(spec.preorder):
        sp = spec.nodes[idx]
        slabs.append(_pad_cols(tail_slabs[idx], sp.col_start, spec.num_cols))
        slabs.append(_pad_cols(out_slabs[idx], sp.subtree_start, spec.num_cols))
    return jnp.concatenate(slabs, axis=0)


def _assemble_band(spec: PlanSpec, tail_slabs, out_slabs) -> jnp.ndarray:
    """Band-wise R₀ assembly (bit-identical layout to the padded path).

    Every slab's destination is a *static* contiguous band recorded in
    ``PlanSpec.bands`` — rows [row0, row0+rows) × columns [col0, col0+width)
    of R₀, zero outside — so the slabs are slice-updated straight into one
    [r0_rows, num_cols] zeros buffer. Static-offset `dynamic_update_slice` is
    a contiguous block write XLA performs in place on the dead operand (NOT a
    row-index scatter, which the emission layout was designed to avoid), so
    the assembly writes each slab once at its own width: r0_rows·num_cols for
    the zero fill plus Σ rowsᵢ·widthᵢ for the bands, instead of the padded
    path's full-width copy of every slab followed by the full-width concat.
    """
    dtype = out_slabs[spec.root].dtype
    r0 = jnp.zeros((spec.r0_rows, spec.num_cols), dtype)
    for b in spec.bands:
        slab = tail_slabs[b.node] if b.kind == "tail" else out_slabs[b.node]
        r0 = jax.lax.dynamic_update_slice(r0, slab, (b.row0, b.col0))
    return r0


def assembly_traffic(spec: PlanSpec, *, assembly: str = "padded",
                     itemsize: int = 8) -> int:
    """Analytic bytes *written* by R₀ assembly.

    ``"padded"`` writes a full-width copy of every slab narrower than
    ``num_cols`` (the pad) plus the final [r0_rows, num_cols] concat;
    ``"band"`` writes the zero fill once plus each slab at its own band
    width. This is the attribution model `benchmarks/engine_bench.py` reports
    next to wall-clock, so a band-vs-padded win is explainable in bytes, not
    just observed in seconds.
    """
    full = spec.r0_rows * spec.num_cols
    if assembly == "padded":
        pad_writes = sum(b.rows * spec.num_cols for b in spec.bands
                         if b.width != spec.num_cols)
        return (pad_writes + full) * itemsize
    if assembly == "band":
        band_writes = sum(b.rows * b.width for b in spec.bands)
        return (full + band_writes) * itemsize
    raise ValueError(f"unknown assembly {assembly!r}; expected {ASSEMBLIES}")


def r0_nonzero_rows_bound(plan: FigaroPlan) -> int:
    """How many of R₀'s rows can be non-zero, from the plan's live structure.

    A group's first row has an empty prefix, so its tail is zero, and so is
    the generalized tail of a parent group's first group; dead capacity rows
    are zero, and so is every row of a slab with no column. What is left:
    per node, its live rows past each group's first (if it has a column),
    its live groups past each parent group's first (if its subtree has a
    column), and at the root every live group's head row.
    """
    rows = 0
    for sp, ix in zip(plan.spec.nodes, plan.index):
        live_rows = (int(np.sum(ix.row_mask)) if ix.row_mask is not None
                     else sp.m)
        groups = int(np.count_nonzero(ix.group_count))
        if sp.n:
            rows += live_rows - groups
        if sp.parent < 0:
            rows += groups
        elif sp.subtree_width:
            rows += groups - int(np.count_nonzero(ix.pgroup_count))
    return rows


def figaro_r0(
    plan: FigaroPlan,
    data: Sequence[jnp.ndarray] | None = None,
    *,
    dtype=jnp.float32,
    use_kernel: bool = False,
    assembly: str = "padded",
) -> jnp.ndarray:
    """Run Algorithm 2; returns R₀ with static shape [plan.r0_rows, plan.num_cols].

    ``data[i]`` overrides node i's data matrix (same row order as the plan) —
    used for jit arguments and for propagating gradients through FiGaRo.
    ``use_kernel`` routes the per-node passes through the fused Pallas kernel;
    ``assembly`` ("padded" | "band") picks the R₀ materialization (see module
    docstring) — the layouts are identical, only the traffic differs.
    """
    if assembly not in ASSEMBLIES:
        raise ValueError(f"unknown assembly {assembly!r}; expected {ASSEMBLIES}")
    if use_kernel:
        from repro.kernels.node_fused import ops as nf_ops
    spec = plan.spec
    if data is None:
        data = plan.data
    data = [jnp.asarray(d, dtype=dtype) for d in data]
    with jax.named_scope("figaro.counts"):
        counts = compute_counts(plan, dtype=dtype)

    # Carried state per node (filled children-first); emitted slabs by node.
    carried_data: dict[int, jnp.ndarray] = {}
    carried_scales: dict[int, jnp.ndarray] = {}
    tail_slabs: dict[int, jnp.ndarray] = {}
    out_slabs: dict[int, jnp.ndarray] = {}

    for idx in reversed(spec.preorder):  # children strictly before parents
        sp = spec.nodes[idx]
        ix = plan.index[idx]
        cnt = counts[idx]
        x = data[idx]
        row_to_group = jnp.asarray(ix.row_to_group)
        pos_in_group = jnp.asarray(ix.pos_in_group)

        # --- HEADS_AND_TAILS (lines 11-16) --------------------------------
        with (jax.named_scope("figaro.heads_tails"),
              jax.named_scope(sp.name)):
            # Capacity-padded plans weight the Givens sequences by the
            # live-row mask: dead rows carry weight 0 (they neither move the
            # prefix sums nor receive a tail) and their data is zeroed so the
            # padded slab rows of R₀ come out identically zero. Dead rows are
            # never segment starts (plan_cache appends them to the last live
            # group), so every division inside the head/tail formulas stays
            # well-posed.
            mask = (jnp.asarray(ix.row_mask, dtype=dtype)
                    if ix.row_mask is not None else None)
            weights = (mask if mask is not None
                       else jnp.ones((sp.m,), dtype=dtype))
            phi_circ_row = cnt["phi_circ"][row_to_group]
            # Heads are read at each group's last row (a dead group slot
            # points at the last live row and is zeroed by `live`).
            group_count = jnp.asarray(ix.group_count)
            last = jnp.asarray(ix.group_start) + group_count - 1
            live = group_count > 0
            if use_kernel:
                # Fused pass: masking (data_scale), scan, tail, √Φ° scaling and
                # start-row zeroing in one kernel.
                slab, heads, _ = nf_ops.fused_node_pass(
                    x, weights, pos_in_group, jnp.sqrt(phi_circ_row), last,
                    live, data_scale=mask)
                tail_slabs[idx] = slab
            else:
                if mask is not None:
                    x = x * mask[:, None]
                heads, tails, _ = segmented_head_tail(
                    x, weights, pos_in_group, last, live)
                tail_slabs[idx] = tails * jnp.sqrt(phi_circ_row)[:, None]

        # --- PROCESS_AND_JOIN_CHILDREN (lines 17-26) ----------------------
        with (jax.named_scope("figaro.join_children"),
              jax.named_scope(sp.name)):
            scales = jnp.sqrt(cnt["rpk"])  # √|S_i^x̄|, one per key
            if sp.children:
                # (data [K, w_ch], scale [K]) in child (column) order
                gathered = []
                for ch in sp.children:
                    lookup = jnp.asarray(ix.child_lookup[ch])
                    gathered.append((carried_data.pop(ch)[lookup],
                                     carried_scales.pop(ch)[lookup]))
                prod_all = functools.reduce(jnp.multiply,
                                            [s for _, s in gathered])
                blocks = [heads * prod_all[:, None]]
                for j, (dj, _) in enumerate(gathered):
                    # scales = √rpk_i (line 24's `scales[x̄_i]` factor)
                    prod_except = functools.reduce(
                        jnp.multiply,
                        [s for k, (_, s) in enumerate(gathered) if k != j],
                        scales)
                    blocks.append(dj * prod_except[:, None])
                # Children subtrees are column-contiguous after the node's own
                # columns (validated at plan build) — Data is a pure concat.
                data_mat = jnp.concatenate(blocks, axis=1)
                scales = scales * prod_all  # line 26
            else:
                data_mat = heads  # width == n for a leaf

        # --- PROJECT_AWAY_JOIN_ATTRIBUTES (lines 27-34) / root (lines 7-8) -
        with (jax.named_scope("figaro.project"),
              jax.named_scope(sp.name)):
            if sp.parent >= 0:
                group_to_pgroup = jnp.asarray(ix.group_to_pgroup)
                pos_in_pgroup = jnp.asarray(ix.pos_in_pgroup)
                phi_up_group = cnt["phi_up"][group_to_pgroup]
                if use_kernel:
                    # Dead group slots continue the last live pgroup's
                    # segment with scale 0, so the segment-final gather index
                    # may safely land on them — the inclusive sums are
                    # unchanged past the last live member.
                    last = jax.ops.segment_max(
                        jnp.arange(sp.K), group_to_pgroup, num_segments=sp.P,
                        indices_are_sorted=True)
                    live = jnp.asarray(ix.pgroup_count) > 0
                    slab, gheads, _ = nf_ops.fused_node_pass(
                        data_mat, scales, pos_in_pgroup,
                        jnp.sqrt(phi_up_group), last, live)
                    out_slabs[idx] = slab
                else:
                    # Live groups are pgroup-sorted and dead group slots sit
                    # after them, so pgroup p ends at its running group
                    # total; a dead pgroup slot lands on the last live group.
                    pgroup_count = jnp.asarray(ix.pgroup_count)
                    last = jnp.cumsum(pgroup_count,
                                      dtype=pgroup_count.dtype) - 1
                    gheads, gtails, _ = segmented_head_tail(
                        data_mat, scales, pos_in_pgroup, last,
                        pgroup_count > 0)
                    out_slabs[idx] = gtails * jnp.sqrt(phi_up_group)[:, None]
                carried_data[idx] = gheads
                carried_scales[idx] = jnp.sqrt(cnt["phi_down"])
            else:
                out_slabs[idx] = data_mat

    with jax.named_scope("figaro.assemble"):
        if assembly == "band":
            r0 = _assemble_band(spec, tail_slabs, out_slabs)
        else:
            r0 = _assemble_padded(spec, tail_slabs, out_slabs)
    assert r0.shape == (spec.r0_rows, spec.num_cols), (r0.shape, spec.r0_rows)
    return r0


def figaro_r0_batched(
    plan: FigaroPlan,
    data_batch: Sequence[jnp.ndarray],
    *,
    dtype=jnp.float32,
    use_kernel: bool = False,
    assembly: str = "padded",
) -> jnp.ndarray:
    """Algorithm 2 vmapped over a leading batch axis of the data matrices.

    ``data_batch[i]`` is [B, m_i, n_i]; the plan (and therefore the counts,
    which depend only on the index structure) is held fixed across the batch —
    one join structure serving B feature-sets per dispatch. Returns
    [B, r0_rows, num_cols].
    """
    fn = functools.partial(figaro_r0, plan, dtype=dtype, use_kernel=use_kernel,
                           assembly=assembly)
    return jax.vmap(lambda d: fn(list(d)))(tuple(data_batch))


def figaro_r0_fn(plan: FigaroPlan, *, dtype=jnp.float32,
                 use_kernel: bool = False, assembly: str = "padded"):
    """A jittable closure ``data_list -> R₀`` for a fixed plan.

    Kept for the pre-engine call sites; new code should go through
    `repro.core.engine.FigaroEngine`, which passes the plan through jit as a
    pytree argument and shares one executable across same-signature plans.
    """

    def fn(data: Sequence[jnp.ndarray]) -> jnp.ndarray:
        return figaro_r0(plan, data, dtype=dtype, use_kernel=use_kernel,
                         assembly=assembly)

    # Deliberately plan-closed: kept for the pre-engine call sites and
    # dispatch-minimal benchmarks (see docstring).
    return jax.jit(fn)  # figaro-lint: disable=FIG002 -- plan-closed by design
