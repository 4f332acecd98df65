"""Heads and tails (paper §3): block effects of Givens-rotation sequences.

``head(A, v)`` / ``tail(A, v)`` implement Definition 3.4 (the unweighted
Definition 3.2 is the ``v = 1`` special case). Together they form a *weighted
Helmert transform*: the orthogonal matrix ``G = R_m … R_2`` of Lemma 3.5, so

    G @ [S⊗v | A]  ==  [ ‖v‖₂·S  head(A,v) ]
                       [   0     tail(A,v) ]

`segmented_head_tail` applies the transform independently per contiguous
segment of rows (one segment per join key) — the vectorized form FiGaRo needs.
`givens_sequence` builds the explicit rotation sequence (test oracle: applying
it row-by-row must reproduce head/tail bit-for-bit-ish).

Numerics note (paper observation (3)): head/tail never squares *data* values —
only the weights are squared — which is where FiGaRo's accuracy edge over
Householder-on-the-join comes from.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

__all__ = [
    "head",
    "tail",
    "head_tail",
    "segmented_head_tail",
    "segmented_cumsum",
    "givens_rotation",
    "givens_sequence",
]


def head(a: jnp.ndarray, v: jnp.ndarray | None = None) -> jnp.ndarray:
    """Generalized head ``H(A, v) = (1/‖v‖₂) Σᵢ vᵢ A[i,:]`` — one row."""
    a = jnp.asarray(a)
    if v is None:
        return jnp.sum(a, axis=0) / jnp.sqrt(a.shape[0])
    # Cast the weights to the data dtype (as `tail` does): a float64 weight
    # vector must not silently upcast low-precision (bf16/f16/f32) data.
    v = jnp.asarray(v, dtype=a.dtype)
    return (v @ a) / jnp.linalg.norm(v)


def tail(a: jnp.ndarray, v: jnp.ndarray | None = None) -> jnp.ndarray:
    """Generalized tail ``T(A, v)`` — (m-1) rows (Definition 3.4).

    Row ``j`` (1-based, j∈[m-1]) is
      ( ‖v₁..ⱼ‖·A[j+1,:] − vⱼ₊₁·(Σᵢ≤ⱼ vᵢA[i,:])/‖v₁..ⱼ‖ ) / ‖v₁..ⱼ₊₁‖.
    """
    a = jnp.asarray(a)
    m = a.shape[0]
    if v is None:
        v = jnp.ones((m,), dtype=a.dtype)
    v = jnp.asarray(v, dtype=a.dtype)
    w2 = v * v
    c_incl = jnp.cumsum(w2)  # ‖v₁..ⱼ‖² at j (inclusive)
    s_incl = jnp.cumsum(v[:, None] * a, axis=0)
    c_excl = c_incl - w2
    s_excl = s_incl - v[:, None] * a
    c_excl_safe = jnp.where(c_excl > 0, c_excl, 1.0)
    t = (jnp.sqrt(c_excl_safe)[:, None] * a
         - v[:, None] * s_excl / jnp.sqrt(c_excl_safe)[:, None])
    t = t / jnp.sqrt(c_incl)[:, None]
    return t[1:]


def head_tail(a: jnp.ndarray, v: jnp.ndarray | None = None):
    return head(a, v), tail(a, v)


# ---------------------------------------------------------------------------
# Segmented (per-join-key) version — FiGaRo's workhorse.
# ---------------------------------------------------------------------------


def _shift_down(x: jnp.ndarray, off: int, fill) -> jnp.ndarray:
    """Rows shifted down by ``off`` (row r reads r-off), ``fill`` on top."""
    pad = ((off, 0),) + ((0, 0),) * (x.ndim - 1)
    return jnp.pad(x[:x.shape[0] - off], pad, constant_values=fill)


def segmented_cumsum(x: jnp.ndarray, first_flag: jnp.ndarray) -> jnp.ndarray:
    """Inclusive cumsum that restarts wherever ``first_flag`` is True.

    A Hillis–Steele ladder, the same scan the Pallas kernels run per row
    block: log₂(m) steps, step k adding the partial sum 2^k rows up unless a
    segment starts in between. It adds only values of the same segment (no
    subtract-the-base trick), so long arrays do not suffer cross-segment
    cancellation. It uses contiguous shifts only: `jax.lax.associative_scan`
    takes strided slices, for which the TPU compiler's time grows with the
    length (minutes for 2^20 rows on a v5e); the ladder compiles in seconds
    at any length.

    The ladder starts behind an optimization barrier, so XLA cannot fold
    or fuse it into its producers: a plan whose weights are constant ones
    and a capacity plan whose weights are a live-row mask of ones then run
    the same arithmetic and give bit-identical results.
    """
    flags = first_flag
    if x.ndim == 2:
        flags = first_flag[:, None]
    flags = jnp.broadcast_to(flags, x.shape)
    x, flags = jax.lax.optimization_barrier((x, flags))
    off = 1
    while off < x.shape[0]:
        x = x + jnp.where(flags, jnp.zeros((), x.dtype),
                          _shift_down(x, off, 0))
        flags = flags | _shift_down(flags, off, False)
        off *= 2
    return x


def segmented_head_tail(
    data: jnp.ndarray,
    weights: jnp.ndarray,
    pos_in_seg: jnp.ndarray,
    last_of_seg: jnp.ndarray,
    seg_live: jnp.ndarray,
    *,
    use_kernel: bool = False,
):
    """Per-segment generalized head & tail over contiguous row segments.

    The segment totals are the inclusive sums at each segment's last row, so
    the heads and norms are gathered there (K indices) rather than reduced a
    second time over all m rows.

    Args:
      data: [m, n]; rows of all segments, concatenated (segment-sorted).
      weights: [m] weights ``v``: positive, or 0 on dead rows, which never
        start a segment.
      pos_in_seg: [m] int — 0 for the first row of a segment.
      last_of_seg: [K] int — row index of each segment's last row.
      seg_live: [K] bool — False for a segment slot that holds no rows; its
        head and norm are 0 and its ``last_of_seg`` may be any row.
      use_kernel: compute the tails with the Pallas kernel
        (`repro.kernels.head_tail`) instead of from the Hillis–Steele
        ladder's sums; the heads come from the ladder either way.

    Returns:
      heads: [K, n]   — H(seg, v_seg)
      tails: [m, n]   — row r holds T(seg, v_seg)[pos-1] for pos>0, else 0
      norms: [K]      — ‖v_seg‖₂ (the scaling Lemma 3.5 applies to the S part)
    """
    dtype = data.dtype
    weights = weights.astype(dtype)
    first = pos_in_seg == 0
    w2 = weights * weights
    wa = data * weights[:, None]
    c_incl = segmented_cumsum(w2, first)
    s_incl = segmented_cumsum(wa, first)
    c_excl_safe = jnp.where(pos_in_seg > 0, c_incl - w2, 1.0)

    if use_kernel:
        from repro.kernels.head_tail import ops as ht_ops
        coef_a = jnp.sqrt(c_excl_safe / c_incl)
        coef_b = -weights / jnp.sqrt(c_excl_safe * c_incl)
        tails = ht_ops.segmented_tail(data, wa, first, coef_a, coef_b)
    else:
        s_excl = s_incl - wa
        tails = (jnp.sqrt(c_excl_safe)[:, None] * data
                 - weights[:, None] * s_excl / jnp.sqrt(c_excl_safe)[:, None])
        tails = tails / jnp.sqrt(c_incl)[:, None]
    tails = jnp.where((pos_in_seg > 0)[:, None], tails, jnp.zeros_like(tails))

    # Each row's head-so-far; a segment's head is the one at its last row.
    row_norms = jnp.sqrt(c_incl)
    row_heads = s_incl / jnp.where(row_norms > 0, row_norms, 1.0)[:, None]
    heads = jnp.where(seg_live[:, None], row_heads[last_of_seg], 0.0)
    norms = jnp.where(seg_live, row_norms[last_of_seg], 0.0)
    return heads, tails, norms


# ---------------------------------------------------------------------------
# Explicit Givens rotations — the oracle the closed forms must agree with.
# ---------------------------------------------------------------------------


def givens_rotation(m: int, i: int, j: int, s: float, c: float) -> np.ndarray:
    """``Giv_m(i, j, sinθ, cosθ)`` (Definition 3.1), 0-based indices."""
    g = np.eye(m)
    g[i, i] = c
    g[j, j] = c
    g[i, j] = -s
    g[j, i] = s
    return g


def givens_sequence(v: np.ndarray) -> np.ndarray:
    """The orthogonal ``G = R_m … R_2`` of Lemma 3.5 for weight vector ``v``.

    Applying G to ``[S⊗v | T]`` zeroes all but the first (scaled) copy of S and
    produces [head; tail] — the oracle used by tests.
    """
    v = np.asarray(v, dtype=np.float64)
    m = v.shape[0]
    g = np.eye(m)
    for i in range(1, m):  # paper's i = 2..m (1-based)
        norm_i = np.linalg.norm(v[: i + 1])
        norm_im1 = np.linalg.norm(v[:i])
        r = givens_rotation(m, 0, i, -v[i] / norm_i, norm_im1 / norm_i)
        g = r @ g
    return g
