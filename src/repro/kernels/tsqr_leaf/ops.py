"""TSQR with every level on the `tsqr_leaf` kernel.

`tsqr_r` does the work of `core.postprocess.tsqr_r`: ⌈m/256⌉ leaves of 256
rows, then ⌈log₂ leaves⌉ levels of pairwise combines (an odd last R meets a
zero R), all with the same Householder reflections, so R agrees with the
loop's to rounding. Which rows form a leaf and which Rs form a pair follow
the kernel's layout instead of the row order, which moves R by rounding
only:

  * leaf l holds rows l, l + L, l + 2L, … of R₀ (L leaves), so R₀ [m, n]
    becomes the kernel's ``[n, rows, L]`` (leaves on lanes) by one XLA
    relayout that moves no lane: row r of every leaf is one contiguous run
    of R₀'s rows;
  * at each combine level R p meets R p + ⌈L/2⌉, so the pair stack
    ``[n, 2n, ⌈L/2⌉]`` is two lane ranges of the R stack one above the
    other, where pairing neighbours would shuffle lanes.

Compiled on TPU/GPU, interpreted elsewhere (`repro.kernels._platform`);
pass ``interpret=`` explicitly to override the platform decision.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels._platform import resolve_interpret

from .kernel import _round_up, tsqr_leaf_kernel


def _pad_to(x: jnp.ndarray, axis: int, size: int) -> jnp.ndarray:
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, size - x.shape[axis])
    return jnp.pad(x, widths)


def _leaf_stack(a: jnp.ndarray, leaves: int, rows: int) -> jnp.ndarray:
    """[m, n] → [n, rows_p, leaves]: row r of leaf l is row r·leaves + l of
    ``a``, at [c, r, l]; zero rows pad ``a`` and each leaf."""
    n = a.shape[1]
    stack = _pad_to(a, 0, leaves * rows).reshape(rows, leaves, n)
    return _pad_to(jnp.transpose(stack, (2, 0, 1)), 1, _round_up(rows, 8))


def _pair_stack(rs: jnp.ndarray, pairs: int) -> jnp.ndarray:
    """R stack [n, n_p, leaves] → [n, 2·n_p, pairs]: pair p stacks the R of
    lane p over that of lane p + pairs (a zero R past the last leaf); the
    zero rows between them change nothing."""
    return jnp.concatenate(
        [rs[:, :, :pairs], _pad_to(rs[:, :, pairs:2 * pairs], 2, pairs)],
        axis=1)


def tsqr_r(a: jnp.ndarray, leaf_rows: int = 256, *,
           interpret: bool | None = None) -> jnp.ndarray:
    """TSQR of [m, n] → R [n, n], upper triangular, under the ``leaves`` and
    ``combine`` named scopes."""
    m, n = a.shape
    interpreted = resolve_interpret(interpret)
    rows = max(leaf_rows, n)
    leaves = max(1, -(-m // rows))
    with jax.named_scope("leaves"):
        rs = tsqr_leaf_kernel(_leaf_stack(a, leaves, rows), rows=rows,
                              interpret=interpreted)
    with jax.named_scope("combine"):
        while leaves > 1:
            leaves = -(-leaves // 2)
            rs = tsqr_leaf_kernel(_pair_stack(rs, leaves),
                                  interpret=interpreted)
    return rs[:, :n, 0].T
