"""Pure-jnp oracle for the TSQR-leaf kernel: `core.postprocess.householder_qr_r`
vmapped over the leaves, in the kernel's layout."""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core.postprocess import householder_qr_r


def tsqr_leaf_ref(stack: jnp.ndarray, *, rows: int | None = None):
    """[n, rows_p, leaves] → [n, n, leaves]: column c, row i, leaf l holds
    R_l[i, c] of leaf l's first ``rows`` rows (default: all)."""
    rows = stack.shape[1] if rows is None else rows
    leaves = jnp.transpose(stack[:, :rows], (2, 1, 0))  # [leaves, rows, n]
    return jnp.transpose(jax.vmap(householder_qr_r)(leaves), (2, 1, 0))
