"""Post-processing (paper §7): triangularize R₀ (M×N) → R (N×N).

The paper's THIN scheme — each thread Givens-reduces its share of rows, then a
parallel combine — is, in block form, exactly TSQR (tall-skinny QR with a
binary combine tree). Here:

  * `householder_qr_r`   — column-at-a-time Householder, pure JAX `fori_loop`
                           (the in-house leaf factorization; MKL-analog).
  * `blocked_qr_r`       — panel/WY blocked variant (the mesh leaves in
                           `core/distributed.py`).
  * `tsqr_r`             — row-blocked leaf QRs + log₂ pairwise combine
                           (THIN on TPU; the mesh version lives in
                           `core/distributed.py`).
  * `postprocess_r0`     — R₀ → upper-triangular R with non-negative diagonal
                           by TSQR; float32 on a TPU runs every level on the
                           Pallas `tsqr_leaf` kernel (`kernels/tsqr_leaf`).

All functions return only R (the paper never materializes Q either).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels import _platform

__all__ = [
    "householder_qr_r",
    "blocked_qr_r",
    "tsqr_r",
    "postprocess_r0",
    "normalize_sign",
]


def normalize_sign(r: jnp.ndarray) -> jnp.ndarray:
    """Flip row signs so diag(R) >= 0 (QR uniqueness normalization).

    Sign vector is built in ``r.dtype`` — a Python-float fill would promote
    low-precision inputs (bf16/f16 serving) and silently upcast the result.
    """
    r = jnp.asarray(r)
    s = jnp.sign(jnp.diagonal(r))
    s = jnp.where(s == 0, jnp.ones((), r.dtype), s).astype(r.dtype)
    return r * s[:, None]


def householder_qr_r(a: jnp.ndarray) -> jnp.ndarray:
    """R factor via Householder reflections; [m, n] -> [n, n] (m >= 1).

    Column-at-a-time `fori_loop`; O(mn²) flops, static shapes throughout.
    """
    m, n = a.shape
    dtype = a.dtype
    steps = min(m - 1, n)
    rows = jnp.arange(m)

    def body(k, a):
        col = jax.lax.dynamic_index_in_dim(a, k, axis=1, keepdims=False)
        x = jnp.where(rows >= k, col, jnp.zeros_like(col))
        sigma = jnp.linalg.norm(x)
        xk = x[k]
        # alpha = -sign(xk)*sigma with sign(0) := 1
        sgn = jnp.where(xk >= 0, jnp.ones((), dtype), -jnp.ones((), dtype))
        alpha = -sgn * sigma
        v = x - alpha * (rows == k).astype(dtype)
        vv = v @ v
        beta = jnp.where(vv > 0, 2.0 / jnp.where(vv > 0, vv, 1.0), 0.0)
        w = v @ a  # [n]
        return a - beta * v[:, None] * w[None, :]

    a = jax.lax.fori_loop(0, steps, body, a)
    r = jnp.triu(a[:n])
    if m < n:  # degenerate tall requirement; pad for a consistent [n, n]
        r = jnp.zeros((n, n), dtype).at[:m].set(jnp.triu(a)[:m])
    return r


def _apply_wy(a: jnp.ndarray, v: jnp.ndarray, t: jnp.ndarray) -> jnp.ndarray:
    """Trailing update A ← Hₙ…H₁·A = (I − V·Tᵀ·Vᵀ)·A (compact WY on the MXU).

    With Q = H₁…Hₙ = I − V·T·Vᵀ (LAPACK forward convention), the QR trailing
    update applies Qᵀ, i.e. Tᵀ.
    """
    return a - v @ (t.T @ (v.T @ a))


def _panel_to_wy(v: jnp.ndarray, beta: jnp.ndarray) -> jnp.ndarray:
    """Compact-WY T from unit reflectors V (columns) and betas: forward recurrence."""
    nb = v.shape[1]
    # Derive the zero init from the inputs so it inherits their vma type
    # (shard_map-manual axes): fresh constants would be "unvarying" and the
    # fori_loop carry would type-mismatch.
    t = jnp.zeros((nb, nb), v.dtype) + 0.0 * beta[0]

    def body(j, t):
        col = -beta[j] * (t @ (v.T @ v[:, j]))
        col = jnp.where(jnp.arange(nb) < j, col, 0.0)
        t = t.at[:, j].set(col)
        return t.at[j, j].set(beta[j])

    return jax.lax.fori_loop(0, nb, body, t)


def householder_panel(a: jnp.ndarray):
    """Factor a panel: returns (V unit-lower reflectors [m, nb], beta [nb], R_panel [m, nb])."""
    m, nb = a.shape
    dtype = a.dtype
    rows = jnp.arange(m)
    vs = a * 0.0  # zeros that inherit `a`'s vma type (see _panel_to_wy note)
    betas = jnp.sum(a, axis=0)[:nb] * 0.0 if m >= 1 else jnp.zeros((nb,), dtype)

    def body(k, carry):
        a, vs, betas = carry
        col = jax.lax.dynamic_index_in_dim(a, k, axis=1, keepdims=False)
        x = jnp.where(rows >= k, col, jnp.zeros_like(col))
        sigma = jnp.linalg.norm(x)
        xk = x[k]
        sgn = jnp.where(xk >= 0, jnp.ones((), dtype), -jnp.ones((), dtype))
        alpha = -sgn * sigma
        v = x - alpha * (rows == k).astype(dtype)
        vk = v[k]
        safe = jnp.abs(vk) > 0
        v = jnp.where(safe, v / jnp.where(safe, vk, 1.0), v)  # unit diagonal
        vv = v @ v
        beta = jnp.where(vv > 0, 2.0 / jnp.where(vv > 0, vv, 1.0), 0.0)
        w = v @ a
        a = a - beta * v[:, None] * w[None, :]
        return a, vs.at[:, k].set(v), betas.at[k].set(beta)

    a, vs, betas = jax.lax.fori_loop(0, min(m, nb), body, (a, vs, betas))
    return vs, betas, a


def blocked_qr_r(a: jnp.ndarray, panel: int = 32) -> jnp.ndarray:
    """Blocked Householder QR (panel + compact-WY trailing update) -> R [n, n]."""
    m, n = a.shape
    if m < n:
        a = jnp.concatenate([a, jnp.zeros((n - m, n), a.dtype)], axis=0)
        m = n
    pos = 0
    while pos < n:
        nb = min(panel, n - pos)
        block = a[pos:, pos:pos + nb]
        v, beta, rp = householder_panel(block)
        t = _panel_to_wy(v, beta)
        a = a.at[pos:, pos:pos + nb].set(rp)
        if pos + nb < n:
            trailing = _apply_wy(a[pos:, pos + nb:], v, t)
            a = a.at[pos:, pos + nb:].set(trailing)
        pos += nb
    return jnp.triu(a[:n])


def tsqr_r(a: jnp.ndarray, leaf_rows: int = 256,
           leaf_qr=householder_qr_r) -> jnp.ndarray:
    """TSQR: row-block leaf QRs, then pairwise combines — THIN (§7) in block form.

    [m, n] -> R [n, n]. Rows are zero-padded to a full grid; zero rows do not
    change R. The two levels run under the ``leaves`` and ``combine`` named
    scopes.
    """
    m, n = a.shape
    leaf_rows = max(leaf_rows, n)
    blocks = max(1, -(-m // leaf_rows))
    pad = blocks * leaf_rows - m
    if pad:
        a = jnp.concatenate([a, jnp.zeros((pad, n), a.dtype)], axis=0)
    with jax.named_scope("leaves"):
        rs = jax.vmap(leaf_qr)(a.reshape(blocks, leaf_rows, n))  # [B, n, n]
    with jax.named_scope("combine"):
        while rs.shape[0] > 1:
            b = rs.shape[0]
            if b % 2:
                rs = jnp.concatenate([rs, jnp.zeros((1, n, n), a.dtype)],
                                     axis=0)
                b += 1
            stacked = rs.reshape(b // 2, 2 * n, n)
            rs = jax.vmap(leaf_qr)(stacked)
    return rs[0]


def _tsqr_on_kernel(r0: jnp.ndarray, leaf_rows: int) -> bool:
    """Whether TSQR runs on the `tsqr_leaf` kernel: float32 on a TPU, with a
    leaf (and a pair of Rs) that fits the kernel's VMEM."""
    from repro.kernels.tsqr_leaf import kernel as tl_kernel
    n = r0.shape[1]
    return (r0.dtype == jnp.float32 and _platform.on_tpu()
            and tl_kernel.fits(n, max(leaf_rows, 2 * n)))


def postprocess_r0(r0: jnp.ndarray, *, leaf_rows: int = 256) -> jnp.ndarray:
    """R₀ (M×N, almost upper-triangular) → R (N×N, diag ≥ 0) by TSQR over
    ``leaf_rows``-row leaves, under the ``figaro.postprocess`` named scope.

    In float32 on a TPU every level runs on the `tsqr_leaf` kernel; elsewhere
    the leaves are `householder_qr_r`.
    """
    with jax.named_scope("figaro.postprocess"):
        if _tsqr_on_kernel(r0, leaf_rows):
            from repro.kernels.tsqr_leaf import ops as tl_ops
            r = tl_ops.tsqr_r(r0, leaf_rows=leaf_rows)
        else:
            r = tsqr_r(r0, leaf_rows=leaf_rows)
        return normalize_sign(r)
