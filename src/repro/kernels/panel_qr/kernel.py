"""Pallas TPU kernel: Householder panel factorization (post-processing hot spot).

The paper finds post-processing (R₀ → R) dominates FiGaRo's runtime for wide
matrices (§8 Exp 1). Blocked Householder QR splits into (a) a *panel*
factorization — sequential over columns, latency-bound — and (b) a trailing
compact-WY update — pure matmuls that the MXU eats. This kernel does (a)
entirely in VMEM: one [m × nb] panel resident on-chip, nb Householder steps
without touching HBM, emitting unit-diagonal reflectors V, betas, and the
triangularized panel.

Column selection uses iota masks instead of dynamic lane slicing (TPU lane
dim is not cheaply dynamically indexable); each step is two VPU reductions +
one rank-1 update.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels._platform import compiled_dtype_check


def _panel_kernel(a_ref, v_ref, beta_ref, r_ref, *, m: int, nb: int):
    # Accumulate in the I/O precision: f64 panels (the x64 post-processing
    # path) keep f64 Householder math; everything else runs the MXU-native
    # f32. A hardcoded f32 here silently cost ~1e-6 in the final R of an
    # otherwise-f64 pipeline.
    acc = jnp.float64 if a_ref.dtype == jnp.float64 else jnp.float32
    a = a_ref[...].astype(acc)  # [m, nb]
    rows = jax.lax.broadcasted_iota(jnp.int32, (m, 1), 0)
    cols = jax.lax.broadcasted_iota(jnp.int32, (1, nb), 1)

    def step(k, carry):
        a, vs, betas = carry
        colmask = (cols == k).astype(acc)        # [1, nb]
        col = jnp.sum(a * colmask, axis=1, keepdims=True)  # [m, 1]
        below = (rows >= k).astype(acc)
        x = col * below
        sigma2 = jnp.sum(x * x)
        sigma = jnp.sqrt(sigma2)
        at_k = (rows == k).astype(acc)
        xk = jnp.sum(x * at_k)
        one = jnp.ones((), acc)  # a bare 1.0 would select in f64 under x64
        sgn = jnp.where(xk >= 0, one, -one)
        alpha = -sgn * sigma
        v = x - alpha * at_k
        vk = jnp.sum(v * at_k)
        safe = jnp.abs(vk) > 0.0
        v = jnp.where(safe, v / jnp.where(safe, vk, 1.0), v)  # unit diagonal
        vv = jnp.sum(v * v)
        beta = jnp.where(vv > 0, 2.0 / jnp.where(vv > 0, vv, 1.0), 0.0)
        w = jnp.sum(v * a, axis=0, keepdims=True)            # [1, nb] = vᵀA
        a = a - beta * v * w                                  # rank-1 update
        vs = vs + v * colmask                                 # store column k
        betas = betas + beta * colmask
        return a, vs, betas

    vs0 = jnp.zeros((m, nb), acc)
    betas0 = jnp.zeros((1, nb), acc)
    # int32 bounds: under jax_enable_x64 Python-int bounds make the loop
    # index int64, which Mosaic cannot lower (the bool->float mask casts
    # recurse without end).
    a, vs, betas = jax.lax.fori_loop(jnp.int32(0), jnp.int32(min(m, nb)),
                                     step, (a, vs0, betas0))

    v_ref[...] = vs.astype(v_ref.dtype)
    beta_ref[...] = betas.astype(beta_ref.dtype)
    # Zero strictly-below-diagonal residue (numerical dust from the updates).
    upper = (rows <= cols).astype(acc)
    r_ref[...] = (a * upper).astype(r_ref.dtype)


@functools.partial(jax.jit, static_argnames=("interpret",))
def panel_qr_kernel(a: jnp.ndarray, *, interpret: bool = False):
    """Factor one panel [m, nb] (entirely VMEM-resident).

    Returns (V [m, nb] unit-diagonal reflectors, beta [nb], R_panel [m, nb]).
    VMEM budget: 4 copies of the panel at the accumulation dtype (f64 for
    f64 panels, f32 otherwise) — keep m·nb ≲ 512·128 (f32) / 512·64 (f64).
    f64 panels run only with ``interpret=True``; a compiled call raises
    `ValueError` (the TPU's Pallas compiler has no float64).
    """
    m, nb = a.shape
    if not interpret:
        compiled_dtype_check(a.dtype, "panel_qr_kernel")
    kern = functools.partial(_panel_kernel, m=m, nb=nb)
    # int32 block indices (a bare 0 is int64 under jax_enable_x64, which
    # Mosaic refuses to return from the index map).
    origin = lambda: (jnp.int32(0), jnp.int32(0))
    spec = pl.BlockSpec((m, nb), origin)
    bspec = pl.BlockSpec((1, nb), origin)
    v, beta, r = pl.pallas_call(
        kern,
        grid=(),
        in_specs=[spec],
        out_specs=[spec, bspec, spec],
        out_shape=[
            jax.ShapeDtypeStruct((m, nb), a.dtype),
            jax.ShapeDtypeStruct((1, nb), a.dtype),
            jax.ShapeDtypeStruct((m, nb), a.dtype),
        ],
        interpret=interpret,
    )(a)
    return v, beta[0], r
