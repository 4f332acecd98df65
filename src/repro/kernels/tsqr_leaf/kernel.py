"""Pallas TPU kernel: Householder R of a whole stack of TSQR leaves in VMEM.

`core.postprocess.tsqr_r` factors R₀ as thousands of 256-row leaves with
`householder_qr_r`, a `fori_loop` over the N columns that reads and writes
the whole leaf stack in HBM once per column: O(M·N²) bytes where O(M·N) do.
This kernel keeps a block of leaves resident in VMEM for all N steps, so the
stack is read from HBM once and only R is written back.

Layout: the leaf axis is on lanes. The input is ``[n, rows, leaves]``
(column, row within the leaf, leaf) and a grid step takes 128 leaves. Every
reduction over a leaf's rows (σ, x_k, vᵀv, w = vᵀA) is then a sum over
sublanes and whole vregs, with no cross-lane work, and column k is a dynamic
index on the leading, untiled axis. No lane's result depends on another's,
so the last grid step may run past the last leaf: those lanes read whatever
is there and are never written back.

Step k updates columns k+1..n−1 only, GROUP of them at a time over chunks of
64 rows, so each chunk of v is loaded once for the group and each column's
partial sums stay in registers; a group that runs past column n−1 meets
zero columns and changes nothing.

The reflections are `householder_qr_r`'s: unnormalized v, β = 2/vᵀv (0 for
v = 0), α = −sign(x_k)·σ with sign(0) = 1, and `min(rows − 1, n)` steps, so R
agrees with it to rounding. R's column k is written as soon as step k has
made it: the rows above k as they stand, α on the diagonal, zeros below. No
Q and no reflectors are written. The output is ``[n, n_pad, leaves]``
(column, row, leaf), n_pad = n rounded up to 8 sublanes, rows ≥ n zero.

Accumulation is float32 for ≤32-bit input and float64 for float64 input.
The float64 path exists only in interpret mode (the TPU's Pallas compiler
has no float64, `compiled_dtype_check`); callers take the kernel for
float32 on a TPU and keep the XLA loop for every other dtype.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels._platform import compiled_dtype_check

LANES = 128  # leaves per grid step: one vreg's lanes
GROUP = 4  # columns updated together, sharing each load of v
_F32 = 4
# Scoped VMEM the kernel may ask for (v5e has 128 MiB per core); a block
# whose working set does not fit stays on the XLA loop (`fits`).
VMEM_LIMIT_BYTES = 64 * 1024 * 1024


def _round_up(x: int, to: int) -> int:
    return -(-x // to) * to


def fits(n: int, rows: int) -> bool:
    """Whether n columns of `rows`-row leaves fit the kernel's VMEM limit
    with a quarter of it left to Mosaic: the input block twice (pipelined),
    the working copy with its GROUP − 1 zero columns, v, the output twice."""
    rows_p, n_p = _round_up(rows, 8), _round_up(n, 8)
    held = _F32 * LANES * ((3 * n + GROUP) * rows_p + 2 * n * n_p)
    return held <= VMEM_LIMIT_BYTES * 3 // 4


def _row_chunk(rows_p: int) -> int:
    """Rows of a column held in registers at once: 8 vregs where the leaf
    allows."""
    return next(c for c in (64, 32, 16, 8) if rows_p % c == 0)


def _sum_rows(x):
    return jnp.sum(x, axis=0, keepdims=True)  # [1, LANES]: one per leaf


def _tsqr_leaf_body(a_ref, r_ref, work_ref, v_ref, *, n: int, n_p: int,
                    steps: int):
    rows_p = a_ref.shape[1]
    acc = work_ref.dtype
    # Columns past n stay zero, so a group that runs past the last column
    # updates nothing: w = vᵀ0 = 0.
    work_ref[n:] = jnp.zeros((GROUP - 1, rows_p, LANES), acc)
    work_ref[:n] = a_ref[...].astype(acc)
    step = functools.partial(_reflect, r_ref=r_ref, work_ref=work_ref,
                             v_ref=v_ref, n=n)
    # int32 bounds: under jax_enable_x64 Python ints make the index int64,
    # which Mosaic cannot lower.
    jax.lax.fori_loop(jnp.int32(0), jnp.int32(steps), step, 0)
    r_row = jax.lax.broadcasted_iota(jnp.int32, (n_p, LANES), 0)
    for k in range(steps, n):  # a leaf of n rows leaves its last column as is
        r_ref[k] = jnp.where(r_row <= k, work_ref[k, :n_p, :],
                             0.0).astype(r_ref.dtype)


def _reflect(k, carry, *, r_ref, work_ref, v_ref, n: int):
    """Householder step k on every leaf of the block: write R's column k
    and reflect columns k+1..n−1."""
    rows_p, n_p = v_ref.shape[0], r_ref.shape[1]
    zero = jnp.zeros((), work_ref.dtype)
    row = jax.lax.broadcasted_iota(jnp.int32, (rows_p, LANES), 0)
    x = jnp.where(row >= k, work_ref[k], zero)
    at_k = row == k
    sigma = jnp.sqrt(_sum_rows(x * x))
    xk = _sum_rows(jnp.where(at_k, x, zero))
    alpha = jnp.where(xk >= 0, -sigma, sigma)
    v = x - jnp.where(at_k, alpha, zero)
    vv = _sum_rows(v * v)
    beta = jnp.where(vv > 0, 2.0 / jnp.where(vv > 0, vv, 1.0), zero)
    v_ref[...] = v
    r_row = jax.lax.broadcasted_iota(jnp.int32, (n_p, LANES), 0)
    r_ref[k] = jnp.where(r_row < k, work_ref[k, :n_p, :], jnp.where(
        r_row == k, alpha, zero)).astype(r_ref.dtype)
    update = functools.partial(_update_group, k=k, beta=beta,
                               work_ref=work_ref, v_ref=v_ref)
    # lax.div: jnp's floor division of a traced int32 does not lower.
    groups = jax.lax.div(n - 1 - k + GROUP - 1, jnp.int32(GROUP))
    jax.lax.fori_loop(jnp.int32(0), groups, update, 0)
    return carry


def _update_group(g, carry, *, k, beta, work_ref, v_ref):
    """a_j ← a_j − v·β·vᵀa_j for the GROUP columns from k+1+g·GROUP on,
    each chunk of v loaded once for all of them."""
    rows_p = v_ref.shape[0]
    chunk = _row_chunk(rows_p)
    chunks = [pl.ds(c, chunk) for c in range(0, rows_p, chunk)]
    cols = [k + 1 + g * GROUP + i for i in range(GROUP)]
    sums = [jnp.zeros((chunk, LANES), work_ref.dtype)] * GROUP
    for rs in chunks:
        vc = v_ref[rs]
        sums = [s + vc * work_ref[j, rs] for s, j in zip(sums, cols)]
    ws = [_sum_rows(s) * beta for s in sums]
    for rs in chunks:
        vc = v_ref[rs]
        for j, w in zip(cols, ws):
            work_ref[j, rs] = work_ref[j, rs] - vc * w
    return carry


@functools.partial(jax.jit, static_argnames=("rows", "interpret"))
def tsqr_leaf_kernel(stack: jnp.ndarray, *, rows: int | None = None,
                     interpret: bool = False) -> jnp.ndarray:
    """R of every leaf of ``stack`` [n, rows_p, leaves] → [n, n_pad, leaves].

    ``rows`` is the leaf's real row count (default: all of ``rows_p``); rows
    past it must be zero, and set only how many reflections are made, as
    `householder_qr_r` makes ``min(rows − 1, n)``. ``rows_p`` must be a
    multiple of 8 and ``rows ≥ n``. ``leaves`` may be any count: the last
    grid step's lanes past it read whatever is there and are not written,
    and no lane's result depends on another's.
    """
    n, rows_p, leaves = stack.shape
    rows = rows_p if rows is None else rows
    if rows_p % 8 or not n <= rows <= rows_p:
        raise ValueError(f"tsqr_leaf_kernel: stack {stack.shape} with "
                         f"rows={rows} needs rows_p % 8 == 0 and "
                         f"n <= rows <= rows_p")
    if not interpret:
        compiled_dtype_check(stack.dtype, "tsqr_leaf_kernel")
    n_p = _round_up(n, 8)
    acc = jnp.float64 if stack.dtype == jnp.float64 else jnp.float32
    # int32 block indices: a bare 0 is int64 under x64, which Mosaic refuses.
    index = lambda i: (jnp.int32(0), jnp.int32(0), i)
    return pl.pallas_call(
        functools.partial(_tsqr_leaf_body, n=n, n_p=n_p,
                          steps=min(rows - 1, n)),
        grid=(pl.cdiv(leaves, LANES),),
        in_specs=[pl.BlockSpec((n, rows_p, LANES), index)],
        out_specs=pl.BlockSpec((n, n_p, LANES), index),
        out_shape=jax.ShapeDtypeStruct((n, n_p, leaves), stack.dtype),
        scratch_shapes=[pltpu.VMEM((n + GROUP - 1, rows_p, LANES), acc),
                        pltpu.VMEM((rows_p, LANES), acc)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",),
            vmem_limit_bytes=VMEM_LIMIT_BYTES),
        name="tsqr_leaf",
        interpret=interpret,
    )(stack)
