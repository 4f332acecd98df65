"""Pallas TPU kernel: one fused FiGaRo node pass (mask · scan · tail · φ · emit).

`figaro_r0` runs two head/tail passes per join-tree node; the unfused XLA path
spends each one as a chain of separate [m, n] ops — live-row mask multiply,
weighted segmented scan, generalized-tail formula, segment-start zeroing,
φ-weight scaling — every link a full HBM round-trip over the node. This kernel
fuses the whole pass:

    d       = data · data_scale             (live-row masking, in-kernel)
    wa      = d · weights
    s_incl  = segmented inclusive prefix sum of wa   (restart at `first`)
    s_excl  = s_incl − wa
    emitted = emit_scale · (coef_a · d + coef_b · s_excl)

and writes BOTH outputs of one pass in a single HBM trip: ``emitted`` — the
finished R₀ slab, with segment-start rows already zeroed and √Φ folded in
because ``emit_scale`` carries both — and ``s_incl``, from whose segment-final
rows the caller gathers the heads with O(m) index work (no second [m, n] pass).

TPU mapping follows `kernels/head_tail`: grid = (col_blocks, row_blocks) with
rows innermost, so each column stripe walks row blocks sequentially and hands
the running segment prefix forward through VMEM scratch; the in-block
segmented scan is a Hillis–Steele ladder (log₂ bm vector steps on the VPU).
Accumulation is f32 for ≤32-bit I/O and f64 for f64 I/O. The f64 path exists
only in interpret mode: the TPU's Pallas compiler has no float64, so a
compiled (``interpret=False``) call with float64 data raises `ValueError`
before lowering — run the kernel path (``use_kernel=True``) at float32, or
take the XLA path for float64.

Grid/block sizing comes from the `AUTOTUNE` table, keyed by
``(backend, itemsize, width bound)``: narrow nodes take taller row blocks
(fewer carry hand-offs per stripe), wide nodes take wider column stripes
(fewer row walks), and f64 tiles halve the row block. TPU rows keep the live
set of four [bm, bn] tiles inside a ~2 MB VMEM budget; GPU (Triton) rows are
power-of-two tiles sized for a 256 KiB shared-memory/register budget, small
enough that even an f64 fall-through fits. Backends without their own rows
(CPU interpret mode) reuse the TPU shapes.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import _platform
from repro.kernels._platform import compiled_dtype_check

# (backend, itemsize, width bound) -> (block_rows, block_cols). Buckets are
# checked in order; `None` is the catch-all bound each (backend, itemsize)
# group must end with.
AUTOTUNE: dict[tuple[str, int, int | None], tuple[int, int]] = {
    ("tpu", 4, 128): (512, 128),
    ("tpu", 4, 512): (256, 256),
    ("tpu", 4, None): (128, 512),
    ("tpu", 8, 128): (256, 128),
    ("tpu", 8, 512): (128, 256),
    ("tpu", 8, None): (64, 512),
    ("gpu", 4, 128): (128, 128),
    ("gpu", 4, 512): (64, 256),
    ("gpu", 4, None): (16, 512),
    ("gpu", 8, 128): (64, 128),
    ("gpu", 8, 512): (32, 256),
    ("gpu", 8, None): (16, 512),
}


def choose_blocks(n: int, dtype, backend: str | None = None) -> tuple[int, int]:
    """(block_rows, block_cols) for an n-wide node from the autotune table.

    ``backend`` defaults to the platform backend (trace-time constant via
    `_platform.backend`); backends without their own table rows — CPU
    interpret mode — reuse the tpu shapes.
    """
    if backend is None:
        backend = _platform.backend()
    if not any(be == backend for be, _, _ in AUTOTUNE):
        backend = "tpu"
    itemsize = 8 if jnp.dtype(dtype).itemsize >= 8 else 4
    for (be, isz, bound), blocks in AUTOTUNE.items():
        if be == backend and isz == itemsize \
                and (bound is None or n <= bound):
            return blocks
    raise AssertionError(
        "AUTOTUNE must end each (backend, itemsize) with a None bound")


def _shift_down(x: jnp.ndarray, off: int) -> jnp.ndarray:
    """Rows shifted down by `off` (row r reads r-off), zero-filled at the top."""
    pad = jnp.zeros((off,) + x.shape[1:], x.dtype)
    return jnp.concatenate([pad, x[: x.shape[0] - off]], axis=0)


def _node_fused_body(data_ref, dscale_ref, w_ref, first_ref, ca_ref, cb_ref,
                     es_ref, out_ref, sincl_ref, carry_ref, *,
                     block_rows: int, acc_dtype):
    i = pl.program_id(1)  # row block (innermost => sequential carry is valid)

    @pl.when(i == 0)
    def _init():
        carry_ref[...] = jnp.zeros_like(carry_ref)

    d = (data_ref[...].astype(acc_dtype)
         * dscale_ref[...].astype(acc_dtype))        # [bm, bn] masked rows
    wa = d * w_ref[...].astype(acc_dtype)
    first = first_ref[...].astype(acc_dtype)         # [bm, 1]; 1.0 at starts

    # Segmented inclusive Hillis–Steele scan within the block:
    #   (f_a, x_a) ⊕ (f_b, x_b) = (f_a|f_b, x_b + (f_b ? 0 : x_a))
    x, f = wa, first
    off = 1
    while off < block_rows:
        x = x + (1.0 - f) * _shift_down(x, off)
        f = jnp.maximum(f, _shift_down(f, off))
        off *= 2
    # f is now "any segment start in this block up to r" — rows before the
    # first in-block boundary continue the previous block's segment.
    incl = x + (1.0 - f) * carry_ref[...]
    excl = incl - wa
    carry_ref[...] = incl[block_rows - 1:block_rows, :]

    out = es_ref[...].astype(acc_dtype) * (
        ca_ref[...].astype(acc_dtype) * d + cb_ref[...].astype(acc_dtype) * excl)
    out_ref[...] = out.astype(out_ref.dtype)
    sincl_ref[...] = incl.astype(sincl_ref.dtype)


@functools.partial(jax.jit, static_argnames=("block_rows", "block_cols",
                                             "interpret"))
def node_fused_kernel(
    data: jnp.ndarray,        # [m, n]
    data_scale: jnp.ndarray,  # [m, 1] row mask / pre-scale (1.0 = untouched)
    weights: jnp.ndarray,     # [m, 1] Givens weight v per row
    first: jnp.ndarray,       # [m, 1] 1.0 at segment starts
    coef_a: jnp.ndarray,      # [m, 1] tail coefficient √(c_excl/c_incl)
    coef_b: jnp.ndarray,      # [m, 1] tail coefficient −v/√(c_excl·c_incl)
    emit_scale: jnp.ndarray,  # [m, 1] √Φ · (pos>0): φ scaling + start zeroing
    *,
    block_rows: int | None = None,
    block_cols: int | None = None,
    interpret: bool = False,
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Returns (emitted [m, n], s_incl [m, n]) — see module docstring."""
    m, n = data.shape
    if not interpret:
        compiled_dtype_check(data.dtype, "node_fused_kernel")
    if block_rows is None or block_cols is None:
        tuned = choose_blocks(n, data.dtype)
        block_rows = block_rows or tuned[0]
        block_cols = block_cols or tuned[1]
    acc_dtype = jnp.float64 if data.dtype == jnp.float64 else jnp.float32
    bm = min(block_rows, max(8, m))
    bn = min(block_cols, max(128, n))
    # Pad rows to the block grid; padded rows start their own segments with
    # data_scale/emit_scale 0, so they neither pollute the carry nor emit.
    mp = -(-m // bm) * bm
    np_ = -(-n // bn) * bn
    if mp != m or np_ != n:
        pad1 = ((0, mp - m), (0, 0))
        data = jnp.pad(data, ((0, mp - m), (0, np_ - n)))
        data_scale = jnp.pad(data_scale, pad1)
        weights = jnp.pad(weights, pad1)
        first = jnp.pad(first, pad1, constant_values=1.0)
        coef_a = jnp.pad(coef_a, pad1)
        coef_b = jnp.pad(coef_b, pad1)
        emit_scale = jnp.pad(emit_scale, pad1)

    grid = (np_ // bn, mp // bm)
    row_spec = pl.BlockSpec((bm, bn), lambda j, i: (i, j))
    # An int32 block index: under jax_enable_x64 a bare 0 is int64, which
    # Mosaic refuses to return from the index map.
    vec_spec = pl.BlockSpec((bm, 1), lambda j, i: (i, jnp.int32(0)))
    emitted, s_incl = pl.pallas_call(
        functools.partial(_node_fused_body, block_rows=bm, acc_dtype=acc_dtype),
        grid=grid,
        in_specs=[row_spec] + [vec_spec] * 6,
        out_specs=[row_spec, row_spec],
        out_shape=[jax.ShapeDtypeStruct((mp, np_), data.dtype),
                   jax.ShapeDtypeStruct((mp, np_), data.dtype)],
        scratch_shapes=[pltpu.VMEM((1, bn), acc_dtype)],
        interpret=interpret,
    )(data, data_scale, weights, first, coef_a, coef_b, emit_scale)
    return emitted[:m, :n], s_incl[:m, :n]
