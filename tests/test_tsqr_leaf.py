"""The `tsqr_leaf` Pallas kernel (interpret mode on the CPU) against the XLA
Householder loop and LAPACK, and `postprocess_r0`'s choice between them."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.postprocess import (householder_qr_r, normalize_sign,
                                    postprocess_r0, tsqr_r)
from repro.kernels import _platform
from repro.kernels.tsqr_leaf import ops, tsqr_leaf_kernel, tsqr_leaf_ref

ZERO, DEFICIENT = 1, 2  # leaves of the stack that are all zero / rank 3
LEAVES = 131  # two grid steps, the second ragged
TOL = {np.float64: 1e-12, np.float32: 2e-5}


def _leaf_stack(rng, n, rows, dtype):
    """[n, rows_p, LEAVES] of random leaves, one all zero and one whose
    columns repeat three columns; rows past ``rows`` are zero padding."""
    leaves = rng.normal(size=(LEAVES, rows, n))
    leaves[ZERO] = 0.0
    leaves[DEFICIENT] = leaves[DEFICIENT][:, np.arange(n) % 3]
    rows_p = -(-rows // 8) * 8
    leaves = np.pad(leaves, ((0, 0), (0, rows_p - rows), (0, 0)))
    return leaves, jnp.asarray(np.transpose(leaves, (2, 1, 0)), dtype)


def _signed(r):
    """R with rows signed to a non-negative diagonal (LAPACK's R may differ
    from Householder's by a row sign)."""
    s = np.sign(np.diagonal(r, axis1=-2, axis2=-1))
    return r * np.where(s == 0, 1.0, s)[..., None]


@pytest.mark.parametrize("dtype", [np.float64, np.float32])
@pytest.mark.parametrize("n, rows", [(1, 256), (1, 2), (8, 256), (8, 16),
                                     (29, 256), (29, 58), (32, 256),
                                     (32, 64)])
def test_kernel_matches_householder_loop_and_lapack(rng, n, rows, dtype):
    leaves, stack = _leaf_stack(rng, n, rows, dtype)
    r = np.asarray(tsqr_leaf_kernel(stack, rows=rows, interpret=True),
                   np.float64)
    assert r.shape == (n, -(-n // 8) * 8, LEAVES)
    assert not r[:, n:].any()
    r = np.transpose(r[:, :n], (2, 1, 0))  # [leaf, row, column]
    loop = np.asarray(tsqr_leaf_ref(stack, rows=rows), np.float64)
    loop = np.transpose(loop, (2, 1, 0))
    scale = np.abs(loop).max()
    assert np.array_equal(r, np.triu(r))
    assert not r[ZERO].any()
    full = np.ones(LEAVES, bool)
    full[DEFICIENT] = False
    np.testing.assert_allclose(r[full], loop[full], atol=TOL[dtype] * scale)
    lapack = np.linalg.qr(leaves[full, :rows], mode="r")
    np.testing.assert_allclose(_signed(r[full]), _signed(lapack),
                               atol=TOL[dtype] * scale)
    # Rank 3: past it R is not unique, but RᵀR is AᵀA.
    a = leaves[DEFICIENT, :rows]
    np.testing.assert_allclose(r[DEFICIENT].T @ r[DEFICIENT], a.T @ a,
                               atol=TOL[dtype] * scale ** 2)


# Leaf counts 131, 4, 3 and 1: none a multiple of 128, odd levels included.
@pytest.mark.parametrize("m, n", [(256 * 130 + 7, 4), (1000, 29), (768, 8),
                                  (40, 32)])
def test_tsqr_on_the_kernel_matches_the_loop(rng, m, n):
    a = rng.normal(size=(m, n))
    a[100:300] = 0.0  # a zero leaf where there are several
    a = jnp.asarray(a)
    r = np.asarray(normalize_sign(ops.tsqr_r(a, 256, interpret=True)))
    loop = np.asarray(normalize_sign(tsqr_r(a, 256)))
    lapack = _signed(np.linalg.qr(np.asarray(a), mode="r"))
    np.testing.assert_allclose(r, loop, atol=1e-12 * np.abs(loop).max())
    np.testing.assert_allclose(r, lapack, atol=1e-12 * np.abs(loop).max())


@pytest.mark.parametrize("m, n", [(600, 5), (300, 12)])
def test_tsqr_on_the_kernel_of_zero_and_repeated_columns(rng, m, n):
    a = rng.normal(size=(m, 3))[:, np.arange(n) % 3]
    r = np.asarray(ops.tsqr_r(jnp.asarray(a, jnp.float32), 256,
                              interpret=True), np.float64)
    np.testing.assert_allclose(r.T @ r, a.T @ a,
                               atol=1e-5 * np.abs(a.T @ a).max())
    assert not np.asarray(ops.tsqr_r(jnp.zeros((m, n), jnp.float32), 256,
                                     interpret=True)).any()


def _uses_kernel(dtype, backend, monkeypatch, n=6):
    monkeypatch.setattr(_platform, "backend", lambda: backend)
    r0 = jnp.zeros((1000, n), dtype)
    jaxpr = jax.make_jaxpr(postprocess_r0)(r0)
    return "tsqr_leaf" in str(jaxpr)


# The widths of the benchmark's cells: favorita_sales and yelp_reviews.
@pytest.mark.parametrize("n", [8, 29])
def test_float32_on_a_tpu_takes_the_kernel(monkeypatch, n):
    assert _uses_kernel(jnp.float32, "tpu", monkeypatch, n)


@pytest.mark.parametrize("dtype, backend", [
    (jnp.float64, "tpu"), (jnp.bfloat16, "tpu"), (jnp.float16, "tpu"),
    (jnp.float32, "cpu"), (jnp.float32, "gpu")])
def test_other_dtypes_and_backends_keep_the_loop(monkeypatch, dtype,
                                                  backend):
    assert not _uses_kernel(dtype, backend, monkeypatch)


def test_a_leaf_too_wide_for_vmem_keeps_the_loop(monkeypatch):
    monkeypatch.setattr(_platform, "backend", lambda: "tpu")
    r0 = jnp.zeros((600, 300), jnp.float32)
    assert "tsqr_leaf" not in str(jax.make_jaxpr(postprocess_r0)(r0))


def test_householder_leaf_is_the_kernels_oracle(rng):
    """`tsqr_leaf_ref` is `householder_qr_r` per leaf, in the kernel's
    layout."""
    _, stack = _leaf_stack(rng, 5, 40, np.float64)
    ref = np.asarray(tsqr_leaf_ref(stack))
    one = np.asarray(householder_qr_r(jnp.transpose(stack[:, :, 7])))
    np.testing.assert_array_equal(ref[:, :, 7], one.T)
