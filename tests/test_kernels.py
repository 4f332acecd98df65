"""Pallas kernels vs pure-jnp oracles (interpret mode on CPU).

Per the deliverable: shape/dtype sweeps asserting allclose against ref.py.
"""

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.heads_tails import segmented_head_tail
from repro.kernels.head_tail import ops as ht_ops, ref as ht_ref


# -- head_tail ----------------------------------------------------------------


@pytest.mark.parametrize("m,n", [(5, 3), (37, 9), (64, 128), (300, 40),
                                 (513, 129)])
@pytest.mark.parametrize("dtype", [jnp.float32])
def test_head_tail_kernel_sweep(rng, m, n, dtype):
    data = jnp.array(rng.normal(size=(m, n)), dtype)
    v = jnp.array(rng.uniform(0.5, 2.0, size=(m,)), dtype)
    first = np.zeros(m)
    first[0] = 1
    first[rng.random(m) < 0.2] = 1
    wa = data * v[:, None]
    ca = jnp.array(rng.normal(size=(m, 1)), dtype)
    cb = jnp.array(rng.normal(size=(m, 1)), dtype)
    f = jnp.array(first[:, None], dtype)
    out_k = ht_ops.segmented_tail(data, wa, f, ca, cb,
                                  block_rows=64, block_cols=128)
    out_r = ht_ref.segmented_tail_ref(data, wa, f, ca, cb)
    err = np.abs(np.asarray(out_k) - np.asarray(out_r)).max()
    assert err < 1e-4, err


def test_head_tail_kernel_integrated(rng):
    """segmented_head_tail(use_kernel=True) == pure-jnp path."""
    m, n = 200, 17
    data = jnp.array(rng.normal(size=(m, n)), jnp.float32)
    w = jnp.array(rng.uniform(0.5, 2.0, size=m), jnp.float32)
    seg = np.sort(rng.integers(0, 12, size=m)).astype(np.int32)
    pos = np.zeros(m, np.int32)
    for i in range(1, m):
        pos[i] = pos[i - 1] + 1 if seg[i] == seg[i - 1] else 0
    count = np.bincount(seg, minlength=12)
    last = np.maximum(np.cumsum(count) - 1, 0)
    args = (data, w, jnp.array(pos), jnp.array(last), jnp.array(count > 0))
    h1, t1, n1 = segmented_head_tail(*args, use_kernel=False)
    h2, t2, n2 = segmented_head_tail(*args, use_kernel=True)
    assert np.abs(np.asarray(t1) - np.asarray(t2)).max() < 1e-4
    np.testing.assert_allclose(np.asarray(h1), np.asarray(h2), rtol=1e-5)
    np.testing.assert_allclose(np.asarray(n1), np.asarray(n2), rtol=1e-6)


@pytest.mark.parametrize("m,block_rows", [(65, 64), (129, 64), (237, 32),
                                          (100, 64)])
def test_segmented_tail_rows_straddle_block_boundary(rng, m, block_rows):
    """`m` not a multiple of `block_rows`, with segments crossing every row
    block: the kernel's carried-prefix path (interpret mode) must agree with
    the XLA associative-scan path row for row."""
    from repro.core.heads_tails import segmented_cumsum

    n = 24
    data = jnp.array(rng.normal(size=(m, n)), jnp.float32)
    w = jnp.array(rng.uniform(0.5, 2.0, size=m), jnp.float32)
    # long segments (~1.5 blocks) so nearly every block boundary falls inside
    # a segment, plus a trailing remnant segment in the partial block
    bounds = list(range(0, m, max(3 * block_rows // 2, 2))) + [m]
    pos = np.concatenate([np.arange(b - a) for a, b in zip(bounds, bounds[1:])])
    first = (pos == 0).astype(np.float32)
    assert any(f == 0 and (i % block_rows) == 0 for i, f in enumerate(first)
               if i), "no segment straddles a block boundary"

    w2 = w * w
    wa = data * w[:, None]
    c_incl = segmented_cumsum(w2, jnp.array(first, bool))
    c_excl = c_incl - w2
    c_excl_safe = jnp.where(jnp.array(pos) > 0, c_excl, 1.0)
    coef_a = jnp.sqrt(c_excl_safe / c_incl)
    coef_b = -w / jnp.sqrt(c_excl_safe * c_incl)

    out_kernel = ht_ops.segmented_tail(
        data, wa, jnp.array(first), coef_a, coef_b,
        block_rows=block_rows, block_cols=128)
    # XLA associative-scan path: same coefficients applied to the segmented
    # exclusive prefix sum (this is segmented_head_tail's non-kernel branch)
    s_excl = segmented_cumsum(wa, jnp.array(first, bool)) - wa
    out_xla = coef_a[:, None] * data + coef_b[:, None] * s_excl
    live = np.asarray(pos) > 0  # rows at segment starts are garbage by spec
    err = np.abs(np.asarray(out_kernel)[live] - np.asarray(out_xla)[live]).max()
    assert err < 1e-4, err


def test_head_tail_kernel_single_row_segments(rng):
    """Degenerate case: every row its own segment -> all tails zero."""
    m, n = 16, 8
    data = jnp.array(rng.normal(size=(m, n)), jnp.float32)
    w = jnp.ones((m,), jnp.float32)
    last = jnp.arange(m, dtype=jnp.int32)
    pos = jnp.zeros(m, jnp.int32)
    h, t, norms = segmented_head_tail(data, w, pos, last, jnp.ones(m, bool),
                                      use_kernel=True)
    np.testing.assert_allclose(np.asarray(t), 0, atol=0)
    np.testing.assert_allclose(np.asarray(h), np.asarray(data), rtol=1e-6)
