"""Paper §3: heads/tails are the closed forms of Givens-rotation sequences."""

import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core.heads_tails import (givens_sequence, head, segmented_cumsum,
                                    segmented_head_tail, tail)


def _rand(rng, *shape):
    return rng.normal(size=shape)


# -- Lemma 3.3 (v = 1) and Lemma 3.5 (weighted) vs explicit rotations --------


@pytest.mark.parametrize("m,n1,n2", [(2, 1, 1), (3, 2, 2), (7, 2, 3),
                                     (16, 1, 5)])
def test_lemma35_vs_explicit_rotations(rng, m, n1, n2):
    s = _rand(rng, 1, n1)
    t = _rand(rng, m, n2)
    v = rng.uniform(0.5, 2.0, size=m)
    a = np.concatenate([v[:, None] * s, t], axis=1)
    g = givens_sequence(v)
    u = g @ a
    # top row: [ ||v|| * S | head(T, v) ]
    expect_top = np.concatenate([np.linalg.norm(v) * s[0],
                                 np.asarray(head(jnp.array(t), jnp.array(v)))])
    np.testing.assert_allclose(u[0], expect_top, atol=1e-12)
    # S-columns below the top row are zeroed
    np.testing.assert_allclose(u[1:, :n1], 0, atol=1e-12)
    # T-columns below the top row are tail(T, v)
    np.testing.assert_allclose(
        u[1:, n1:], np.asarray(tail(jnp.array(t), jnp.array(v))), atol=1e-12)


def test_lemma33_unweighted_is_v_equals_one(rng):
    t = _rand(rng, 9, 4)
    ones = jnp.ones(9)
    np.testing.assert_allclose(np.asarray(head(jnp.array(t))),
                               np.asarray(head(jnp.array(t), ones)), atol=0)
    np.testing.assert_allclose(np.asarray(tail(jnp.array(t))),
                               np.asarray(tail(jnp.array(t), ones)), atol=0)


def test_rotation_sequence_is_orthogonal(rng):
    v = rng.uniform(0.1, 3.0, size=12)
    g = givens_sequence(v)
    np.testing.assert_allclose(g @ g.T, np.eye(12), atol=1e-12)


def test_head_tail_preserve_gram(rng):
    """[head; tail] stacked with the scaled-S row is an orthogonal transform
    of [S⊗v | A]: Frobenius norm and Gram matrix are preserved."""
    a = _rand(rng, 11, 5)
    v = rng.uniform(0.5, 2.0, size=11)
    s = _rand(rng, 1, 2)
    m = np.concatenate([v[:, None] * s, a], axis=1)
    g = givens_sequence(v)
    np.testing.assert_allclose(np.linalg.norm(g @ m), np.linalg.norm(m),
                               rtol=1e-12)
    h = np.asarray(head(jnp.array(a), jnp.array(v)))
    t = np.asarray(tail(jnp.array(a), jnp.array(v)))
    top = np.concatenate([np.linalg.norm(v) * s[0], h])
    rest = np.concatenate([np.zeros((10, 2)), t], axis=1)
    u = np.concatenate([top[None, :], rest], axis=0)
    np.testing.assert_allclose(u.T @ u, m.T @ m, rtol=1e-10, atol=1e-10)


def test_lemma37_scaling(rng):
    """H(kA, l v) = k H(A, v); same for tails (Lemma 3.7)."""
    a = jnp.array(_rand(rng, 6, 3))
    v = jnp.array(rng.uniform(0.5, 2.0, size=6))
    k, l = 2.5, 3.0
    np.testing.assert_allclose(np.asarray(head(k * a, l * v)),
                               k * np.asarray(head(a, v)), rtol=1e-12)
    np.testing.assert_allclose(np.asarray(tail(k * a, l * v)),
                               k * np.asarray(tail(a, v)), rtol=1e-12)


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float16, jnp.float32,
                                   jnp.float64])
def test_head_tail_preserve_dtype(rng, dtype):
    """Mirrors the normalize_sign dtype test: the weight vector is cast to
    the data dtype, so a float64 v must not silently upcast low-precision
    (bf16/f16/f32) data through `head` (tail already cast)."""
    a = jnp.asarray(_rand(rng, 6, 3), dtype=dtype)
    v = jnp.asarray(rng.uniform(0.5, 2.0, size=6))  # float64 weights
    h = head(a, v)
    t = tail(a, v)
    assert h.dtype == dtype, (h.dtype, dtype)
    assert t.dtype == dtype, (t.dtype, dtype)


# -- property test: the transform is orthogonal for arbitrary inputs ---------


@settings(max_examples=40, deadline=None)
@given(m=st.integers(2, 20), n=st.integers(1, 6), seed=st.integers(0, 2**31))
def test_property_gram_preserved(m, n, seed):
    rng = np.random.default_rng(seed)
    a = rng.normal(size=(m, n))
    v = rng.uniform(0.1, 4.0, size=m)
    h = np.asarray(head(jnp.array(a), jnp.array(v)))
    t = np.asarray(tail(jnp.array(a), jnp.array(v)))
    u = np.concatenate([h[None, :], t], axis=0)
    # U = G' A for orthogonal G' acting on the weighted stack; Gram of the
    # *weighted* matrix [v⊗1 ⊙ A] is NOT preserved, but Lemma 3.5 says
    # U^T U == A^T A when v == 1; for general v the invariant involves S too.
    if np.allclose(v, v[0]):
        np.testing.assert_allclose(u.T @ u, a.T @ a, rtol=1e-9, atol=1e-9)
    # Always: stacking with the scaled S column preserves the full Gram.
    s = rng.normal(size=(1, 2))
    mfull = np.concatenate([v[:, None] * s, a], axis=1)
    top = np.concatenate([np.linalg.norm(v) * s[0], h])
    rest = np.concatenate([np.zeros((m - 1, 2)), t], axis=1)
    ufull = np.concatenate([top[None, :], rest], axis=0)
    np.testing.assert_allclose(ufull.T @ ufull, mfull.T @ mfull,
                               rtol=1e-8, atol=1e-8)


# -- segmented version --------------------------------------------------------


def test_segmented_cumsum_restarts(rng):
    x = jnp.array(rng.normal(size=10))
    first = jnp.array([1, 0, 0, 1, 0, 1, 0, 0, 0, 1], bool)
    out = np.asarray(segmented_cumsum(x, first))
    expect = np.empty(10)
    acc = 0.0
    for i in range(10):
        acc = float(x[i]) if bool(first[i]) else acc + float(x[i])
        expect[i] = acc
    np.testing.assert_allclose(out, expect, rtol=1e-12)


@pytest.mark.parametrize("m, width", [(37, None), (40, 3), (64, 1)])
def test_segmented_cumsum_ladder_matches_loop(rng, m, width):
    """The Hillis–Steele ladder at lengths that are and are not powers of
    two, with segments spanning several ladder strides."""
    shape = (m,) if width is None else (m, width)
    x = rng.normal(size=shape)
    first = rng.uniform(size=m) < 0.1
    first[0] = True
    out = np.asarray(segmented_cumsum(jnp.array(x), jnp.array(first)))
    expect = np.empty(shape)
    acc = np.zeros(shape[1:])
    for i in range(m):
        acc = x[i] if first[i] else acc + x[i]
        expect[i] = acc
    np.testing.assert_allclose(out, expect, rtol=1e-12, atol=1e-12)


def _segment_layout(sizes):
    """(pos_in_seg, last_of_seg, seg_live) of contiguous segments of the
    given sizes; a size-0 slot is dead and points at the row before it."""
    sizes = np.asarray(sizes)
    pos = np.concatenate([np.arange(s) for s in sizes])
    last = np.maximum(np.cumsum(sizes) - 1, 0)
    return jnp.array(pos), jnp.array(last), jnp.array(sizes > 0)


def test_segmented_head_tail_matches_per_segment(rng):
    sizes = [3, 1, 5, 2]
    data = _rand(rng, sum(sizes), 4)
    w = rng.uniform(0.5, 2.0, size=sum(sizes))
    heads, tails, norms = segmented_head_tail(
        jnp.array(data), jnp.array(w), *_segment_layout(sizes))
    ofs = 0
    for k, s in enumerate(sizes):
        blk, vb = data[ofs:ofs + s], w[ofs:ofs + s]
        np.testing.assert_allclose(np.asarray(heads[k]),
                                   np.asarray(head(jnp.array(blk),
                                                   jnp.array(vb))), rtol=1e-10)
        np.testing.assert_allclose(np.asarray(norms[k]), np.linalg.norm(vb),
                                   rtol=1e-12)
        if s > 1:
            np.testing.assert_allclose(
                np.asarray(tails[ofs + 1:ofs + s]),
                np.asarray(tail(jnp.array(blk), jnp.array(vb))), rtol=1e-9)
        # first row of each segment carries no tail
        np.testing.assert_allclose(np.asarray(tails[ofs]), 0, atol=0)
        ofs += s


def _np_head_tail(a, v):
    """Definition 3.4 in float64 numpy, row by row: (head, [m-1, n] tail)."""
    a, v = np.asarray(a, np.float64), np.asarray(v, np.float64)
    h = (v @ a) / np.linalg.norm(v)
    t = np.empty((a.shape[0] - 1, a.shape[1]))
    for j in range(1, a.shape[0]):
        nj, nj1 = np.linalg.norm(v[:j]), np.linalg.norm(v[:j + 1])
        t[j - 1] = (nj * a[j] - v[j] * (v[:j] @ a[:j]) / nj) / nj1
    return h, t


# Live segment sizes and trailing dead rows / dead group slots, laid out as a
# capacity-padded plan does: dead rows extend the last live segment with
# weight 0 and zeroed data, dead slots hold no rows.
SEGMENT_LAYOUTS = {
    "one_row": ([1] * 12, 0, 0),
    "long_uneven": ([1, 17, 2, 40, 1, 9, 33], 0, 0),
    "capacity_padded": ([4, 1, 6, 2], 5, 3),
}


@pytest.mark.parametrize("layout", list(SEGMENT_LAYOUTS))
@pytest.mark.parametrize("dtype,tol", [(np.float32, 1e-5), (np.float64, 1e-12)])
def test_segmented_head_tail_gathers_segment_totals(rng, layout, dtype, tol):
    """Heads and norms read at each segment's last row equal the per-segment
    Definition 3.4; dead rows emit zero tails, dead slots zero heads and
    norms."""
    live_sizes, dead_rows, dead_slots = SEGMENT_LAYOUTS[layout]
    m_live = sum(live_sizes)
    m = m_live + dead_rows
    data = _rand(rng, m, 3)
    data[m_live:] = 0.0
    w = rng.uniform(0.5, 2.0, size=m)
    w[m_live:] = 0.0
    sizes = live_sizes[:-1] + [live_sizes[-1] + dead_rows] + [0] * dead_slots
    heads, tails, norms = segmented_head_tail(
        jnp.array(data, dtype), jnp.array(w, dtype), *_segment_layout(sizes))
    heads, tails, norms = map(np.asarray, (heads, tails, norms))
    assert heads.dtype == tails.dtype == norms.dtype == dtype
    assert heads.shape == (len(sizes), 3) and norms.shape == (len(sizes),)
    ofs = 0
    for k, s in enumerate(live_sizes):
        h, t = _np_head_tail(data[ofs:ofs + s], w[ofs:ofs + s])
        scale = max(np.abs(data[ofs:ofs + s]).max(), 1.0)
        np.testing.assert_allclose(heads[k], h, rtol=tol, atol=tol * scale)
        np.testing.assert_allclose(norms[k], np.linalg.norm(w[ofs:ofs + s]),
                                   rtol=tol)
        np.testing.assert_allclose(tails[ofs + 1:ofs + s], t, rtol=tol,
                                   atol=tol * scale)
        assert not tails[ofs].any()
        ofs += s
    assert not tails[m_live:].any()
    assert not heads[len(live_sizes):].any()
    assert not norms[len(live_sizes):].any()
