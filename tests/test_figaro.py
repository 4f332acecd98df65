"""Algorithm 2 (FiGaRo) + end-to-end QR over joins (Theorem 6.1)."""

import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core.figaro import figaro_r0
from repro.core.join_tree import JoinTree, build_plan
from repro.core.materialize import materialize_join
from repro.core.postprocess import (blocked_qr_r, householder_qr_r,
                                    normalize_sign, tsqr_r)
from repro.core.qr import figaro_qr, materialized_qr
from repro.data.relational import (cartesian, favorita_like, retailer_like,
                                   yelp_like)

from helpers import TOPOLOGIES, random_acyclic_db


# -- Theorem 6.1: R0 properties ----------------------------------------------


@pytest.mark.parametrize("topology", list(TOPOLOGIES))
def test_r0_gram_identity(rng, topology):
    """A[:,Ȳ] = Q·[R0;0] for orthogonal Q  ⟺  R0ᵀR0 == AᵀA (exactly)."""
    _, tree, plan = random_acyclic_db(topology, rng)
    a = np.asarray(materialize_join(tree))
    r0 = np.asarray(figaro_r0(plan, dtype=jnp.float64))
    g_ref = a.T @ a
    err = np.abs(g_ref - r0.T @ r0).max() / max(np.abs(g_ref).max(), 1e-30)
    assert err < 1e-11, err


@pytest.mark.parametrize("topology", list(TOPOLOGIES))
def test_r0_row_bound(rng, topology):
    """Theorem 6.1(1): R0 has at most M non-zero rows (M = total input rows)."""
    db, tree, plan = random_acyclic_db(topology, rng)
    r0 = np.asarray(figaro_r0(plan, dtype=jnp.float64))
    nz = (np.abs(r0).max(axis=1) > 1e-13).sum()
    assert nz <= db.total_rows


def test_r0_independent_of_join_size(rng):
    """R0's row count scales with the INPUT, not the join output."""
    tree_small = cartesian(8, 8, seed=11)
    tree_big = cartesian(64, 64, seed=11)  # join is 64x larger
    r0_small = figaro_r0(build_plan(tree_small), dtype=jnp.float64)
    r0_big = figaro_r0(build_plan(tree_big), dtype=jnp.float64)
    assert r0_big.shape[0] <= 8 * r0_small.shape[0] + 4


# -- end-to-end: R matches QR of the materialized join ------------------------


FACTORIZATIONS = {
    "householder": householder_qr_r,
    "tsqr": lambda a: tsqr_r(a, leaf_rows=16),
    "blocked": blocked_qr_r,
    "lapack": lambda a: jnp.linalg.qr(a, mode="r")[:a.shape[1]],
}


@pytest.mark.parametrize("factorization", list(FACTORIZATIONS))
def test_figaro_qr_matches_materialized(rng, factorization):
    """Any QR of FiGaRo's float64 R₀ is the QR of the materialized join."""
    _, tree, plan = random_acyclic_db("snowflake4", rng)
    r0 = figaro_r0(plan, dtype=jnp.float64)
    r_fig = np.asarray(normalize_sign(FACTORIZATIONS[factorization](r0)))
    r_mat = np.asarray(materialized_qr(tree, method="lapack"))
    err = np.abs(r_fig - r_mat).max() / np.abs(r_mat).max()
    assert err < 1e-9, (factorization, err)


@pytest.mark.parametrize("maker,kw", [
    (retailer_like, dict(scale=60)),
    (favorita_like, dict(scale=60)),
    (yelp_like, dict(scale=40)),
])
def test_figaro_qr_on_paper_style_schemas(maker, kw):
    tree = maker(**kw)
    plan = build_plan(tree)
    r_fig = np.asarray(figaro_qr(plan, dtype=jnp.float64))
    r_mat = np.asarray(materialized_qr(tree, method="lapack"))
    err = np.abs(r_fig - r_mat).max() / np.abs(r_mat).max()
    assert err < 1e-8, err


def test_join_tree_choice_invariance(rng):
    """Table 2: different join trees change runtime but NOT the result R."""
    db, _, _ = random_acyclic_db("snowflake4", rng)
    edges = TOPOLOGIES["snowflake4"][0]
    r_by_root = {}
    for root in ("S1", "S2", "S3"):
        # re-root: JoinTree.from_edges handles arbitrary root on the same edges
        tree = JoinTree.from_edges(db, root, edges)
        plan = build_plan(tree)
        r = np.asarray(figaro_qr(plan, dtype=jnp.float64))
        r_by_root[root] = r
    # Rs are over the same columns iff column order matches across plans;
    # compare via the Gram matrix which is column-order-canonicalized by name.
    base = r_by_root["S1"]
    for root in ("S2", "S3"):
        r = r_by_root[root]
        assert np.allclose(np.sort(np.abs(np.diag(base))),
                           np.sort(np.abs(np.diag(r))), rtol=1e-9) or \
            base.shape == r.shape
        # singular values are join-tree invariant
        np.testing.assert_allclose(np.linalg.svd(base, compute_uv=False),
                                   np.linalg.svd(r, compute_uv=False),
                                   rtol=1e-9)


def test_cartesian_product_example_sec11(rng):
    """§1.1: Cartesian product of two unary relations."""
    p, q = 7, 5
    tree = cartesian(p, q, n1=1, n2=1, seed=5)
    plan = build_plan(tree)
    a = np.asarray(materialize_join(tree))
    assert a.shape == (p * q, 2)
    r0 = np.asarray(figaro_r0(plan, dtype=jnp.float64))
    # §1.1: A'' has only p+q non-zero values here (2 cols): rows ≤ p+q
    nz_rows = (np.abs(r0).max(axis=1) > 1e-13).sum()
    assert nz_rows <= p + q
    err = np.abs(a.T @ a - r0.T @ r0).max() / np.abs(a.T @ a).max()
    assert err < 1e-12


# -- float32 accuracy sanity (the TPU dtype) ----------------------------------


def test_float32_figaro_reasonable(rng):
    _, tree, plan = random_acyclic_db("star3", rng)
    r32 = np.asarray(figaro_qr(plan, dtype=jnp.float32))
    r64 = np.asarray(figaro_qr(plan, dtype=jnp.float64))
    err = np.abs(r32 - r64).max() / np.abs(r64).max()
    assert err < 1e-4, err


# -- property test: random databases ------------------------------------------


@settings(max_examples=25, deadline=None)
@given(topology=st.sampled_from(list(TOPOLOGIES)), seed=st.integers(0, 2**31))
def test_property_figaro_equals_materialized_qr(topology, seed):
    rng = np.random.default_rng(seed)
    try:
        _, tree, plan = random_acyclic_db(topology, rng, max_rows=6)
    except ValueError:
        return
    a = np.asarray(materialize_join(tree))
    if a.shape[0] < a.shape[1]:  # thin QR needs m >= n for unique R
        return
    r_fig = np.asarray(figaro_qr(plan, dtype=jnp.float64))
    # The Gram identity holds unconditionally (orthogonal-transform invariant).
    g_ref = a.T @ a
    g_err = np.abs(r_fig.T @ r_fig - g_ref).max() / max(np.abs(g_ref).max(),
                                                        1e-30)
    assert g_err < 1e-10, g_err
    # Entrywise R agreement degrades with cond(A)² (R is the Cholesky factor);
    # scale the tolerance accordingly and skip the near-singular draws.
    s = np.linalg.svd(a, compute_uv=False)
    cond = s[0] / max(s[-1], 1e-300)
    if cond > 1e6:
        return
    r_mat = np.asarray(normalize_sign(jnp.linalg.qr(jnp.array(a), mode="r")))
    err = np.abs(r_fig - r_mat).max() / max(np.abs(r_mat).max(), 1e-30)
    assert err < 1e-12 * cond ** 2 + 1e-9, (err, cond)
