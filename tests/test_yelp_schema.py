"""FiGaRo over a Yelp-shaped many-to-many star, against the materialized join.

Review(user, business) at the root, User(user) and Business(business) below
it, and below Business a Category relation with no data column (it only
multiplies the join) and an Hours relation whose groups hold one to seven
rows, one business holding most of the reviews. Everything runs through
`figaro.Session` in float64 and is compared with numpy over
`materialize_join`.

Tolerances: float64 carries about 1e-16 per operation; FiGaRo applies a few
hundred rotations per entry here and the join matrix's condition number is
below 1e3, so relative errors of 1e-9 leave room for both; the least-squares
coefficients also divide by the smallest singular value, hence 1e-8.
"""

import jax.numpy as jnp
import numpy as np
import pytest

from repro import figaro
from repro.core.join_tree import JoinTree
from repro.core.materialize import materialize_join
from repro.core.relation import Database

EDGES = [("Review", "User"), ("Review", "Business"),
         ("Business", "Category"), ("Business", "Hours")]
WIDTHS = {"Review": 4, "User": 17, "Business": 5, "Category": 0, "Hours": 3}
RTOL = 1e-9


def _tables(rng, users=30, businesses=8):
    # Skewed: business 0 is reviewed by every user, the others by a few.
    pairs = {(u, 0) for u in range(users)}
    pairs |= {(u, int(b)) for u in range(users)
              for b in rng.choice(np.arange(1, businesses), 2, replace=False)}
    user, biz = np.array(sorted(pairs)).T
    cats = rng.integers(1, 5, businesses)
    hours = np.array([7, 1, 3, 7, 5, 2, 6, 4])[:businesses]
    keys = {"Review": {"user": user, "business": biz},
            "User": {"user": np.arange(users)},
            "Business": {"business": np.arange(businesses)},
            "Category": {"business": np.repeat(np.arange(businesses), cats)},
            "Hours": {"business": np.repeat(np.arange(businesses), hours)}}
    return {name: (k, rng.uniform(-3.0, 3.0, (len(next(iter(k.values()))),
                                              WIDTHS[name])),
                   [f"{name.lower()}{j}" for j in range(WIDTHS[name])])
            for name, k in keys.items()}


def _join(tables):
    tree = JoinTree.from_edges(Database.from_arrays(tables), "Review", EDGES)
    return materialize_join(tree)


def _r_positive(r):
    r = np.asarray(r, np.float64)
    return r * np.sign(np.diag(r))[:, None]


def _rel(got, want):
    return np.linalg.norm(np.asarray(got) - want) / np.linalg.norm(want)


@pytest.fixture
def tables(rng):
    return _tables(rng)


def _dataset(tables, **session):
    return figaro.Session(dtype=jnp.float64, **session).ingest(tables).join(
        EDGES, root="Review")


def test_the_schema_is_wide_many_to_many_and_skewed(tables):
    a = _join(tables)
    assert a.shape[1] == 29
    assert a.shape[0] > 4 * sum(len(v) for _, v, _ in tables.values())
    hours = np.bincount(tables["Hours"][0]["business"])
    assert hours.max() == 7 and hours.min() == 1


@pytest.mark.parametrize("headroom", [0, 16])
def test_qr_svd_pca_lsq_match_the_materialized_join(tables, headroom):
    a = _join(tables)
    ds = _dataset(tables, headroom=headroom)
    r_ref = _r_positive(np.linalg.qr(a, mode="r"))
    assert _rel(_r_positive(ds.qr()), r_ref) < RTOL

    s, _ = ds.svd()
    s_ref = np.linalg.svd(a, compute_uv=False)
    assert _rel(s, s_ref) < RTOL

    pca = ds.pca(k=3)
    evals, evecs = np.linalg.eigh(np.cov(a.T))
    assert _rel(pca.explained_variance, evals[::-1][:3]) < RTOL
    top = evecs[:, ::-1][:, :3].T
    signs = np.sign(np.sum(np.asarray(pca.components) * top, axis=1))
    assert _rel(np.asarray(pca.components) * signs[:, None], top) < RTOL
    np.testing.assert_allclose(pca.mean, a.mean(axis=0), rtol=RTOL,
                               atol=RTOL)

    label = ds.columns.index("Hours.hours1")
    beta, resid = ds.lsq("Hours.hours1")
    feats = np.delete(a, label, axis=1)
    beta_ref, res_ref, *_ = np.linalg.lstsq(feats, a[:, label], rcond=None)
    assert _rel(beta, beta_ref) < 1e-8
    np.testing.assert_allclose(float(resid) ** 2, res_ref[0], rtol=1e-8)


def test_an_append_to_the_column_free_relation_grows_the_join(tables):
    ds = _dataset(tables, headroom=16)
    ds.qr()
    new = {"business": np.array([0, 0, 5])}
    assert ds.append("Category", new, np.zeros((3, 0)))
    keys, values, names = tables["Category"]
    grown = dict(tables, Category=(
        {"business": np.concatenate([keys["business"], new["business"]])},
        np.zeros((len(values) + 3, 0)), names))
    a = _join(grown)
    assert a.shape[0] > _join(tables).shape[0]
    assert ds.stats()["join_rows"] == a.shape[0]
    r_ref = _r_positive(np.linalg.qr(a, mode="r"))
    assert _rel(_r_positive(ds.qr()), r_ref) < RTOL


def test_a_served_batch_of_four_matches_each_request(tables, rng):
    ds = _dataset(tables, headroom=16)
    order = ds.tree.preorder()
    payloads = [tuple(tables[n][1] * (1 + 0.1 * rng.standard_normal(
        tables[n][1].shape)) for n in order) for _ in range(4)]
    server = ds.serve(kind="qr", max_batch=4, dtype=jnp.float64)
    try:
        server.pause()
        futures = [server.submit(p) for p in payloads]
        server.resume()
        answers = [f.result() for f in futures]
        assert server.stats()["dispatches"] == 1
    finally:
        server.close()
    for p, r in zip(payloads, answers):
        a = _join({n: (tables[n][0], v, tables[n][2])
                   for n, v in zip(order, p)})
        assert _rel(_r_positive(r), _r_positive(np.linalg.qr(a, "r"))) < RTOL


@pytest.mark.parametrize("headroom", [0, 16])
def test_stats_count_the_join_and_the_rows_r0_can_fill(tables, headroom):
    ds = _dataset(tables, headroom=headroom)
    before = ds.stats()
    assert before["join_rows"] is None and before["r0_rows"] is None
    r0 = np.asarray(ds.r0())
    stats = ds.stats()
    assert stats["join_rows"] == _join(tables).shape[0]
    assert stats["r0_rows"] == r0.shape[0] == ds.plan.spec.r0_rows
    # Random values fill every row the structure allows: the bound is met.
    nonzero = int(np.count_nonzero(np.any(r0 != 0.0, axis=1)))
    assert stats["r0_nonzero_rows_bound"] == nonzero < r0.shape[0]
