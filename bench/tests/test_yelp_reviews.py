"""The Yelp configuration: a key structure fixed across seeds, float32
counts that stay exact at full size, and the façade's join counters against
numpy at the tiny size of every configuration."""

import jax.numpy as jnp
import numpy as np
import pytest

from bench import reference, registry

yelp = registry.config("yelp_reviews")
CONFIGS = [c["name"] for c in registry.benchmark()["configs"]]
SEEDS = (2 ** 31 + 5, 3_000_000_019)


def _group_sizes(tables):
    return {name: np.sort(np.unique(
        np.stack(list(k.values()), axis=1), axis=0, return_counts=True)[1])
        for name, (k, _, _) in tables.items()}


def test_two_seeds_share_the_keys_and_differ_in_the_values():
    a, b = (yelp.relations(np.random.default_rng(s), yelp.TINY)
            for s in SEEDS)
    for name in a:
        assert a[name][0].keys() == b[name][0].keys()
        for attr in a[name][0]:
            np.testing.assert_array_equal(a[name][0][attr], b[name][0][attr])
        assert a[name][2] == b[name][2]
    sizes_a, sizes_b = _group_sizes(a), _group_sizes(b)
    for name in sizes_a:
        np.testing.assert_array_equal(sizes_a[name], sizes_b[name])
    assert not np.array_equal(a["Review"][1], b["Review"][1])
    assert not np.array_equal(a["User"][1][:, 1:], b["User"][1][:, 1:])


def test_the_schema_has_29_columns_and_no_dangling_key():
    tables = yelp.relations(np.random.default_rng(SEEDS[0]), yelp.TINY)
    widths = {n: v.shape[1] for n, (_, v, _) in tables.items()}
    assert widths == {"Review": 4, "User": 17, "Business": 5, "Category": 0,
                      "Hours": 3}
    review = tables["Review"][0]
    assert len(np.unique(review["user"] * yelp.TINY["businesses"]
                         + review["business"])) == len(review["user"])
    per_user = np.bincount(review["user"], minlength=yelp.TINY["users"])
    per_biz = np.bincount(review["business"],
                          minlength=yelp.TINY["businesses"])
    assert per_user.min() >= 1 and per_biz.min() >= 5
    # review_count is the generated count, in User and in Business.
    np.testing.assert_array_equal(tables["User"][1][:, 0], per_user)
    np.testing.assert_array_equal(tables["Business"][1][:, 3], per_biz)


def test_the_largest_count_at_full_size_is_exact_in_float32():
    """Every count of Algorithm 1 is a part of some key's join rows: a
    business's reviews x categories x opening days, or the sum of that over
    a user's reviews. float32 holds integers exactly up to 2^24."""
    keys, _ = yelp.structure(yelp.SIZES)
    review = keys["Review"]
    n_biz = yelp.SIZES["businesses"]
    fan = (np.bincount(keys["Category"]["business"], minlength=n_biz)
           * np.bincount(keys["Hours"]["business"], minlength=n_biz))
    per_biz = np.bincount(review["business"], minlength=n_biz) * fan
    per_user = np.bincount(review["user"], weights=fan[review["business"]])
    assert len(review["user"]) == yelp.SIZES["review_rows"]
    assert max(per_biz.max(), per_user.max()) < 2 ** 24
    assert per_biz.sum() > 15 * len(review["user"])  # the join, ~18x


def test_the_planner_picks_review_as_the_root():
    from repro import figaro

    tables = yelp.relations(np.random.default_rng(SEEDS[0]), yelp.TINY)
    ds = figaro.Session().ingest(tables).join(list(yelp.EDGES))
    assert ds.stats()["root"] == yelp.ROOT


@pytest.mark.parametrize("config", CONFIGS)
def test_join_counters_equal_a_numpy_count(config):
    from repro import figaro

    cfg = registry.config(config)
    tables = cfg.relations(np.random.default_rng(SEEDS[1]), cfg.TINY)
    order = reference.preorder(cfg.ROOT, cfg.EDGES)
    join = reference.JoinReference({r: tables[r][0] for r in order},
                                   cfg.ROOT, cfg.EDGES)
    want, = join.moments([{r: tables[r][1] for r in order}])
    ds = figaro.Session().ingest(tables).join(list(cfg.EDGES), root=cfg.ROOT)
    r0 = np.asarray(ds.r0(dtype=jnp.float64))
    stats = ds.stats()
    assert stats["join_rows"] == want["rows"]
    assert stats["r0_rows"] == r0.shape[0]
    nonzero = int(np.count_nonzero(np.any(r0 != 0.0, axis=1)))
    assert stats["r0_nonzero_rows_bound"] == nonzero
