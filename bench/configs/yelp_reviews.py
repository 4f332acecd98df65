"""Yelp reviews: a many-to-many star over reviews, users and businesses.

Review(user, business) is the root, with User(user) and Business(business)
below it and Category(business) and Hours(business) below Business: the join
tree of the FiGaRo paper's Yelp experiment, with Hours in place of its
CheckIn. A review meets one user and one business, but a business meets
every one of its categories and opening days, so the join is many times the
input (about 18x at ``SIZES``), and the Category and Hours passes have
groups of several rows.

Numeric columns are those the dataset documents (categorical, text and list
fields are left out: ingest has no one-hot encoding); Category keeps no
column at all and only multiplies the join. N = 4 + 17 + 5 + 0 + 3 = 29.

The service holds one join structure and serves many tenants' values over
it, so the key structure (who reviewed what, how many categories and
opening days each business lists) comes from `STRUCTURE_SEED` and is the
same for every run; the run's seed draws the values. Reviews per business
and per user follow fixed quantile profiles (heavy-tailed), every user and
business has a review, and (user, business) pairs are unique, so nothing
dangles and every relation's live rows are its generated rows.

Each relation is returned sorted the way the FiGaRo plan orders its rows.
"""

import numpy as np
from scipy.special import ndtri

SOURCE = ("https://www.yelp.com/dataset/documentation/main (review.json, "
          "user.json, business.json with categories and hours); "
          "https://arxiv.org/abs/2204.00525 Sec. 8")
ROOT = "Review"
EDGES = (("Review", "User"), ("Review", "Business"),
         ("Business", "Category"), ("Business", "Hours"))
STRUCTURE_SEED = 20_220_125

# The published counts cut by one factor, 2^20 / 6,990,280: reviews per user
# and per business keep their published means. Categories and opening days
# per business are not cut.
SIZES = {
    "review_rows": 2**20,
    "users": 298_194,
    "businesses": 22_553,
}
# Review is cut to the largest power of two at which a whole run fits one
# v5e and its host: at 2^21 the batch-of-4 float32 qr program fits the chip,
# but the plain reference that decides `correct` enumerates the 57.5M-row
# join and the host's 40 GiB do not hold it beside the runtime. User and
# Business are cut by the same factor. The deployment it stands for: one
# tenant-facing join of the whole dataset on one chip, at 15% of its rows.
REDUCED = ("review_rows", "users", "businesses")
ASSUMED = {
    "file_counts": "6,990,280 reviews, 1,987,897 users, 150,346 businesses "
                   "(recalled, not checked)",
    "left_out": "CheckIn (one row per check-in timestamp: its join with the "
                "reviews is too large for the reference to enumerate) and "
                "Tip (not in the paper's tree)",
    "reviews_per_business": "5 + a lognormal quantile profile, sigma 1.5, "
                            "mean 46.5 (the published ratio); largest about "
                            "7,800 (the dataset's largest, recalled, 7,568)",
    "reviews_per_user": "1 + a lognormal quantile profile, sigma 1.6, mean "
                        "3.52 (the published ratio); most users one review",
    "categories_per_business": "1-10 in fixed shares, mean 4.27",
    "hours_per_business": "7 days for 70%, 6 for 15%, 5 for 10%, 4 to 1 "
                          "for 5%; every business lists at least one day",
    "values": "stars, counts and coordinates in the documented ranges, "
              "drawn per run from the seed; review_count is the generated "
              "count",
}
TINY = {
    "review_rows": 2_000,
    "users": 568,
    "businesses": 100,
}

CATEGORY_SHARES = {1: 4, 2: 12, 3: 22, 4: 22, 5: 17, 6: 11, 7: 6, 8: 3, 9: 2,
                   10: 1}  # percent of businesses listing that many
HOURS_SHARES = {7: 70, 6: 15, 5: 10, 4: 2, 3: 1, 2: 1, 1: 1}
USER_COLUMNS = ["review_count", "useful", "funny", "cool", "fans",
                "average_stars"] + [f"compliment_{c}" for c in (
                    "hot", "more", "profile", "cute", "list", "note", "plain",
                    "cool", "funny", "writer", "photos")]


def _profile(n: int, total: int, lo: int, hi: int, sigma: float):
    """``n`` counts in ``[lo, hi]`` summing to ``total``, from the quantiles
    of ``lo`` + a lognormal of shape ``sigma``: the same for every seed,
    ascending."""
    raw = np.exp(sigma * ndtri((np.arange(n) + 0.5) / n))

    def counts(c):
        return np.clip(lo + np.floor(c * raw).astype(np.int64), lo, hi)

    a, b = 0.0, 1.0
    while counts(b).sum() < total:
        a, b = b, 2 * b
    for _ in range(100):
        mid = (a + b) / 2
        a, b = (mid, b) if counts(mid).sum() < total else (a, mid)
    d = counts(a)
    short = total - int(d.sum())
    room = np.flatnonzero(d < hi)[::-1]  # largest first
    d[room[:short]] += 1
    return d


def _shares(rng, n: int, shares: dict) -> np.ndarray:
    """``n`` counts in the given percentages, in an order drawn from
    ``rng``."""
    sizes = np.array(list(shares))
    cut = np.round(np.cumsum(list(shares.values())) / 100 * n).astype(int)
    counts = np.repeat(sizes, np.diff(np.concatenate([[0], cut])))
    return rng.permutation(counts)


def _pairs(rng, per_user, per_business) -> tuple[np.ndarray, np.ndarray]:
    """Distinct (user, business) pairs with the given degrees: a random
    matching of review slots, then each repeated pair swaps its business
    with a random review where neither new pair exists yet."""
    user = np.repeat(np.arange(len(per_user)), per_user)
    business = rng.permutation(np.repeat(np.arange(len(per_business)),
                                         per_business))
    n_b = len(per_business)
    while True:
        code = user * n_b + business
        order = np.argsort(code, kind="stable")
        known = code[order]
        repeat = np.zeros(len(code), bool)
        repeat[order[1:]] = known[1:] == known[:-1]
        bad = np.flatnonzero(repeat)
        if not bad.size:
            return user, business
        other = rng.integers(0, len(code), bad.size)
        a = user[bad] * n_b + business[other]
        b = user[other] * n_b + business[bad]
        fresh = lambda c: known[np.minimum(np.searchsorted(known, c),
                                           len(known) - 1)] != c
        ok = fresh(a) & fresh(b) & ~repeat[other] & (a != b)
        bad, other, a, b = bad[ok], other[ok], a[ok], b[ok]
        # One swap per review, and no two swaps making the same pair.
        _, first = np.unique(other, return_index=True)
        keep = np.zeros(bad.size, bool)
        keep[first] = True
        new = np.concatenate([a, b])
        _, once = np.unique(new, return_index=True)
        made = np.zeros(new.size, bool)
        made[once] = True
        keep &= made[:bad.size] & made[bad.size:]
        bad, other = bad[keep], other[keep]
        business[bad], business[other] = business[other], business[bad]


def structure(sizes: dict) -> tuple[dict, np.ndarray]:
    """The key columns of every relation, ``{name: {attr: int array}}``,
    plan-sorted, and the day of each Hours row: from `STRUCTURE_SEED`
    alone."""
    rng = np.random.default_rng(STRUCTURE_SEED)
    n_rev, n_user, n_biz = (sizes["review_rows"], sizes["users"],
                            sizes["businesses"])
    per_biz = rng.permutation(_profile(n_biz, n_rev, 5, n_user // 2, 1.5))
    per_user = rng.permutation(_profile(n_user, n_rev, 1, n_biz // 4, 1.6))
    user, biz = _pairs(rng, per_user, per_biz)
    order = np.lexsort((biz, user))
    cats = _shares(rng, n_biz, CATEGORY_SHARES)
    days = _shares(rng, n_biz, HOURS_SHARES)
    hours_biz = np.repeat(np.arange(n_biz), days)
    # Each business's days are a run of the week from a day of its own.
    first = np.repeat(rng.integers(0, 7, n_biz), days)
    within = np.arange(len(hours_biz)) - np.repeat(np.cumsum(days) - days,
                                                   days)
    return {
        "Review": {"user": user[order], "business": biz[order]},
        "User": {"user": np.arange(n_user)},
        "Business": {"business": np.arange(n_biz)},
        "Category": {"business": np.repeat(np.arange(n_biz), cats)},
        "Hours": {"business": hours_biz},
    }, (first + within) % 7


def _counts(rng, p: float, shape) -> np.ndarray:
    """Heavy-tailed non-negative counts: geometric with success ``p``."""
    return (rng.geometric(p, shape) - 1).astype(np.float64)


def relations(rng: np.random.Generator, sizes: dict) -> dict:
    """``{name: (key_columns, values, column_names)}``, plan-sorted."""
    keys, day = structure(sizes)
    n_rev = len(keys["Review"]["user"])
    n_user, n_biz = len(keys["User"]["user"]), len(keys["Business"]["business"])
    n_cat, n_hours = (len(keys["Category"]["business"]),
                      len(keys["Hours"]["business"]))
    per_user = np.bincount(keys["Review"]["user"], minlength=n_user)
    per_biz = np.bincount(keys["Review"]["business"], minlength=n_biz)
    stars = rng.choice(np.arange(1.0, 6.0), n_rev,
                       p=[0.15, 0.08, 0.10, 0.22, 0.45])
    opens = rng.choice(np.arange(5.0, 12.5, 0.5), n_hours)
    user_values = np.column_stack([
        per_user.astype(np.float64), _counts(rng, 0.05, n_user),
        _counts(rng, 0.15, n_user), _counts(rng, 0.10, n_user),
        _counts(rng, 0.40, n_user),
        np.round(rng.uniform(1.0, 5.0, n_user), 2),
        _counts(rng, 0.30, (n_user, 11))])
    return {
        "Review": (keys["Review"], np.column_stack([
            stars, _counts(rng, 0.45, n_rev), _counts(rng, 0.75, n_rev),
            _counts(rng, 0.60, n_rev)]), ["stars", "useful", "funny", "cool"]),
        "User": (keys["User"], user_values, USER_COLUMNS),
        "Business": (keys["Business"], np.column_stack([
            rng.uniform(27.0, 54.0, n_biz), rng.uniform(-120.0, -74.0, n_biz),
            rng.choice(np.arange(1.0, 5.5, 0.5), n_biz),
            per_biz.astype(np.float64),
            (rng.random(n_biz) < 0.8).astype(np.float64)]),
            ["latitude", "longitude", "stars", "review_count", "is_open"]),
        "Category": (keys["Category"], np.zeros((n_cat, 0)), []),
        "Hours": (keys["Hours"], np.column_stack([
            day.astype(np.float64), opens,
            opens + rng.choice(np.arange(6.0, 14.5, 0.5), n_hours)]),
            ["day", "open", "close"]),
    }
