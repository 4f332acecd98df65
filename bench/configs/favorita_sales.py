"""Favorita grocery sales: a key-foreign-key star over one fact table.

Sales(store, item, date) is the root, with Stores, Items and
Transactions(store, date) below it, Oil(date) below Transactions and
Holidays(date) below Oil: the join tree of the repository's Favorita-shaped
generator. Every Sales row meets exactly one row of each other relation, so
the join is the size of Sales and FiGaRo's many-to-many advantage is absent:
the fact-node pass and the post-processing of R0 set the pace.

Numeric columns are those of the Kaggle files (categorical ones are left
out: ingest has no one-hot encoding). Sales' (store, date) pairs are drawn
from Transactions' pairs and items uniformly, with (store, item, date)
unique as in the files. Every store, item, date and Transactions pair is
used, so no tuple dangles and every seed gives the same row counts.

Each relation is returned sorted the way the FiGaRo plan orders its rows.
"""

import numpy as np

SOURCE = ('https://www.kaggle.com/c/favorita-grocery-sales-forecasting/data '
          '(train, stores, items, transactions, oil, holidays_events); '
          'https://arxiv.org/abs/2204.00525 Sec. 8')
ROOT = "Sales"
EDGES = (("Sales", "Stores"), ("Sales", "Items"), ("Sales", "Transactions"),
         ("Transactions", "Oil"), ("Oil", "Holidays"))

SIZES = {
    "sales_rows": 2**22,
    "stores": 54,
    "items": 4_100,
    "transactions_rows": 83_488,
    "dates": 1_684,
}
# train.csv has 125,497,040 rows (as recalled); Sales is cut to the largest
# power-of-two capacity whose batch-of-4 qr program the TPU compiler fits on
# one v5e.
REDUCED = ("sales_rows",)
ASSUMED = {
    "file_counts": "125,497,040 sales, 54 stores, 4,100 items, 83,488 "
                   "transactions over 1,684 dates (recalled, not checked)",
    "oil_holidays": "one Oil row and one Holidays row per date (the files "
                    "have 1,218 oil prices and 350 holiday rows)",
    "items": "items drawn uniformly for each Sales row",
    "values": "unit_sales lognormal(1.5, 1.0); onpromotion Bernoulli(0.08); "
              "cluster 1..17; class 1000..6999; perishable Bernoulli(0.24); "
              "transactions round(lognormal(7.3, 0.5)); dcoilwtico "
              "U[26, 111); transferred Bernoulli(0.01), at least one",
}
TINY = {
    "sales_rows": 4_000,
    "stores": 6,
    "items": 50,
    "transactions_rows": 300,
    "dates": 60,
}


def _bernoulli(rng, p: float, rows: int) -> np.ndarray:
    return (rng.random(rows) < p).astype(np.float64)


def _transaction_pairs(rng, stores: int, dates: int, rows: int):
    """``rows`` distinct (store, date) pairs covering every store and date."""
    forced = np.arange(dates) % stores * dates + np.arange(dates)
    rest = np.setdiff1d(np.arange(stores * dates), forced)
    codes = np.sort(np.concatenate(
        [forced, rng.choice(rest, rows - dates, replace=False)]))
    return codes // dates, codes % dates  # sorted store-major


def _sales_keys(rng, pairs: int, items: int, rows: int) -> np.ndarray:
    """``rows`` distinct (pair, item) codes using every pair and item."""
    cover = np.concatenate([
        np.arange(pairs) * items + rng.integers(0, items, pairs),
        rng.integers(0, pairs, items) * items + np.arange(items)])
    codes = cover
    while True:
        _, first = np.unique(codes, return_index=True)
        if first.size >= rows:
            return codes[np.sort(first)[:rows]]
        extra = rows - first.size + rows // 50 + 1_000
        codes = np.concatenate([codes, rng.integers(0, pairs * items, extra)])


def relations(rng: np.random.Generator, sizes: dict) -> dict:
    """``{name: (key_columns, values, column_names)}``, plan-sorted."""
    n_sales, n_store, n_item, n_txn, n_date = (
        sizes["sales_rows"], sizes["stores"], sizes["items"],
        sizes["transactions_rows"], sizes["dates"])
    t_store, t_date = _transaction_pairs(rng, n_store, n_date, n_txn)
    codes = _sales_keys(rng, n_txn, n_item, n_sales)
    pair, item = codes // n_item, codes % n_item
    store, date = t_store[pair], t_date[pair]
    order = np.lexsort((date, item, store))
    holidays = _bernoulli(rng, 0.01, n_date)
    holidays[rng.integers(0, n_date)] = 1.0
    return {
        "Sales": ({"store": store[order], "item": item[order],
                   "date": date[order]},
                  np.stack([rng.lognormal(1.5, 1.0, n_sales),
                            _bernoulli(rng, 0.08, n_sales)], axis=1)[order],
                  ["unit_sales", "onpromotion"]),
        "Stores": ({"store": np.arange(n_store)},
                   rng.integers(1, 18, (n_store, 1)).astype(np.float64),
                   ["cluster"]),
        "Items": ({"item": np.arange(n_item)},
                  np.stack([rng.integers(1000, 7000, n_item).astype(
                      np.float64), _bernoulli(rng, 0.24, n_item)], axis=1),
                  ["class", "perishable"]),
        "Transactions": ({"store": t_store, "date": t_date},
                         np.round(rng.lognormal(7.3, 0.5, (n_txn, 1))),
                         ["transactions"]),
        "Oil": ({"date": np.arange(n_date)},
                rng.uniform(26.0, 111.0, (n_date, 1)), ["dcoilwtico"]),
        "Holidays": ({"date": np.arange(n_date)}, holidays[:, None],
                     ["transferred"]),
    }
