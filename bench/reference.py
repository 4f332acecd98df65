"""Plain float64 reference: the join enumerated in blocks, with numpy alone.

The join of the relations over a tree of edges is built row by row in the
plainest way: take a block of the root's rows, and for every edge, parent
before child, pair each partial join row with every child row whose shared
key attributes are equal. Each block of join rows is then a dense float64
matrix, and the block's Gram matrix and row count are added up.
Nothing here imports the system under test or reads anything it made.

From the Gram matrix G = AᵀA of the join matrix A follows the answer the
benchmark compares: R of A's QR with a positive diagonal is the Cholesky
factor of G.
"""

from __future__ import annotations

import concurrent.futures
import os

import numpy as np

BLOCK_ROWS = 1 << 20  # join rows per block
THREADS = min(8, os.cpu_count() or 1)


def preorder(root: str, edges) -> list[str]:
    """Relations in preorder, children in the order their edges are listed."""
    adj: dict[str, list[str]] = {}
    for a, b in edges:
        adj.setdefault(a, []).append(b)
        adj.setdefault(b, []).append(a)
    out, seen = [], set()

    def visit(node: str) -> None:
        out.append(node)
        seen.add(node)
        for nb in adj.get(node, []):
            if nb not in seen:
                visit(nb)

    visit(root)
    return out


def _parents(root: str, edges) -> dict[str, str]:
    order = preorder(root, edges)
    pos = {n: i for i, n in enumerate(order)}
    return {b if pos[a] < pos[b] else a: a if pos[a] < pos[b] else b
            for a, b in edges}


def _codes(keys: dict, attrs, cards: dict) -> np.ndarray:
    code = np.zeros(len(next(iter(keys.values()))), dtype=np.int64)
    for a in attrs:
        code = code * cards[a] + np.asarray(keys[a], dtype=np.int64)
    return code


class JoinReference:
    """Enumerates the join of ``keys`` (``{relation: {attr: int array}}``)
    over the tree ``(root, edges)``; values are supplied per call, so one
    enumeration serves several requests over the same keys."""

    def __init__(self, keys: dict, root: str, edges,
                 block_rows: int = BLOCK_ROWS):
        self.keys = keys
        self.order = preorder(root, edges)
        self.parent = _parents(root, edges)
        self.block_rows = block_rows
        cards: dict[str, int] = {}
        for rel in keys.values():
            for a, col in rel.items():
                cards[a] = max(cards.get(a, 0), int(np.max(col)) + 1)
        # Per child: its rows sorted by the key shared with its parent, the
        # distinct shared keys, and where each key's rows start and end.
        self.lookup = {}
        for child in self.order[1:]:
            par = self.parent[child]
            shared = [a for a in keys[child] if a in keys[par]]
            code = _codes(keys[child], shared, cards)
            rows = np.argsort(code, kind="stable")
            uniq, start, count = np.unique(code[rows], return_index=True,
                                           return_counts=True)
            parent_code = _codes(keys[par], shared, cards)
            self.lookup[child] = (rows, uniq, start, count, parent_code)

    def _expand(self, root_rows: np.ndarray) -> dict[str, np.ndarray]:
        """Row indices, per relation, of every join row that starts from
        ``root_rows``."""
        idx = {self.order[0]: root_rows}
        for child in self.order[1:]:
            rows, uniq, start, count, parent_code = self.lookup[child]
            code = parent_code[idx[self.parent[child]]]
            pos = np.clip(np.searchsorted(uniq, code), 0, len(uniq) - 1)
            hit = uniq[pos] == code
            n = np.where(hit, count[pos], 0)
            rep = np.repeat(np.arange(len(code)), n)
            first = np.cumsum(n) - n
            within = np.arange(len(rep)) - first[rep]
            idx = {k: v[rep] for k, v in idx.items()}
            idx[child] = rows[start[pos[rep]] + within]
        return idx

    def moments(self, values: list[dict]) -> list[dict]:
        """Gram matrix and row count of the join, per request.

        ``values`` holds one ``{relation: [rows, cols] array}`` per request;
        columns are laid out in preorder. Blocks of root rows are spread
        over a pool of threads (numpy's gathers and BLAS release the
        interpreter lock); each block's sums are added in block order."""
        root = self.order[0]
        m_root = len(next(iter(self.keys[root].values())))
        values = [{r: np.asarray(v[r], np.float64) for r in self.order}
                  for v in values]
        probe = min(m_root, 4096)
        fan_out = max(len(self._expand(np.arange(probe))[root]), 1) / probe
        step = max(1, int(self.block_rows / fan_out))
        starts = range(0, m_root, step)
        with concurrent.futures.ThreadPoolExecutor(THREADS) as pool:
            parts = list(pool.map(
                lambda lo: self._block_moments(
                    values, np.arange(lo, min(lo + step, m_root))), starts))
        out = []
        for p in range(len(values)):
            gram, rows = parts[0][p]
            for part in parts[1:]:
                gram = gram + part[p][0]
                rows += part[p][1]
            out.append({"gram": gram, "rows": rows})
        return out

    def _block_moments(self, values: list[dict], root_rows: np.ndarray):
        idx = self._expand(root_rows)
        rows = len(idx[self.order[0]])
        out = []
        for vals in values:
            block = np.concatenate([vals[r][idx[r]] for r in self.order],
                                   axis=1)
            out.append((block.T @ block, rows))
        return out


def r_factor(gram: np.ndarray) -> np.ndarray:
    """R of the join matrix's QR with a positive diagonal."""
    return np.linalg.cholesky(gram).T
