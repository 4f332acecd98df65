"""Public QR APIs: FiGaRo end-to-end and materialized-join baselines.

`figaro_qr` is the paper's pipeline: plan → counts → Algorithm 2 → post-process.
`figaro_qr_batched` is the serving form — one compiled dispatch factorizes B
feature-sets over the same join structure. Both are thin delegations onto the
process-wide `repro.api.default_session()` (the `repro.figaro` façade), so
repeat calls with same-signature plans hit its engine's cached executables;
new code should use `figaro.Session` / `JoinDataset` directly.
`materialized_qr` / `givens_qr_r` are the baselines the paper benchmarks
against (LAPACK Householder on the join output / textbook Givens rotations).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from .join_tree import FigaroPlan, JoinTree
from .materialize import materialize_join
from .postprocess import householder_qr_r, normalize_sign


def _session():
    # Lazy: repro.api imports repro.core.engine; importing it at module top
    # would cycle through repro.core.__init__ during a cold `import repro.api`.
    from repro.api import default_session

    return default_session()

__all__ = [
    "figaro_qr",
    "figaro_qr_batched",
    "figaro_qr_fn",
    "materialized_qr",
    "givens_qr_r",
    "implicit_q_gram_check",
]


def figaro_qr(
    tree_or_plan: JoinTree | FigaroPlan,
    data=None,
    *,
    dtype=jnp.float32,
    leaf_rows: int = 256,
    use_kernel: bool = False,
) -> jnp.ndarray:
    """Upper-triangular R of the QR decomposition of the (unmaterialized) join."""
    return _session().qr(tree_or_plan, data, batched=False, dtype=dtype,
                         leaf_rows=leaf_rows, use_kernel=use_kernel)


def figaro_qr_batched(
    tree_or_plan: JoinTree | FigaroPlan,
    data_batch,
    *,
    dtype=jnp.float32,
    leaf_rows: int = 256,
    use_kernel: bool = False,
) -> jnp.ndarray:
    """R for a batch of feature-sets over one join structure: ``data_batch[i]``
    is [B, m_i, n_i]; returns [B, N, N] from a single compiled dispatch."""
    return _session().qr(tree_or_plan, data_batch, batched=True, dtype=dtype,
                         leaf_rows=leaf_rows, use_kernel=use_kernel)


def figaro_qr_fn(plan: FigaroPlan, *, dtype=jnp.float32,
                 leaf_rows: int = 256, use_kernel: bool = False):
    """A jitted closure ``data_list -> R`` for a fixed plan.

    One compiled program for counts + Algorithm 2 + post-processing, with the
    plan *closed over* so each call dispatches on the data buffers alone —
    the minimum-overhead form wall-clock benchmarks time (compile excluded).
    For plan-generic dispatch (one executable shared across same-signature
    plans, batching, donation) use `FigaroEngine` / `figaro_qr` instead.
    """
    from .figaro import figaro_r0
    from .postprocess import postprocess_r0

    def fn(data):
        r0 = figaro_r0(plan, data, dtype=dtype, use_kernel=use_kernel)
        return postprocess_r0(r0, leaf_rows=leaf_rows)

    # Deliberately plan-closed: this factory exists for dispatch-minimal
    # wall-clock benchmarks; plan-generic dispatch lives in FigaroEngine.
    return jax.jit(fn)  # figaro-lint: disable=FIG002 -- plan-closed by design


def materialized_qr(tree: JoinTree, *, dtype=jnp.float64,
                    method: str = "lapack") -> jnp.ndarray:
    """Baseline: materialize the join, then classical QR (paper's MKL role)."""
    a = jnp.asarray(materialize_join(tree), dtype=dtype)
    if method == "lapack":
        r = jnp.linalg.qr(a, mode="r")[: a.shape[1]]
    elif method == "householder":
        r = householder_qr_r(a)
    elif method == "givens":
        r = givens_qr_r(a)
    else:
        raise ValueError(method)
    return normalize_sign(r)


def givens_qr_r(a: jnp.ndarray) -> jnp.ndarray:
    """Textbook Givens-rotation QR (one rotation per zeroed entry) -> R.

    The O(mn) rotations × O(n) work each that FiGaRo's block transforms replace.
    Kept for op-count comparisons and accuracy experiments on small inputs.
    """
    m, n = a.shape
    dtype = a.dtype

    def zero_entry(carry, idx):
        a = carry
        i, k = idx  # zero a[i, k] against a[i-1, k]
        xi = a[i - 1, k]
        xj = a[i, k]
        r = jnp.hypot(xi, xj)
        safe = r > 0
        c = jnp.where(safe, xi / jnp.where(safe, r, 1.0), 1.0)
        s = jnp.where(safe, -xj / jnp.where(safe, r, 1.0), 0.0)
        row_i = a[i - 1]
        row_j = a[i]
        a = a.at[i - 1].set(c * row_i - s * row_j)
        a = a.at[i].set(s * row_i + c * row_j)
        return a, None

    # Rotation schedule: for each column k, bubble zeros up from the bottom.
    idx = [(i, k) for k in range(n) for i in range(m - 1, k, -1)]
    if idx:
        idx = jnp.array(idx, dtype=jnp.int32)
        a, _ = jax.lax.scan(zero_entry, a.astype(dtype), idx)
    return jnp.triu(a[:n])


def implicit_q_gram_check(r: jnp.ndarray, gram: jnp.ndarray) -> jnp.ndarray:
    """‖RᵀR − AᵀA‖_F / ‖AᵀA‖_F — orthogonality proxy without materializing Q.

    (The paper computes Q lazily as A·R⁻¹; since Q never needs materializing,
    accuracy is checked on the Gram identity instead.)
    """
    return jnp.linalg.norm(r.T @ r - gram) / jnp.linalg.norm(gram)
