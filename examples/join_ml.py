"""ML over relational joins — the paper's motivating application (§1).

A feature store defined as a snowflake join feeds three classical-ML tasks,
all computed *without materializing the join* by reading everything off
FiGaRo's R factor, through the one `repro.figaro` façade:

  * linear regression  — ``ds.lsq(label)`` (closed form via
    back-substitution on R),
  * PCA                — ``ds.pca(k=)`` (eigen-decomposition of the N x N
    Gram from R, factorized centering),
  * SVD                — ``ds.svd()`` (singular values/right vectors of the
    join matrix).

Run:  PYTHONPATH=src python examples/join_ml.py
"""

import jax
jax.config.update("jax_enable_x64", True)

from repro.compile_cache import enable_compile_cache

enable_compile_cache()

import numpy as np

from repro import figaro
from repro.core.materialize import materialize_join
from repro.data.relational import retailer_like

# Retailer-style snowflake: Inventory fact + Location->Census, Item, Weather.
sess = figaro.Session()  # one engine/dtype/bucketing policy for all 3 tasks
ds = sess.from_tree(retailer_like(scale=800, cols=4))
n = len(ds.columns)

# --- linear regression: predict the last column from the rest ---------------
beta, resid = ds.lsq(n - 1)  # label by index; names work too ("Weather.w3")
a = materialize_join(ds.tree)  # ONLY to verify; FiGaRo never builds this
beta_ref, *_ = np.linalg.lstsq(a[:, :-1], a[:, -1], rcond=None)
print(f"join matrix         : {a.shape[0]} x {a.shape[1]} "
      f"(input rows: {ds.tree.db.total_rows})")
print(f"regression beta err : {np.abs(np.asarray(beta) - beta_ref).max():.2e}")
print(f"residual norm       : {float(resid):.4f}")

# --- PCA ---------------------------------------------------------------------
pca = ds.pca(k=3)
ac = a - a.mean(axis=0)
ev_ref = np.sort(np.linalg.eigvalsh(ac.T @ ac / (a.shape[0] - 1)))[::-1][:3]
print(f"PCA top-3 variance  : {np.asarray(pca.explained_variance).round(3)}")
print(f"       (reference)  : {ev_ref.round(3)}")

# --- SVD ----------------------------------------------------------------------
s, vt = ds.svd()
s_ref = np.linalg.svd(a, compute_uv=False)
print(f"singular values err : {np.abs(np.asarray(s) - s_ref[:len(s)]).max():.2e}")

assert np.abs(np.asarray(beta) - beta_ref).max() < 1e-6
assert np.allclose(np.asarray(pca.explained_variance), ev_ref, rtol=1e-7)
# All three reads hit ONE engine; the QR inside compiled once per signature.
assert ds.stats()["trace_count"] == 3  # qr is re-derived per kind's pipeline
print("OK — regression/PCA/SVD over the join, join never materialized.")
