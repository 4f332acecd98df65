"""The number that decides ``correct`` for the qr kind.

R with its rows' signs made to give a positive diagonal, against the
Cholesky factor of the reference Gram matrix of the same request's join
(`reference.JoinReference.moments`): the R of the join matrix's QR with a
positive diagonal, which is unique. ``r_err`` is the relative Frobenius
error; a run's number is the largest over the answers compared.
"""

import numpy as np

from bench import reference


def compare(answer, moments: dict) -> dict:
    r = np.asarray(answer, np.float64)
    sign = np.sign(np.diag(r))
    r = r * np.where(sign == 0, 1.0, sign)[:, None]
    want = reference.r_factor(moments["gram"])
    return {"r_err": float(np.linalg.norm(r - want) / np.linalg.norm(want))}
