"""Householder R of a stack of TSQR leaves, each leaf resident in VMEM.

One Pallas kernel factors 128 leaves per grid step with the leaf axis on
lanes — see `kernel.py` for the layout, `ops.py` for `tsqr_r` (every TSQR
level through the kernel), `ref.py` for the XLA loop it is compared with.
"""

from .kernel import fits, tsqr_leaf_kernel
from .ops import tsqr_r
from .ref import tsqr_leaf_ref

__all__ = ["fits", "tsqr_leaf_kernel", "tsqr_r", "tsqr_leaf_ref"]
