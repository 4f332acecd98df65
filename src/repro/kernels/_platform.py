"""One platform policy for every Pallas kernel wrapper.

Each ``kernels/*/ops.py`` used to carry its own copy of ``_on_tpu()`` and the
``interpret=not _on_tpu()`` dispatch decision. This module is the single
source of truth:

  * `backend()`            — `jax.default_backend()` (cached; the backend
                             cannot change after the first dispatch).
  * `on_accelerator()`     — True on TPU **or GPU**: platforms where Pallas
                             lowers to a real kernel (Mosaic on TPU, Triton
                             on GPU) instead of the interpreter.
  * `resolve_interpret(x)` — the value every wrapper passes as
                             ``interpret=``: an explicit override wins
                             (``True``/``False``), ``None`` falls back to
                             interpret-off-accelerator. The override is how
                             tests force the interpreter on an accelerator
                             (numerics triage) or assert compiled lowering.
  * `compiled_dtype_check` — the refusal every kernel makes before a compiled
                             lowering of float64 data, which the TPU's Pallas
                             compiler cannot build. The refusal names the
                             switch (``use_kernel``) and the dtype instead of
                             failing deep inside XLA, and nothing swaps in
                             the XLA path behind the caller's back.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

__all__ = ["backend", "on_accelerator", "on_tpu", "resolve_interpret",
           "compiled_dtype_check"]

_ACCELERATORS = ("tpu", "gpu")


@functools.lru_cache(maxsize=None)
def backend() -> str:
    """The default JAX backend name ("cpu" / "gpu" / "tpu")."""
    return jax.default_backend()


def on_tpu() -> bool:
    return backend() == "tpu"


def on_accelerator() -> bool:
    """True where Pallas compiles to a native kernel (TPU or GPU)."""
    return backend() in _ACCELERATORS


def resolve_interpret(interpret: bool | None = None) -> bool:
    """Interpret-mode decision for a kernel dispatch.

    ``None`` (the default everywhere) = run compiled on an accelerator and
    interpreted elsewhere (CPU — the validation mode of this container). An
    explicit ``True``/``False`` is honored verbatim.
    """
    if interpret is not None:
        return bool(interpret)
    return not on_accelerator()


def compiled_dtype_check(dtype, kernel: str) -> None:
    """Raise `ValueError` for float64 data on a compiled kernel path."""
    if jnp.dtype(dtype) == jnp.float64:
        raise ValueError(
            f"{kernel}: use_kernel=True cannot run float64 data compiled "
            f"(got dtype {jnp.dtype(dtype).name}); the TPU's Pallas compiler "
            f"has no float64. Pass dtype=jnp.float32 with use_kernel=True, "
            f"or use_kernel=False for float64.")
