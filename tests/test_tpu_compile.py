"""Ahead-of-time compiles of the main path for a described TPU v5e chip.

The TPU compiler is installed alongside JAX, so these tests compile for a
chip that is described, not attached: they catch what the interpret-mode
kernel tests cannot (Mosaic lowering limits, VMEM budgets, int64 leaking
into a kernel under ``jax_enable_x64``) at no chip time. Nothing here runs a
program, so nothing here says anything about results or times.

The topology is described inside a module-scoped fixture, never at import:
only one process at a time may load the TPU library, and every test worker
imports every test file.
"""

import contextlib
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core.engine import FigaroEngine
from repro.core.plan_cache import build_capacity_plan
from repro.data.relational import yelp_like
from repro.kernels import _platform
from repro.kernels.node_fused.kernel import node_fused_kernel
from repro.kernels.tsqr_leaf import ops as tsqr_leaf_ops

from helpers import hlo_instructions


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    # The TPU library logs under /tmp unless told otherwise; keep the test
    # from writing outside the checkout.
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def tpu_backend(monkeypatch):
    """Steer the platform policy to the chip's branch: compiled kernels and
    the TPU block table, as `resolve_interpret(None)` picks on a TPU."""
    monkeypatch.setattr(_platform, "backend", lambda: "tpu")


def _spec(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


# Node capacities of yelp_like(scale=1_000_000): Review [2^21, 1] and
# User/Business [2^17, 3].
@pytest.mark.parametrize("m, n", [(2**21, 1), (2**17, 3)])
def test_node_fused_compiles_at_full_size(one_chip, m, n):
    col = _spec((m, 1), jnp.float32, one_chip)
    compiled = node_fused_kernel.lower(
        _spec((m, n), jnp.float32, one_chip), col, col, col, col, col, col,
        interpret=False).compile()
    assert "tpu_custom_call" in compiled.as_text()


# R0 of the benchmark's cells: yelp_reviews (14,336 leaves of 256 rows, N =
# 29) and favorita_sales (33,889 leaves, the last one partial, N = 8).
@pytest.mark.parametrize("m, n", [(14336 * 256, 29), (8675456, 8)])
def test_tsqr_leaf_compiles_at_full_size(one_chip, m, n):
    """Every TSQR level on the kernel: the leaves and each combine level."""
    tsqr = jax.jit(tsqr_leaf_ops.tsqr_r,
                   static_argnames=("leaf_rows", "interpret"))
    compiled = tsqr.lower(_spec((m, n), jnp.float32, one_chip),
                          leaf_rows=256, interpret=False).compile()
    leaves = -(-m // 256)
    levels = 1 + int(np.ceil(np.log2(leaves)))
    assert _kernel_calls(compiled.as_text()) == ["tsqr_leaf"] * levels


def _kernel_calls(text):
    """The name of every Pallas kernel call in an optimized HLO text."""
    return re.findall(r'%([a-z_]+)\.\d+ = [^\n]*custom_call_target='
                      r'"tpu_custom_call"', text)


@pytest.mark.parametrize("use_kernel", [False, True])
def test_qr_engine_program_compiles(one_chip, tpu_backend, use_kernel):
    """The whole batched qr program the async server dispatches (B=4,
    float32), for a small capacity plan: TSQR runs on the `tsqr_leaf` kernel
    whatever ``use_kernel`` says, and the fused node passes only under it."""
    calls = set(_kernel_calls(_qr_program(one_chip, use_kernel).as_text()))
    assert calls == ({"tsqr_leaf", "node_fused_kernel"} if use_kernel
                     else {"tsqr_leaf"})


def test_float64_svd_program_has_no_tsqr_leaf(one_chip, tpu_backend):
    """float64 keeps the XLA loop: the svd program lowered for the chip
    carries no kernel call at all."""
    text = _lowered(one_chip, "svd_batched", np.float64).as_text()
    assert "tsqr_leaf" not in text and "tpu_custom_call" not in text


def test_phase_scopes_leave_the_tpu_program_unchanged(one_chip, tpu_backend,
                                                      monkeypatch):
    """The named phase scopes are metadata: without them the TPU compiler
    makes the same fusions under the same names."""
    scoped = _qr_program(one_chip, False).as_text()
    assert "figaro.heads_tails" in scoped
    monkeypatch.setattr(jax, "named_scope",
                        lambda name: contextlib.nullcontext())
    plain = _qr_program(one_chip, False).as_text()
    assert hlo_instructions(scoped).count("fusion(") > 100
    assert hlo_instructions(scoped) == hlo_instructions(plain)


def _qr_program(one_chip, use_kernel):
    return _lowered(one_chip, "qr_batched", np.float32,
                    use_kernel=use_kernel).compile()


def _lowered(one_chip, kind, dtype, *, use_kernel=False):
    plan = build_capacity_plan(yelp_like())
    as_spec = lambda x: _spec(np.shape(x), np.asarray(x).dtype, one_chip)
    plan_spec = jax.tree.map(as_spec, plan.without_data())
    data_spec = tuple(_spec((4,) + np.shape(d), np.float64, one_chip)
                      for d in plan.data)
    program = FigaroEngine()._make_jitted(kind, False, None, None, None)
    return program.lower(
        plan_spec, data_spec, dtype=np.dtype(dtype), leaf_rows=256,
        use_kernel=use_kernel, assembly="padded")


@pytest.mark.parametrize("kernel", ["node_fused"])
def test_compiled_kernel_refuses_float64(kernel):
    """float64 on a compiled kernel path fails with a ValueError naming the
    switch and the dtype, before anything is lowered."""
    col = np.ones((64, 1))
    with pytest.raises(ValueError, match=r"use_kernel.*float64"):
        node_fused_kernel(np.ones((64, 2)), col, col, col, col, col, col,
                          interpret=False)
