"""Shared test config.

float64 is enabled so the core-math oracles are tight (the paper evaluates in
double precision); model tests cast explicitly via cfg dtypes and are
unaffected. The XLA device-count flag is NEVER set here — distributed tests
spawn subprocesses (see test_distributed.py / test_dryrun.py) so smoke tests
and benchmarks keep seeing the single real device.
"""

import jax
import numpy as np
import pytest

jax.config.update("jax_enable_x64", True)


@pytest.fixture
def rng():
    return np.random.default_rng(0)
