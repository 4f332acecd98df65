"""Training-loop behaviour: learning, microbatching, schedules, compression."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.data.pipeline import TokenPipeline
from repro.launch.mesh import make_host_mesh
from repro.optim.adamw import AdamWConfig, adamw_init, adamw_update
from repro.optim.compression import compressed_psum, init_residual
from repro.optim.orthogonal import orthogonalize
from repro.optim.schedules import warmup_cosine, wsd
from repro.train.step import init_state, make_eval_step, make_train_step


def test_loss_decreases_on_learnable_data():
    cfg = get_config("granite-3-8b", smoke=True)
    cfg = dataclasses.replace(cfg, remat=False)
    mesh = make_host_mesh()
    opt_cfg = AdamWConfig(lr=3e-3, weight_decay=0.0)
    state = init_state(jax.random.PRNGKey(0), cfg, opt_cfg)
    pipe = TokenPipeline(cfg.vocab, seq_len=64, global_batch=8, seed=0)
    step = jax.jit(make_train_step(cfg, opt_cfg, mesh))
    losses = []
    with mesh:
        for s in range(30):
            state, metrics = step(state, pipe.batch_at(s))
            losses.append(float(metrics["loss"]))
    assert np.mean(losses[-5:]) < np.mean(losses[:5]) - 0.2, losses


def test_microbatch_equivalence():
    """grad-accumulated microbatching == single big batch (same update)."""
    cfg = get_config("qwen3-8b", smoke=True)
    cfg = dataclasses.replace(cfg, remat=False, compute_dtype="float32")
    mesh = make_host_mesh()
    opt_cfg = AdamWConfig(lr=1e-3)
    state = init_state(jax.random.PRNGKey(0), cfg, opt_cfg)
    batch = {"tokens": jax.random.randint(jax.random.PRNGKey(7), (4, 32), 0,
                                          cfg.vocab)}
    with mesh:
        s1, m1 = jax.jit(make_train_step(cfg, opt_cfg, mesh))(state, batch)
        s2, m2 = jax.jit(make_train_step(cfg, opt_cfg, mesh,
                                         microbatch=2))(state, batch)
    for a, b in zip(jax.tree_util.tree_leaves(s1.params),
                    jax.tree_util.tree_leaves(s2.params)):
        np.testing.assert_allclose(np.asarray(a, np.float32),
                                   np.asarray(b, np.float32),
                                   rtol=2e-4, atol=2e-6)


def test_eval_step_runs():
    cfg = get_config("granite-3-8b", smoke=True)
    mesh = make_host_mesh()
    params = init_state(jax.random.PRNGKey(0), cfg, AdamWConfig()).params
    ev = jax.jit(make_eval_step(cfg, mesh))
    batch = {"tokens": jax.random.randint(jax.random.PRNGKey(1), (2, 16), 0,
                                          cfg.vocab)}
    with mesh:
        metrics = ev(params, batch)
    assert np.isfinite(float(metrics["loss"]))


# -- optimizer unit tests ------------------------------------------------------


def test_adamw_matches_manual_reference(rng):
    p = {"w": jnp.array(rng.normal(size=(4, 3)), jnp.float32)}
    g = {"w": jnp.array(rng.normal(size=(4, 3)), jnp.float32) * 0.01}
    cfg = AdamWConfig(lr=1e-2, b1=0.9, b2=0.95, eps=1e-8, weight_decay=0.0,
                      clip_norm=1e9)
    st = adamw_init(p, cfg)
    new_p, st, _ = adamw_update(g, st, p, cfg)
    m = 0.1 * np.asarray(g["w"])
    v = 0.05 * np.asarray(g["w"]) ** 2
    mhat = m / (1 - 0.9)
    vhat = v / (1 - 0.95)
    expect = np.asarray(p["w"]) - 1e-2 * mhat / (np.sqrt(vhat) + 1e-8)
    np.testing.assert_allclose(np.asarray(new_p["w"]), expect, rtol=1e-5)


def test_adamw_clipping():
    p = {"w": jnp.ones((2, 2), jnp.float32)}
    g = {"w": jnp.full((2, 2), 100.0, jnp.float32)}
    cfg = AdamWConfig(lr=1.0, clip_norm=1.0, weight_decay=0.0)
    st = adamw_init(p, cfg)
    _, _, metrics = adamw_update(g, st, p, cfg)
    assert float(metrics["grad_norm"]) == pytest.approx(200.0)


def test_schedules():
    fn = warmup_cosine(1.0, warmup=10, total=110)
    assert float(fn(jnp.array(0))) == 0.0
    assert float(fn(jnp.array(10))) == pytest.approx(1.0, rel=1e-5)
    assert float(fn(jnp.array(110))) == pytest.approx(0.1, rel=1e-4)
    fn = wsd(1.0, warmup=10, stable=50, decay=40, floor=0.01)
    assert float(fn(jnp.array(5))) == pytest.approx(0.5)
    assert float(fn(jnp.array(30))) == pytest.approx(1.0)
    assert float(fn(jnp.array(100))) == pytest.approx(0.01, rel=1e-3)
    # plateau really is flat (WSD's continued-pretraining property)
    assert float(fn(jnp.array(12))) == float(fn(jnp.array(58))) == 1.0


def test_orthogonalize_produces_orthonormal_frame(rng):
    g = jnp.array(rng.normal(size=(64, 16)), jnp.float32)
    q = np.asarray(orthogonalize(g))
    gram = q.T @ q / q.shape[1]  # RMS-scaled: QᵀQ == n·I
    np.testing.assert_allclose(gram, np.eye(16), atol=5e-3)


def test_compressed_psum_error_feedback(rng):
    """int8+EF all-reduce: single-step error bounded; residual carries it."""
    mesh = make_host_mesh()  # 1 device -> axis size 1: exactness check
    from jax.sharding import PartitionSpec as P

    from repro.compat import shard_map

    g = {"w": jnp.array(rng.normal(size=(8, 8)), jnp.float32)}
    r = init_residual(g)

    def f(gg, rr):
        return compressed_psum(gg, rr, "data")

    out, res = shard_map(f, mesh=mesh, in_specs=(P(), P()),
                         out_specs=(P(), P()))(g, r)
    # with one participant the only error is quantization; EF captures it
    np.testing.assert_allclose(np.asarray(out["w"]) + np.asarray(res["w"]),
                               np.asarray(g["w"]), atol=1e-6)
    err = np.abs(np.asarray(out["w"]) - np.asarray(g["w"])).max()
    assert err < np.abs(np.asarray(g["w"])).max() / 64  # ~int8 resolution
