"""Compiled FiGaRo engine: plan-as-pytree jit, batched serving, cache hits,
and the scatter-free R₀ assembly path."""

import contextlib
import inspect
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.engine import FigaroEngine
from repro.core.figaro import figaro_r0, figaro_r0_batched
from repro.core.join_tree import build_plan
from repro.core.materialize import materialize_join
from repro.core.plan_cache import pad_plan
from repro.data.relational import cartesian

from helpers import hlo_instructions, random_acyclic_db

# Batched-vs-per-sample coverage: a path join, a star join, and a Cartesian
# edge (constant keys => the degenerate single-group path).
BATCH_TOPOLOGIES = {
    "path": ("chain3", False),
    "star": ("star3", False),
    "cartesian": ("chain2", True),
}


def _plan(topology, rng):
    name, cart = BATCH_TOPOLOGIES[topology]
    _, tree, plan = random_acyclic_db(name, rng, cartesian=cart)
    return tree, plan


def _batch(plan, rng, b, dtype):
    return tuple(
        np.stack([rng.normal(size=np.asarray(d).shape) for _ in range(b)])
        .astype(dtype) for d in plan.data)


# -- acceptance: batched == per-sample on >= 3 join topologies ----------------


@pytest.mark.parametrize("topology", list(BATCH_TOPOLOGIES))
@pytest.mark.parametrize("dtype,tol", [(np.float32, 1e-5),
                                       (np.float64, 1e-10)])
def test_batched_r0_matches_per_sample(rng, topology, dtype, tol):
    _, plan = _plan(topology, rng)
    batch = _batch(plan, rng, 4, dtype)
    rb = np.asarray(figaro_r0_batched(plan, batch, dtype=dtype))
    scale = max(np.abs(rb).max(), 1.0)
    for i in range(4):
        ri = np.asarray(figaro_r0(plan, [d[i] for d in batch], dtype=dtype))
        assert np.abs(rb[i] - ri).max() / scale < tol, (topology, i)


@pytest.mark.parametrize("topology", list(BATCH_TOPOLOGIES))
def test_engine_batched_qr_matches_per_sample(rng, topology):
    _, plan = _plan(topology, rng)
    # donate_data=False: the per-sample loop below re-reads `batch` after the
    # batched dispatch, which would read donated buffers on TPU (FIG011).
    engine = FigaroEngine(donate_data=False)
    batch = _batch(plan, rng, 3, np.float64)
    rb = np.asarray(engine.qr(plan, batch, batched=True, dtype=jnp.float64))
    for i in range(3):
        ri = np.asarray(engine.qr(plan, [d[i] for d in batch],
                                  dtype=jnp.float64))
        np.testing.assert_allclose(rb[i], ri, atol=1e-10 * max(
            np.abs(ri).max(), 1.0), err_msg=topology)


def test_batched_gram_invariant(rng):
    """Sample 0 of the batch is the plan's own data: R₀ᵀR₀ == AᵀA against the
    materialized join, per batch element."""
    tree, plan = _plan("star", rng)
    a = np.asarray(materialize_join(tree))
    other = tuple(
        np.stack([np.asarray(d), 2.0 * np.asarray(d)]) for d in plan.data)
    rb = np.asarray(figaro_r0_batched(plan, other, dtype=jnp.float64))
    g = a.T @ a
    err0 = np.abs(rb[0].T @ rb[0] - g).max() / max(np.abs(g).max(), 1e-30)
    err1 = np.abs(rb[1].T @ rb[1] - 4.0 * g).max() / max(np.abs(g).max(), 1e-30)
    assert err0 < 1e-11 and err1 < 1e-10, (err0, err1)


# -- acceptance: one compilation per plan signature ---------------------------


def test_engine_cache_hit_same_plan(rng):
    _, plan = _plan("path", rng)
    engine = FigaroEngine()
    engine.qr(plan, dtype=jnp.float64)
    assert engine.trace_count("qr") == 1
    engine.qr(plan, dtype=jnp.float64)  # same plan, same signature
    assert engine.trace_count("qr") == 1


def test_engine_cache_hit_across_plans_same_signature(rng):
    """A *different* plan object with equal static spec + data shapes must not
    retrace — the signature, not the identity, keys the executable cache."""
    _, plan = _plan("star", rng)
    engine = FigaroEngine()
    engine.qr(plan, dtype=jnp.float64)
    plan2 = plan.with_data([2.0 * np.asarray(d) for d in plan.data])
    r2 = engine.qr(plan2, dtype=jnp.float64)
    assert engine.trace_count("qr") == 1, "same-signature plan retraced"
    # and it really used plan2's data
    r1 = engine.qr(plan, dtype=jnp.float64)
    np.testing.assert_allclose(np.asarray(r2), 2.0 * np.asarray(r1),
                               atol=1e-9 * np.abs(np.asarray(r1)).max())


def test_engine_retraces_on_new_signature(rng):
    _, plan_a = _plan("path", rng)
    _, plan_b = _plan("star", rng)  # different topology => different spec
    engine = FigaroEngine()
    engine.qr(plan_a, dtype=jnp.float64)
    engine.qr(plan_b, dtype=jnp.float64)
    assert engine.trace_count("qr") == 2
    engine.qr(plan_a, dtype=jnp.float64)
    engine.qr(plan_b, dtype=jnp.float64)
    assert engine.trace_count("qr") == 2


def test_engine_batched_cache_hit(rng):
    _, plan = _plan("cartesian", rng)
    engine = FigaroEngine(donate_data=False)
    batch = _batch(plan, rng, 2, np.float64)
    engine.r0(plan, batch, batched=True, dtype=jnp.float64)
    engine.r0(plan, batch, batched=True, dtype=jnp.float64)
    assert engine.trace_count("r0_batched") == 1


# -- acceptance: scatter-free R0 assembly, plan passes through jit ------------


def test_r0_assembly_is_scatter_free(rng):
    """The R₀ emission path must contain no scatter / dynamic_update_slice —
    only concatenation/padding. (scatter-add from the counts' segment_sum is
    fine: that's Algorithm 1's reduction, not R₀ assembly.)"""
    for topology in BATCH_TOPOLOGIES:
        _, plan = _plan(topology, rng)
        jaxpr = str(jax.make_jaxpr(
            lambda p, d: figaro_r0(p, list(d), dtype=jnp.float64))(
                plan.without_data(), plan.data))
        assert "dynamic_update_slice" not in jaxpr, topology
        assert not re.search(r"\bscatter\[", jaxpr), topology


@pytest.mark.parametrize("topology", list(BATCH_TOPOLOGIES))
def test_node_passes_are_scatter_free(rng, topology):
    """The XLA path reads each segment's head and norm from its inclusive
    sums: no scatter lies under `figaro.heads_tails` or `figaro.project`,
    on an exact and on a capacity-padded plan, per sample and batched.
    (Algorithm 1's scatter-adds under `figaro.counts` stay.)"""
    _, exact = _plan(topology, rng)
    for plan in (exact, pad_plan(exact)):
        batch = _batch(plan, rng, 2, np.float32)
        for fn, data in ((figaro_r0, plan.data), (figaro_r0_batched, batch)):
            hlo = jax.jit(lambda p, d, fn=fn: fn(p, list(d), use_kernel=False)
                          ).lower(plan.without_data(), data).compile().as_text()
            scatters = [re.search(r'op_name="([^"]*)"', line).group(1)
                        for line in hlo.splitlines()
                        if re.search(r"= \S+ scatter\(", line)]
            assert "figaro.heads_tails" in hlo, (topology, fn.__name__)
            for name in scatters:
                assert not re.search(r"figaro\.(heads_tails|project)\b",
                                     name), (topology, fn.__name__, name)


def test_figaro_r0_jits_with_plan_argument(rng):
    """The plan crosses the jit boundary as a pytree argument; the traced
    function is plan-generic (no closure rebuild per plan)."""
    _, plan = _plan("star", rng)
    traces = []

    @jax.jit
    def f(p, d):
        traces.append(1)  # figaro-lint: disable=FIG010 -- once-per-trace append IS the retrace probe
        return figaro_r0(p, list(d), dtype=jnp.float64)

    r_a = f(plan.without_data(), plan.data)
    plan2 = plan.with_data([3.0 * np.asarray(d) for d in plan.data])
    r_b = f(plan2.without_data(), plan2.data)
    assert len(traces) == 1
    np.testing.assert_allclose(np.asarray(r_b), 3.0 * np.asarray(r_a),
                               atol=1e-9 * np.abs(np.asarray(r_a)).max())


def test_plan_pytree_roundtrip(rng):
    _, plan = _plan("path", rng)
    leaves, treedef = jax.tree_util.tree_flatten(plan)
    plan2 = jax.tree_util.tree_unflatten(treedef, leaves)
    assert plan2.spec == plan.spec
    r1 = np.asarray(figaro_r0(plan, dtype=jnp.float64))
    r2 = np.asarray(figaro_r0(plan2, dtype=jnp.float64))
    np.testing.assert_array_equal(r1, r2)


# -- engine downstream reads on the Cartesian-edge schema ---------------------


def test_make_figaro_server_batched_qr_and_lsq(rng):
    from repro.train.serve import make_figaro_server

    _, plan = _plan("star", rng)
    batch = _batch(plan, rng, 3, np.float64)
    serve_qr = make_figaro_server(plan, kind="qr", dtype=jnp.float64)
    rb = np.asarray(serve_qr(batch))
    engine = FigaroEngine()
    for i in range(3):
        ri = np.asarray(engine.qr(plan, [d[i] for d in batch],
                                  dtype=jnp.float64))
        np.testing.assert_allclose(rb[i], ri,
                                   atol=1e-10 * max(np.abs(ri).max(), 1.0))

    if plan.num_cols >= 2:
        serve_lsq = make_figaro_server(plan, kind="lsq",
                                       label_col=plan.num_cols - 1,
                                       dtype=jnp.float64)
        betas, resids = serve_lsq(batch)
        assert betas.shape == (3, plan.num_cols - 1)
        assert resids.shape == (3,)


def test_engine_svd_cartesian_edge():
    tree = cartesian(9, 6, n1=2, n2=2, seed=3)
    plan = build_plan(tree)
    engine = FigaroEngine()
    s, vt = engine.svd(plan, dtype=jnp.float64)
    a = np.asarray(materialize_join(tree))
    np.testing.assert_allclose(np.asarray(s),
                               np.linalg.svd(a, compute_uv=False), rtol=1e-9)
    assert engine.trace_count("svd") == 1
    engine.svd(plan, dtype=jnp.float64)
    assert engine.trace_count("svd") == 1


# -- one body per kind: batching and static options --------------------------

KINDS = ["r0", "qr", "svd", "pca", "least_squares"]


def _dispatch(engine, kind, plan, data, **options):
    """One dispatch through the kind's public engine method; least_squares
    takes its label column positionally."""
    if kind == "least_squares":
        label = options.pop("label_col", plan.num_cols - 1)
        return engine.least_squares(plan, label, data, **options)
    return getattr(engine, kind)(plan, data, **options)


def _comparable(kind, out):
    """The sign-free arrays of one answer: SVD and PCA as their Gram
    reconstructions, whose rows' signs do not matter."""
    if kind == "svd":
        s, vt = (np.asarray(x) for x in out)
        return [s, (vt.T * s ** 2) @ vt]
    if kind == "pca":
        comp = np.asarray(out.components)
        ev = np.asarray(out.explained_variance)
        return [(comp.T * ev) @ comp, ev, np.asarray(out.mean),
                np.asarray(out.num_rows)]
    return [np.asarray(x) for x in jax.tree.leaves(out)]


@pytest.mark.parametrize("kind", KINDS)
def test_every_kind_batched_matches_per_request(rng, kind):
    """The batched kind is the per-request body under vmap: one trace
    answers the batch, and each answer equals the request's own dispatch."""
    _, plan = _plan("star", rng)
    engine = FigaroEngine(donate_data=False)
    batch = _batch(plan, rng, 3, np.float64)
    out = _dispatch(engine, kind, plan, batch, batched=True,
                    dtype=jnp.float64)
    assert engine.trace_count(f"{kind}_batched") == 1
    for i in range(3):
        ref = _dispatch(engine, kind, plan, [d[i] for d in batch],
                        dtype=jnp.float64)
        got = jax.tree.map(lambda x: x[i], out)
        for g, r in zip(_comparable(kind, got), _comparable(kind, ref)):
            np.testing.assert_allclose(g, r, atol=1e-10 * max(
                np.abs(r).max(), 1.0), err_msg=f"{kind}[{i}]")
    assert engine.trace_count(kind) == 1


# A value other than the test's own for every keyword-only option of a body.
OPTION_CHANGES = {"dtype": jnp.float32, "leaf_rows": 8, "use_kernel": True,
                  "assembly": "band", "k": 1, "center": False,
                  "label_col": 0, "ridge": 0.5}


@pytest.mark.parametrize("kind", KINDS)
def test_static_options_reach_the_cache_key(rng, kind):
    """Every keyword-only option of the kind's body is part of the cache
    key: a repeat hits, and each changed option traces exactly once more."""
    _, plan = _plan("path", rng)
    engine = FigaroEngine(donate_data=False)
    params = inspect.signature(getattr(engine, f"_{kind}_impl")).parameters
    static = [name for name, p in params.items()
              if p.kind is inspect.Parameter.KEYWORD_ONLY]
    assert set(static) <= set(OPTION_CHANGES), static
    _dispatch(engine, kind, plan, None, dtype=jnp.float64)
    _dispatch(engine, kind, plan, None, dtype=jnp.float64)
    assert engine.trace_count(kind) == 1
    for n, name in enumerate(static, start=2):
        options = dict(dtype=jnp.float64)
        options[name] = OPTION_CHANGES[name]
        _dispatch(engine, kind, plan, None, **options)
        _dispatch(engine, kind, plan, None, **options)
        assert engine.trace_count(kind) == n, name
    _dispatch(engine, kind, plan, None, dtype=jnp.float64)
    assert engine.trace_count(kind) == len(static) + 1


# -- genuinely-batched pca / least_squares ------------------------------------


def test_engine_batched_least_squares_matches_per_sample(rng):
    _, plan = _plan("star", rng)
    label = plan.num_cols - 1
    engine = FigaroEngine(donate_data=False)
    batch = _batch(plan, rng, 3, np.float64)
    betas, resids = engine.least_squares(plan, label, batch, batched=True,
                                         ridge=0.4, dtype=jnp.float64)
    assert engine.trace_count("least_squares_batched") == 1
    for i in range(3):
        b_i, r_i = engine.least_squares(plan, label, [d[i] for d in batch],
                                        ridge=0.4, dtype=jnp.float64)
        np.testing.assert_allclose(np.asarray(betas[i]), np.asarray(b_i),
                                   atol=1e-10)
        np.testing.assert_allclose(np.asarray(resids[i]), np.asarray(r_i),
                                   atol=1e-10)


def test_engine_batched_pca_matches_per_sample(rng):
    _, plan = _plan("path", rng)
    engine = FigaroEngine(donate_data=False)
    batch = _batch(plan, rng, 3, np.float64)
    res = engine.pca(plan, batch, batched=True, k=2, dtype=jnp.float64)
    assert engine.trace_count("pca_batched") == 1
    assert res.explained_variance.shape == (3, 2)
    for i in range(3):
        ref = engine.pca(plan, [d[i] for d in batch], k=2, dtype=jnp.float64)
        np.testing.assert_allclose(
            np.asarray(res.explained_variance[i]),
            np.asarray(ref.explained_variance), atol=1e-10)
        np.testing.assert_allclose(np.asarray(res.mean[i]),
                                   np.asarray(ref.mean), atol=1e-12)


def test_lsq_server_is_single_dispatch(rng):
    """kind='lsq' must answer the whole batch through the batched executable,
    never a per-sample Python loop of engine dispatches."""
    from repro.train.serve import make_figaro_server

    _, plan = _plan("star", rng)
    engine = FigaroEngine(donate_data=False)
    batch = _batch(plan, rng, 4, np.float64)
    serve = make_figaro_server(plan, kind="lsq", label_col=plan.num_cols - 1,
                               dtype=jnp.float64, engine=engine)
    betas, resids = serve(batch)
    assert betas.shape == (4, plan.num_cols - 1) and resids.shape == (4,)
    assert engine.trace_count("least_squares_batched") == 1
    assert engine.trace_count("least_squares") == 0
    serve(batch)
    assert engine.trace_count("least_squares_batched") == 1


# -- regression: ridge residual & PCA eigenvalue clamp ------------------------


def test_least_squares_ridge_residual_is_true_residual(rng):
    """resid must be ‖Aβ − y‖ of the *ridge* solution — |rr[n-1,n-1]| alone
    understates it for every regularized regression."""
    tree, plan = _plan("path", rng)
    a = np.asarray(materialize_join(tree))
    n = plan.num_cols
    if n < 2:
        pytest.skip("needs >= 2 columns")
    x, y = a[:, : n - 1], a[:, n - 1]
    ridge = 0.7
    beta_ref = np.linalg.solve(x.T @ x + ridge * np.eye(n - 1), x.T @ y)
    resid_ref = np.linalg.norm(x @ beta_ref - y)
    engine = FigaroEngine()
    beta, resid = engine.least_squares(plan, n - 1, ridge=ridge,
                                       dtype=jnp.float64)
    np.testing.assert_allclose(np.asarray(beta), beta_ref, atol=1e-9)
    np.testing.assert_allclose(float(resid), resid_ref, rtol=1e-9)


def test_pca_explained_variance_nonnegative_near_constant_column(rng):
    """The centered-Gram subtraction can leave tiny negative eigenvalues; the
    engine must clamp them at 0 before the top-k select."""
    _, plan = _plan("star", rng)
    data = [np.array(d, dtype=np.float64, copy=True) for d in plan.data]
    data[0][:, 0] = 1.0  # constant column over the join -> zero variance
    engine = FigaroEngine()
    res = engine.pca(plan.with_data(data), dtype=jnp.float64)
    ev = np.asarray(res.explained_variance)
    assert (ev >= 0.0).all(), ev
    # descending order must survive the clamp
    assert (np.diff(ev) <= 1e-12).all(), ev


# -- sharded dispatch plumbing on the in-process (1-device) mesh --------------


def test_sharded_dispatch_single_device_mesh(rng):
    """shard= on a 1-device data mesh is the degenerate case of the sharded
    serving layer: same results as the unsharded batched dispatch, separate
    executable-cache entry (mesh signature), shard without batched rejected.
    Real multi-device coverage lives in tests/_sharded_driver.py."""
    from repro.launch.mesh import make_data_mesh

    _, plan = _plan("star", rng)
    engine = FigaroEngine(donate_data=False)
    batch = _batch(plan, rng, 3, np.float64)
    mesh = make_data_mesh()
    r_plain = np.asarray(engine.qr(plan, batch, batched=True,
                                   dtype=jnp.float64))
    r_shard = np.asarray(engine.qr(plan, batch, batched=True, shard=mesh,
                                   dtype=jnp.float64))
    np.testing.assert_allclose(r_shard, r_plain, atol=1e-12)
    assert engine.trace_count("qr_batched") == 2  # mesh vs None signatures
    engine.qr(plan, batch, batched=True, shard=mesh, dtype=jnp.float64)
    assert engine.trace_count("qr_batched") == 2
    with pytest.raises(ValueError, match="batched"):
        engine.qr(plan, [d[0] for d in batch], shard=mesh, dtype=jnp.float64)
    with pytest.raises(ValueError, match="axis"):
        engine.qr(plan, batch, batched=True, shard=(mesh, "model"),
                  dtype=jnp.float64)


def test_sharded_dispatch_empty_batch(rng):
    """B=0: the pad-by-repeating-the-trailing-request bucketing would index
    an empty batch out of range — the engine must return correctly-shaped
    empty results instead."""
    from repro.launch.mesh import make_data_mesh

    _, plan = _plan("star", rng)
    engine = FigaroEngine(donate_data=False)
    mesh = make_data_mesh()
    n = plan.num_cols
    empty = tuple(np.zeros((0,) + np.asarray(d).shape, np.float64)
                  for d in plan.data)
    r = engine.qr(plan, empty, batched=True, shard=mesh, dtype=jnp.float64)
    assert np.asarray(r).shape == (0, n, n)
    betas, resids = engine.least_squares(plan, n - 1, empty, batched=True,
                                         shard=mesh, dtype=jnp.float64)
    assert np.asarray(betas).shape == (0, n - 1)
    assert np.asarray(resids).shape == (0,)


def test_sharded_dispatch_single_request_batch(rng):
    """B=1 (the smallest bucketable batch) matches the unsharded dispatch."""
    from repro.launch.mesh import make_data_mesh

    _, plan = _plan("star", rng)
    engine = FigaroEngine(donate_data=False)
    mesh = make_data_mesh()
    batch = _batch(plan, rng, 1, np.float64)
    r_shard = np.asarray(engine.qr(plan, batch, batched=True, shard=mesh,
                                   dtype=jnp.float64))
    r_plain = np.asarray(engine.qr(plan, [d[0] for d in batch],
                                   dtype=jnp.float64))
    assert r_shard.shape[0] == 1
    np.testing.assert_allclose(r_shard[0], r_plain, atol=1e-12)


# -- phase scopes: metadata only ---------------------------------------------

ALGORITHM_2 = ("figaro.counts", "figaro.heads_tails", "figaro.join_children",
               "figaro.project", "figaro.assemble", "figaro.postprocess")
KIND_OPTIONS = {"qr": {}, "pca": {"k": 2, "center": True}}


def _compiled_hlo(kind, plan, data, leaf_rows=256):
    """Optimized HLO of the batched ``kind`` program as the engine jits it."""
    fn = FigaroEngine(donate_data=False)._make_jitted(
        f"{kind}_batched", False, None, None, None)
    options = dict(KIND_OPTIONS[kind], dtype=np.dtype(np.float64),
                   leaf_rows=leaf_rows, use_kernel=False, assembly="padded")
    return fn.lower(plan.without_data(), data,
                    **options).compile().as_text()


@pytest.mark.parametrize("kind", list(KIND_OPTIONS))
def test_every_phase_scope_reaches_the_op_metadata(rng, kind):
    _, plan = _plan("star", rng)
    hlo = _compiled_hlo(kind, plan, _batch(plan, rng, 2, np.float64))
    names = set(re.findall(r'op_name="([^"]*)"', hlo))
    want = ALGORITHM_2 + (("figaro.downstream",) if kind == "pca" else ())
    for scope in want:
        assert any(re.search(rf"(^|[/(]){re.escape(scope)}[/)]", n)
                   for n in names), scope
    node = plan.spec.nodes[plan.spec.root].name
    assert any(f"figaro.heads_tails)/{node}/" in n for n in names), node
    # The node passes name their node too, so a trace can tell the gathers
    # at the root from those further down.
    assert any(f"figaro.join_children)/{node}/" in n for n in names), node
    child = plan.spec.nodes[plan.spec.nodes[plan.spec.root].children[0]].name
    assert any(f"figaro.project)/{child}/" in n for n in names), child


@pytest.mark.parametrize("kind", list(KIND_OPTIONS))
def test_tsqr_levels_reach_the_op_metadata(rng, kind):
    """TSQR's leaves and its combine levels carry their own sub-scopes of
    ``figaro.postprocess``, so a trace can tell one from the other."""
    _, plan = _plan("star", rng)
    hlo = _compiled_hlo(kind, plan, _batch(plan, rng, 2, np.float64),
                        leaf_rows=8)
    names = set(re.findall(r'op_name="([^"]*)"', hlo))
    for level in ("leaves", "combine"):
        assert any(f"figaro.postprocess)/{level}/" in n for n in names), level


def test_phase_scopes_leave_the_compiled_program_unchanged(rng, monkeypatch):
    """Without its named scopes the batched qr program compiles to the same
    optimized HLO, once the metadata is stripped."""
    _, plan = _plan("star", rng)
    data = _batch(plan, rng, 2, np.float64)
    scoped = hlo_instructions(_compiled_hlo("qr", plan, data))
    monkeypatch.setattr(jax, "named_scope",
                        lambda name: contextlib.nullcontext())
    plain = hlo_instructions(_compiled_hlo("qr", plan, data))
    assert "figaro." not in plain and scoped.count("fusion(") > 20
    assert scoped == plain
