"""Paper §5 / Algorithm 1: batched group-by counts over the join tree."""

import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core.counts import compute_counts, compute_counts_reference
from repro.core.materialize import materialize_join

from helpers import TOPOLOGIES, random_acyclic_db


@pytest.mark.parametrize("topology", list(TOPOLOGIES))
def test_counts_match_exact_reference(rng, topology):
    _, _, plan = random_acyclic_db(topology, rng)
    cj = compute_counts(plan, dtype=jnp.float64)
    cr = compute_counts_reference(plan)
    for i in range(len(plan.nodes)):
        for k in ("rpk", "theta_down", "full", "phi_circ", "phi_up"):
            if k in cr[i]:
                np.testing.assert_allclose(np.asarray(cj[i][k]), cr[i][k],
                                           rtol=1e-12, err_msg=f"node{i}:{k}")


def test_full_join_size_equals_materialized(rng):
    """FULL_JOIN_SIZE summed over the root's groups == |A| (join row count)."""
    db, tree, plan = random_acyclic_db("snowflake4", rng)
    a = materialize_join(tree)
    cr = compute_counts_reference(plan)
    root = plan.preorder[0]
    assert int(cr[root]["full"].sum()) == a.shape[0]


def test_phi_circ_semantics_bruteforce(rng):
    """Φ°_i(x̄_i) == size of the join of all relations except S_i at that key.

    Brute-force check on a snowflake: remove one relation's *data* rows but
    keep the key multiplicity 1 (semijoin semantics of Φ°).
    """
    db, tree, plan = random_acyclic_db("snowflake4", rng, max_rows=5)
    a = materialize_join(tree)
    cr = compute_counts_reference(plan)
    # check the identity full == rpk * phi_circ — exact division enforced in
    # the reference; and that sum_groups rpk*phi_circ == |A| at every node.
    for i, nd in enumerate(plan.nodes):
        np.testing.assert_array_equal(cr[i]["full"],
                                      cr[i]["rpk"] * cr[i]["phi_circ"])
        assert int(cr[i]["full"].sum()) == a.shape[0]


def test_two_pass_structure():
    """Counts visit each node exactly twice (paper: two passes)."""
    rng = np.random.default_rng(3)
    _, _, plan = random_acyclic_db("chain3", rng)
    # pass structure is encoded in plan.preorder; verify it is a valid
    # preorder of the tree (parents before children).
    seen = set()
    for idx in plan.preorder:
        nd = plan.nodes[idx]
        assert nd.parent == -1 or nd.parent in seen
        seen.add(idx)


def test_counts_default_dtype_exact_above_2pow24():
    """Counts multiply along the tree and cross 2^24 fast; the float32
    default used to round them there (corrupting phi_circ's scaling). The
    float64 default must reproduce the int64 reference exactly."""
    from repro.data.relational import cartesian as cartesian_tree
    from repro.core.join_tree import build_plan

    # |join| = 5001 * 3355 = 16_778_355 > 2^24, and odd — not representable
    # in float32, so the old default provably corrupted it.
    tree = cartesian_tree(5001, 3355, n1=1, n2=1, seed=0)
    plan = build_plan(tree)
    cr = compute_counts_reference(plan)
    root = plan.preorder[0]
    full = int(cr[root]["full"].sum())
    assert full > 2**24 and int(np.float32(full)) != full

    cj = compute_counts(plan)  # default dtype — must be exact
    for i in range(len(plan.nodes)):
        for k in ("rpk", "theta_down", "full", "phi_circ"):
            np.testing.assert_array_equal(np.asarray(cj[i][k]), cr[i][k],
                                          err_msg=f"node{i}:{k}")

    # the regression the default guards against: float32 rounds `full`
    c32 = compute_counts(plan, dtype=jnp.float32)
    assert int(np.asarray(c32[root]["full"]).sum()) != full


def test_a_float32_request_past_2pow24_is_refused():
    """The façade runs Algorithm 1 in the served dtype; where a key's count
    passes 2^24 a float32 request would be answered with corrupt scalings,
    so it is refused before anything is traced, naming float64."""
    from repro import figaro
    from repro.core.counts import check_counts_exact, largest_count
    from repro.data.relational import cartesian as cartesian_tree

    ds = figaro.Session().from_tree(cartesian_tree(5001, 3355, n1=1, n2=1,
                                                   seed=0))
    with pytest.raises(ValueError, match="float64"):
        ds.qr()
    with pytest.raises(ValueError, match="float64"):
        ds.serve(kind="qr", dtype=jnp.float32)
    largest = largest_count(compute_counts_reference(ds.plan))
    assert largest == ds.stats()["join_rows"] == 5001 * 3355
    check_counts_exact(largest, jnp.float64)
    check_counts_exact(2 ** 24, jnp.float32)  # the last exact count
    check_counts_exact(largest, jnp.bfloat16)  # a control: not checked
    assert ds.stats()["traces"] == {}


@settings(max_examples=25, deadline=None)
@given(topology=st.sampled_from(list(TOPOLOGIES)), seed=st.integers(0, 2**31),
       cartesian=st.booleans())
def test_property_counts_exact(topology, seed, cartesian):
    rng = np.random.default_rng(seed)
    try:
        _, _, plan = random_acyclic_db(topology, rng, cartesian=cartesian)
    except ValueError:  # a relation emptied out in full reduction
        return
    cj = compute_counts(plan, dtype=jnp.float64)
    cr = compute_counts_reference(plan)
    for i in range(len(plan.nodes)):
        for k in ("rpk", "theta_down", "full", "phi_circ"):
            np.testing.assert_allclose(np.asarray(cj[i][k]), cr[i][k],
                                       rtol=1e-12)
