"""Orchestration-side Pallas kernel parity vs the lax references.

The serve engine runs these kernels by default (interpreted on CPU),
so exact agreement with the unfused references — ``segment_sum`` +
gather for ``group_occupancy``, the sequential per-lane ``fori_loop``
for ``queue_admit`` — is a correctness requirement, not a nicety:
admission order decides which requests are dropped.

The randomized sweeps run twice: a fixed-seed ``parametrize`` pass that
always runs, and a ``hypothesis`` pass (shrinking, fresh seeds every CI
run) when the package is installed.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

try:
    from hypothesis import given, settings, strategies as st
    settings.register_profile("ci", max_examples=25, deadline=None)
    settings.load_profile("ci")
    HAVE_HYPOTHESIS = True
except ImportError:
    HAVE_HYPOTHESIS = False

from repro.fleet import latency
from repro.kernels.orchestration import (group_occupancy_lax,
                                         group_occupancy_pallas,
                                         queue_admit_lax,
                                         queue_admit_pallas)

needs_hypothesis = pytest.mark.skipif(not HAVE_HYPOTHESIS,
                                      reason="hypothesis not installed")


# ------------------------------------------------------ group_occupancy
def check_group_occupancy(c, n_groups, seed):
    k1, k2 = jax.random.split(jax.random.PRNGKey(seed))
    own = jax.random.uniform(k1, (c,), jnp.float32, 0.0, 5.0)
    # the kernel's documented domain: group ids in [0, C)
    groups = jax.random.randint(k2, (c,), 0, min(n_groups, c))
    got = group_occupancy_pallas(own, groups, interpret=True)
    want = group_occupancy_lax(own, groups)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=1e-5, rtol=1e-5)


@pytest.mark.parametrize("seed", range(8))
def test_group_occupancy_matches_lax_seeded(seed):
    rng = np.random.default_rng(seed)
    check_group_occupancy(int(rng.integers(1, 300)),
                          int(rng.integers(1, 12)), seed)


if HAVE_HYPOTHESIS:
    @given(st.integers(1, 300), st.integers(1, 12),
           st.integers(0, 2**31 - 1))
    def test_group_occupancy_matches_lax_hyp(c, n_groups, seed):
        check_group_occupancy(c, n_groups, seed)


@pytest.mark.parametrize("blk", [32, 128])
@pytest.mark.parametrize("c", [7, 32, 100, 129])
def test_group_occupancy_padding_edges(c, blk):
    """Sizes straddling the block boundary: the -1/-2 pad ids must never
    alias a real group."""
    key = jax.random.PRNGKey(c * 1000 + blk)
    own = jax.random.uniform(key, (c,), jnp.float32)
    groups = jnp.arange(c) % 3
    got = group_occupancy_pallas(own, groups, blk=blk, interpret=True)
    np.testing.assert_allclose(np.asarray(got),
                               np.asarray(group_occupancy_lax(own, groups)),
                               atol=1e-5, rtol=1e-5)


def test_group_occupancy_singleton_and_single_group():
    own = jnp.asarray([1.0, 2.0, 3.0, 4.0])
    # singleton groups: each cell sees only itself
    np.testing.assert_allclose(
        np.asarray(group_occupancy_pallas(own, jnp.arange(4))),
        np.asarray(own))
    # one group: every cell sees the full sum
    np.testing.assert_allclose(
        np.asarray(group_occupancy_pallas(own, jnp.zeros(4, jnp.int32))),
        np.full(4, 10.0))


def test_latency_wrapper_kernel_matches_ref():
    """The fleet-layer default (kernel on) agrees with the ref impl and
    with the kernels-off escape hatch."""
    key = jax.random.PRNGKey(3)
    own = jax.random.uniform(key, (65,), jnp.float32)
    groups = jnp.arange(65) // 4
    want = latency.group_occupancy_ref(own, groups)
    np.testing.assert_allclose(np.asarray(latency.group_occupancy(own, groups)),
                               np.asarray(want), atol=1e-5, rtol=1e-5)
    old = latency.USE_KERNELS
    try:
        latency.USE_KERNELS = False
        np.testing.assert_allclose(
            np.asarray(latency.group_occupancy(own, groups)),
            np.asarray(want), atol=0)
    finally:
        latency.USE_KERNELS = old


def test_latency_axis_path_single_device_mesh():
    """The psum path (axis= under shard_map) reduces to the ref on a
    one-device cells mesh."""
    from jax.sharding import PartitionSpec as P

    from repro.sharding.runtime import CELLS_AXIS, cells_mesh

    mesh = cells_mesh(1)
    own = jax.random.uniform(jax.random.PRNGKey(5), (32,), jnp.float32)
    groups = jnp.arange(32) // 8
    f = jax.shard_map(
        lambda o, g: latency.group_occupancy(o, g, axis=CELLS_AXIS,
                                             num_segments=32),
        mesh=mesh, in_specs=(P(CELLS_AXIS), P(CELLS_AXIS)),
        out_specs=P(CELLS_AXIS), check_vma=False)
    np.testing.assert_allclose(
        np.asarray(f(own, groups)),
        np.asarray(latency.group_occupancy_ref(own, groups)),
        atol=1e-5, rtol=1e-5)


# ---------------------------------------------------------- queue_admit
def check_queue_admit(seed, c, q, a):
    key = jax.random.PRNGKey(seed)
    k1, k2, k3, k4 = jax.random.split(key, 4)
    q_len = jax.random.randint(k1, (c,), 0, q + 1)
    q_head = jax.random.randint(k2, (c,), 0, q)
    q_ids = jnp.full((c, q), -1, jnp.int32)
    cell = jax.random.randint(k3, (a,), 0, c)
    valid = jax.random.bernoulli(k4, 0.7, (a,))
    rid = jnp.arange(a, dtype=jnp.int32) + 100
    got = queue_admit_pallas(q_ids, q_head, q_len, rid, cell, valid,
                             interpret=True)
    want = queue_admit_lax(q_ids, q_head, q_len, rid, cell, valid)
    for g, w, name in zip(got, want, ("q_ids", "q_len", "admitted")):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w),
                                      err_msg=name)


@pytest.mark.parametrize("seed", range(12))
def test_queue_admit_matches_sequential_seeded(seed):
    rng = np.random.default_rng(seed + 1000)
    check_queue_admit(seed, int(rng.integers(1, 8)),
                      int(rng.integers(1, 9)), int(rng.integers(1, 16)))


if HAVE_HYPOTHESIS:
    @given(st.integers(0, 2**31 - 1), st.integers(1, 8),
           st.integers(1, 9), st.integers(1, 16))
    def test_queue_admit_matches_sequential_hyp(seed, c, q, a):
        check_queue_admit(seed, c, q, a)


def test_queue_admit_overflow_drops_in_fifo_order():
    """A full-but-one queue admits exactly the first same-cell lane of
    the tick and rejects the rest."""
    c, q, a = 2, 4, 5
    q_ids = jnp.full((c, q), -1, jnp.int32)
    q_head = jnp.zeros((c,), jnp.int32)
    q_len = jnp.asarray([q - 1, 0], jnp.int32)
    rid = jnp.arange(a, dtype=jnp.int32)
    cell = jnp.zeros((a,), jnp.int32)
    valid = jnp.ones((a,), bool)
    ids, ln, adm = queue_admit_pallas(q_ids, q_head, q_len, rid, cell,
                                      valid)
    assert np.asarray(adm).tolist() == [True, False, False, False, False]
    assert int(ln[0]) == q and int(ln[1]) == 0
    assert int(ids[0, q - 1]) == 0  # admitted at head + len0


def test_queue_admit_ignores_invalid_lanes():
    c, q, a = 3, 4, 6
    q_ids = jnp.full((c, q), -1, jnp.int32)
    q_head = jnp.zeros((c,), jnp.int32)
    q_len = jnp.zeros((c,), jnp.int32)
    rid = jnp.arange(a, dtype=jnp.int32)
    cell = jnp.asarray([0, 0, 1, 1, 2, 2], jnp.int32)
    valid = jnp.asarray([True, False, True, False, False, False])
    ids, ln, adm = queue_admit_pallas(q_ids, q_head, q_len, rid, cell,
                                      valid)
    assert np.asarray(ln).tolist() == [1, 1, 0]
    assert np.asarray(adm).tolist() == [True, False, True, False, False,
                                        False]
    assert int(ids[0, 0]) == 0 and int(ids[1, 0]) == 2
