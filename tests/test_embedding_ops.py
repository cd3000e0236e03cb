"""Disaggregated-pool ops: near-data lookup/bag vs plain gather, strategy
auto-pick, gradient (near-data update) equivalence under shard_map."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

pytest.importorskip("hypothesis")  # property tests need the optional dev dep
from hypothesis import given, settings, strategies as st

from repro.core import embedding_ops as eo
from repro.distributed import sharding
from repro.launch.mesh import make_local_mesh


def test_lookup_no_context_is_take(rng):
    t = jnp.asarray(rng.standard_normal((64, 8)).astype(np.float32))
    ids = jnp.asarray(rng.integers(0, 64, (3, 5)).astype(np.int32))
    np.testing.assert_array_equal(np.asarray(eo.lookup(t, ids)),
                                  np.asarray(jnp.take(t, ids, axis=0)))


@pytest.mark.parametrize("mode", ["near_data", "table_gather", "auto"])
def test_lookup_modes_match_on_mesh(rng, mode):
    mesh = make_local_mesh(model_parallel=1)
    t = jnp.asarray(rng.standard_normal((64, 8)).astype(np.float32))
    ids = jnp.asarray(rng.integers(0, 64, (4, 5)).astype(np.int32))
    with sharding.use_sharding(mesh, {"batch": "data"}):
        with eo.lookup_mode(mode):
            got = jax.jit(lambda t, i: eo.lookup(t, i))(t, ids)
    np.testing.assert_allclose(np.asarray(got),
                               np.asarray(jnp.take(t, ids, axis=0)),
                               atol=1e-6)


@pytest.mark.parametrize("mode", ["near_data", "table_gather"])
def test_bag_modes_match_on_mesh(rng, mode):
    mesh = make_local_mesh(model_parallel=1)
    T, R, d = 3, 32, 8
    tables = jnp.asarray(rng.standard_normal((T, R, d)).astype(np.float32))
    ids = jnp.asarray(rng.integers(0, R, (4, T, 6)).astype(np.int32))
    want = eo.bag_lookup(tables, ids)          # no-context reference
    with sharding.use_sharding(mesh, {"batch": "data"}):
        with eo.lookup_mode(mode):
            got = jax.jit(lambda t, i: eo.bag_lookup(t, i))(tables, ids)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-5)


def test_near_data_gradient_is_scatter_add(rng):
    """The VJP of the shard_map near-data lookup == scatter-add (the
    near-data update of the paper)."""
    mesh = make_local_mesh(model_parallel=1)
    t = jnp.asarray(rng.standard_normal((32, 4)).astype(np.float32))
    ids = jnp.asarray(rng.integers(0, 32, (8,)).astype(np.int32))
    ct = jnp.asarray(rng.standard_normal((8, 4)).astype(np.float32))

    def f_plain(t):
        return (jnp.take(t, ids, axis=0) * ct).sum()

    with sharding.use_sharding(mesh, {"batch": None}):
        with eo.lookup_mode("near_data"):
            def f_pool(t):
                return (eo.lookup(t, ids) * ct).sum()
            g_pool = jax.grad(f_pool)(t)
    g_plain = jax.grad(f_plain)(t)
    np.testing.assert_allclose(np.asarray(g_pool), np.asarray(g_plain),
                               atol=1e-6)


def test_auto_strategy_picks_by_traffic():
    # decode-ish: few tokens, big vocab -> near_data
    assert eo._pick("auto", tokens=128, vocab=150000, tp=16) == "near_data"
    # training: 1M tokens, small vocab -> table_gather
    assert eo._pick("auto", tokens=1_000_000, vocab=32000, tp=16) \
        == "table_gather"
    assert eo._pick("auto", 10, 100, 1) == "table_gather"


@settings(deadline=None, max_examples=10)
@given(seed=st.integers(0, 99), b=st.integers(1, 6), k=st.integers(1, 8))
def test_property_bag_sum(seed, b, k):
    rng = np.random.default_rng(seed)
    tables = jnp.asarray(rng.standard_normal((2, 16, 4)).astype(np.float32))
    ids = jnp.asarray(rng.integers(0, 16, (b, 2, k)).astype(np.int32))
    got = eo.bag_lookup(tables, ids)
    want = np.zeros((b, 2, 4), np.float32)
    for bi in range(b):
        for t in range(2):
            for li in range(k):
                want[bi, t] += np.asarray(tables)[t, int(ids[bi, t, li])]
    np.testing.assert_allclose(np.asarray(got), want, rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32])
@pytest.mark.parametrize("T", [1, 5])
@pytest.mark.parametrize("combine", ["sum", "mean"])
def test_bag_lookup_is_per_table_gather_and_sum(rng, combine, T, dtype):
    """Bitwise a per-table gather, summed over the bag in order in f32 and
    rounded once, as ``jnp.sum`` / ``jnp.mean`` do for bf16. L is a power
    of two, so the mean's division is exact, whether XLA divides or
    multiplies by the reciprocal."""
    R, d, B, L = 40, 8, 3, 8
    tables = jnp.asarray(rng.standard_normal((T, R, d)), dtype)
    ids = rng.integers(0, R, (B, T, L)).astype(np.int32)
    ids[0, :, 0], ids[-1, :, -1] = 0, R - 1          # both ends of a table
    got = eo.bag_lookup(tables, jnp.asarray(ids), combine=combine)
    tab = np.asarray(tables.astype(jnp.float32))
    want = np.zeros((B, T, d), np.float32)
    for t in range(T):
        for li in range(L):
            want[:, t] += tab[t, ids[:, t, li]]
    if combine == "mean":
        want /= np.float32(L)
    assert got.dtype == dtype
    np.testing.assert_array_equal(np.asarray(got.astype(jnp.float32)),
                                  np.asarray(jnp.asarray(want, dtype)
                                             .astype(jnp.float32)))


def _reshapes(jaxpr):
    """Output shapes of every ``reshape`` in ``jaxpr`` and its sub-jaxprs."""
    out = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "reshape":
            out.extend(v.aval.shape for v in eqn.outvars)
        for sub in jax.core.jaxprs_in_params(eqn.params):
            out.extend(_reshapes(sub))
    return out


@pytest.mark.parametrize("mode", [None, "table_gather"])
def test_bag_lookup_never_views_the_tables_flat(rng, mode):
    """The stale gather takes rows from the ``(T, R, d)`` tables as stored:
    a ``[T·R, d]`` view would relay out every table on a TPU."""
    T, R, d = 4, 32, 8
    tables = jnp.zeros((T, R, d), jnp.bfloat16)
    ids = jnp.asarray(rng.integers(0, R, (2, T, 5)).astype(np.int32))
    fn = jax.jit(lambda t, i: eo.bag_lookup(t, i))
    if mode is None:
        jaxpr = jax.make_jaxpr(fn)(tables, ids).jaxpr
    else:
        with sharding.use_sharding(make_local_mesh(model_parallel=1),
                                   {"batch": "data"}):
            with eo.lookup_mode(mode):
                jaxpr = jax.make_jaxpr(fn)(tables, ids).jaxpr
    flat = [s for s in _reshapes(jaxpr) if s and s[0] == T * R]
    assert not flat, flat
