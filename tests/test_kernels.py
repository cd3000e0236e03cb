"""Pallas kernel validation: interpret-mode vs pure-jnp oracles over
shape/dtype sweeps (+ hypothesis randomized shapes)."""
import jax.numpy as jnp
import numpy as np
import pytest

pytest.importorskip("hypothesis")  # property tests need the optional dev dep
from hypothesis import given, settings, strategies as st

from repro.kernels import ops, ref
from repro.kernels.embedding_bag import embedding_bag_pallas, gather_rows_pallas
from repro.kernels.flash_attention import flash_attention_pallas
from repro.kernels.scatter_update import (scatter_update_logged_pallas,
                                          scatter_update_pallas)


def _bag_case(rng, R, D, N, B, dtype):
    table = jnp.asarray(rng.standard_normal((R, D)).astype(dtype))
    idx = jnp.asarray(np.sort(rng.integers(0, R, N)).astype(np.int32))
    seg = jnp.asarray(np.sort(rng.integers(0, B, N)).astype(np.int32))
    return table, idx, seg


@pytest.mark.parametrize("R,D,N,B", [(32, 128, 17, 4), (64, 256, 64, 8),
                                     (128, 384, 100, 16), (16, 128, 5, 2)])
@pytest.mark.parametrize("dtype", [np.float32, np.float16])
def test_embedding_bag_sweep(rng, R, D, N, B, dtype):
    table, idx, seg = _bag_case(rng, R, D, N, B, dtype)
    out = embedding_bag_pallas(table, idx, seg, B, interpret=True)
    # the kernel accumulates in fp32; compare against the fp32 oracle
    want = ref.embedding_bag_ref(table.astype(jnp.float32), idx, seg, B)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(want, np.float32),
                               rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("R,D,N", [(64, 128, 20), (32, 256, 32)])
def test_gather_rows(rng, R, D, N):
    table = jnp.asarray(rng.standard_normal((R, D)).astype(np.float32))
    idx = jnp.asarray(rng.integers(0, R, N).astype(np.int32))
    out = gather_rows_pallas(table, idx, interpret=True)
    np.testing.assert_array_equal(np.asarray(out),
                                  np.asarray(jnp.take(table, idx, axis=0)))


@pytest.mark.parametrize("R,D,N", [(64, 128, 16), (128, 256, 48)])
def test_scatter_update_sweep(rng, R, D, N):
    table = jnp.asarray(rng.standard_normal((R, D)).astype(np.float32))
    idx = jnp.asarray(rng.permutation(R)[:N].astype(np.int32))
    delta = jnp.asarray(rng.standard_normal((N, D)).astype(np.float32))
    got = scatter_update_pallas(table, idx, delta, interpret=True)
    want = ref.scatter_update_ref(table, idx, delta)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-6)

    got_t, got_old = scatter_update_logged_pallas(table, idx, delta,
                                                  interpret=True)
    want_t, want_old = ref.scatter_update_logged_ref(table, idx, delta)
    np.testing.assert_allclose(np.asarray(got_t), np.asarray(want_t),
                               atol=1e-6)
    np.testing.assert_array_equal(np.asarray(got_old), np.asarray(want_old))


@pytest.mark.parametrize("B,S,H,D,causal", [
    (1, 128, 2, 64, True), (2, 256, 4, 64, False), (2, 128, 2, 128, True)])
def test_flash_attention_sweep(rng, B, S, H, D, causal):
    q, k, v = (jnp.asarray(rng.standard_normal((B, S, H, D))
                           .astype(np.float32)) for _ in range(3))
    def flat(x):
        return jnp.moveaxis(x, 2, 1).reshape(B * H, S, D)
    out = flash_attention_pallas(flat(q), flat(k), flat(v), causal=causal,
                                 bq=64, bk=64, interpret=True)
    out = jnp.moveaxis(out.reshape(B, H, S, D), 1, 2)
    want = ref.flash_attention_ref(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


@settings(deadline=None, max_examples=10)
@given(r=st.integers(8, 100), n=st.integers(1, 60), b=st.integers(1, 12),
       seed=st.integers(0, 1000))
def test_property_bag_matches_oracle(r, n, b, seed):
    rng = np.random.default_rng(seed)
    table, idx, seg = _bag_case(rng, r, 128, n, b, np.float32)
    out = embedding_bag_pallas(table, idx, seg, b, interpret=True)
    want = ref.embedding_bag_ref(table, idx, seg, b)
    # sequential (kernel) vs pairwise (segment_sum) fp32 accumulation order
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               rtol=1e-4, atol=1e-4)


def test_ops_backend_dispatch(rng):
    table = jnp.asarray(rng.standard_normal((32, 96)).astype(np.float32))
    idx = jnp.asarray(np.sort(rng.integers(0, 32, 10)).astype(np.int32))
    seg = jnp.asarray(np.sort(rng.integers(0, 4, 10)).astype(np.int32))
    ops.set_backend("xla")
    a = ops.embedding_bag(table, idx, seg, 4)
    ops.set_backend("pallas_interpret")
    b = ops.embedding_bag(table, idx, seg, 4)   # pads 96 -> 128 lanes
    ops.set_backend("xla")
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-5)


@pytest.mark.parametrize("op,operands", [("embedding_bag", 2),
                                         ("gather_rows", 1),
                                         ("scatter_update", 1)])
def test_ops_refuse_ids_past_smem(op, operands):
    """One call's scalar-prefetched ids must fit in SMEM: the wrapper says
    so before the TPU compiler runs out of it."""
    n = (ops.SMEM_BYTES - ops.SMEM_RESERVE) // (4 * operands) + 1
    table = jnp.zeros((8, 128), jnp.float32)
    idx = jnp.zeros((n,), jnp.int32)
    args = {"embedding_bag": (table, idx, idx, 4),
            "gather_rows": (table, idx),
            "scatter_update": (table, idx, jnp.zeros((n, 128)))}[op]
    ops.set_backend("pallas_interpret")
    try:
        with pytest.raises(ValueError, match="SMEM"):
            getattr(ops, op)(*args)
    finally:
        ops.set_backend("xla")


@settings(deadline=None, max_examples=10)
@given(n=st.integers(2, 40), rmax=st.integers(4, 64), seed=st.integers(0, 99))
def test_property_combine_duplicates(n, rmax, seed):
    rng = np.random.default_rng(seed)
    idx = jnp.asarray(rng.integers(0, rmax, n).astype(np.int32))
    delta = jnp.asarray(rng.standard_normal((n, 8)).astype(np.float32))
    ui, cd = ops.combine_duplicates(idx, delta, rmax)
    dense_want = jnp.zeros((rmax, 8)).at[idx].add(delta)
    dense_got = jnp.zeros((rmax, 8)).at[ui].add(cd)
    np.testing.assert_allclose(np.asarray(dense_got), np.asarray(dense_want),
                               rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("B,S,H,chunk", [(2, 64, 2, 16), (1, 48, 1, 16)])
def test_wkv6_pallas_kernel(rng, B, S, H, chunk):
    from repro.kernels.wkv6 import wkv6_pallas
    from repro.models import rwkv6 as rw
    K = 64
    r, k, v = (jnp.asarray(rng.standard_normal((B, S, H, K))
                           .astype(np.float32) * 0.5) for _ in range(3))
    logw = jnp.clip(jnp.asarray(
        -np.exp(rng.standard_normal((B, S, H, K)) * 0.5 - 1)
        .astype(np.float32)), rw.LOG_W_MIN, -1e-4)
    u = jnp.asarray(rng.standard_normal((H, K)).astype(np.float32) * 0.3)
    y_p = wkv6_pallas(r, k, v, logw, u, chunk=chunk, interpret=True)
    y_r, _ = ref.wkv6_ref(r, k, v, logw, u,
                          jnp.zeros((B, H, K, K), jnp.float32))
    np.testing.assert_allclose(np.asarray(y_p), np.asarray(y_r),
                               rtol=3e-4, atol=3e-4)
