"""Pallas TPU kernel: chunked wkv6 (RWKV-6 time-mix) with VMEM-resident state.

The pure-JAX chunked form is memory-bound: the (K x K) per-head state and
its backward cotangent chain stream HBM on every one of S/chunk scan steps
(dry-run: ~100 s memory term for rwkv6-3b train_4k). This kernel keeps the
running state in a VMEM scratch across the chunk sweep — HBM traffic drops
to the r/k/v/logw inputs and the y output, read/written exactly once.

Grid: (B*H, S/chunk) — the chunk sweep is the inner (sequential) dimension,
so the state scratch carries across chunks of one (batch, head) pair and is
re-initialised when the outer index changes.

Math is identical to models/rwkv6.wkv6_chunked (same LOG_W_MIN clamp
contract; validated against the sequential oracle in tests/test_kernels.py).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _wkv_kernel(r_ref, k_ref, v_ref, lw_ref, u_ref, o_ref, s_scr, *,
                chunk: int):
    nc_idx = pl.program_id(1)

    @pl.when(nc_idx == 0)
    def _reset():
        s_scr[...] = jnp.zeros_like(s_scr)

    r = r_ref[0].astype(jnp.float32)          # (chunk, K)
    k = k_ref[0].astype(jnp.float32)
    v = v_ref[0].astype(jnp.float32)
    lw = lw_ref[0].astype(jnp.float32)        # (chunk, K), < 0
    u = u_ref[0].astype(jnp.float32)          # (1, K) block of (H, K)

    cum_incl = jnp.cumsum(lw, axis=0)
    cum_excl = cum_incl - lw
    r_f = r * jnp.exp(cum_excl)
    k_f = k * jnp.exp(-cum_incl)
    scores = jax.lax.dot_general(r_f, k_f, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
    mask = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0) > \
        jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    scores = jnp.where(mask, scores, 0.0)     # strictly lower triangular
    y = jax.lax.dot_general(scores, v, (((1,), (0,)), ((), ())),
                            preferred_element_type=jnp.float32)
    bonus = jnp.sum(r * u * k, axis=1, keepdims=True)
    y = y + bonus * v
    # cross-chunk: y += (r e^{L(t-1)}) @ S_prev
    y = y + jax.lax.dot_general(r_f, s_scr[...], (((1,), (0,)), ((), ())),
                                preferred_element_type=jnp.float32)
    o_ref[0] = y.astype(o_ref.dtype)

    # state update: S = diag(e^{L(end)}) S + sum_j e^{L(end)-L(j)} k_j^T v_j
    dec_to_end = jnp.exp(cum_incl[-1:] - cum_incl)         # (chunk, K)
    st_c = jax.lax.dot_general(k * dec_to_end, v, (((0,), (0,)), ((), ())),
                               preferred_element_type=jnp.float32)
    s_scr[...] = s_scr[...] * jnp.exp(cum_incl[-1])[:, None] + st_c


def wkv6_pallas(r, k, v, logw, u, *, chunk: int = 16,
                interpret: bool):
    """r,k,v,logw: (B, S, H, K); u: (H, K). Returns y: (B, S, H, K).

    Zero initial state (the train-step case; decode carries state in JAX).
    """
    B, S, H, K = r.shape
    assert S % chunk == 0, "pad sequence to a chunk multiple"
    nc = S // chunk

    def bh(x):   # (B,S,H,K) -> (B*H, S, K)
        return jnp.moveaxis(x, 2, 1).reshape(B * H, S, K)

    kern = functools.partial(_wkv_kernel, chunk=chunk)
    spec = pl.BlockSpec((1, chunk, K), lambda h, c: (h, c, 0))
    u_full = jnp.broadcast_to(u[None], (B, H, K)).reshape(B * H, K)
    out = pl.pallas_call(
        kern,
        grid=(B * H, nc),
        in_specs=[spec, spec, spec, spec,
                  pl.BlockSpec((1, K), lambda h, c: (h, 0))],
        out_specs=spec,
        out_shape=jax.ShapeDtypeStruct((B * H, S, K), jnp.float32),
        scratch_shapes=[pltpu.VMEM((K, K), jnp.float32)],
        interpret=interpret,
    )(bh(r), bh(k), bh(v), bh(logw), u_full)
    return jnp.moveaxis(out.reshape(B, H, S, K), 1, 2)
