"""Pallas TPU kernel: blockwise flash attention (prefill hot-spot).

Streaming-softmax over KV blocks with fp32 running (m, l, acc) in VMEM
scratch. Grid: (batch*heads, q_blocks, kv_blocks), kv innermost so the
(m, l, acc) scratch for one q block stays resident across the kv sweep.
Block shapes default to (128, head_dim) — MXU-aligned on both matmul dims.

Causal masking is applied in-block from global positions; fully-masked
blocks are computed-and-masked (a production variant would skip them with a
custom grid order — recorded as a §Perf note, not needed for correctness).
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr, *,
                  causal: bool, sm_scale: float, kv_steps: int,
                  bq: int, bk: int):
    qi = pl.program_id(1)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q = q_ref[0].astype(jnp.float32)                      # (bq, D)
    k = k_ref[0].astype(jnp.float32)                      # (bk, D)
    v = v_ref[0].astype(jnp.float32)
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * sm_scale
    if causal:
        q_pos = qi * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
        k_pos = ki * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
        s = jnp.where(q_pos >= k_pos, s, NEG_INF)

    m_prev = m_scr[...]
    m_new = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
    p = jnp.exp(s - m_new)
    corr = jnp.exp(m_prev - m_new)
    l_scr[...] = l_scr[...] * corr + p.sum(axis=-1, keepdims=True)
    acc_scr[...] = acc_scr[...] * corr + jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
    m_scr[...] = m_new

    @pl.when(ki == kv_steps - 1)
    def _finish():
        o_ref[0] = (acc_scr[...] / jnp.maximum(l_scr[...], 1e-30)) \
            .astype(o_ref.dtype)


def flash_attention_pallas(q, k, v, *, causal: bool = True, bq: int = 128,
                           bk: int = 128, interpret: bool):
    """q,k,v: (BH, S, D) — batch*heads flattened, same head count (GQA
    expansion by caller). Returns (BH, S, D)."""
    BH, Sq, D = q.shape
    Sk = k.shape[1]
    bq = min(bq, Sq)
    bk = min(bk, Sk)
    assert Sq % bq == 0 and Sk % bk == 0, "pad sequence to block multiple"
    kv_steps = Sk // bk
    kern = functools.partial(_flash_kernel, causal=causal,
                             sm_scale=1.0 / math.sqrt(D),
                             kv_steps=kv_steps, bq=bq, bk=bk)
    return pl.pallas_call(
        kern,
        grid=(BH, Sq // bq, kv_steps),
        in_specs=[
            pl.BlockSpec((1, bq, D), lambda h, i, j: (h, i, 0)),
            pl.BlockSpec((1, bk, D), lambda h, i, j: (h, j, 0)),
            pl.BlockSpec((1, bk, D), lambda h, i, j: (h, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, bq, D), lambda h, i, j: (h, i, 0)),
        out_shape=jax.ShapeDtypeStruct((BH, Sq, D), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, D), jnp.float32),
        ],
        interpret=interpret,
    )(q, k, v)
