"""Pallas TPU kernel: fused embedding gather + segment reduce.

This is the CXL-MEM *computing logic* re-thought for the TPU memory
hierarchy: instead of adders beside PMEM, the scalar-prefetch grid spec lets
the DMA engine stream exactly the needed table rows HBM->VMEM (one row block
per grid step, chosen by the prefetched index), and the VPU accumulates the
bag sum in a VMEM-resident output block. Consecutive grid steps that hit the
same bag keep the output block in VMEM (no HBM round trip) — indices arrive
grouped by bag, which the callers guarantee by construction.

Layout requirements (ops.py enforces/pads):
  * D padded to a multiple of 128 (lane width)
  * seg non-decreasing; idx in [0, R)

Row blocks: the TPU lowering wants the last two block dims to be multiples
of (8, 128) or whole, so one row cannot be a ``(1, D)`` block of an
``(R, D)`` array. Every row-addressed operand is viewed as ``(R, 1, D)``
and moved in ``(None, 1, D)`` blocks, whose last two dims are whole.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def row_view(x):
    """(R, D) -> (R, 1, D): one row per block, tiling-legal on the TPU."""
    return x.reshape(x.shape[0], 1, x.shape[1])


def row_spec(D: int, row_of):
    """A one-row ``(1, D)`` kernel block at row ``row_of(*grid_args)``."""
    return pl.BlockSpec((None, 1, D), lambda *a: (row_of(*a), 0, 0))


def _bag_kernel(idx_ref, seg_ref, row_ref, out_ref, *, num_bags: int):
    """Grid prologue (i < num_bags): zero bag block i — Pallas outputs are
    uninitialised, and a bag with no items must read as zeros (hypothesis
    found this). Steps i >= num_bags: out[seg[j]] += table[idx[j]]."""
    i = pl.program_id(0)

    @pl.when(i < num_bags)
    def _zero():
        out_ref[...] = jnp.zeros_like(out_ref)

    @pl.when(i >= num_bags)
    def _acc():
        out_ref[...] += row_ref[...].astype(out_ref.dtype)


def embedding_bag_pallas(table, idx, seg, num_bags: int, *, interpret: bool):
    """table: (R, D); idx/seg: (N,) int32; -> (num_bags, D) fp32 bag sums."""
    n = idx.shape[0]
    D = table.shape[1]

    def row_map(i, idx_ref, seg_ref):
        return idx_ref[jnp.maximum(i - num_bags, 0)]

    def out_map(i, idx_ref, seg_ref):
        j = jnp.maximum(i - num_bags, 0)
        return jnp.where(i < num_bags, i, seg_ref[j])

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,                     # idx, seg
        grid=(num_bags + n,),                      # zeroing prologue + items
        in_specs=[row_spec(D, row_map)],
        out_specs=row_spec(D, out_map),
    )
    out = pl.pallas_call(
        functools.partial(_bag_kernel, num_bags=num_bags),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((num_bags, 1, D), jnp.float32),
        interpret=interpret,
    )(idx, seg, row_view(table))
    return out.reshape(num_bags, D)


def _gather_kernel(idx_ref, row_ref, out_ref):
    out_ref[...] = row_ref[...]


def gather_rows_pallas(table, idx, *, interpret: bool):
    """Pure near-data gather: out[i] = table[idx[i]] (no reduce)."""
    n = idx.shape[0]
    D = table.shape[1]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(n,),
        in_specs=[row_spec(D, lambda i, idx_ref: idx_ref[i])],
        out_specs=row_spec(D, lambda i, idx_ref: i),
    )
    out = pl.pallas_call(
        _gather_kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((n, 1, D), table.dtype),
        interpret=interpret,
    )(idx, row_view(table))
    return out.reshape(n, D)
