"""Pallas TPU kernel: write one step's updated rows into stacked tables.

The relaxed step's sparse update leaves, per table, a sorted list of slots
(row id, f32 delta, live flag); each row a live slot names becomes
``round(f32(T[t, r]) + delta)`` and every other row stays as it was. XLA
writes such rows with a scatter that costs about one HBM round trip per row
(~130 ns on a v5e), whatever the row's size. This kernel instead streams
each table once, in its stored order, and lands the rows of each block of
``block_rows`` table rows with one small matmul on the MXU: the block's
deltas are a one-hot (slot, row) matrix times the slots' deltas.

Stored order: a bf16 ``(T, R, d)`` table with a small ``d`` is kept rows-
minor on the TPU (layout ``{1,2,0}``), so ``swapaxes(tables, 1, 2)``, a
``(T, d, R)`` array in the default layout, is the same bytes, and a
``(d, block_rows)`` block is a run of whole tiles.

Exactness: the MXU multiplies bf16. Each f32 delta is split into three bf16
pieces whose f32 sum is the delta (8 + 8 + 8 significand bits, cut with
bit masks: a rounding convert would let XLA keep the f32 value through the
split, as its excess-precision rule allows, and lose the low pieces).
A one-hot column selects exactly one live slot, and adding zeros is exact,
so the kernel adds the same f32 delta to the same f32 row as the XLA write.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

CHUNK = 128            # slots per one-hot matmul (the MXU's depth)
BLOCK_ROWS = 4096      # table rows per grid step
VMEM_BUDGET = 48 << 20  # bytes the kernel may ask for (v5e: 128 MiB VMEM)


def _top16(x):
    """The f32 ``x`` cut to its sign, exponent and top 7 significand bits,
    as f32 (exact in bf16). Bit operations, so no compiler may keep more
    precision than the cut leaves."""
    bits = lax.bitcast_convert_type(x, jnp.uint32) & jnp.uint32(0xFFFF0000)
    return lax.bitcast_convert_type(bits, jnp.float32)


def split3(u):
    """f32 (..., d) -> bf16 (..., 3, d) whose f32 sum, hi + mid + lo in that
    order, is ``u``: each piece takes the next 8 significand bits."""
    hi = _top16(u)
    mid = _top16(u - hi)
    lo = (u - hi) - mid
    return jnp.stack([hi, mid, lo], axis=-2).astype(jnp.bfloat16)


def _rows_of(d: int) -> int:
    """Matmul rows per slot: three pieces of d, the live flag, padded to
    the bf16 sublane tile."""
    return -(-(3 * d + 1) // 16) * 16


def vmem_bytes(slots: int, d: int, block_rows: int = BLOCK_ROWS) -> int:
    """VMEM the kernel needs for one table's ``slots`` (double-buffered
    operands plus the matmul's intermediates)."""
    nc = -(-slots // CHUNK)
    per_table = nc * (_rows_of(d) * CHUNK * 2 + 8 * CHUNK * 4)
    blocks = 4 * d * block_rows * 2
    temps = block_rows * CHUNK * 2 + _rows_of(d) * block_rows * 4 * 2
    return 2 * per_table + blocks + temps


def fits(slots: int, d: int) -> bool:
    return vmem_bytes(slots, d) <= VMEM_BUDGET


def _merge_kernel(starts_ref, ids_ref, parts_ref, tab_ref, out_ref, *,
                  d: int, block_rows: int):
    t, j = pl.program_id(0), pl.program_id(1)
    out_ref[...] = tab_ref[...]
    first, end = starts_ref[t, j], starts_ref[t, j + 1]
    row = lax.broadcasted_iota(jnp.int32, (block_rows, CHUNK), 0) \
        + j * block_rows

    def chunk(c, carry):
        onehot = (row == ids_ref[0, c]).astype(jnp.bfloat16)   # (rows, slots)
        p = lax.dot_general(parts_ref[0, c], onehot,
                            (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32)
        delta = (p[:d] + p[d:2 * d]) + p[2 * d:3 * d]           # (d, rows)
        live = p[3 * d:3 * d + 1] > 0
        cur = out_ref[0]
        out_ref[0] = jnp.where(live, (cur.astype(jnp.float32) + delta)
                               .astype(cur.dtype), cur)
        return carry

    lax.fori_loop(first // CHUNK, (end + CHUNK - 1) // CHUNK, chunk, 0)


# A jit of its own traces the kernel once per shape. The Mosaic body keeps
# its ops' source locations, which hold the call stack of the trace; traced
# anew from each caller of an enclosing jit, it would change that program's
# persistent-cache key with every call site.
@functools.partial(jax.jit, static_argnames=("interpret", "block_rows"))
def merge_rows(tables, ids, rows, live, *, interpret: bool,
               block_rows: int = BLOCK_ROWS):
    """tables: (T, R, d); ids: (T, W) int32, ascending per table, W a
    multiple of ``CHUNK``; rows: (T, W, d) f32 deltas; live: (T, W) bool, at
    most one live slot per row. Returns the tables with
    ``round(f32(T[t, r]) + rows[t, w])`` at each live slot's row and every
    other row unchanged."""
    T, R, d = tables.shape
    W = ids.shape[1]
    nc, nb = W // CHUNK, -(-R // block_rows)
    parts = jnp.concatenate(
        [split3(jnp.where(live[..., None], rows, 0.0)).reshape(T, W, 3 * d),
         live[..., None].astype(jnp.bfloat16)], axis=-1)
    parts = jnp.pad(parts, ((0, 0), (0, 0), (0, _rows_of(d) - 3 * d - 1)))
    parts = jnp.swapaxes(parts.reshape(T, nc, CHUNK, _rows_of(d)), 2, 3)
    # slots [starts[t, j], starts[t, j + 1]) hold the ids of row block j
    bounds = jnp.arange(nb + 1, dtype=jnp.int32) * block_rows
    starts = jax.vmap(lambda k: jnp.searchsorted(k, bounds))(ids)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(T, nb),
        in_specs=[
            pl.BlockSpec((1, nc, 1, CHUNK), lambda t, j, s: (t, 0, 0, 0)),
            pl.BlockSpec((1, nc, _rows_of(d), CHUNK),
                         lambda t, j, s: (t, 0, 0, 0)),
            pl.BlockSpec((1, d, block_rows), lambda t, j, s: (t, 0, j)),
        ],
        out_specs=pl.BlockSpec((1, d, block_rows), lambda t, j, s: (t, 0, j)),
    )
    out = pl.pallas_call(
        functools.partial(_merge_kernel, d=d, block_rows=block_rows),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((T, d, R), tables.dtype),
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=max(vmem_bytes(W, d, block_rows), 16 << 20)),
        interpret=interpret,
    )(starts, ids.reshape(T, nc, 1, CHUNK), parts,
      jnp.swapaxes(tables, 1, 2))
    return jnp.swapaxes(out, 1, 2)
