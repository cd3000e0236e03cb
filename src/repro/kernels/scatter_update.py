"""Pallas TPU kernel: in-place sparse row update (+ fused undo capture).

The CXL-MEM *checkpointing logic* fused with the embedding update (paper
Fig. 7): for each touched row the kernel first copies the old value into the
log buffer ("2: copy embedding vectors from the data region to the log
region"), then applies the delta in place via input/output aliasing ("4: the
embedding table in the data region can be directly updated").

Constraint: ``idx`` must be unique (duplicates pre-combined by the caller via
segment-sum, as in production sparse-core updates); ops.py provides the
combine helper. D padded to a lane multiple by ops.py. Rows move as
``(None, 1, D)`` blocks of an ``(R, 1, D)`` view (see embedding_bag.py).
"""
from __future__ import annotations

import jax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.embedding_bag import row_spec, row_view


def _update_kernel(idx_ref, delta_ref, row_ref, out_ref):
    out_ref[...] = row_ref[...] + delta_ref[...].astype(row_ref.dtype)


def scatter_update_pallas(table, idx, delta, *, interpret: bool):
    """table: (R, D); idx: (N,) unique; delta: (N, D). Rows += delta in place.

    Aliasing: the table is donated; untouched rows pass through because every
    grid step writes the block it read (identity for rows not in idx happens
    by construction — only touched blocks are visited, others remain).
    """
    n = idx.shape[0]
    R, D = table.shape
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(n,),
        in_specs=[
            row_spec(D, lambda i, idx_ref: i),                 # delta
            row_spec(D, lambda i, idx_ref: idx_ref[i]),        # row in
        ],
        out_specs=row_spec(D, lambda i, idx_ref: idx_ref[i]),
    )
    out = pl.pallas_call(
        _update_kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((R, 1, D), table.dtype),
        input_output_aliases={2: 0},               # table -> out (in-place)
        interpret=interpret,
    )(idx, row_view(delta), row_view(table))
    return out.reshape(R, D)


def _update_logged_kernel(idx_ref, delta_ref, row_ref, out_ref, log_ref):
    log_ref[...] = row_ref[...]                    # undo image first (Fig. 7)
    out_ref[...] = row_ref[...] + delta_ref[...].astype(row_ref.dtype)


def scatter_update_logged_pallas(table, idx, delta, *, interpret: bool):
    """Fused update + undo-log capture. Returns (new_table, old_rows)."""
    n = idx.shape[0]
    R, D = table.shape
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(n,),
        in_specs=[
            row_spec(D, lambda i, idx_ref: i),
            row_spec(D, lambda i, idx_ref: idx_ref[i]),
        ],
        out_specs=[
            row_spec(D, lambda i, idx_ref: idx_ref[i]),
            row_spec(D, lambda i, idx_ref: i),
        ],
    )
    new_t, old = pl.pallas_call(
        _update_logged_kernel,
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct((R, 1, D), table.dtype),
                   jax.ShapeDtypeStruct((n, 1, D), table.dtype)],
        input_output_aliases={2: 0},
        interpret=interpret,
    )(idx, row_view(delta), row_view(table))
    return new_t.reshape(R, D), old.reshape(n, D)
