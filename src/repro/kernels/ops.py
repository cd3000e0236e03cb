"""jit'd wrappers around the Pallas kernels with an XLA fallback backend.

Backend selection:
  * "xla"              — pure-jnp reference path (default on CPU; what the
                         dry-run lowers so cost analysis reflects real HLO)
  * "pallas_interpret" — Pallas kernels executed in interpret mode (CPU
                         validation of kernel logic)
  * "pallas"           — compiled Pallas (the TPU target)

Every call takes ONE table. The kernels scalar-prefetch their int32 index
operands into SMEM (1 MiB on TPU v5e), so all tables of a model in one call
would overflow it (RM1: 20 tables x 256 x 80 ids = 1.6 MB per operand);
``_check_smem`` refuses such a call before the compiler does.
"""
from __future__ import annotations

import threading

import jax
import jax.numpy as jnp

from repro.kernels import embedding_bag as eb
from repro.kernels import ref
from repro.kernels import scatter_update as su

_state = threading.local()

SMEM_BYTES = 1 << 20        # scalar memory of one TPU v5e core
SMEM_RESERVE = 16 << 10     # left for the kernel's own scalars


def set_backend(name: str):
    assert name in ("xla", "pallas_interpret", "pallas")
    _state.backend = name


def get_backend() -> str:
    return getattr(_state, "backend", "xla")


def _pad_lanes(x, mult: int = 128):
    d = x.shape[-1]
    pad = (-d) % mult
    if pad == 0:
        return x, d
    widths = [(0, 0)] * (x.ndim - 1) + [(0, pad)]
    return jnp.pad(x, widths), d


def _check_smem(n: int, operands: int):
    """The ``operands`` prefetched int32 arrays of length ``n`` must fit in
    SMEM beside the reserve; split the call (one table, fewer ids) if not."""
    need = operands * 4 * n
    if need + SMEM_RESERVE > SMEM_BYTES:
        raise ValueError(
            f"{operands} scalar-prefetch operand(s) of {n} int32 ids need "
            f"{need} B of SMEM; at most {SMEM_BYTES - SMEM_RESERVE} B fit — "
            f"call the kernel once per table with fewer ids")


def embedding_bag(table, idx, seg, num_bags: int):
    """Fused gather + segment-sum. idx/seg (N,), seg non-decreasing."""
    backend = get_backend()
    if backend == "xla":
        return ref.embedding_bag_ref(table, idx, seg, num_bags)
    _check_smem(idx.shape[0], operands=2)
    tp, d = _pad_lanes(table)
    out = eb.embedding_bag_pallas(tp, idx, seg, num_bags,
                                  interpret=(backend == "pallas_interpret"))
    return out[:, :d]


def gather_rows(table, idx):
    backend = get_backend()
    if backend == "xla":
        return jnp.take(table, idx, axis=0)
    _check_smem(idx.shape[0], operands=1)
    tp, d = _pad_lanes(table)
    out = eb.gather_rows_pallas(tp, idx,
                                interpret=(backend == "pallas_interpret"))
    return out[:, :d]


def combine_duplicates(idx, delta, num_rows: int):
    """Pre-combine duplicate indices (sorted-unique static-shape form).

    Returns (uniq_idx, combined_delta) with shape (N,) / (N, D): position i
    holds the i-th *sorted* index; duplicate slots are filled with row 0 and
    zero delta (harmless for the update kernels).
    """
    order = jnp.argsort(idx)
    si = idx[order]
    sd = delta[order]
    first = jnp.concatenate([jnp.ones((1,), bool), si[1:] != si[:-1]])
    seg = jnp.cumsum(first) - 1                     # dense segment ids
    combined = jax.ops.segment_sum(sd, seg, num_segments=idx.shape[0])
    uniq_slots = jax.ops.segment_max(si, seg, num_segments=idx.shape[0])
    n_uniq = seg[-1] + 1
    valid = jnp.arange(idx.shape[0]) < n_uniq
    uniq_idx = jnp.where(valid, uniq_slots, 0)
    combined = jnp.where(valid[:, None], combined, 0)
    return uniq_idx, combined


def scatter_update(table, idx, delta):
    """table rows at (unique) idx += delta."""
    backend = get_backend()
    if backend == "xla":
        return ref.scatter_update_ref(table, idx, delta)
    _check_smem(idx.shape[0], operands=1)
    tp, d = _pad_lanes(table)
    dp, _ = _pad_lanes(delta)
    out = su.scatter_update_pallas(tp, idx, dp,
                                   interpret=(backend == "pallas_interpret"))
    return out[:, :d]


def scatter_update_logged(table, idx, delta):
    """Fused update + undo capture -> (new_table, old_rows)."""
    backend = get_backend()
    if backend == "xla":
        return ref.scatter_update_logged_ref(table, idx, delta)
    _check_smem(idx.shape[0], operands=1)
    tp, d = _pad_lanes(table)
    dp, _ = _pad_lanes(delta)
    new_t, old = su.scatter_update_logged_pallas(
        tp, idx, dp, interpret=(backend == "pallas_interpret"))
    return new_t[:, :d], old[:, :d]
