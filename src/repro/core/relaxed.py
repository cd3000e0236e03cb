"""Relaxed embedding lookup (paper §"Relaxation of Failure Tolerant Training").

The RAW hazard: batch N's embedding *update* and batch N+1's *lookup* touch
the same pool rows (~80 % overlap across consecutive batches, paper ref (10)).
The strict schedule serialises:   update_N -> lookup_{N+1} -> fwd_{N+1}.
The relaxed schedule exploits commutativity of the (additive) row update:

    gather(T + U, idx) == gather(T, idx) + gather(U, idx)        (exact)
    bag(T + U, idx)    == bag(T, idx)   + bag(U, idx)            (linear)

so batch N+1's lookup runs against the *pre-update* table concurrently with
batch N's backward, and the correction term ``gather(U, idx)`` — U is batch
N's sparse row delta — is added once the gradient exists. Both gathers are
off the critical path; the scatter-update no longer blocks the next step.

Because gather is a pure selection and the add is performed in the same
dtype/ordering as the in-table add, relaxed == strict **bitwise** for
row-gather models (LM) and to float-sum tolerance for bag models (the reduce
order differs) — property-tested in tests/test_relaxed.py.

These helpers are model-agnostic: "rows" means (…, d) pre-reduced embedding
outputs — full rows for LMs, reduced bag vectors for DLRM (the paper operates
on reduced vectors too, Fig. 8 bottom).

Under a row-local optimizer the DLRM update U need not be table-shaped: it
is nonzero only on the rows the batch touched. ``RowUpdate`` carries it as
each table's sorted ids with one f32 row delta per distinct id;
``write_rows`` applies it with the same rounding as ``apply_embed_update``,
and ``row_correction`` computes ``bag(U, idx)`` from it.
"""
from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.core import embedding_ops
from repro.distributed import sharding
from repro.distributed.sharding import constrain
from repro.kernels import row_merge


# ---------------------------------------------------------------------------
# Lookup / scatter / prefetch for the two pool layouts
# ---------------------------------------------------------------------------


def lookup_rows(embed_params: dict, cfg, batch: dict):
    """Pool lookup for a batch -> 'rows' (pre-reduced embedding outputs)."""
    if cfg.arch_type == "dlrm":
        return embedding_ops.bag_lookup(embed_params["emb_tables"],
                                        batch["sparse"])
    return embedding_ops.lookup(embed_params["table"], batch["tokens"])


def scatter_rows_grad(embed_params: dict, cfg, batch: dict, rows_grad):
    """Adjoint of lookup_rows: dense table-shaped gradient from row grads."""
    if cfg.arch_type == "dlrm":
        tables = embed_params["emb_tables"]
        T, R, d = tables.shape
        idx = batch["sparse"]                              # (B, T, L)
        g = jnp.zeros((T, R, d), jnp.float32)
        # every row in the bag receives the bag's gradient (d bag / d row = 1)
        B, _, L = idx.shape
        flat_idx = (jnp.arange(T)[None, :, None] * R + idx).reshape(-1)
        flat_g = jnp.broadcast_to(rows_grad[:, :, None, :].astype(jnp.float32),
                                  (B, T, L, d)).reshape(-1, d)
        g = g.reshape(T * R, d).at[flat_idx].add(flat_g).reshape(T, R, d)
        return {"emb_tables": g}
    table = embed_params["table"]
    V, d = table.shape
    idx = batch["tokens"].reshape(-1)
    g = jnp.zeros((V, d), jnp.float32).at[idx].add(
        rows_grad.reshape(-1, rows_grad.shape[-1]).astype(jnp.float32))
    # keep the dense-but-sparse-content gradient on the pool layout
    return {"table": constrain(g, ("vocab", None))}


def prefetch_corrected(embed_params_old: dict, updates: dict, cfg,
                       next_batch: dict):
    """Relaxed prefetch of batch N+1's rows.

    ``embed_params_old`` is the PRE-update pool (available at the start of
    batch N — the gather is schedulable in parallel with N's compute);
    ``updates`` is batch N's sparse delta U. Returns rows exactly equal to
    looking up the post-update pool:  gather(T, idx) + gather(U, idx).
    """
    stale = lookup_rows(embed_params_old, cfg, next_batch)
    corr = lookup_rows(jax.tree.map(lambda u: u, updates), cfg, next_batch) \
        if updates is not None else None
    if corr is None:
        return stale
    # mirror the in-table update arithmetic: f32 add, round to table dtype
    table_dtype = jax.tree.leaves(embed_params_old)[0].dtype
    return (stale.astype(jnp.float32) + corr.astype(jnp.float32)) \
        .astype(table_dtype)


def _round_add(t, u):
    """round(f32(t) + f32(u)) in t's dtype: the one rule every table write
    and every corrected prefetch follows."""
    return (t.astype(jnp.float32) + u.astype(jnp.float32)).astype(t.dtype)


def apply_embed_update(embed_params: dict, updates: dict):
    """T_new = round(T + U) — the arithmetic prefetch_corrected mirrors."""
    return jax.tree.map(_round_add, embed_params, updates)


# ---------------------------------------------------------------------------
# Row-sparse update (DLRM tables, row-local optimizer, no mesh)
# ---------------------------------------------------------------------------


class RowUpdate(NamedTuple):
    """One step's sparse update of stacked tables ``(T, R, d)``, as rows.

    Per table, ``ids`` holds the batch's ``B·L`` row ids in ascending order,
    duplicates kept and padded with ``R`` to a multiple of ``SEG_BLOCK``, so
    every shape is static. ``rows`` holds one f32 row per slot: the running
    sum of its run of equal ids. Only the last slot of each run of real ids
    is ``live``; it carries the run's total, and only it is written.
    """
    ids: jax.Array        # (T, W) int32, ascending per table
    rows: jax.Array       # (T, W, d) float32
    live: jax.Array       # (T, W) bool

    @property
    def count(self):
        """Distinct (table, row) pairs the update writes (int32 scalar)."""
        return jnp.sum(self.live, dtype=jnp.int32)


# slots per block of the segmented sum's masked matmul, and per chunk of the
# streaming write, which needs whole chunks
SEG_BLOCK = row_merge.CHUNK


def row_update_applies(cfg, embed_params: dict, embed_opt) -> bool:
    """The row path serves DLRM's stacked tables outside a mesh, under an
    optimizer whose update of a row reads only that row's gradient."""
    return (cfg.arch_type == "dlrm" and "emb_tables" in embed_params
            and sharding.current() is None and embed_opt.row_local)


def _segmented_sum(ids, vals):
    """Running sums of ``vals`` (T, W, d) along W that restart wherever the
    ascending ``ids`` (T, W) change. Within a block of ``SEG_BLOCK`` slots
    the sums are one masked matmul (same id, not later); a run that crosses
    blocks adds the carry from the blocks before it."""
    T, W, d = vals.shape
    nb = W // SEG_BLOCK
    k = ids.reshape(T, nb, SEG_BLOCK)
    earlier = jnp.tril(jnp.ones((SEG_BLOCK, SEG_BLOCK), bool))
    same = (k[..., :, None] == k[..., None, :]) & earlier
    s = jnp.einsum("tnij,tnjd->tnid", same.astype(jnp.float32),
                   vals.reshape(T, nb, SEG_BLOCK, d),
                   precision=jax.lax.Precision.HIGHEST)
    head, tail = k[..., 0], k[..., -1]
    # carry[b] = cont[b] * (s[b - 1, -1] + whole[b - 1] * carry[b - 1])
    cont = jnp.concatenate([jnp.zeros((T, 1), bool),
                            head[:, 1:] == tail[:, :-1]], axis=1)
    through = jnp.concatenate([jnp.zeros((T, 1), bool),
                               cont[:, 1:] & (head == tail)[:, :-1]], axis=1)
    add = jnp.concatenate([jnp.zeros((T, 1, d), jnp.float32),
                           jnp.where(cont[:, 1:, None], s[:, :-1, -1], 0.0)],
                          axis=1)

    def compose(x, y):
        tx, ax = x
        ty, ay = y
        return tx & ty, jnp.where(ty[..., None], ax, 0.0) + ay
    carry = jax.lax.associative_scan(compose, (through, add), axis=1)[1]
    s = s + jnp.where((k == head[..., None])[..., None], carry[:, :, None],
                      0.0)
    return s.reshape(T, W, d)


def row_grads(tables, ids, rows_grad) -> RowUpdate:
    """Adjoint of ``bag_lookup`` as rows. ``ids``: (B, T, L) row ids;
    ``rows_grad``: (B, T, d) bag gradients. Every row in a bag receives the
    bag's gradient (d bag / d row = 1); each table's ids are sorted and the
    gradients of equal ids summed. Nothing of the table's shape is built."""
    _, R, _ = tables.shape
    B, T, L = ids.shape
    W = -(-B * L // SEG_BLOCK) * SEG_BLOCK
    keys = jnp.swapaxes(ids, 0, 1).reshape(T, B * L)      # slot j: bag j // L
    keys = jnp.pad(keys, ((0, 0), (0, W - B * L)), constant_values=R)
    slot = jnp.broadcast_to(jnp.arange(W, dtype=jnp.int32), (T, W))
    keys, slot = jax.lax.sort((keys, slot), dimension=1, num_keys=1)
    bags = jnp.pad(jnp.swapaxes(rows_grad, 0, 1).astype(jnp.float32),
                   ((0, 0), (0, 1), (0, 0)))            # bag B: zero (padding)
    g = jnp.take_along_axis(bags, jnp.minimum(slot // L, B)[..., None],
                            axis=1)                       # (T, W, d)
    live = jnp.concatenate([keys[:, 1:] != keys[:, :-1],
                            jnp.ones_like(keys[:, :1], bool)], axis=1)
    return RowUpdate(keys, _segmented_sum(keys, g), live & (keys < R))


def _write_rows_xla(tables, delta: RowUpdate):
    """``write_rows`` as XLA scatters, one per table's ``[R, d]`` slab. Slots
    that are not live go to distinct rows past the end, which it drops."""
    T, R, _ = tables.shape
    W = delta.ids.shape[1]
    dropped = R + jnp.arange(W, dtype=delta.ids.dtype)
    out = []
    for t in range(T):
        slab, ids = tables[t], delta.ids[t]
        new = _round_add(jnp.take(slab, ids, axis=0, mode="clip"),
                         delta.rows[t])
        at = jnp.where(delta.live[t], ids, dropped)
        out.append(slab.at[at].set(new, mode="drop", unique_indices=True))
    return jnp.stack(out)


def write_rows(tables, delta: RowUpdate):
    """round(f32(T[t, r]) + u) at each live slot; every other row stays
    bitwise. On a TPU one streaming pass of ``kernels.row_merge`` writes
    them (an XLA scatter pays an HBM round trip per row); elsewhere, or
    where a table's slots do not fit the kernel's VMEM, ``_write_rows_xla``.
    Neither views the tables as a flat ``[T·R, d]`` array: the stored
    layout is rows-minor, and such a view relays out the whole table."""
    _, W = delta.ids.shape
    if not row_merge.fits(W, tables.shape[-1]):
        return _write_rows_xla(tables, delta)
    return jax.lax.platform_dependent(
        tables, delta,
        tpu=lambda t, u: row_merge.merge_rows(t, u.ids, u.rows, u.live,
                                              interpret=False),
        default=_write_rows_xla)


def row_correction(delta: RowUpdate, ids):
    """``bag(U, ids)`` from the rows alone. Per table, the next ids and the
    update's ids are sorted together, each next id after the update's equal
    ones: the count of update slots before a next id is one past the last
    slot of its run. The id takes that slot's row where it matches and 0
    where the update left it alone. ids: (B, T, L); returns (B, T, d) f32."""
    B, T, L = ids.shape
    W = delta.ids.shape[1]
    q = jnp.swapaxes(ids, 0, 1).reshape(T, B * L)
    key = jnp.concatenate([delta.ids * 2, q * 2 + 1], axis=1)
    pos = jnp.broadcast_to(jnp.arange(key.shape[1], dtype=jnp.int32),
                           key.shape)
    key, pos = jax.lax.sort((key, pos), dimension=1, num_keys=1)
    before = jnp.cumsum((key & 1) == 0, axis=1, dtype=jnp.int32)
    _, before = jax.lax.sort((pos, before), dimension=1, num_keys=1)
    at = jnp.maximum(before[:, W:] - 1, 0)                    # (T, B·L)
    hit = jnp.take_along_axis(delta.ids, at, axis=1) == q
    rows = jnp.take_along_axis(delta.rows, at[..., None], axis=1)
    rows = jnp.where(hit[..., None], rows, 0.0).reshape(T, B, L, -1)
    return jnp.swapaxes(rows, 0, 1).sum(axis=2)


def prefetch_rows_corrected(embed_params_old: dict, delta: RowUpdate, cfg,
                            next_batch: dict):
    """``prefetch_corrected`` with the update given as rows: the stale bags
    from the pre-update table plus ``bag(U, idx_next)``, rounded as the
    table write rounds."""
    stale = lookup_rows(embed_params_old, cfg, next_batch)
    return _round_add(stale, row_correction(delta, next_batch["sparse"]))


def constrain_pool(tree: dict):
    """Keep table-shaped tensors (grads/updates/deltas) on the pool layout."""
    out = dict(tree)
    if "table" in out:
        out["table"] = constrain(out["table"], ("vocab", None))
    if "emb_tables" in out:
        out["emb_tables"] = constrain(out["emb_tables"],
                                      (None, "table_rows", None))
    return out


def touched_indices(cfg, batch: dict):
    """The batch-aware property: the rows a batch WILL update, known from the
    sparse features before any compute (paper Fig. 6)."""
    if cfg.arch_type == "dlrm":
        return batch["sparse"]
    return batch["tokens"]


def consecutive_overlap(cfg, batch_a: dict, batch_b: dict) -> jnp.ndarray:
    """Fraction of batch_b's lookups that hit rows batch_a updated — the RAW
    frequency the paper's relaxation targets (ref (10): ~80%)."""
    ia = touched_indices(cfg, batch_a).reshape(-1)
    ib = touched_indices(cfg, batch_b).reshape(-1)
    if cfg.arch_type == "dlrm":
        size = cfg.dlrm_rows_per_table
    else:
        size = cfg.vocab_size
    hit = jnp.zeros((size,), jnp.bool_).at[ia].set(True)
    return jnp.mean(hit[ib].astype(jnp.float32))
