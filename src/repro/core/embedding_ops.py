"""Near-data embedding operations over the disaggregated pool.

This is the TPU adaptation of CXL-MEM's *computing logic* (paper §"Designing
CXL-MEM"): embedding tables are row-sharded across the ``model`` mesh axis —
the pod's aggregate HBM plays the role of the PMEM pool — and lookups execute
*next to the data*: each shard gathers and (for bags) reduces its own rows
locally, then only the reduced ``(batch, dim)`` vectors cross the interconnect
via ``psum``. Raw rows never move. The backward pass of the same ``shard_map``
is automatically the near-data *update*: every shard scatter-adds gradients
into its own rows only.

Four strategies (hillclimb knobs — see EXPERIMENTS.md §Perf):
  * ``near_data``    — local masked gather + psum of results (paper-faithful).
                       Link bytes = tokens x d. Optimal when tokens << vocab
                       (decode, DLRM bags).
  * ``table_gather`` — replicate the table (all-gather rows) then gather
                       locally. Link bytes = vocab_local x d x (tp-1). Optimal
                       when tokens >> vocab (big-batch training).
  * ``pool``         — route the lookup through an attached
                       ``repro.pool.EmbeddingPoolMirror``: the host mirror
                       lives in an emulated Dram/Pmem ``PoolDevice`` and the
                       gather (bag lookups: the reduction too) executes as a
                       near-memory op with per-byte traffic accounting.
                       Forward-path only (serving / eval / traffic studies);
                       updates go pool-side via ``mirror.apply_grad``.
  * ``auto``         — picks by comparing the two byte counts at trace time.

Outside a sharding context everything degrades to a plain ``take`` so models
run unsharded on CPU.
"""
from __future__ import annotations

import contextlib
import threading
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.distributed import sharding

_state = threading.local()
_pool_mirror = None   # module-global EmbeddingPoolMirror (host-side object)


def attach_pool(mirror):
    """Install the pool mirror that backs the ``pool`` lookup strategy."""
    global _pool_mirror
    _pool_mirror = mirror


def detach_pool():
    global _pool_mirror
    _pool_mirror = None


def pool_mirror():
    return _pool_mirror


def _pool_call(cb, out_shape, out_dtype, ids):
    """Run a host-side pool op; under jit, via pure_callback."""
    res = jax.ShapeDtypeStruct(out_shape, out_dtype)
    if isinstance(ids, jax.core.Tracer):
        return jax.pure_callback(cb, res, ids)
    return jnp.asarray(cb(np.asarray(ids)), dtype=out_dtype)


@contextlib.contextmanager
def lookup_mode(mode: str):
    prev = getattr(_state, "mode", "auto")
    _state.mode = mode
    try:
        yield
    finally:
        _state.mode = prev


def current_mode() -> str:
    return getattr(_state, "mode", "auto")


def _axis_size(mesh, ax) -> int:
    if ax is None:
        return 1
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape, strict=True))
    if isinstance(ax, tuple):
        n = 1
        for a in ax:
            n *= sizes[a]
        return n
    return sizes[ax]


def _pick(mode: str, tokens: int, vocab: int, tp: int) -> str:
    if mode != "auto":
        return mode
    if tp == 1:
        return "table_gather"
    # near_data link bytes ~ tokens*d ; table_gather ~ vocab/tp*d*(tp-1)
    return "near_data" if tokens < vocab * (tp - 1) // tp else "table_gather"


def lookup(table, ids, *, mode: Optional[str] = None):
    """Pool lookup. table: (V, d); ids: int array -> ids.shape + (d,)."""
    ctx = sharding.current()
    mode = mode or current_mode()
    if mode == "pool":
        if _pool_mirror is None:
            raise RuntimeError("lookup(mode='pool') needs attach_pool(...)")
        mir = _pool_mirror
        return _pool_call(lambda i: mir.lookup(i).astype(table.dtype),
                          tuple(ids.shape) + (table.shape[-1],),
                          table.dtype, ids)
    if ctx is None:
        return jnp.take(table, ids, axis=0)
    tp_ax = ctx.rules.get("vocab")
    tp = _axis_size(ctx.mesh, tp_ax)
    strat = _pick(mode, ids.size, table.shape[0], tp)
    dp_rule = ctx.rules.get("batch")
    if table.shape[0] % tp or (
            dp_rule and ids.shape[0] % _axis_size(ctx.mesh, dp_rule)):
        strat = "table_gather"   # pool rows (or batch) don't divide the mesh
    if strat == "table_gather" or tp == 1:
        # force a replicated copy of the table, then local gather
        t = jax.lax.with_sharding_constraint(
            table, NamedSharding(ctx.mesh, P()))
        out = jnp.take(t, ids, axis=0)
        return sharding.constrain(out, ("batch",) + (None,) * (ids.ndim - 1)
                                  + ("embed",))

    dp_ax = ctx.rules.get("batch")
    V, d = table.shape
    rows_local = V // tp
    batch_spec = (dp_ax,) + (None,) * (ids.ndim - 1)

    def local(tshard, ids_loc):
        base = jax.lax.axis_index(tp_ax) * rows_local
        idx = ids_loc - base
        valid = (idx >= 0) & (idx < rows_local)
        rows = jnp.take(tshard, jnp.clip(idx, 0, rows_local - 1), axis=0)
        rows = jnp.where(valid[..., None], rows, jnp.zeros((), rows.dtype))
        return jax.lax.psum(rows, tp_ax)

    return jax.shard_map(
        local, mesh=ctx.mesh,
        in_specs=(P(tp_ax, None), P(*batch_spec)),
        out_specs=P(*batch_spec, None))(table, ids)


def _stacked_bags(tables, ids, combine: str):
    """Bags of ``rows[b, t, l] = tables[t, ids[b, t, l]]``, reduced over l:
    one gather whose slices are whole rows of the ``(T, R, d)`` tables.
    Ids lie in ``[0, R)``; XLA clamps one that does not."""
    rows = tables[jnp.arange(tables.shape[0])[None, :, None], ids]
    return rows.sum(axis=2) if combine == "sum" else rows.mean(axis=2)


def bag_lookup(tables, ids, *, mode: Optional[str] = None, combine: str = "sum"):
    """DLRM multi-table bag lookup with near-data reduction.

    tables: (T, R, d) stacked embedding tables; ids: (B, T, L) row indices.
    Returns (B, T, d) — each bag's L rows reduced by ``combine``.

    Near-data form: every shard owns R/tp rows *per table*; it reduces the
    rows it holds for each bag locally and the partial bag vectors are
    psum-combined — exactly the CXL-MEM adder array. Link bytes: B*T*d,
    independent of L (the paper's headline traffic saving).

    Unsharded (and ``table_gather``), the rows come from the stacked tables
    as stored, indexed by table and row together (``_stacked_bags``). No
    form views the tables as a flat ``[T·R, d]`` array: on a TPU the stored
    layout is rows-minor, and such a view relays out every table on every
    call.
    """
    ctx = sharding.current()
    mode = mode or current_mode()
    T, R, d = tables.shape
    if mode == "pool":
        if _pool_mirror is None:
            raise RuntimeError("bag_lookup(mode='pool') needs attach_pool(...)")
        mir = _pool_mirror
        return _pool_call(
            lambda i: mir.bag_lookup(i, combine).astype(tables.dtype),
            (ids.shape[0], T, d), tables.dtype, ids)
    if ctx is None:
        return _stacked_bags(tables, ids, combine)

    tp_ax = ctx.rules.get("table_rows")
    tp = _axis_size(ctx.mesh, tp_ax)
    if tp == 1 or mode == "table_gather":
        t = jax.lax.with_sharding_constraint(
            tables, NamedSharding(ctx.mesh, P()))
        out = _stacked_bags(t, ids, combine)
        return sharding.constrain(out, ("batch", None, "embed"))

    dp_ax = ctx.rules.get("batch")
    rows_local = R // tp

    def local(tshard, ids_loc):
        # tshard: (T, R/tp, d); ids_loc: (B_loc, T, L)
        base = jax.lax.axis_index(tp_ax) * rows_local
        idx = ids_loc - base
        valid = (idx >= 0) & (idx < rows_local)
        idx = jnp.clip(idx, 0, rows_local - 1)
        # gather per table: vmap over the table axis (moved to front)
        def per_table(tab, ix, vd):
            r = jnp.take(tab, ix, axis=0)                 # (B_loc, L, d)
            r = jnp.where(vd[..., None], r, jnp.zeros((), r.dtype))
            return r.sum(axis=1)                          # (B_loc, d)
        part = jax.vmap(per_table, in_axes=(0, 0, 0), out_axes=1)(
            tshard, jnp.swapaxes(idx, 0, 1), jnp.swapaxes(valid, 0, 1))
        # part: (B_loc, T, d) partial bag sums — the "reduced vectors"
        return jax.lax.psum(part, tp_ax)

    out = jax.shard_map(
        local, mesh=ctx.mesh,
        in_specs=(P(None, tp_ax, None), P(dp_ax, None, None)),
        out_specs=P(dp_ax, None, None))(tables, ids)
    if combine == "mean":
        out = out / ids.shape[-1]
    return out


def sparse_rows_grad(table_grad, ids):
    """Extract (unique-ish) touched rows from a dense table gradient —
    utility for tests validating the sparse-tier contract."""
    return jnp.take(table_grad, ids.reshape(-1), axis=0)
