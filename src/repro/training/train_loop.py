"""Training steps: strict (dependent) and relaxed (paper) schedules.

strict_step:
    lookup_N -> fwd/bwd_N -> update_dense -> update_pool
    (batch N+1's lookup must wait for update_pool — the RAW dependency)

relaxed_step (TrainingCXL):
    uses rows prefetched at step N-1; inside step N it
      * runs fwd/bwd on the carried rows,
      * updates the pool,
      * prefetches batch N+1's rows from the PRE-update table + the
        commutative correction gather(U, idx_next)
    so no gather ever waits on a scatter: XLA can schedule the two prefetch
    gathers (and their psum, under the sharded pool) in parallel with the
    backward pass. The rows the batch-aware checkpoint logs are idx_N,
    known from the batch before any compute.

    The pool update takes one of two forms, by what the step can observe.
    DLRM's stacked tables outside a mesh, under a row-local optimizer (plain
    SGD), carry the update as rows: each table's sorted ids and one f32
    delta per distinct id (``rx.row_grads``), written into the tables
    (``rx.write_rows``) and read back by the correction
    (``rx.row_correction``); the step reports ``rows_updated``. Everything
    else (other optimizers, LM tables, a mesh) builds a table-shaped f32
    gradient and update.

Both step functions are pure jit-able pytree->pytree maps; the checkpoint
manager hooks observe their outputs from the host side.

Tracing. The step programs name their phases with ``jax.named_scope``:
``embed_grad`` (the lookup's adjoint), ``dense_update`` (clip, optimizer,
apply), ``embed_update`` (optimizer, write into the pool), ``prefetch``
(relaxed prefetch with correction) and ``ckpt_feed``; the model adds
``bottom_mlp``, ``interaction`` and ``top_mlp`` (``models/dlrm.py``), which
the backward pass inherits. Scopes are metadata only: the compiled program
is the same with or without them. ``train()`` marks each iteration with a
``StepTraceAnnotation`` (``repro.train.step``) and its host work with
``TraceAnnotation`` spans carrying ``step=n``: ``repro.train.next_batch``,
``.dispatch``, ``.loss_read``, ``.ckpt_on_step`` and ``.on_metrics``. All
of them land in the profiler's own trace, on one clock with the device.
"""
from __future__ import annotations

from typing import Callable, Optional

import jax
import jax.numpy as jnp

from repro.core import relaxed as rx
from repro.models.registry import get_api
from repro.optim import optimizers as opt
from repro.training import state as st


def _loss_with_rows(api, cfg):
    def f(dense, embed, rows, batch):
        params = st.merge_params(dense, embed)
        b = dict(batch)
        if rows is not None:
            b["embed_rows"] = rows
        return api.loss(params, cfg, b)
    return f


def make_step_fns(cfg, train_cfg):
    """Returns (init_fn, strict_step, relaxed_step, warmup_fn)."""
    api = get_api(cfg)
    dense_opt = opt.make_optimizer(train_cfg.optimizer, train_cfg.learning_rate,
                                   train_cfg)
    embed_opt = opt.make_optimizer(train_cfg.embed_optimizer,
                                   train_cfg.embed_learning_rate)
    loss_fn = _loss_with_rows(api, cfg)

    def init_fn(key):
        params = api.init(key, cfg)
        return st.make_state(params, dense_opt, embed_opt)

    def dense_update(state, g_dense):
        """Clip, optimizer and apply of the dense tier (both schedules)."""
        with jax.named_scope("dense_update"):
            if train_cfg.grad_clip:
                g_dense, gnorm = opt.global_norm_clip(g_dense,
                                                      train_cfg.grad_clip)
            else:
                gnorm = jnp.zeros(())
            upd_d, od = dense_opt.update(g_dense, state["opt_dense"],
                                         state["dense"])
            dense = jax.tree.map(lambda p, u: (p.astype(jnp.float32) + u)
                                 .astype(p.dtype), state["dense"], upd_d)
        return dense, od, gnorm

    # -- strict ------------------------------------------------------------
    def strict_step(state, batch):
        def full_loss(dense, embed):
            return loss_fn(dense, embed, None, batch)

        loss, grads = jax.value_and_grad(full_loss, argnums=(0, 1))(
            state["dense"], state["embed"])
        g_dense, g_embed = grads
        dense, od, gnorm = dense_update(state, g_dense)
        with jax.named_scope("embed_update"):
            upd_e, oe = embed_opt.update(g_embed, state["opt_embed"],
                                         state["embed"])
            embed = rx.apply_embed_update(state["embed"], upd_e)
        new_state = {**state, "dense": dense, "embed": embed,
                     "opt_dense": od, "opt_embed": oe,
                     "step": state["step"] + 1, "prefetch": state["prefetch"]}
        return new_state, {"loss": loss, "grad_norm": gnorm}

    # -- relaxed -----------------------------------------------------------
    def warmup(state, batch0):
        """Fill the prefetch carry for step 0 (no previous step to overlap)."""
        rows = rx.lookup_rows(state["embed"], cfg, batch0)
        return {**state, "prefetch": {"rows": rows}}

    def table_update(state, batch, next_batch, g_embed_direct, g_rows):
        """Sparse update through a table-shaped gradient: any optimizer,
        the LM tables, a mesh."""
        with jax.named_scope("embed_grad"):
            # adjoint of the lookup: dense table-shaped grad (sparse content)
            g_pool = rx.scatter_rows_grad(state["embed"], cfg, batch, g_rows)
            # tied heads / direct table uses contribute densely
            g_embed = jax.tree.map(lambda a, b: a + b.astype(jnp.float32),
                                   g_pool, g_embed_direct)
        with jax.named_scope("embed_update"):
            upd_e, oe = embed_opt.update(g_embed, state["opt_embed"],
                                         state["embed"])
            upd_e = rx.constrain_pool(upd_e)
            embed = rx.apply_embed_update(state["embed"], upd_e)
        # relaxed prefetch: stale gather (pre-update pool) + correction.
        # No data dependency on `embed` — the scatter never blocks it.
        with jax.named_scope("prefetch"):
            rows_next = rx.prefetch_corrected(state["embed"], upd_e, cfg,
                                              next_batch)
        return embed, oe, rows_next, {}

    def row_update(state, batch, next_batch, g_rows):
        """Sparse update as (sorted ids, row deltas): DLRM tables under a
        row-local optimizer, no mesh. The DLRM loss reads the tables only
        through the carried rows, so they have no direct gradient."""
        tables = state["embed"]["emb_tables"]
        with jax.named_scope("embed_grad"):
            grad = rx.row_grads(tables, batch["sparse"], g_rows)
        with jax.named_scope("embed_update"):
            upd, oe = embed_opt.update({"emb_tables": grad.rows},
                                       state["opt_embed"], None)
            delta = grad._replace(rows=upd["emb_tables"])
            embed = {"emb_tables": rx.write_rows(tables, delta)}
        with jax.named_scope("prefetch"):
            rows_next = rx.prefetch_rows_corrected(state["embed"], delta, cfg,
                                                   next_batch)
        return embed, oe, rows_next, {"rows_updated": delta.count}

    def relaxed_step(state, batch, next_batch):
        rows_in = state["prefetch"]["rows"]

        loss, grads = jax.value_and_grad(
            lambda d, e, r: loss_fn(d, e, r, batch), argnums=(0, 1, 2),
        )(state["dense"], state["embed"], rows_in)
        g_dense, g_embed_direct, g_rows = grads

        if rx.row_update_applies(cfg, state["embed"], embed_opt):
            embed, oe, rows_next, counts = row_update(
                state, batch, next_batch, g_rows)
        else:
            embed, oe, rows_next, counts = table_update(
                state, batch, next_batch, g_embed_direct, g_rows)
        dense, od, gnorm = dense_update(state, g_dense)

        new_state = {**state, "dense": dense, "embed": embed,
                     "opt_dense": od, "opt_embed": oe,
                     "step": state["step"] + 1,
                     "prefetch": {"rows": rows_next}}
        # the batch-aware checkpoint logs exactly the rows this batch touched
        # (known in advance); the manager reads their new values from `embed`
        with jax.named_scope("ckpt_feed"):
            ckpt_feed = {"touched": rx.touched_indices(cfg, batch)}
        return new_state, {"loss": loss, "grad_norm": gnorm,
                           "ckpt_feed": ckpt_feed, **counts}

    return init_fn, strict_step, relaxed_step, warmup


def train(cfg, train_cfg, batches, num_steps: int, *, relaxed: bool = True,
          jit: bool = True, state=None, start_step: int = 0,
          ckpt_manager=None, on_metrics: Optional[Callable] = None,
          checkpoint_dir: Optional[str] = None,
          pool_backend: Optional[str] = None,
          pool_addr: Optional[str] = None,
          pool_tenant: Optional[str] = None):
    """Host-side loop (examples / tests). Returns (state, losses).

    ``checkpoint_dir``/``pool_backend`` build a two-tier CheckpointManager
    internally (over the dram/pmem emulated pool, or a remote memory node
    at ``pool_addr`` under ``pool_tenant``) when the caller did not pass
    ``ckpt_manager``; the manager is flushed before returning.
    """
    init_fn, strict_step, relaxed_step, warmup = make_step_fns(cfg, train_cfg)
    if state is None:
        state = init_fn(jax.random.PRNGKey(train_cfg.seed))
    own_manager = False
    if ckpt_manager is None and checkpoint_dir:
        import dataclasses

        from repro.core.checkpoint.manager import CheckpointManager
        overrides = {"pool_backend": pool_backend, "pool_addr": pool_addr,
                     "pool_tenant": pool_tenant}
        cc = dataclasses.replace(
            train_cfg.checkpoint, directory=checkpoint_dir,
            **{k: v for k, v in overrides.items() if v})
        ckpt_manager = CheckpointManager(cfg, cc, embed_init=state["embed"])
        own_manager = True
    step_strict = jax.jit(strict_step) if jit else strict_step
    step_relaxed = jax.jit(relaxed_step) if jit else relaxed_step
    losses = []
    if relaxed and state.get("prefetch") is None:
        state = (jax.jit(warmup) if jit else warmup)(
            state, batches.next(start_step))
    span = jax.profiler.TraceAnnotation
    for n in range(start_step, start_step + num_steps):
        with jax.profiler.StepTraceAnnotation("repro.train.step", step_num=n):
            with span("repro.train.next_batch", step=n):
                batch = batches.next(n)
                batch_next = batches.next(n + 1) if relaxed else None
            with span("repro.train.dispatch", step=n):
                if relaxed:
                    state, metrics = step_relaxed(state, batch, batch_next)
                else:
                    state, metrics = step_strict(state, batch)
            with span("repro.train.loss_read", step=n):
                losses.append(float(metrics["loss"]))
            if ckpt_manager is not None:
                with span("repro.train.ckpt_on_step", step=n):
                    ckpt_manager.on_step(n, state, metrics.get("ckpt_feed"))
            if on_metrics is not None:
                with span("repro.train.on_metrics", step=n):
                    on_metrics(n, metrics)
    if ckpt_manager is not None:
        ckpt_manager.flush()
        if own_manager:
            ckpt_manager.close()   # release the pool fd/mmap we opened
    return state, losses
