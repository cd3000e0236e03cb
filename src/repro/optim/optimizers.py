"""Pure-JAX optimizers (no optax dependency).

Two tiers, matching the paper:
  * dense tier (MLP/backbone): AdamW / SGD-momentum
  * sparse tier (embedding pool): plain SGD or row-wise Adagrad — *additive*
    update rules, which is what makes the relaxed embedding lookup exact
    (commutativity of the row update, paper §Relaxed Embedding Lookup).
"""
from __future__ import annotations

from typing import Any, Callable, NamedTuple

import jax
import jax.numpy as jnp


class Optimizer(NamedTuple):
    init: Callable[[Any], Any]            # params -> state
    update: Callable[[Any, Any, Any], tuple]  # (grads, state, params) -> (updates, state)
    # the update of each row reads that row's gradient alone, keeps no state
    # and maps a zero gradient to a zero update: it may be applied to the
    # touched rows only (the relaxed step's row-sparse update)
    row_local: bool = False


def sgd(lr: float, momentum: float = 0.0) -> Optimizer:
    def init(params):
        if momentum == 0.0:
            return ()
        return jax.tree.map(lambda p: jnp.zeros_like(p, jnp.float32), params)

    def update(grads, state, params):
        if momentum == 0.0:
            return jax.tree.map(lambda g: -lr * g, grads), state
        new_m = jax.tree.map(lambda m, g: momentum * m + g.astype(jnp.float32),
                             state, grads)
        return jax.tree.map(lambda m: (-lr * m), new_m), new_m

    return Optimizer(init, update, row_local=momentum == 0.0)


def adamw(lr: float, b1: float = 0.9, b2: float = 0.95, eps: float = 1e-8,
          weight_decay: float = 0.0) -> Optimizer:
    def init(params):
        def zeros(p):
            return jnp.zeros(p.shape, jnp.float32)
        return {"m": jax.tree.map(zeros, params),
                "v": jax.tree.map(zeros, params),
                "t": jnp.zeros((), jnp.int32)}

    def update(grads, state, params):
        t = state["t"] + 1
        m = jax.tree.map(lambda m, g: b1 * m + (1 - b1) * g.astype(jnp.float32),
                         state["m"], grads)
        v = jax.tree.map(lambda v, g: b2 * v
                         + (1 - b2) * jnp.square(g.astype(jnp.float32)),
                         state["v"], grads)
        bc1 = 1 - b1 ** t.astype(jnp.float32)
        bc2 = 1 - b2 ** t.astype(jnp.float32)

        def upd(m, v, p):
            u = -lr * (m / bc1) / (jnp.sqrt(v / bc2) + eps)
            if weight_decay:
                u = u - lr * weight_decay * p.astype(jnp.float32)
            return u

        return (jax.tree.map(upd, m, v, params),
                {"m": m, "v": v, "t": t})

    return Optimizer(init, update)


def rowwise_adagrad(lr: float, eps: float = 1e-8) -> Optimizer:
    """Row-wise Adagrad for embedding tables (one accumulator scalar per row).

    The accumulator update uses the *lagged* scale (scale read before the
    batch), so the row delta remains a pure function of (row grad, old
    accumulator) — additive across non-overlapping batches, which keeps the
    relaxed-lookup correction algebra exact for disjoint rows and a first-
    order approximation for overlapping hot rows (measured in tests).
    """
    def init(params):
        return jax.tree.map(
            lambda p: jnp.zeros(p.shape[:1] + (1,) * (p.ndim - 1), jnp.float32)
            if p.ndim >= 2 else jnp.zeros((), jnp.float32), params)

    def update(grads, state, params):
        def upd(g, a):
            g32 = g.astype(jnp.float32)
            gsq = jnp.mean(jnp.square(g32), axis=tuple(range(1, g.ndim)),
                           keepdims=True) if g.ndim >= 2 else jnp.square(g32)
            new_a = a + gsq
            return -lr * g32 / (jnp.sqrt(a + gsq) + eps), new_a

        out = jax.tree.map(upd, grads, state)
        ups = jax.tree.map(lambda x: x[0], out,
                           is_leaf=lambda x: isinstance(x, tuple))
        sts = jax.tree.map(lambda x: x[1], out,
                           is_leaf=lambda x: isinstance(x, tuple))
        return ups, sts

    return Optimizer(init, update)


def make_optimizer(name: str, lr: float, cfg=None) -> Optimizer:
    if name == "sgd":
        return sgd(lr)
    if name == "sgdm":
        return sgd(lr, 0.9)
    if name == "adamw":
        return adamw(lr,
                     b1=getattr(cfg, "beta1", 0.9),
                     b2=getattr(cfg, "beta2", 0.95),
                     weight_decay=getattr(cfg, "weight_decay", 0.0))
    if name == "rowwise_adagrad":
        return rowwise_adagrad(lr)
    raise ValueError(name)


def global_norm_clip(grads, max_norm: float):
    leaves = jax.tree.leaves(grads)
    norm = jnp.sqrt(sum(jnp.sum(jnp.square(g.astype(jnp.float32)))
                        for g in leaves))
    scale = jnp.minimum(1.0, max_norm / jnp.maximum(norm, 1e-12))
    return jax.tree.map(lambda g: g * scale.astype(g.dtype), grads), norm
