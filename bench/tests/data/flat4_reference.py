"""Plain reference of the ``flat4`` test configuration, in float32.

A model of the shape a configuration with per-table ``rows`` and
``pooling`` brings: the tables in one flat ``(sum(rows), d)`` array, each
table's bag the sum of its ``pooling`` rows, the bottom MLP's output and
the bags concatenated into the top MLP, mean binary cross-entropy. Dense gradients clipped to global
norm ``grad_clip``, Adagrad (no initial accumulator) on the dense tier, SGD
on the rows.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32
HI = jax.lax.Precision.HIGHEST

RECIPES = {("adagrad", "sgd"): {
    "keys": {"lr": "learning_rate", "eps": None, "grad_clip": "grad_clip",
             "embed_lr": "embed_learning_rate"},
    "fixed": {}}}


def _mlp_init(key, dims, dtype):
    out = []
    for k, n_in, n_out in zip(jax.random.split(key, len(dims) - 1),
                              dims[:-1], dims[1:], strict=True):
        s = math.sqrt(1.0 / n_in)
        out.append({"w": jax.random.uniform(k, (n_in, n_out), dtype, -s, s),
                    "b": jnp.zeros((n_out,), dtype)})
    return out


def _mlp(layers, x, final_relu):
    for i, p in enumerate(layers):
        x = jnp.dot(x, p["w"], precision=HI) + p["b"]
        if final_relu or i < len(layers) - 1:
            x = jnp.maximum(x, 0.0)
    return x


def top_dims(sizes: dict) -> tuple:
    return ((len(sizes["rows"]) + 1) * sizes["embed_dim"],
            *sizes["top_mlp"])


def loss_fn(dense, table, batch, offsets, pooling):
    z0 = _mlp(dense["bottom"], batch["dense"].astype(F32), True)
    ids = batch["sparse"] + jnp.asarray(np.repeat(offsets, pooling))[None, :]
    rows = jnp.take(table, ids, axis=0)                       # (B, sum p, d)
    ends = np.cumsum(pooling)
    bags = [rows[:, e - p:e].sum(axis=1)
            for e, p in zip(ends, pooling, strict=True)]
    logit = _mlp(dense["top"], jnp.concatenate([z0, *bags], axis=-1),
                 False)[:, 0]
    y = batch["labels"].astype(F32)
    return jnp.mean(jnp.maximum(logit, 0) - logit * y
                    + jnp.log1p(jnp.exp(-jnp.abs(logit))))


@jax.jit
def leaf_norm(a):
    return jnp.sqrt(jnp.sum(jnp.square(a.astype(F32))))


@jax.jit
def diff_norm(a, b):
    return jnp.sqrt(jnp.sum(jnp.square(a.astype(F32) - b.astype(F32))))


def leaf_names(tree) -> dict:
    return {".".join(str(getattr(k, "key", getattr(k, "idx", k)))
                     for k in path): leaf
            for path, leaf in jax.tree_util.tree_flatten_with_path(tree)[0]}


def program_grad_norms(opt_dense, recipe: dict) -> dict:
    """Adagrad's accumulator after one step from zero is g * g, of the
    clipped gradient."""
    return {name: float(jnp.sqrt(jnp.sum(acc.astype(F32))))
            for name, acc in leaf_names(opt_dense["acc"]).items()}


def readings(key, sizes: dict, recipe: dict, batches: list, *,
             store, act=F32, rows=None) -> dict:
    """Each step's loss, each leaf's first gradient norm and its change."""
    del act                       # the fixture's control rounds by ``store``
    pooling = np.asarray(sizes["pooling"])
    offsets = np.concatenate([[0], np.cumsum(sizes["rows"][:-1])])
    k_tab, k_bot, k_top = jax.random.split(key, 3)
    d = sizes["embed_dim"]
    params = {"bottom": _mlp_init(k_bot, tuple(sizes["bottom_mlp"]), F32),
              "top": _mlp_init(k_top, top_dims(sizes), F32),
              "table": jax.random.normal(k_tab, (sum(sizes["rows"]), d), F32)
              / math.sqrt(d)}
    params = jax.tree.map(lambda a: a.astype(store).astype(F32), params)
    first = params
    acc = jax.tree.map(jnp.zeros_like, {k: params[k] for k in ("bottom",
                                                               "top")})
    grad = jax.value_and_grad(loss_fn, argnums=(0, 1))
    losses, grad_norms = [], {}
    for i, b in enumerate(batches):
        b = {k: jnp.asarray(v[:rows]) for k, v in b.items()}
        dense = {k: params[k] for k in ("bottom", "top")}
        loss, (g_dense, g_tab) = grad(dense, params["table"], b, offsets,
                                      pooling)
        losses.append(float(loss))
        gnorm = jnp.sqrt(sum(jnp.sum(g * g)
                             for g in jax.tree.leaves(g_dense)))
        scale = jnp.minimum(1.0, recipe["grad_clip"]
                            / jnp.maximum(gnorm, 1e-12))
        g_dense = jax.tree.map(lambda g: g * scale, g_dense)
        acc = jax.tree.map(lambda a, g: a + g * g, acc, g_dense)
        dense = jax.tree.map(
            lambda p, g, a: p - recipe["lr"] * g / (jnp.sqrt(a)
                                                    + recipe["eps"]),
            dense, g_dense, acc)
        params = jax.tree.map(lambda a: a.astype(store).astype(F32),
                              {**dense, "table": params["table"]
                               - recipe["embed_lr"] * g_tab})
        if i == 0:
            grad_norms = {k: float(leaf_norm(g))
                          for k, g in leaf_names(g_dense).items()}
            grad_norms["table"] = float(leaf_norm(g_tab))
    before, after = leaf_names(first), leaf_names(params)
    return {"losses": losses, "grad_norms": grad_norms,
            "change_norms": {k: float(diff_norm(after[k], before[k]))
                             for k in after}}
