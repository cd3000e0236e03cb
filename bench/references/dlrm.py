"""Plain DLRM training reference in float32 ``jax.numpy``.

Written from the model's description (Naumov et al., arXiv:1906.00091) and
the training recipe the configuration states, with no code of the system
under test:

    bottom MLP (ReLU after every layer) on the dense features -> z0
    sum of L embedding rows per table and sample             -> bags
    pairwise dots of [z0, bags] above the diagonal, concat z0 -> top MLP
    logit -> mean binary cross-entropy

One step: gradients of the loss; dense gradients clipped to global norm
``clip``; AdamW (no weight decay) on the dense tier; plain SGD on the table
rows. Parameters are stored in ``store`` (the configuration's dtype) and
rounded to it after every update, as the configuration states; everything
else is computed in float32 at the highest matmul precision.

``act`` rounds every activation to a dtype, as a program that computes in
that dtype would: float32 (no rounding) for the reference, a lower one for
the control. The rounding saturates at the dtype's largest value (fp8 has
no infinity) and passes gradients straight through in float32.

Initial weights are drawn from the key by the recipe the configuration
states: tables N(0, 1/d), weights U(-1/sqrt(n_in), 1/sqrt(n_in)) in the
stored dtype, zero biases.
"""
from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp
from jax import lax

F32 = jnp.float32
HI = lax.Precision.HIGHEST

# The recipes implemented here, by their (dense, embedding) optimizers: the
# TrainConfig field each recipe key sets (None: the program's AdamW holds
# eps constant) and the fields held to the one value implemented here.
RECIPES = {("adamw", "sgd"): {
    "keys": {"lr": "learning_rate", "beta1": "beta1", "beta2": "beta2",
             "eps": None, "grad_clip": "grad_clip",
             "embed_lr": "embed_learning_rate"},
    "fixed": {"weight_decay": 0.0}}}


def top_dims(sizes: dict) -> tuple:
    F = sizes["num_tables"] + 1
    return (sizes["embed_dim"] + F * (F - 1) // 2,) + tuple(sizes["top_mlp"])


def _mlp_init(key, dims, dtype):
    keys = jax.random.split(key, len(dims) - 1)
    out = []
    for k, n_in, n_out in zip(keys, dims[:-1], dims[1:], strict=True):
        s = math.sqrt(1.0 / n_in)
        out.append({"w": jax.random.uniform(k, (n_in, n_out), dtype, -s, s),
                    "b": jnp.zeros((n_out,), dtype)})
    return out


def init(key, sizes: dict, store) -> dict:
    """{"bottom": [...], "top": [...], "table": (T, R, d)} in ``store``."""
    k_tab, k_bot, k_top = jax.random.split(key, 3)
    T, R, d = (sizes["num_tables"], sizes["rows_per_table"],
               sizes["embed_dim"])
    table = jax.random.normal(k_tab, (T, R, d), F32) / math.sqrt(d)
    return {"bottom": _mlp_init(k_bot, tuple(sizes["bottom_mlp"]), store),
            "top": _mlp_init(k_top, top_dims(sizes), store),
            "table": table.astype(store)}


def _round(x, dtype):
    if jnp.dtype(dtype) == F32:
        return x
    big = float(jnp.finfo(dtype).max)
    r = jnp.clip(x, -big, big).astype(dtype).astype(F32)
    return x + lax.stop_gradient(r - x)


def _mlp(layers, x, act, final_relu):
    for i, p in enumerate(layers):
        x = _round(jnp.dot(x, p["w"], precision=HI), act)
        x = _round(x + p["b"], act)
        if final_relu or i < len(layers) - 1:
            x = jnp.maximum(x, 0.0)
    return x


def loss_fn(dense, table, batch, act):
    """Mean BCE of one batch; ``dense`` and ``table`` in float32."""
    x = _round(batch["dense"].astype(F32), act)
    z0 = _mlp(dense["bottom"], x, act, True)                  # (B, d)
    T, R, d = table.shape
    ids = batch["sparse"] + (jnp.arange(T)[None, :, None] * R)
    rows = jnp.take(table.reshape(T * R, d), ids.reshape(-1), axis=0)
    bags = _round(rows.reshape(*ids.shape, d).sum(axis=2), act)  # (B, T, d)
    feats = jnp.concatenate([z0[:, None, :], bags], axis=1)
    dots = _round(jnp.einsum("bnd,bmd->bnm", feats, feats, precision=HI),
                  act)
    iu, ju = jnp.triu_indices(feats.shape[1], k=1)
    top_in = jnp.concatenate([z0, dots[:, iu, ju]], axis=-1)
    logit = _mlp(dense["top"], top_in, act, False)[:, 0]
    y = batch["labels"].astype(F32)
    return jnp.mean(jnp.maximum(logit, 0) - logit * y
                    + jnp.log1p(jnp.exp(-jnp.abs(logit))))


@partial(jax.jit, static_argnames=("store", "act", "hp"))
def step(params, adam, batch, *, store, act, hp):
    """One training step. ``hp``: (lr, embed_lr, b1, b2, eps, clip).

    Returns (params, adam, loss, dense_grads_as_clipped, table_grad_norm).
    """
    lr, elr, b1, b2, eps, clip = hp
    dense = {k: jax.tree.map(lambda a: a.astype(F32), params[k])
             for k in ("bottom", "top")}
    table = params["table"].astype(F32)
    loss, (g_dense, g_table) = jax.value_and_grad(loss_fn, argnums=(0, 1))(
        dense, table, batch, act)
    gnorm = jnp.sqrt(sum(jnp.sum(jnp.square(g))
                         for g in jax.tree.leaves(g_dense)))
    scale = jnp.minimum(1.0, clip / jnp.maximum(gnorm, 1e-12))
    g_dense = jax.tree.map(lambda g: g * scale, g_dense)
    t = adam["t"] + 1
    m = jax.tree.map(lambda m, g: b1 * m + (1 - b1) * g, adam["m"], g_dense)
    v = jax.tree.map(lambda v, g: b2 * v + (1 - b2) * g * g, adam["v"],
                     g_dense)
    bc1 = 1 - b1 ** t.astype(F32)
    bc2 = 1 - b2 ** t.astype(F32)
    new_dense = jax.tree.map(
        lambda p, m, v: (p + (-lr * (m / bc1) / (jnp.sqrt(v / bc2) + eps)))
        .astype(store), dense, m, v)
    new_table = (table - elr * g_table).astype(store)
    out = {**new_dense, "table": new_table}
    return (out, {"m": m, "v": v, "t": t}, loss, g_dense,
            jnp.sqrt(jnp.sum(jnp.square(g_table))))


@jax.jit
def leaf_norm(a):
    return jnp.sqrt(jnp.sum(jnp.square(a.astype(F32))))


@jax.jit
def diff_norm(a, b):
    return jnp.sqrt(jnp.sum(jnp.square(a.astype(F32) - b.astype(F32))))


def leaf_names(tree) -> dict:
    """{"bottom.0.w": leaf, ...} for a {"bottom", "top", ("table")} tree."""
    out = {}
    for path, leaf in jax.tree_util.tree_flatten_with_path(tree)[0]:
        out[".".join(str(getattr(k, "key", getattr(k, "idx", k)))
                     for k in path)] = leaf
    return out


def program_grad_norms(opt_dense, recipe: dict) -> dict:
    """Each dense leaf's first gradient, after clipping, as the program's
    AdamW state holds it after one step (m / (1 - beta1)): its norm by leaf
    name, as ``readings`` names the reference's."""
    return {name: float(leaf_norm(m)) / (1.0 - recipe["beta1"])
            for name, m in leaf_names(opt_dense["m"]).items()}


def readings(key, sizes: dict, recipe: dict, batches: list, *,
             store, act=F32, rows=None) -> dict:
    """Train ``len(batches)`` steps from the key's initial weights.

    Returns each step's loss, each leaf's gradient norm at step 1 (dense:
    after clipping, as the optimizer takes it; the table: its raw gradient)
    and each leaf's change after the last step. ``rows`` (an int, optional)
    keeps only the first rows of every batch: a planted fault.
    """
    hp = (recipe["lr"], recipe["embed_lr"], recipe["beta1"], recipe["beta2"],
          recipe["eps"], recipe["grad_clip"])
    params = init(key, sizes, jnp.dtype(sizes["dtype"]))
    params = jax.tree.map(lambda a: a.astype(store), params)
    dense0 = {k: params[k] for k in ("bottom", "top")}
    first = params
    zeros = jax.tree.map(lambda a: jnp.zeros(a.shape, F32), dense0)
    adam = {"m": zeros, "v": zeros, "t": jnp.zeros((), jnp.int32)}
    losses, grad_norms = [], {}
    for i, b in enumerate(batches):
        if rows is not None:
            b = {k: v[:rows] for k, v in b.items()}
        b = {k: jnp.asarray(v) for k, v in b.items()}
        params, adam, loss, g_dense, g_tab = step(
            params, adam, b, store=jnp.dtype(store), act=jnp.dtype(act),
            hp=hp)
        losses.append(float(loss))
        if i == 0:
            grad_norms = {k: float(leaf_norm(g))
                          for k, g in leaf_names(g_dense).items()}
            grad_norms["table"] = float(g_tab)
    before, after = leaf_names(first), leaf_names(params)
    change = {k: float(diff_norm(after[k], before[k])) for k in after}
    return {"losses": losses, "grad_norms": grad_norms,
            "change_norms": change}
