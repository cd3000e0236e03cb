"""DLRM — the paper's own model (Meta AI, arXiv:1906.00091), RM1–RM4 configs.

bottom-MLP(dense features) -> z0
bag_lookup(sparse features) -> z1..zT   (the disaggregated-pool operation)
feature interaction (pairwise dots) + concat -> top-MLP -> CTR logit.

The embedding bags run through ``core.embedding_ops.bag_lookup`` — the
near-data gather+reduce that is the heart of TrainingCXL.

``forward`` names its phases with ``jax.named_scope`` (``bottom_mlp``,
``interaction``, ``top_mlp``); the backward pass inherits them, so a device
trace can split the step program's time by phase.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from repro.core import embedding_ops
from repro.distributed.sharding import constrain
from repro.models import layers


def _init_mlp_stack(key, dims, dtype):
    ks = jax.random.split(key, len(dims) - 1)
    return [{"w": layers.dense_init(ks[i], dims[i], dims[i + 1], dtype),
             "b": jnp.zeros((dims[i + 1],), dtype)}
            for i in range(len(dims) - 1)]


def _mlp_stack(ps, x, final_act=True):
    for i, p in enumerate(ps):
        x = x @ p["w"] + p["b"]
        if i < len(ps) - 1 or final_act:
            x = jax.nn.relu(x)
    return x


def init_dlrm(key, cfg):
    ks = jax.random.split(key, 3)
    dt = cfg.activation_dtype
    d_emb = cfg.dlrm_bottom_mlp[-1]
    T, R = cfg.dlrm_num_tables, cfg.dlrm_rows_per_table
    tables = (jax.random.normal(ks[0], (T, R, d_emb), jnp.float32)
              / math.sqrt(d_emb)).astype(dt)
    n_feat = T + 1
    n_inter = n_feat * (n_feat - 1) // 2
    top_in = d_emb + n_inter
    top_dims = (top_in,) + tuple(cfg.dlrm_top_mlp)
    return {
        "embed": {"emb_tables": tables},
        "bottom": _init_mlp_stack(ks[1], cfg.dlrm_bottom_mlp, dt),
        "top": _init_mlp_stack(ks[2], top_dims, dt),
    }


def forward(params, cfg, batch):
    """batch: dense (B, n_dense) float; sparse (B, T, L) int32 -> logits (B,)."""
    dense = batch["dense"].astype(cfg.activation_dtype)
    with jax.named_scope("bottom_mlp"):
        z0 = _mlp_stack(params["bottom"], dense)              # (B, d_emb)
    if batch.get("embed_rows") is not None:
        # relaxed lookup: reduced bag vectors prefetched at batch N-1
        bags = batch["embed_rows"]
    else:
        bags = embedding_ops.bag_lookup(params["embed"]["emb_tables"],
                                        batch["sparse"])      # (B, T, d_emb)
    bags = constrain(bags, ("batch", None, "embed"))
    with jax.named_scope("interaction"):
        feats = jnp.concatenate([z0[:, None, :], bags.astype(z0.dtype)],
                                axis=1)
        inter = jnp.einsum("bnd,bmd->bnm", feats, feats)      # (B, F, F)
        iu = jnp.triu_indices(feats.shape[1], k=1)
        inter = inter[:, iu[0], iu[1]]                        # (B, F(F-1)/2)
        x = jnp.concatenate([z0, inter.astype(z0.dtype)], axis=-1)
    with jax.named_scope("top_mlp"):
        logit = _mlp_stack(params["top"], x, final_act=False)[:, 0]
    return logit


def bce_loss(params, cfg, batch):
    logit = forward(params, cfg, batch).astype(jnp.float32)
    y = batch["labels"].astype(jnp.float32)
    return jnp.mean(jnp.maximum(logit, 0) - logit * y
                    + jnp.log1p(jnp.exp(-jnp.abs(logit))))


lm_loss = bce_loss  # registry-uniform name
