"""Operations and bytes a DLRM training step requires, from its shapes.

The counts of the pairwise-dot DLRM, which a configuration file gets where
it names no ``"counts"`` module of its own. Independent of how the program
computes the step. Counts follow the shape arithmetic of
``repro.sim.models_rm``, extended to the backward pass:

- model FLOPs per sample (forward + backward of the bottom MLP, the bag
  sums, the pairwise interaction and the top MLP; a multiply-add is 2);
- bytes a step cannot avoid moving: its ids and features read once, each
  unique table row read once and written once, the dense parameters and
  both AdamW moments read once and written once.

A table-shaped pass is never required work, so it is never counted.
"""
from __future__ import annotations

import numpy as np

from bench.layout import layout

ADAM_FLOPS_PER_PARAM = 12      # two moments, bias corrections, the step
SGD_FLOPS_PER_ELEM = 2         # scale the gradient, add it


def _pairs(dims):
    return list(zip(dims[:-1], dims[1:], strict=True))


def top_dims(sizes: dict) -> tuple:
    F = layout(sizes).num_tables + 1
    return (sizes["embed_dim"] + F * (F - 1) // 2,) + tuple(sizes["top_mlp"])


def dense_params(sizes: dict) -> int:
    n = 0
    for dims in (tuple(sizes["bottom_mlp"]), top_dims(sizes)):
        n += sum(a * b + b for a, b in _pairs(dims))
    return n


def model_flops_per_sample(sizes: dict) -> float:
    """Forward + backward FLOPs of one sample."""
    lay, d = layout(sizes), sizes["embed_dim"]
    T = lay.num_tables
    F = T + 1
    fwd = bwd = 0.0
    for i, (a, b) in enumerate(_pairs(tuple(sizes["bottom_mlp"]))):
        fwd += 2 * a * b
        # weight gradient always; input gradient except into the features
        bwd += 2 * a * b * (2 if i > 0 else 1)
    for a, b in _pairs(top_dims(sizes)):
        fwd += 2 * a * b
        bwd += 4 * a * b
    pairs = F * (F - 1) // 2
    fwd += 2 * pairs * d
    bwd += 4 * pairs * d            # both operands of every dot
    fwd += (lay.lookups - T) * d    # bag sums
    bwd += lay.lookups * d          # each looked-up row gets its bag's grad
    return fwd + bwd


def step_work(sizes: dict, unique_rows: int) -> tuple[float, float]:
    """(FLOPs, bytes) one step requires."""
    B, d = sizes["batch"], sizes["embed_dim"]
    P = dense_params(sizes)
    width = np.dtype(sizes["dtype"]).itemsize
    flops = (B * model_flops_per_sample(sizes) + ADAM_FLOPS_PER_PARAM * P
             + SGD_FLOPS_PER_ELEM * unique_rows * d)
    nbytes = (4 * B * layout(sizes).lookups + 4 * B * sizes["num_dense"]
              + 4 * B
              + 2 * unique_rows * d * width
              + 2 * P * width + 2 * 2 * P * 4)
    return flops, nbytes


def step_min_seconds(sizes: dict, unique_rows: int, peaks: dict):
    """(least seconds, bound) for one step at the chip's peaks."""
    flops, nbytes = step_work(sizes, unique_rows)
    t_flops = flops / peaks["bf16_flops_per_s"]
    t_bytes = nbytes / peaks["hbm_bytes_per_s"]
    return (t_bytes, "memory") if t_bytes >= t_flops else (t_flops, "compute")
