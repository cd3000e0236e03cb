"""Traffic: seeded synthetic DLRM batches and their hand-off to the trainer.

The sampler copies the program's Criteo-like Zipf draw
(``repro.data.synthetic.zipf_indices``: inverse CDF of a truncated Zipf,
rank -> row scrambled by a multiplicative hash) with the CDF built once per
table size instead of on every call. A run builds a ring of distinct batches
from ``--seed`` in set-up; the window cycles through it. Each batch is kept
as host arrays and put on the device when the trainer asks for it, as the
program's ``DLRMBatches.next`` does.
"""
from __future__ import annotations

import numpy as np

from bench.layout import layout


class ZipfSampler:
    """Zipf(alpha) row ids in [0, num_rows), hot rows spread by a hash."""

    def __init__(self, num_rows: int, alpha: float):
        ranks = np.arange(1, num_rows + 1, dtype=np.float64)
        probs = 1.0 / np.power(ranks, alpha)
        probs /= probs.sum()
        self.cdf = np.cumsum(probs)
        self.num_rows = num_rows

    def ids(self, u: np.ndarray) -> np.ndarray:
        """The row ids of uniforms ``u`` in [0, 1), elementwise."""
        idx = np.searchsorted(self.cdf, u)
        n = self.num_rows
        perm_seed = np.uint64(n * 2654435761 % (2**31))
        rows = (idx.astype(np.uint64) * np.uint64(2654435761)
                + perm_seed) % np.uint64(n)
        return rows.astype(np.int32)


def make_ring(sizes: dict, traffic: dict, seed: int) -> list[dict]:
    """``traffic["ring"]`` distinct host batches, the same for the same seed:
    dense features N(0, 1), labels Bernoulli(1/2), sparse ids Zipf in the
    layout of ``bench/layout.py``. A batch's ids come from one uniform draw
    over its ``sparse`` shape; each table's columns are mapped to ids by a
    sampler of that table's rows."""
    rng = np.random.default_rng(seed)
    lay = layout(sizes)
    B = sizes["batch"]
    samplers = {R: ZipfSampler(R, traffic["zipf_alpha"])
                for R in set(lay.rows)}
    ends = np.cumsum(lay.pooling)

    def sparse():
        u = rng.random(size=lay.batch_shape(B)).reshape(B, -1)
        return np.concatenate(
            [samplers[R].ids(u[:, e - L:e])
             for R, L, e in zip(lay.rows, lay.pooling, ends, strict=True)],
            axis=1).reshape(lay.batch_shape(B))

    ring = []
    for _ in range(traffic["ring"]):
        dense = rng.standard_normal((B, sizes["num_dense"])).astype(np.float32)
        labels = (rng.random(B) < 0.5).astype(np.float32)
        ring.append({"dense": dense, "sparse": sparse(), "labels": labels})
    return ring


def unique_rows(sizes: dict, batch: dict) -> int:
    """Distinct (table, row) pairs one batch touches."""
    return int(np.unique(layout(sizes).flat_ids(batch["sparse"])).size)


class Feed:
    """``next(step)`` hands batch ``step mod ring`` to the trainer as device
    arrays, transferred at the call, inside a host span of its own."""

    def __init__(self, ring: list[dict]):
        self.ring = ring

    def batch_for(self, step: int) -> dict:
        return self.ring[step % len(self.ring)]

    def next(self, step: int) -> dict:
        import jax
        import jax.numpy as jnp
        with jax.profiler.TraceAnnotation("bench.batch_handoff"):
            return {k: jnp.asarray(v) for k, v in self.batch_for(step).items()}
