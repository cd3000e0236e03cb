"""The embedding tables and the pooling a configuration file describes.

A file's ``sizes`` give the tables in one of two ways. Scalars:
``num_tables`` tables of ``rows_per_table`` rows each, with
``lookups_per_table`` ids per table and sample; a batch's ``sparse`` is
``int32 (B, T, L)``. Or per-table lists, both of them: ``rows`` and
``pooling`` (length T each); a batch's ``sparse`` is then
``int32 (B, sum(pooling))``, the columns grouped by table in table order.
Either way the ids are local to their table, the columns of a batch
flattened to ``(B, -1)`` are grouped by table in table order, and the
program holds its tables in ``state["embed"]["emb_tables"]``, which,
flattened to ``(-1, d)``, holds table t's rows at
``[offsets[t], offsets[t] + rows[t])``.
"""
from __future__ import annotations

import dataclasses

import numpy as np

SCALARS = ("num_tables", "rows_per_table", "lookups_per_table")
LISTS = ("rows", "pooling")


@dataclasses.dataclass(frozen=True)
class Layout:
    rows: tuple[int, ...]          # rows of each table
    pooling: tuple[int, ...]       # ids of each table per sample
    pooled: bool                   # sparse is (B, sum(pooling)), not (B, T, L)

    @property
    def num_tables(self) -> int:
        return len(self.rows)

    @property
    def offsets(self) -> np.ndarray:
        """Each table's first row in the flat ``(sum(rows), d)`` view."""
        return np.concatenate([[0], np.cumsum(self.rows[:-1])]).astype(
            np.int64)

    @property
    def total_rows(self) -> int:
        return int(sum(self.rows))

    @property
    def lookups(self) -> int:
        """Ids per sample, over all tables."""
        return int(sum(self.pooling))

    def batch_shape(self, batch: int) -> tuple[int, ...]:
        """The shape of a batch's ``sparse``."""
        if self.pooled:
            return (batch, self.lookups)
        return (batch, self.num_tables, self.pooling[0])

    def flat_ids(self, sparse: np.ndarray) -> np.ndarray:
        """A batch's ids as rows of the flat table, in the batch's shape."""
        cols = sparse.reshape(len(sparse), -1)
        return (cols + np.repeat(self.offsets, self.pooling)[None, :]
                ).reshape(sparse.shape)


def layout(sizes: dict) -> Layout:
    """The tables of ``sizes``; raises ValueError where they give both
    lists and scalars, one list without the other, or lists of different
    lengths."""
    lists = [k for k in LISTS if k in sizes]
    if lists:
        scalars = [k for k in SCALARS if k in sizes]
        if lists != list(LISTS) or scalars:
            raise ValueError(f"sizes give {lists + scalars}: give both of "
                             f"{list(LISTS)} or all of {list(SCALARS)}")
        rows = tuple(int(r) for r in sizes["rows"])
        pooling = tuple(int(p) for p in sizes["pooling"])
    else:
        T = sizes["num_tables"]
        rows = (int(sizes["rows_per_table"]),) * T
        pooling = (int(sizes["lookups_per_table"]),) * T
    if not rows or len(rows) != len(pooling):
        raise ValueError(f"sizes give {len(rows)} row counts and "
                         f"{len(pooling)} poolings")
    if min(rows) < 1 or min(pooling) < 1:
        raise ValueError(f"every table needs rows and lookups: rows {rows}, "
                         f"pooling {pooling}")
    return Layout(rows, pooling, pooled=bool(lists))
