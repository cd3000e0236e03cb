"""FLOP and byte counts against counts made by hand."""
import pytest

from bench import counts

# 2 tables, 3 lookups, d = 4; bottom 5-6-4; top (4 + 3) -> 2 -> 1
TINY = {"num_tables": 2, "lookups_per_table": 3, "embed_dim": 4,
        "bottom_mlp": [5, 6, 4], "top_mlp": [2, 1], "batch": 10,
        "num_dense": 5, "rows_per_table": 100, "dtype": "bfloat16"}


def test_dense_parameters():
    # bottom 5*6+6 + 6*4+4, top 7*2+2 + 2*1+1
    assert counts.dense_params(TINY) == 36 + 28 + 16 + 3


def test_model_flops_per_sample():
    bottom_fwd = 2 * (5 * 6 + 6 * 4)
    bottom_bwd = 2 * 5 * 6 + 4 * 6 * 4     # no gradient into the features
    top_fwd = 2 * (7 * 2 + 2 * 1)
    top_bwd = 2 * top_fwd
    pairs = 3                               # F = 3 features
    inter = 2 * pairs * 4 + 4 * pairs * 4
    bags = 2 * 2 * 4 + 2 * 3 * 4            # (L - 1) adds fwd, L bwd
    want = bottom_fwd + bottom_bwd + top_fwd + top_bwd + inter + bags
    assert counts.model_flops_per_sample(TINY) == want


def test_step_work_and_its_bound():
    P = counts.dense_params(TINY)
    flops, nbytes = counts.step_work(TINY, unique_rows=7)
    assert flops == (10 * counts.model_flops_per_sample(TINY) + 12 * P
                     + 2 * 7 * 4)
    ids_feats_labels = 4 * 10 * 2 * 3 + 4 * 10 * 5 + 4 * 10
    rows = 2 * 7 * 4 * 2                    # bf16 rows read and written
    dense = 2 * P * 2 + 4 * P * 4           # params r/w, two f32 moments r/w
    assert nbytes == ids_feats_labels + rows + dense
    t, bound = counts.step_min_seconds(
        TINY, 7, {"bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e9})
    assert bound == "memory" and t == pytest.approx(nbytes / 1e9)
    t, bound = counts.step_min_seconds(
        TINY, 7, {"bf16_flops_per_s": 1.0, "hbm_bytes_per_s": 1e12})
    assert bound == "compute" and t == flops


def test_rm1_counts_no_table_shaped_pass():
    """At RM1 width the required bytes stay far under one pass over the
    tables (20 x 1M x 32 bf16 = 1.28 GB)."""
    rm1 = {"num_tables": 20, "lookups_per_table": 80, "embed_dim": 32,
           "bottom_mlp": [13, 8192, 2048, 32], "top_mlp": [64, 1],
           "batch": 256, "num_dense": 13, "rows_per_table": 1_000_000,
           "dtype": "bfloat16"}
    assert 16.9e6 < counts.dense_params(rm1) < 17.1e6
    _, nbytes = counts.step_work(rm1, unique_rows=165_000)
    assert nbytes < 0.5e9
