"""The traffic generator: a faithful copy of the program's Zipf draw, and
the same batches for the same seed."""
import numpy as np
import pytest

from bench import feed


def test_sampler_draws_what_the_programs_sampler_draws():
    from repro.data.synthetic import zipf_indices
    s = feed.ZipfSampler(5000, 1.05)
    got = s.ids(np.random.default_rng(3).random(size=(4, 7, 9)))
    want = zipf_indices(np.random.default_rng(3), (4, 7, 9), 5000, 1.05)
    np.testing.assert_array_equal(got, want)
    assert got.dtype == np.int32 and got.min() >= 0 and got.max() < 5000


SIZES = {"rows_per_table": 1000, "batch": 8, "num_tables": 3,
         "lookups_per_table": 5, "num_dense": 13}
TRAFFIC = {"zipf_alpha": 1.05, "ring": 4}


@pytest.mark.parametrize("seed", [0, 7, 2**31 + 11, 2**40 + 1])
def test_ring_is_a_function_of_the_seed(seed):
    a = feed.make_ring(SIZES, TRAFFIC, seed)
    b = feed.make_ring(SIZES, TRAFFIC, seed)
    assert len(a) == TRAFFIC["ring"]
    for x, y in zip(a, b, strict=True):
        for k in x:
            np.testing.assert_array_equal(x[k], y[k])
    c = feed.make_ring(SIZES, TRAFFIC, seed + 1)
    assert not np.array_equal(a[0]["sparse"], c[0]["sparse"])
    # every batch of the ring differs from the others
    assert len({r["sparse"].tobytes() for r in a}) == len(a)
    assert a[0]["sparse"].shape == (8, 3, 5)
    assert a[0]["dense"].shape == (8, 13) and a[0]["labels"].shape == (8,)


def test_unique_rows_counts_table_row_pairs():
    batch = {"sparse": np.array([[[1, 1, 2], [1, 3, 3]]], np.int32)}
    sizes = {"num_tables": 2, "rows_per_table": 10, "lookups_per_table": 3}
    assert feed.unique_rows(sizes, batch) == 4


def test_feed_hands_device_arrays_and_cycles():
    import jax
    ring = feed.make_ring(SIZES, TRAFFIC, 5)
    f = feed.Feed(ring)
    b = f.next(5)
    assert isinstance(b["sparse"], jax.Array)
    np.testing.assert_array_equal(np.asarray(b["sparse"]), ring[1]["sparse"])
