"""The checkpointing cells' pmem pool keeps its image in memory: the
program's ``PmemPool`` opens it by its usual path, persists to it, and a
reopen after close reads back what was persisted, with no file on disk."""
import os

import numpy as np

from bench import harness


def test_pmem_image_in_memory_survives_close_and_reopen(tmp_path):
    from repro.pool.device import PmemPool, make_pool
    fd = harness._memory_image(tmp_path)
    try:
        path = str(tmp_path / "pool.img")
        pool = make_pool("pmem", path=path, capacity=1 << 16, check=False)
        data = np.arange(256, dtype=np.uint8)
        pool.write(4096, data)
        pool.persist()
        pool.write(8192, data)          # never persisted
        pool.close()
        again = PmemPool.open(path)
        np.testing.assert_array_equal(again.read(4096, 256), data)
        assert not again.read(8192, 256).any()
        again.close()
        assert os.path.islink(path)
        assert [p.name for p in tmp_path.iterdir()] == ["pool.img"]
        assert os.fstat(fd).st_size == 1 << 16
    finally:
        os.close(fd)
