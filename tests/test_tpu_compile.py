"""Compile-only checks for one TPU v5e chip, at real widths.

The TPU compiler is installed without a chip: it compiles for a described
``v5e:2x2`` topology and refuses what the chip would refuse (misaligned
blocks, SMEM or HBM overflow). Nothing runs, so these say nothing about
results or times. The topology is described inside a fixture, never at
import: only one process at a time may load the TPU library.
"""
import os
import re

import numpy as np
import pytest

R, D = 1_000_000, 32          # one DLRM-RM1 table (configs/dlrm_rm1.py)
B, L = 256, 80                # batch (sim/models_rm.py), lookups per table
STEP_BYTES_MAX = 5e9          # relaxed RM1 step: args + outputs + temps
STEP_TEMPS_MAX = 3.5e9        # relaxed RM1 step: temps alone


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip cannot be read back without one
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _on(sharding, tree):
    import jax
    return jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sharding),
        tree)


@pytest.fixture(scope="module")
def relaxed_rm1(one_chip):
    """The relaxed RM1 step compiled for one described v5e chip."""
    import jax
    import jax.numpy as jnp

    from repro.configs import get_arch
    from repro.configs.base import TrainConfig
    from repro.training import train_loop
    cfg = get_arch("dlrm-rm1", smoke=False).model
    assert (cfg.dlrm_rows_per_table, cfg.dlrm_bottom_mlp[-1],
            cfg.dlrm_num_sparse) == (R, D, L)
    init_fn, _, relaxed_step, warmup = train_loop.make_step_fns(
        cfg, TrainConfig())
    T = cfg.dlrm_num_tables
    batch = {"dense": jax.ShapeDtypeStruct((B, cfg.dlrm_num_dense),
                                           jnp.float32),
             "sparse": jax.ShapeDtypeStruct((B, T, L), jnp.int32),
             "labels": jax.ShapeDtypeStruct((B,), jnp.float32)}
    state = jax.eval_shape(warmup, jax.eval_shape(
        init_fn, jax.random.PRNGKey(0)), batch)
    batch = _on(one_chip, batch)
    return T, jax.jit(relaxed_step).lower(
        _on(one_chip, state), batch, batch).compile()


def test_relaxed_rm1_step_fits_one_v5e(relaxed_rm1):
    _, compiled = relaxed_rm1
    m = compiled.memory_analysis()
    total = (m.argument_size_in_bytes + m.output_size_in_bytes
             + m.temp_size_in_bytes)
    assert total <= STEP_BYTES_MAX, (
        f"relaxed RM1 step needs {total / 1e9:.2f} GB (args "
        f"{m.argument_size_in_bytes}, outputs {m.output_size_in_bytes}, "
        f"temps {m.temp_size_in_bytes})")


def test_relaxed_rm1_step_updates_rows_not_tables(relaxed_rm1):
    """The sparse update builds nothing of the tables' shape in f32, and
    the only loops over a whole table are the two that relay it out for the
    prefetch's stale gather (``embedding_ops.bag_lookup``'s flat view)."""
    T, compiled = relaxed_rm1
    hlo = compiled.as_text()
    n = T * R * D
    f32_tables = [s for s in re.findall(r"f32\[([\d,]+)\]", hlo)
                  if np.prod([int(x) for x in s.split(",")]) == n]
    assert not f32_tables
    loops = [line for line in hlo.splitlines()
             if re.search(r"= \(.*\) while\(", line)
             and any(np.prod([int(x) for x in s.split(",")]) == n
                     for s in re.findall(r"\[([\d,]+)\]", line))]
    assert len(loops) <= 2, loops
    assert "tpu_custom_call" in hlo          # the rows' streaming write
    temps = compiled.memory_analysis().temp_size_in_bytes
    assert temps <= STEP_TEMPS_MAX, f"temps {temps / 1e9:.2f} GB"


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("kernel", ["embedding_bag", "gather_rows",
                                    "scatter_update",
                                    "scatter_update_logged"])
def test_dlrm_kernel_compiles_at_rm1_table_width(one_chip, kernel, dtype):
    """One table per call through ``kernels.ops`` (lane padding, SMEM
    bound), as a caller on the chip would make it."""
    import jax

    from repro.kernels import ops
    n = B * L
    table = jax.ShapeDtypeStruct((R, D), np.dtype(dtype), sharding=one_chip)
    ids = jax.ShapeDtypeStruct((n,), np.int32, sharding=one_chip)
    delta = jax.ShapeDtypeStruct((n, D), np.dtype(dtype), sharding=one_chip)
    fn, args = {
        "embedding_bag": (lambda t, i, s: ops.embedding_bag(t, i, s, B),
                          (table, ids, ids)),
        "gather_rows": (ops.gather_rows, (table, ids)),
        "scatter_update": (ops.scatter_update, (table, ids, delta)),
        "scatter_update_logged": (ops.scatter_update_logged,
                                  (table, ids, delta)),
    }[kernel]
    ops.set_backend("pallas")
    try:
        compiled = jax.jit(fn).lower(*args).compile()
    finally:
        ops.set_backend("xla")
    assert "tpu_custom_call" in compiled.as_text()


def test_embedding_bag_compiles_at_the_smem_bound(one_chip):
    """The most ids ``ops`` lets through (two prefetched operands) still fit
    the compiler's SMEM budget, so the wrapper's bound is not too loose."""
    import jax

    from repro.kernels import ops
    n = (ops.SMEM_BYTES - ops.SMEM_RESERVE) // 8
    table = jax.ShapeDtypeStruct((R, D), np.float32, sharding=one_chip)
    ids = jax.ShapeDtypeStruct((n,), np.int32, sharding=one_chip)
    ops.set_backend("pallas")
    try:
        jax.jit(lambda t, i, s: ops.embedding_bag(t, i, s, B)).lower(
            table, ids, ids).compile()
    finally:
        ops.set_backend("xla")
