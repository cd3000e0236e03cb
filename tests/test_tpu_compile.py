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

R, D = 1_000_000, 32          # one DLRM-RM1/RM3 table (configs/dlrm_rm1.py)
B, L = 256, 80                # batch (sim/models_rm.py), lookups per table
STEP_BYTES_MAX = 3.8e9        # relaxed RM1/RM3 step: args + outputs + temps
STEP_TEMPS_MAX = 0.5e9        # relaxed RM1/RM3 step: temps alone


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


def _whole_table_loops(hlo: str, n: int) -> list[str]:
    """The ``while`` loops whose carry holds an array of ``n`` elements."""
    return [line for line in hlo.splitlines()
            if re.search(r"= \(.*\) while\(", line)
            and any(np.prod([int(x) for x in s.split(",")]) == n
                    for s in re.findall(r"\[([\d,]+)\]", line))]


def _compile_step(one_chip, arch: str, relaxed: bool):
    """(T, compiled) of ``arch``'s full-width step for one described chip:
    the relaxed step, or the strict one."""
    import jax
    import jax.numpy as jnp

    from repro.configs import get_arch
    from repro.configs.base import TrainConfig
    from repro.training import train_loop
    cfg = get_arch(arch, smoke=False).model
    assert (cfg.dlrm_rows_per_table, cfg.dlrm_bottom_mlp[-1]) == (R, D)
    init_fn, strict_step, relaxed_step, warmup = train_loop.make_step_fns(
        cfg, TrainConfig())
    T = cfg.dlrm_num_tables
    batch = {"dense": jax.ShapeDtypeStruct((B, cfg.dlrm_num_dense),
                                           jnp.float32),
             "sparse": jax.ShapeDtypeStruct((B, T, cfg.dlrm_num_sparse),
                                            jnp.int32),
             "labels": jax.ShapeDtypeStruct((B,), jnp.float32)}
    state = jax.eval_shape(init_fn, jax.random.PRNGKey(0))
    if relaxed:
        state = jax.eval_shape(warmup, state, batch)
    state, batch = _on(one_chip, state), _on(one_chip, batch)
    if relaxed:
        return T, jax.jit(relaxed_step).lower(state, batch, batch).compile()
    return T, jax.jit(strict_step).lower(state, batch).compile()


@pytest.fixture(scope="module", params=["dlrm-rm1", "dlrm-rm3"])
def relaxed_rm1(one_chip, request):
    """The relaxed step of RM1, and of RM3 (the same tables, 20-row bags),
    compiled for one described v5e chip."""
    return _compile_step(one_chip, request.param, relaxed=True)


def test_relaxed_rm1_step_fits_one_v5e(relaxed_rm1):
    _, compiled = relaxed_rm1
    m = compiled.memory_analysis()
    total = (m.argument_size_in_bytes + m.output_size_in_bytes
             + m.temp_size_in_bytes)
    assert total <= STEP_BYTES_MAX, (
        f"relaxed step needs {total / 1e9:.2f} GB (args "
        f"{m.argument_size_in_bytes}, outputs {m.output_size_in_bytes}, "
        f"temps {m.temp_size_in_bytes})")


def test_relaxed_rm1_step_updates_rows_not_tables(relaxed_rm1):
    """No loop runs over a whole table: the sparse update builds nothing of
    the tables' shape in f32, and the prefetch's stale gather indexes the
    stacked tables as stored (``embedding_ops.bag_lookup``), so nothing
    relays them out to a flat ``[T·R, d]`` view."""
    T, compiled = relaxed_rm1
    hlo = compiled.as_text()
    n = T * R * D
    f32_tables = [s for s in re.findall(r"f32\[([\d,]+)\]", hlo)
                  if np.prod([int(x) for x in s.split(",")]) == n]
    assert not f32_tables
    loops = _whole_table_loops(hlo, n)
    assert not loops, loops
    assert "tpu_custom_call" in hlo          # the rows' streaming write
    temps = compiled.memory_analysis().temp_size_in_bytes
    assert temps <= STEP_TEMPS_MAX, f"temps {temps / 1e9:.2f} GB"


def test_strict_rm1_step_has_at_most_two_whole_table_loops(one_chip):
    """The strict step differentiates the bag lookup: its backward
    scatter-adds into a table-shaped f32 gradient, whose two whole-table
    loops remain. The bf16 tables themselves are never relaid out."""
    T, compiled = _compile_step(one_chip, "dlrm-rm1", relaxed=False)
    loops = _whole_table_loops(compiled.as_text(), T * R * D)
    assert len(loops) <= 2, loops


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
