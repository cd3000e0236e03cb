"""The relaxed step's row-sparse embedding update against the table path.

The row path carries (sorted ids, row deltas) where the table path builds a
table-shaped f32 gradient; both round ``f32(T) + u`` into the table dtype,
so untouched rows stay bitwise and touched rows agree exactly where a batch
repeats no id, and to f32-sum order where it does."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_arch
from repro.configs.base import TrainConfig
from repro.core import embedding_ops
from repro.core import relaxed as rx
from repro.data.synthetic import make_batches
from repro.distributed import sharding
from repro.kernels import row_merge
from repro.optim import optimizers as opt
from repro.training import train_loop

LR = 0.05


def _tables(T, R, d, dtype, seed=0):
    return (jax.random.normal(jax.random.PRNGKey(seed), (T, R, d))
            / 4).astype(dtype)


def _distinct_ids(B, T, L, R, seed=0):
    """(B, T, L) ids with no id repeated within a table."""
    rng = np.random.default_rng(seed)
    per_table = [rng.permutation(R)[:B * L].reshape(B, L) for _ in range(T)]
    return jnp.asarray(np.stack(per_table, axis=1), jnp.int32)


def _zipf_ids(B, T, L, R, seed=0):
    rng = np.random.default_rng(seed)
    return jnp.asarray((rng.zipf(1.5, (B, T, L)) - 1) % R, jnp.int32)


def _table_path(tables, ids, g_rows):
    """Today's update: table-shaped f32 gradient, SGD, round into the table."""
    cfg = get_arch("dlrm-rm1", smoke=True).model
    embed = {"emb_tables": tables}
    g = rx.scatter_rows_grad(embed, cfg, {"sparse": ids}, g_rows)
    upd, _ = opt.sgd(LR).update(g, (), embed)
    return rx.apply_embed_update(embed, upd)["emb_tables"], upd["emb_tables"]


def _row_path(tables, ids, g_rows, write=rx.write_rows):
    grad = rx.row_grads(tables, ids, g_rows)
    upd, _ = opt.sgd(LR).update({"emb_tables": grad.rows}, (), None)
    delta = grad._replace(rows=upd["emb_tables"])
    return write(tables, delta), delta


def _bits(x):
    x = np.asarray(x)
    return x.view({2: np.uint16, 4: np.uint32}[x.dtype.itemsize])


def _keys(ids, R):
    ids = np.asarray(ids)
    return ids + np.arange(ids.shape[1])[None, :, None] * R


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_row_path_is_bitwise_the_table_path_without_duplicates(dtype):
    T, R, d, B, L = 3, 600, 32, 4, 5
    tables = _tables(T, R, d, dtype)
    ids = _distinct_ids(B, T, L, R)
    g = jax.random.normal(jax.random.PRNGKey(1), (B, T, d))
    want, _ = jax.jit(_table_path)(tables, ids, g)
    got, _ = jax.jit(_row_path)(tables, ids, g)
    assert np.array_equal(_bits(got), _bits(want))
    assert not np.array_equal(_bits(got), _bits(tables))


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_row_path_with_duplicates(dtype):
    """Zipf ids repeat within bags and across them; runs cross the segmented
    sum's blocks. Untouched rows: bitwise; touched: f32-sum order."""
    T, R, d, B, L = 3, 500, 32, 8, 64
    tables = _tables(T, R, d, dtype)
    ids = _zipf_ids(B, T, L, R)
    assert np.bincount(np.asarray(ids)[:, 0].ravel()).max() > rx.SEG_BLOCK
    g = jax.random.normal(jax.random.PRNGKey(2), (B, T, d))
    want, _ = jax.jit(_table_path)(tables, ids, g)
    got, _ = jax.jit(_row_path)(tables, ids, g)
    touched = np.zeros((T, R), bool)
    for t in range(T):
        touched[t, np.asarray(ids)[:, t].ravel()] = True
    assert np.array_equal(_bits(got)[~touched], _bits(tables)[~touched])
    tol = {"float32": 1e-5, "bfloat16": 1e-2}[dtype]
    np.testing.assert_allclose(np.asarray(got, np.float32)[touched],
                               np.asarray(want, np.float32)[touched],
                               rtol=tol, atol=tol)


def test_segmented_sum_is_each_runs_total():
    T, R, d, B, L = 2, 300, 8, 8, 40
    ids = _zipf_ids(B, T, L, R, seed=3)
    g = jax.random.normal(jax.random.PRNGKey(4), (B, T, d))
    delta = jax.jit(rx.row_grads)(jnp.zeros((T, R, d)), ids, g)
    keys, rows, live = map(np.asarray, delta)
    gn = np.asarray(g, np.float64)
    for t in range(T):
        want = np.zeros((R, d))
        np.add.at(want, np.asarray(ids)[:, t].ravel(),
                  np.repeat(gn[:, t], L, axis=0))
        assert np.array_equal(np.sort(keys[t][live[t]]),
                              np.unique(np.asarray(ids)[:, t]))
        np.testing.assert_allclose(rows[t][live[t]], want[keys[t][live[t]]],
                                   rtol=1e-5, atol=1e-5)


def test_correction_from_rows_is_the_gather_of_the_table_shaped_update():
    T, R, d, B, L = 3, 400, 32, 6, 30
    tables = _tables(T, R, d, "float32")
    ids = _zipf_ids(B, T, L, R, seed=5)
    nxt = _zipf_ids(B, T, L, R, seed=6)
    g = jax.random.normal(jax.random.PRNGKey(7), (B, T, d))
    _, delta = jax.jit(_row_path)(tables, ids, g)
    U = np.zeros((T, R, d), np.float32)
    keys, rows, live = map(np.asarray, delta)
    for t in range(T):
        U[t, keys[t][live[t]]] = rows[t][live[t]]
    want = jax.jit(embedding_ops.bag_lookup)(jnp.asarray(U), nxt)
    got = jax.jit(rx.row_correction)(delta, nxt)
    assert np.array_equal(np.asarray(got), np.asarray(want))


def test_slots_that_are_not_live_never_write():
    """Partial sums of a run, the batch's padding (id R) and their rows are
    dropped: only one row per distinct id changes, by the run's total."""
    T, R, d, B, L = 2, 300, 16, 3, 7          # B·L = 21: 107 padded slots
    tables = _tables(T, R, d, "float32")
    ids = _zipf_ids(B, T, L, R, seed=8)
    g = jax.random.normal(jax.random.PRNGKey(9), (B, T, d))
    delta = rx.row_grads(tables, ids, g)
    assert delta.ids.shape[1] % rx.SEG_BLOCK == 0
    assert (np.asarray(delta.ids) == R).sum() == T * (rx.SEG_BLOCK - B * L)
    loud = delta._replace(rows=delta.rows + 1e3 * ~delta.live[..., None])
    for write in (rx._write_rows_xla, rx.write_rows):
        out = np.asarray(jax.jit(write)(tables, loud))
        changed = (out != np.asarray(tables)).any(-1)
        for t in range(T):
            assert set(np.flatnonzero(changed[t])) <= set(
                np.unique(np.asarray(ids)[:, t]))
        assert np.abs(out - np.asarray(tables)).max() < 1e2


@pytest.mark.parametrize("shape", [(2, 700, 16, 3, 5, 128),
                                   (1, 1000, 32, 4, 40, 256)])
def test_merge_kernel_is_bitwise_the_xla_write(shape):
    """The Pallas write in interpret mode: ragged last block, slots that
    span blocks, bf16 tables."""
    T, R, d, B, L, block = shape
    tables = _tables(T, R, d, "bfloat16")
    ids = _zipf_ids(B, T, L, R, seed=10).at[0, :, :2].set(R - 1)
    g = jax.random.normal(jax.random.PRNGKey(11), (B, T, d))
    _, delta = _row_path(tables, ids, g, write=lambda t, u: t)
    want = rx._write_rows_xla(tables, delta)
    got = row_merge.merge_rows(tables, delta.ids, delta.rows, delta.live,
                               interpret=True, block_rows=block)
    assert np.array_equal(_bits(got), _bits(want))
    assert not np.array_equal(_bits(got), _bits(tables))


def test_split3_sums_back_exactly():
    u = jax.random.normal(jax.random.PRNGKey(12), (4096,)) * jnp.logspace(
        -20, 20, 4096)
    p = row_merge.split3(u.astype(jnp.float32)).astype(jnp.float32)
    assert np.array_equal(np.asarray((p[0] + p[1]) + p[2]), np.asarray(u))


def _step(arch, embed_opt, batch=4, seed=0):
    b = get_arch(arch, smoke=True)
    tc = TrainConfig(embed_learning_rate=LR, embed_optimizer=embed_opt)
    init_fn, _, relaxed_step, warmup = train_loop.make_step_fns(b.model, tc)
    data = make_batches(b.model, batch, 16, seed=seed)
    state = warmup(init_fn(jax.random.PRNGKey(seed)), data.next(0))
    return b.model, tc, relaxed_step, state, data


def test_rows_updated_counts_the_distinct_keys():
    cfg, _, relaxed_step, state, data = _step("dlrm-rm1", "sgd")
    _, metrics = jax.jit(relaxed_step)(state, data.next(0), data.next(1))
    keys = _keys(data.next(0)["sparse"], cfg.dlrm_rows_per_table)
    assert int(metrics["rows_updated"]) == np.unique(keys).size


@pytest.mark.parametrize("arch,embed_opt", [
    ("dlrm-rm1", "sgdm"), ("dlrm-rm1", "rowwise_adagrad"),
    ("tinyllama-1.1b", "sgd"), ("rwkv6-3b", "sgd")])
def test_other_cases_keep_the_table_path(arch, embed_opt):
    cfg, tc, relaxed_step, state, data = _step(arch, embed_opt)
    embed_opt_ = opt.make_optimizer(embed_opt, LR)
    assert not rx.row_update_applies(cfg, state["embed"], embed_opt_)
    new, metrics = jax.jit(relaxed_step)(state, data.next(0), data.next(1))
    assert "rows_updated" not in metrics
    assert not np.array_equal(np.asarray(jax.tree.leaves(new["embed"])[0]),
                              np.asarray(jax.tree.leaves(state["embed"])[0]))


def test_a_mesh_keeps_the_table_path():
    cfg, tc, relaxed_step, state, data = _step("dlrm-rm1", "sgd")
    assert rx.row_update_applies(cfg, state["embed"], opt.sgd(LR))
    mesh = jax.sharding.Mesh(np.array(jax.devices()[:1]), ("model",))
    with sharding.use_sharding(mesh):
        assert not rx.row_update_applies(cfg, state["embed"], opt.sgd(LR))
        _, metrics = jax.jit(relaxed_step)(state, data.next(0), data.next(1))
    assert "rows_updated" not in metrics


@pytest.mark.parametrize("embed_opt", ["sgdm", "rowwise_adagrad"])
def test_dlrm_table_path_relaxed_matches_strict(embed_opt):
    """The table path, which these optimizers keep, still gives the strict
    schedule's losses."""
    b = get_arch("dlrm-rm1", smoke=True)
    tc = TrainConfig(embed_learning_rate=LR, embed_optimizer=embed_opt)
    data = make_batches(b.model, 4, 16, seed=0)
    _, s = train_loop.train(b.model, tc, data, 4, relaxed=False)
    _, r = train_loop.train(b.model, tc, data, 4, relaxed=True)
    np.testing.assert_allclose(s, r, rtol=2e-5, atol=2e-5)
