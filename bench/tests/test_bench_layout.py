"""The configuration-file contract on a fixture of four tables with
unequal rows and pooling (``data/flat4.json``): the layout helper, the
ring, the reference and counts the file names, and the program check."""
import json
import math
import types

import jax.numpy as jnp
import numpy as np
import pytest

from bench import control, feed, harness
from bench.layout import layout
from bench.tests.helpers import ROOT, benchmark

FIXTURE = "bench/tests/data/flat4.json"
ROWS, POOLING = [2048, 3, 500, 1000], [3, 1, 10, 2]


def fixture_bench(file: str = FIXTURE) -> dict:
    """BENCHMARK.json with the fixture's configuration, read from ``file``,
    and one cell of it."""
    b = benchmark()
    return {**b,
            "configs": b["configs"] + [{"name": "flat4", "file": file}],
            "workloads": b["workloads"] + [
                {"name": "flat4.nockpt", "config": "flat4",
                 "traffic": "nockpt", "chips": 1}]}


def fixture_spec():
    return harness.load_spec("flat4.nockpt", fixture_bench())


def stub_program(monkeypatch, **change):
    """``get_arch`` hands back a model config with the fixture's fields."""
    import repro.configs
    model = types.SimpleNamespace(
        table_rows=tuple(ROWS), table_pooling=tuple(POOLING), embed_dim=8,
        bottom_mlp=(13, 16, 8), top_mlp=(16, 1), dtype="bfloat16")
    for k, v in change.items():
        setattr(model, k, v)
    monkeypatch.setattr(repro.configs, "get_arch",
                        lambda arch, smoke=False: types.SimpleNamespace(
                            model=model))


def test_layout_reads_lists_and_scalars():
    lay = layout(fixture_spec().config["sizes"])
    assert lay.rows == tuple(ROWS) and lay.pooling == tuple(POOLING)
    assert lay.pooled and lay.num_tables == 4 and lay.lookups == 16
    assert lay.offsets.tolist() == [0, 2048, 2051, 2551]
    assert lay.total_rows == 3551 and lay.batch_shape(32) == (32, 16)
    rm1 = layout({"num_tables": 20, "rows_per_table": 1000,
                  "lookups_per_table": 80})
    assert rm1.rows == (1000,) * 20 and rm1.pooling == (80,) * 20
    assert not rm1.pooled and rm1.offsets[-1] == 19_000
    assert rm1.batch_shape(256) == (256, 20, 80)


@pytest.mark.parametrize("sizes", [
    {"rows": ROWS, "pooling": POOLING, "rows_per_table": 2048},
    {"rows": ROWS, "pooling": POOLING, "lookups_per_table": 3},
    {"rows": ROWS, "pooling": POOLING, "num_tables": 4},
    {"rows": ROWS, "num_tables": 4, "lookups_per_table": 3},
    {"pooling": POOLING, "num_tables": 4, "rows_per_table": 2048},
    {"rows": ROWS, "pooling": POOLING[:3]}])
def test_layout_refuses_mixed_or_unequal_tables(sizes):
    with pytest.raises(ValueError, match="sizes give"):
        layout(sizes)


@pytest.mark.parametrize("seed", [5, 2**33 + 7])
def test_ring_has_one_column_group_per_table_within_its_rows(seed):
    spec = fixture_spec()
    sizes = spec.config["sizes"]
    ring = feed.make_ring(sizes, spec.traffic, seed)
    assert len(ring) == spec.traffic["ring"]
    col = 0
    for R, L in zip(ROWS, POOLING, strict=True):
        ids = np.stack([b["sparse"][:, col:col + L] for b in ring])
        assert ids.min() >= 0 and ids.max() < R
        if R > 100:
            assert ids.max() >= R // 2     # the draw spans the table
        col += L
    for b in ring:
        assert b["sparse"].shape == (32, 16) and b["sparse"].dtype == np.int32
        assert b["dense"].shape == (32, 13) and b["labels"].shape == (32,)
    again = feed.make_ring(sizes, spec.traffic, seed)
    for x, y in zip(ring, again, strict=True):
        for k in x:
            np.testing.assert_array_equal(x[k], y[k])
    other = feed.make_ring(sizes, spec.traffic, seed + 1)
    assert not np.array_equal(ring[0]["sparse"], other[0]["sparse"])


def test_unique_rows_uses_the_cumulative_offsets():
    sizes = fixture_spec().config["sizes"]
    row = [1, 1, 2] + [1] + [1] * 10 + [1, 999]
    batch = {"sparse": np.array([row, row], np.int32)}
    flat = layout(sizes).flat_ids(batch["sparse"])
    assert sorted(set(flat[0].tolist())) == [1, 2, 2049, 2052, 2552, 3550]
    assert feed.unique_rows(sizes, batch) == 6


def test_the_files_reference_and_counts_are_loaded():
    spec = fixture_spec()
    data = ROOT / "bench" / "tests" / "data"
    assert spec.reference.__file__ == str(data / "flat4_reference.py")
    assert spec.counts.__file__ == str(data / "flat4_counts.py")
    rm1 = harness.load_spec("rm1.nockpt")
    assert rm1.reference.__file__ == str(ROOT / "bench" / "references"
                                         / "dlrm.py")
    assert rm1.counts.__file__ == str(ROOT / "bench" / "counts.py")


@pytest.mark.parametrize("key,path", [
    ("reference", "tests/data/no_such_reference.py"),
    ("counts", "tests/data/no_such_counts.py"),
    ("reference", "../src/repro/__init__.py")])
def test_a_wrong_module_path_fails_clearly(key, path, tmp_path):
    data = json.loads((ROOT / FIXTURE).read_text())
    data[key] = path
    file = tmp_path / "flat4.json"
    file.write_text(json.dumps(data))
    with pytest.raises(FileNotFoundError, match=f"flat4.json's {key}"):
        harness.load_spec("flat4.nockpt", fixture_bench(str(file)))


def test_program_check_follows_program_fields(monkeypatch):
    spec = fixture_spec()
    stub_program(monkeypatch)
    cfg, tc = harness._program(spec, ROOT / "bench" / ".scratch" / "unused")
    assert cfg.table_rows == tuple(ROWS)
    assert tc.optimizer == "adagrad" and tc.learning_rate == 0.01
    assert tc.grad_clip == 1.0 and tc.embed_learning_rate == 0.05
    stub_program(monkeypatch, table_rows=(2048, 3, 500, 999))
    with pytest.raises(ValueError, match="differs"):
        harness._program(spec, ROOT / "bench" / ".scratch" / "unused")


def test_recipe_sets_every_field_the_reference_implements():
    rm1 = harness.load_spec("rm1.nockpt")
    assert harness.train_fields(rm1.config["recipe"], rm1.reference) == {
        "optimizer": "adamw", "embed_optimizer": "sgd",
        "learning_rate": 0.001, "beta1": 0.9, "beta2": 0.95,
        "grad_clip": 1.0, "embed_learning_rate": 0.05, "weight_decay": 0.0}


@pytest.mark.parametrize("change,match", [
    ({"momentum": 0.9}, r"has \['momentum'\] unused"),
    ({"grad_clip": None}, r"lacks \['grad_clip'\]"),
    ({"optimizer": "adamw"}, "implements no recipe"),
    ({"embed_optimizer": "rowwise_adagrad"}, "implements no recipe")])
def test_a_recipe_the_reference_does_not_implement_is_refused(
        change, match, tmp_path):
    data = json.loads((ROOT / FIXTURE).read_text())
    recipe = {**data["recipe"], **change}
    data["recipe"] = {k: v for k, v in recipe.items() if v is not None}
    file = tmp_path / "flat4.json"
    file.write_text(json.dumps(data))
    with pytest.raises(ValueError, match=match):
        harness.load_spec("flat4.nockpt", fixture_bench(str(file)))


def test_smoke_sizes_come_from_the_file_or_the_dlrm_default():
    rm1 = control.smoke_config(harness.load_spec("rm1.nockpt").config)
    assert rm1["smoke_preset"] and rm1["sizes"]["rows_per_table"] == 2048
    assert rm1["sizes"]["top_mlp"] == [32, 1]
    assert rm1["sizes"]["bottom_mlp"] == [13, 64, 32]
    config = dict(fixture_spec().config)
    del config["smoke"]
    with pytest.raises(ValueError, match="no \"smoke\" block"):
        control.smoke_config(config)


def test_setup_readings_take_the_touched_rows_of_a_flat_table():
    spec = fixture_spec()
    sizes = spec.config["sizes"]
    ring = feed.make_ring(sizes, spec.traffic, 9)
    d = sizes["embed_dim"]
    table0 = jnp.zeros((sum(ROWS), d), jnp.float32)
    dense0 = {"bottom": [{"w": jnp.ones((13, 16))}], "top": []}
    state0 = {"dense": dense0, "embed": {"emb_tables": table0}}
    setup = harness.SetupReadings(state0, ring, sizes,
                                  spec.config["recipe"], spec.reference)
    off = np.repeat([0, 2048, 2051, 2551], POOLING)
    touched = np.unique(np.concatenate(
        [(b["sparse"] + off).reshape(-1) for b in ring[:3]]))
    np.testing.assert_array_equal(np.asarray(setup.ids), touched)
    g = jnp.full((13, 16), 3.0)
    setup.on_step(0, {"opt_dense": {"acc": {"bottom": [{"w": g * g}],
                                            "top": []}}})
    assert setup.grad_norms == {"bottom.0.w": pytest.approx(3.0 * 4 * 13**.5)}
    untouched = np.setdiff1d(np.arange(sum(ROWS)), touched)[0]
    table3 = table0.at[touched].add(1.0).at[untouched].add(100.0)
    setup.on_step(2, {"dense": dense0, "embed": {"emb_tables": table3}})
    assert setup.change_norms["table"] == pytest.approx(
        math.sqrt(touched.size * d))
    assert setup.change_norms["bottom.0.w"] == 0.0


def test_a_new_file_runs_through_the_harness(monkeypatch):
    """load_spec -> make_ring -> reference_readings -> the counts -> the
    program check, for a file that brings its own tables, pooling,
    reference, counts, program fields and smoke sizes."""
    spec = fixture_spec()
    spec.config = control.smoke_config(spec.config)
    sizes = spec.config["sizes"]
    assert sizes["batch"] == 8 and sizes["dtype"] == "float32"
    assert sizes["rows"] == ROWS and spec.config["smoke_preset"]
    ring = feed.make_ring(sizes, spec.traffic, 2**32 + 3)
    ref = harness.reference_readings(spec, 2**32 + 3, ring)
    assert len(ref["losses"]) == 3 and all(map(math.isfinite, ref["losses"]))
    assert set(ref["change_norms"]) == set(ref["grad_norms"])
    assert ref["change_norms"]["table"] > 0
    ctl = harness.reference_readings(spec, 2**32 + 3, ring, store="bfloat16")
    assert ctl["losses"] != ref["losses"]
    flops = spec.counts.model_flops_per_sample(sizes)
    assert flops == 3 * (2 * (13 * 16 + 16 * 8) + 2 * (40 * 16 + 16)
                         + 12 * 8)
    t, bound = spec.counts.step_min_seconds(
        sizes, feed.unique_rows(sizes, ring[0]),
        harness.peaks_for("TPU v5 lite"))
    assert t > 0 and bound in {"compute", "memory"}
    stub_program(monkeypatch, dtype="float32")
    cfg, _ = harness._program(spec, ROOT / "bench" / ".scratch" / "unused")
    assert list(cfg.table_pooling) == sizes["pooling"]
