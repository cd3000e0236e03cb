"""BENCHMARK.json and the files it names: each cell, configuration, traffic
mix and metric is found by its name, and the configurations are the
program's own."""
import json
import math
import re

import pytest

from bench import harness
from bench.layout import layout
from bench.tests.helpers import PENDING, ROOT, benchmark, spec_of

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")


def test_top_level_keys_and_command():
    b = benchmark()
    assert set(b) == {"command", "paths", "run_seconds", "configs",
                      "workloads", "end_to_end", "per_layer"}
    assert b["command"] == ["python3", "bench/run.py"]
    assert b["paths"] == ["bench"]
    assert isinstance(b["run_seconds"], int) and 1 <= b["run_seconds"] <= 51


def test_every_name_is_well_formed_and_unique():
    b = benchmark()
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        names = [e["name"] for e in b[group]]
        assert len(names) == len(set(names))
        assert all(NAME.match(n) for n in names)
    metrics = [m["name"] for m in b["end_to_end"] + b["per_layer"]]
    assert len(metrics) == len(set(metrics))


@pytest.mark.parametrize("cell", [
    c["name"] for c in benchmark()["workloads"]
    + json.loads(PENDING.read_text())["workloads"]])
def test_cell_resolves_to_its_files_and_metrics(cell):
    spec = spec_of(cell)
    assert spec.cell["chips"] == 1
    e2e = {m["name"] for m in spec.metrics_for(False)}
    assert "setup_s" in e2e and len(e2e) >= 2
    assert spec.metrics_for(True), "no per-layer metric in this cell"
    for m in spec.metrics_for(False) + spec.metrics_for(True):
        assert callable(harness.load_reader(m["name"]))


def test_per_layer_metrics_name_their_layer_and_move_a_reported_metric():
    b = benchmark()
    cells = {c["name"] for c in b["workloads"]}
    e2e = {m["name"]: m for m in b["end_to_end"]}
    for m in b["per_layer"]:
        assert m["moves"] in e2e
        for cell in m.get("workloads", cells):
            assert cell in cells
            assert cell in e2e[m["moves"]].get("workloads", cells)
        assert m["source"] in {"device_trace", "program_span",
                               "program_counter", "host_clock"}
    for m in b["end_to_end"]:
        assert m["source"] in {"host_clock", "device_trace"}
        assert 0.01 <= m["bound"] <= 0.25


@pytest.mark.parametrize("conf", benchmark()["configs"],
                         ids=lambda c: c["name"])
def test_configuration_file_matches_the_programs_config(conf):
    """The harness refuses a file whose sizes differ from the program's
    configuration; the reference reads the same file."""
    from bench import control
    spec = control.spec_for(conf["name"])
    cfg, tc = harness._program(spec, ROOT / "bench" / ".scratch" / "unused")
    data = json.loads((ROOT / conf["file"]).read_text())
    assert data["name"] == conf["name"] and data["reduced"] == conf["reduced"]
    # the program's tables, flattened to (-1, d), hold every table's rows
    import jax

    from repro.training import train_loop
    state = jax.eval_shape(train_loop.make_step_fns(cfg, tc)[0],
                           harness.key_for(0))
    tab = state["embed"]["emb_tables"].shape
    lay = layout(data["sizes"])
    assert math.prod(tab[:-1]) == lay.total_rows
    assert tab[-1] == data["sizes"]["embed_dim"]
    wrong = dict(spec.config, sizes=dict(spec.config["sizes"], embed_dim=8))
    spec.config = wrong
    with pytest.raises(ValueError, match="differs"):
        harness._program(spec, ROOT / "bench" / ".scratch" / "unused")


def test_peaks_name_their_source_and_refuse_an_unknown_device():
    table = json.loads((ROOT / "bench" / "peaks.json").read_text())
    assert "TPU v5e" in table["source"]
    assert harness.peaks_for("TPU v5 lite")["bf16_flops_per_s"] == 197e12
    with pytest.raises(harness.NoAccelerator):
        harness.peaks_for("cpu")


@pytest.mark.parametrize("seed", [0, 1, 2**32 - 1, 2**32, 2**33 + 3])
def test_seed_keys_differ_beyond_32_bits(seed):
    import jax
    import numpy as np
    a = np.asarray(jax.random.key_data(harness.key_for(seed)))
    b = np.asarray(jax.random.key_data(harness.key_for(seed + 2**32)))
    assert not np.array_equal(a, b)
