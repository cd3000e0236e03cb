"""The plain reference against the program, at the smoke size of dlrm-rm1."""
import jax.numpy as jnp
import numpy as np

from bench import checks, counts, harness
from bench.references import dlrm as ref
from bench.tests.helpers import run_smoke, smoke_spec


def test_nockpt_run_matches_the_reference(tmp_path):
    out = run_smoke("rm1.nockpt", tmp_path)
    assert out["correct"] is True
    assert set(out["checks"]) == set(smoke_spec("rm1.nockpt")
                                     .config["limits"])
    for check in out["checks"].values():
        # float32 program against the float32 reference: rounding only
        assert check["value"] < 1e-4, out["checks"]
    assert set(out["metrics"]) == {"samples_per_s", "step_ms_p95", "setup_s"}
    assert out["attempted"] > 0 and out["failed"] == 0
    assert list(out)[-1] == "checks"


def test_ckpt_run_recovers_the_trainers_state(tmp_path):
    out = run_smoke("rm1.ckpt_pmem", tmp_path, seed=12)
    assert out["correct"] is True, out["checks"]
    for k in ("ckpt_step_gap", "ckpt_table_elems_off",
              "ckpt_dense_leaves_off"):
        assert out["checks"][k] == {"value": 0, "limit": 0}
    assert set(out["metrics"]) == {"samples_per_s", "commit_lag_s",
                                   "resume_s", "setup_s"}
    assert not any(tmp_path.joinpath("pool").iterdir())   # pool removed


def test_traced_ckpt_run_reads_its_per_layer_metrics(tmp_path):
    out = run_smoke("rm1.ckpt_pmem", tmp_path, seed=13, trace=True)
    m = out["metrics"]
    # the window warms nothing: every shape ran in set-up
    assert m["compiles_per_step"]["value"] == 0.0
    for k in ("ckpt_on_step_ms", "writer_ms_per_step",
              "pool_media_mb_per_step"):
        assert m[k]["value"] > 0
    # a CPU trace holds no device plane: those readers return nothing
    assert "step_device_ms" not in m and "relaxed_step_roofline" not in m


def test_program_readings_match_the_reference_for_another_seed():
    spec = smoke_spec("rm1.nockpt")
    seed = 2**33 + 5
    ring = harness.feed.make_ring(spec.config["sizes"], spec.traffic, seed)
    prog = harness.program_readings(spec, seed)
    refr = harness.reference_readings(spec, seed, ring)
    gaps = checks.gaps(prog, refr)
    assert max(gaps.values()) < 1e-4, gaps
    assert sorted(prog["change_norms"]) == sorted(refr["change_norms"])


def test_reference_loss_is_the_mean_bce_of_its_logits():
    """The reference's loss, at an all-zero model, is log 2 per sample."""
    sizes = dict(smoke_spec("rm1.nockpt").config["sizes"])
    T, R, d = sizes["num_tables"], 8, sizes["embed_dim"]
    sizes["rows_per_table"] = R
    dense = {"bottom": [{"w": jnp.zeros((a, b)), "b": jnp.zeros((b,))}
                        for a, b in zip(sizes["bottom_mlp"][:-1],
                                        sizes["bottom_mlp"][1:])],
             "top": [{"w": jnp.zeros((a, b)), "b": jnp.zeros((b,))}
                     for a, b in zip(counts.top_dims(sizes)[:-1],
                                     counts.top_dims(sizes)[1:])]}
    B, L = 4, sizes["lookups_per_table"]
    batch = {"dense": jnp.ones((B, 13)), "labels": jnp.array([0., 1., 1., 0.]),
             "sparse": jnp.zeros((B, T, L), jnp.int32)}
    loss = ref.loss_fn(dense, jnp.zeros((T, R, d)), batch, jnp.float32)
    np.testing.assert_allclose(float(loss), np.log(2.0), rtol=1e-6)
