"""Runs with the timed path broken underneath: ``correct`` must come out
false for each fault a training cell can have on one chip. The limits are
the configuration's own, as a run on the chip holds them."""
import dataclasses

import jax.numpy as jnp
import pytest

from bench import checks, control, harness
from bench.tests.helpers import limits_for, run_smoke, smoke_spec


def _frozen_step_fns(real):
    def make(cfg, tc):
        init_fn, strict, relaxed, warmup = real(cfg, tc)

        def relaxed_frozen(state, batch, next_batch):
            new, metrics = relaxed(state, batch, next_batch)
            return {**state, "step": new["step"]}, metrics
        return init_fn, strict, relaxed_frozen, warmup
    return make


def _half_batch_api(real_get_api):
    def get_api(cfg):
        api = real_get_api(cfg)

        def loss(params, cfg, batch):
            half = batch["labels"].shape[0] // 2
            return api.loss(params, cfg, {k: v[:half] for k, v in
                                          batch.items()})
        return dataclasses.replace(api, loss=loss)
    return get_api


def _altered_rows(real_take):
    def take(flat_tab, idx):
        rows = real_take(flat_tab, idx)
        return rows.at[0].add(jnp.ones((), rows.dtype))
    return take


@pytest.mark.parametrize("fault", ["state_unchanged", "half_batch",
                                   "checkpoint_row_altered"])
def test_a_broken_timed_path_is_not_correct(fault, tmp_path, monkeypatch):
    from repro.core.checkpoint import manager
    from repro.training import train_loop
    cell = "rm1.nockpt"
    if fault == "state_unchanged":
        monkeypatch.setattr(train_loop, "make_step_fns",
                            _frozen_step_fns(train_loop.make_step_fns))
    elif fault == "half_batch":
        monkeypatch.setattr(train_loop, "get_api",
                            _half_batch_api(train_loop.get_api))
    else:
        cell = "rm1.ckpt_pmem"
        monkeypatch.setattr(manager, "jnp_take",
                            _altered_rows(manager.jnp_take))
    spec = smoke_spec(cell)
    spec.config["limits"] = limits_for(spec.cell["config"])
    out = run_smoke(cell, tmp_path, seed=21, spec=spec)
    assert out["correct"] is False, out["checks"]
    failed = {k for k, c in out["checks"].items() if c["value"] > c["limit"]}
    expect = {"state_unchanged": {"change_gap", "grad_gap"},
              "half_batch": {"grad_gap"},
              "checkpoint_row_altered": {"ckpt_table_elems_off"}}[fault]
    assert expect <= failed, out["checks"]


def test_the_control_fails_the_limits_the_program_passes():
    """The reference computed in the configuration's control precision, put
    in the program's place, is not correct; the program is."""
    for config in ("dlrm-rm1", "dlrm-rm3"):
        spec = control.spec_for(config, smoke=True)
        limits = limits_for(config)
        seed = 31
        ring = harness.feed.make_ring(spec.config["sizes"], spec.traffic,
                                      seed)
        refr = harness.reference_readings(spec, seed, ring)
        ctl_dtype = spec.config["control_dtype"]
        ctl = harness.reference_readings(spec, seed, ring, store=ctl_dtype,
                                         act=ctl_dtype)
        prog = harness.program_readings(spec, seed)
        assert checks.passed(checks.training(prog, refr, limits))
        assert not checks.passed(checks.training(ctl, refr, limits))
