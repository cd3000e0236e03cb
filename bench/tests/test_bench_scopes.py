"""The step program's device time by named phase, and the loop's host time,
read from the program's own spans (``bench/scopes.py``), on a recorded TPU
trace of the scoped step; and the readers that were there before read the
same as they did."""
import shutil
import types
from pathlib import Path

import pytest

from bench import feed, harness, scopes, traces

DATA = Path(__file__).parent / "data"
# four steps of the scoped relaxed step at the smoke preset on one TPU v5e,
# under the profiler from the first step's on_metrics, in a bench.window span
SCOPED = DATA / "tiny_scoped_tpu.xplane.pb"
# the program before its spans: three steps of a small jitted relaxed_step
UNSCOPED = DATA / "tiny_tpu.xplane.pb"
NEW = ("embed_update_device_ms", "prefetch_device_ms", "mlp_device_ms",
       "loop_host_ms")


def _run_on(trace_file: Path, scratch: Path, monkeypatch):
    """A traced run whose trace directory holds ``trace_file``."""
    cell = scratch / "trace" / "cell" / "plugins"
    cell.mkdir(parents=True)
    shutil.copy(trace_file, cell / "x.xplane.pb")
    monkeypatch.setattr(harness, "SCRATCH", scratch)
    return types.SimpleNamespace(trace=object(),
                                 spec=types.SimpleNamespace(name="cell"))


def test_every_op_of_the_step_program_is_attributed():
    scoped = scopes.read_trace(SCOPED)
    protos = scopes.hlo_protos(SCOPED.read_bytes())
    prog = next(p for p in protos if p.startswith(scopes.PROGRAM))
    insts = scopes.instructions(protos[prog])
    assert scoped.steps == 3 and scoped.has_scopes
    assert scoped.op_s and set(scoped.op_s) <= set(insts)
    for name, (phase, seconds) in scoped.op_s.items():
        assert phase in scopes.PHASES + (scopes.UNSCOPED,), name
        assert seconds >= 0
    named = {p for p, _ in scoped.op_s.values()}
    assert {"bottom_mlp", "top_mlp", "embed_grad", "embed_update",
            "prefetch", "dense_update"} <= named


def test_phases_and_unscoped_sum_to_the_programs_device_time():
    scoped = scopes.read_trace(SCOPED)
    total = sum(scoped.phase_s.values())
    assert total == pytest.approx(sum(s for _, s in scoped.op_s.values()))
    assert 0.9 * scoped.module_s <= total <= scoped.module_s


def test_the_new_readers_read_the_recorded_trace(tmp_path, monkeypatch):
    run = _run_on(SCOPED, tmp_path, monkeypatch)
    got = {m: harness.load_reader(m)(run) for m in NEW}
    assert all(isinstance(v, float) and v > 0 for v in got.values()), got
    # the three device readings and the rest make up the program's time
    scoped = scopes.of_run(run)
    assert {h[2] for h in scoped.host} == {
        scopes.STEP_SPAN, scopes.LOSS_READ, "repro.train.next_batch",
        "repro.train.dispatch", "repro.train.on_metrics"}
    assert sorted({h[3] for h in scoped.host}) == [3, 4, 5]
    rest = scoped.phase_ms([scopes.UNSCOPED, "ckpt_feed"])
    assert sum(got[m] for m in NEW[:3]) + rest == pytest.approx(
        1e3 * sum(scoped.phase_s.values()) / scoped.steps)


def test_the_new_readers_read_nothing_where_the_program_has_no_spans(
        tmp_path, monkeypatch):
    run = _run_on(UNSCOPED, tmp_path, monkeypatch)
    assert {m: harness.load_reader(m)(run) for m in NEW} == dict.fromkeys(NEW)
    untraced = types.SimpleNamespace(trace=None)
    assert all(harness.load_reader(m)(untraced) is None for m in NEW)


def test_the_loop_reading_is_each_step_less_its_loss_read():
    ms = 1_000_000
    scoped = scopes.Scoped(
        steps=0, module_s=0.0, phase_s={}, has_scopes=False, op_s={},
        host=[(0, 10 * ms, scopes.STEP_SPAN, 5),
              (2 * ms, 9 * ms, scopes.LOSS_READ, 5),
              (10 * ms, 14 * ms, scopes.STEP_SPAN, 6),
              (11 * ms, 12 * ms, scopes.LOSS_READ, 6)])
    assert scoped.loop_host_ms() == pytest.approx((3 + 3) / 2)
    assert scoped.phase_ms(["prefetch"]) is None


@pytest.mark.parametrize("op_name, phase", [
    ("jit(relaxed_step)/jvp(bottom_mlp)/dot_general", "bottom_mlp"),
    ("jit(relaxed_step)/transpose(jvp(top_mlp))/add", "top_mlp"),
    ("jit(relaxed_step)/embed_update/add", "embed_update"),
    ("jit(relaxed_step)/embed_grad/jvp(prefetch)/add", "prefetch"),
    ("jit(relaxed_step)/prefetch_corrected/gather", None),
    ("jit(relaxed_step)/reduce_sum", None),
])
def test_an_op_name_gives_its_innermost_phase(op_name, phase):
    assert scopes.phase_of(op_name) == phase


def test_an_instruction_without_a_scope_takes_its_callers_then_its_users():
    def inst(op_name, comp, operands=(), called=()):
        return scopes.Inst(op_name, comp, list(operands), list(called))
    insts = {
        "body.dus": inst("", 2),                      # inside the loop
        "while.1": inst("", 1, called=[2]),           # XLA's relayout loop
        "gte.1": inst("", 1, ["while.1"]),
        "fusion.3": inst("jit(f)/embed_update/add", 1, ["gte.1"]),
        "copy.4": inst("", 1),                        # used by nothing
    }
    got = scopes.assign(insts)
    assert got["body.dus"] == got["while.1"] == "embed_update"
    assert got["copy.4"] == scopes.UNSCOPED


def test_program_spans_never_reach_the_existing_reduction():
    _, _, host = traces.events_of(SCOPED)
    assert host and all(n.startswith(traces.HOST_PREFIX) for _, _, n in host)


@pytest.fixture(scope="module")
def older_run():
    """A run over the older recorded trace, whose program had no spans."""
    spec = harness.load_spec("rm1.nockpt")
    sizes = spec.config["sizes"]
    return types.SimpleNamespace(
        trace=traces.reduce_events(*traces.events_of(UNSCOPED), window_s=1.0),
        sizes=sizes, batch=sizes["batch"], spec=spec,
        ring=feed.make_ring(sizes, spec.traffic, 7)[:2],
        window=types.SimpleNamespace(steps=[0, 1, 2], length=1.0),
        peaks=harness.peaks_for("TPU v5 lite"))


@pytest.mark.parametrize("metric, value", [
    ("step_device_ms", 0.003935999999999999),
    ("device_idle_share", 99.998821),
    ("relaxed_step_roofline", 11238.064092637263),
    ("mfu", 0.039654486286294414),
])
def test_the_existing_readers_read_what_they_read_before(older_run, metric,
                                                         value):
    """The values these readers gave at the commit before the program's
    spans, on the same trace and run."""
    assert harness.load_reader(metric)(older_run) == value
