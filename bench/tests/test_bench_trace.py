"""The reduction from trace events to busy time, program times, the
heaviest operations and idle gaps named by the host's spans."""
import pytest

from bench import traces


def test_union_merges_overlaps_and_touching_intervals():
    assert traces.union([[5, 7], [0, 2], [1, 3], [3, 4], [9, 10]]) == [
        [0, 4], [5, 7], [9, 10]]


def test_reduce_events_counts_busy_ops_modules_and_gaps():
    ms = 1_000_000
    dev0 = [(0, 10 * ms, "fusion.1"), (10 * ms, 12 * ms, "scatter.2"),
            (20 * ms, 30 * ms, "fusion.1"), (31 * ms, 33 * ms, "copy.3")]
    dev1 = [(0, 4 * ms, "fusion.1")]
    modules = [(0, 12 * ms, "jit_relaxed_step"),
               (20 * ms, 33 * ms, "jit_relaxed_step"),
               (40 * ms, 41 * ms, "jit_take")]
    host = [(13 * ms, 19 * ms, "bench.batch_handoff"),
            (14 * ms, 15 * ms, "bench.on_step")]
    r = traces.reduce_events([dev0, dev1], modules, host, window_s=0.05)
    # device 0 busy 12 + 10 + 2 ms, device 1 busy 4 ms: mean 14 ms
    assert r.busy_s == pytest.approx(0.014)
    assert r.window_s == 0.05
    assert r.module_times("jit_relaxed_step") == pytest.approx([0.012, 0.013])
    assert r.op_seconds["fusion.1"] == pytest.approx(0.020)
    # gaps of device 0: 12-20 ms under the hand-off, 30-31 ms under nothing
    assert r.gaps == [("bench.batch_handoff", pytest.approx(0.008)),
                      (traces.LOOP, pytest.approx(0.001))]
    b = r.breakdown(n=2)
    assert b["device_ops"][0] == ["fusion.1", pytest.approx(0.020)]
    assert len(b["device_ops"]) == 2 and len(b["idle_gaps"]) == 2


def test_reduce_dir_without_a_trace_is_nothing(tmp_path):
    assert traces.reduce_dir(tmp_path, 1.0) is None


def test_the_window_span_clips_device_time_and_sets_the_window():
    ms = 1_000_000
    dev0 = [(0, 10 * ms, "a"), (15 * ms, 25 * ms, "b"), (30 * ms, 40 * ms, "c")]
    modules = [(0, 10 * ms, "jit_relaxed_step"),
               (15 * ms, 25 * ms, "jit_relaxed_step")]
    host = [(5 * ms, 20 * ms, traces.WINDOW)]
    r = traces.reduce_events([dev0], modules, host, window_s=99.0)
    assert r.window_s == pytest.approx(0.015)
    assert r.busy_s == pytest.approx(0.010)     # 5-10 and 15-20 ms
    assert r.module_times("jit_relaxed_step") == []   # none wholly inside
    assert r.gaps == [(traces.LOOP, pytest.approx(0.005))]


def test_short_names_and_self_time_of_nested_ops():
    assert traces.short_name(
        "%while.5 = (u32[]{:T(128)}, f32[640]{0:T(1024)}) while((u32[]) "
        "%tuple.78), condition=%c, body=%b") == "while.5 while"
    assert traces.short_name(
        "%fusion.5 = f32[20,32]{0,1:T(8,128)} fusion(f32[20,32] %r), "
        "kind=kLoop") == "fusion.5 fusion"
    ops = [(0, 10, "while.1 while"), (2, 5, "dus.2 dynamic-update-slice"),
           (6, 8, "dus.2 dynamic-update-slice"), (12, 13, "copy.3 copy")]
    got = traces.self_seconds(ops)
    assert got == pytest.approx({"while.1 while": 5e-9,
                                 "dus.2 dynamic-update-slice": 5e-9,
                                 "copy.3 copy": 1e-9})


def test_a_recorded_tpu_trace_reduces_to_its_programs_and_spans():
    """A trace of three steps of a small jitted ``relaxed_step`` on one TPU
    v5e, with the benchmark's host spans around a hand-off and a sleep."""
    from pathlib import Path
    path = Path(__file__).parent / "data" / "tiny_tpu.xplane.pb"
    ops, modules, host = traces.events_of(path)
    assert len(ops) == 1 and ops[0]
    names = {n for _, _, n in host}
    assert names == {"bench.batch_handoff", "bench.on_step"}
    r = traces.reduce_events(ops, modules, host, window_s=1.0)
    assert len(r.module_times("jit_relaxed_step")) == 3
    assert 0 < r.busy_s < 1e-3
    # the two longest idle stretches are the host's 2 ms sleeps
    assert [g[0] for g in r.gaps[:2]] == ["bench.on_step"] * 2
    assert all(g[1] > 1e-3 for g in r.gaps[:2])
    assert any(k.endswith(" fusion") for k in r.op_seconds)
