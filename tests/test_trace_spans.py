"""The program's host spans and checkpoint counters: a few steps of
``train()`` with a pmem checkpoint manager under the profiler leave the
loop's, the manager's, the writer's and the pool's spans in the trace, in
loop order and tagged with their step, and the queue and persist counters
read sensibly. With the profiler off a span costs next to nothing."""
import time
from collections import defaultdict
from pathlib import Path

import jax
import pytest

from repro.configs import get_arch
from repro.configs.base import CheckpointConfig, TrainConfig
from repro.core.checkpoint.manager import CheckpointManager
from repro.data.synthetic import make_batches
from repro.training import train_loop

LOOP = ("repro.train.next_batch", "repro.train.dispatch",
        "repro.train.loss_read", "repro.train.ckpt_on_step",
        "repro.train.on_metrics")
WRITER = ("repro.ckpt.tier_e", "repro.ckpt.log_and_apply",
          "repro.ckpt.manifest", "repro.ckpt.tier_m", "repro.ckpt.serialize",
          "repro.ckpt.blob_put")
START, STEPS, DENSE_EVERY = 2, 3, 2


@pytest.fixture(scope="module")
def traced(tmp_path_factory):
    """(spans, manager): every ``repro.*`` host span of three traced steps as
    (start_ns, end_ns, name, stats, thread line), and the flushed manager."""
    from jax.profiler import ProfileData
    tmp = tmp_path_factory.mktemp("spans")
    cfg = get_arch("dlrm-rm1", smoke=True).model
    cc = CheckpointConfig(directory=str(tmp / "ckpt"),
                          dense_interval=DENSE_EVERY, pool_backend="pmem")
    tc = TrainConfig(embed_learning_rate=0.05, checkpoint=cc)
    data = make_batches(cfg, 4, 16, seed=3)
    state = train_loop.make_step_fns(cfg, tc)[0](jax.random.PRNGKey(0))
    mgr = CheckpointManager(cfg, cc, embed_init=state["embed"])
    state, _ = train_loop.train(cfg, tc, data, START, state=state,
                                ckpt_manager=mgr)
    with jax.profiler.trace(str(tmp / "trace")):
        train_loop.train(cfg, tc, data, STEPS, state=state,
                         start_step=START, ckpt_manager=mgr,
                         on_metrics=lambda n, m: None)
    mgr.close()
    path = next(Path(tmp / "trace").rglob("*.xplane.pb"))
    spans = []
    for plane in ProfileData.from_file(str(path)).planes:
        for i, line in enumerate(plane.lines):
            spans.extend((e.start_ns, e.end_ns, e.name, dict(e.stats),
                          (plane.name, i))
                         for e in line.events if e.name.startswith("repro."))
    return sorted(spans), mgr


def _by_name(spans):
    out = defaultdict(list)
    for s in spans:
        out[s[2]].append(s)
    return out


def test_loop_spans_run_in_loop_order_inside_each_step(traced):
    spans, _ = traced
    by = _by_name(spans)
    steps = by["repro.train.step"]
    assert [s[3]["step_num"] for s in steps] == list(
        range(START, START + STEPS))
    for s0, e0, _, stats, _ in steps:
        n = stats["step_num"]
        inner = [s for s in spans if s[2] in LOOP and s0 <= s[0] < e0]
        assert [s[2] for s in inner] == list(LOOP)
        assert all(s[3]["step"] == n and s[1] <= e0 for s in inner)


def test_on_step_spans_nest_in_the_loops_checkpoint_span(traced):
    spans, _ = traced
    by = _by_name(spans)
    for s0, e0, _, stats, line in by["repro.train.ckpt_on_step"]:
        n = stats["step"]
        inner = [s[2] for s in spans if s[2].startswith("repro.ckpt.")
                 and s0 <= s[0] and s[1] <= e0 and s[4] == line]
        want = ["repro.ckpt.touched_to_host", "repro.ckpt.row_gather",
                "repro.ckpt.enqueue"]
        if n % DENSE_EVERY == 0:
            want += ["repro.ckpt.dense_to_host", "repro.ckpt.enqueue"]
        assert inner == want


def test_writer_spans_sit_on_the_writer_thread_with_their_step(traced):
    spans, _ = traced
    by = _by_name(spans)
    loop_line = by["repro.train.step"][0][4]
    steps = range(START, START + STEPS)
    for name in WRITER:
        assert by[name], name
        assert all(s[4] != loop_line for s in by[name]), name
    assert sorted(s[3]["step"] for s in by["repro.ckpt.tier_e"]) == list(
        steps)
    assert sorted(s[3]["step"] for s in by["repro.ckpt.tier_m"]) == [
        n for n in steps if n % DENSE_EVERY == 0]
    for s0, e0, _, stats, line in by["repro.ckpt.tier_e"]:
        inner = [s[2] for s in spans if s[4] == line and s0 < s[0]
                 and s[1] <= e0 and s[2] in WRITER]
        assert inner == ["repro.ckpt.log_and_apply", "repro.ckpt.manifest"]


def test_each_persist_is_one_span_with_its_bytes_and_ranges(traced):
    spans, mgr = traced
    persists = _by_name(spans)["repro.pool.persist"]
    assert persists
    assert all(s[3]["bytes"] >= 0 and s[3]["ranges"] >= 0 for s in persists)
    assert any(s[3]["ranges"] > 1 for s in persists)   # a row apply


def test_queue_and_persist_counters(traced):
    _, mgr = traced
    assert mgr.stats["enqueue_wait_s"] >= 0
    assert mgr.stats["queue_wait_s"] >= 0
    persist = mgr.pool.metrics.media["persist"]
    assert persist.wall_s > 0
    assert "wall=" in mgr.pool.metrics.report()


def test_a_span_costs_next_to_nothing_with_the_profiler_off():
    n = 100_000
    span = jax.profiler.TraceAnnotation
    t0 = time.perf_counter()
    for i in range(n):
        with span("repro.train.dispatch", step=i):
            pass
    per_span = (time.perf_counter() - t0) / n
    print(f"TraceAnnotation enter/exit, profiler off: {per_span * 1e9:.0f} ns")
    assert per_span < 50e-6
