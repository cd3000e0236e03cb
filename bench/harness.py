"""Runs one benchmark cell: set-up, the measured window, the checks.

The window drives the program's own entry, ``repro.training.train_loop.train``
(the relaxed schedule through ``make_step_fns``' ``relaxed_step``), with a
``CheckpointManager`` over the pmem pool in the checkpointing cells, as
``python -m repro.launch.train --full --batch 256`` does. The pool's image
is held in memory (``_memory_image``).

Set-up: weights from the seed in one jitted call of the program's
``init_fn``; a ring of batches from the seed; one warm ``train()`` call of
``traffic["warm_steps"]`` steps, which compiles the step and runs every
batch shape the window will use; its first three steps are the ones the
reference checks. ``train()`` builds its jitted step anew on every call, so
the measured call's first step traces and loads the step again: the window
opens when that step's ``on_metrics`` runs. It closes at the first
``on_metrics`` after ``seconds`` have passed and, in checkpointing cells,
after ``flush()`` has returned, so deferred checkpoint work is paid inside.

What belongs to one configuration comes from its file: the tables and
their pooling (``sizes``, read through ``bench/layout.py``), the plain
reference (``"reference"``, a module under ``bench/``), the counts of its
required work (``"counts"``, default ``counts.py``), which program fields
its sizes are held to (``"program_fields"``) and its smoke sizes
(``"smoke"``, ``bench/control.py``). A reference module gives
``readings(key, sizes, recipe, batches, *, store, act, rows)``,
``leaf_names(tree)``, ``diff_norm(a, b)``,
``program_grad_norms(opt_dense, recipe)``, the program's first dense
gradient read from its optimizer state, and ``RECIPES``, the recipes it
implements and what each key sets in the program (``train_fields``);
a counts module ``model_flops_per_sample(sizes)`` and
``step_min_seconds(sizes, unique_rows, peaks)``.
"""
from __future__ import annotations

import dataclasses
import gc
import importlib.util
import json
import math
import os
import re
import shutil
import sys
import time
from pathlib import Path

import numpy as np

from bench import checks, feed
from bench.layout import layout

ROOT = Path(__file__).resolve().parents[1]
BENCH = Path(__file__).resolve().parent
SCRATCH = BENCH / ".scratch"


def _log(t_start: float, what: str):
    """A timestamped progress line on standard error."""
    print(f"bench: {time.time() - t_start:8.2f}s {what}", file=sys.stderr,
          flush=True)


class NoAccelerator(RuntimeError):
    """JAX found no TPU, or fewer chips than the cell asks for."""


class WindowClosed(Exception):
    """Raised from ``on_metrics`` to end the measured ``train()`` call."""


# -- the cell's data files -------------------------------------------------


@dataclasses.dataclass
class Spec:
    cell: dict
    config: dict
    traffic: dict
    end_to_end: list
    per_layer: list

    @property
    def name(self) -> str:
        return self.cell["name"]

    @property
    def reference(self):
        """The plain reference module the configuration file names."""
        return load_module(self.config["reference"],
                           f"{self.config['name']}.json's reference")

    @property
    def counts(self):
        """The counts of required work the configuration file names."""
        return load_module(self.config.get("counts", "counts.py"),
                           f"{self.config['name']}.json's counts")

    def metrics_for(self, trace: bool) -> list[dict]:
        """The metrics this cell prints: end-to-end without the trace,
        per-layer with it, each only where its ``workloads`` list the cell."""
        return [m for m in (self.per_layer if trace else self.end_to_end)
                if self.name in m.get("workloads", [self.name])]


def load_spec(workload: str, bench: dict | None = None) -> Spec:
    """The cell's spec from ``bench``, which defaults to BENCHMARK.json."""
    bench = bench or json.loads((ROOT / "BENCHMARK.json").read_text())
    cells = {c["name"]: c for c in bench["workloads"]}
    if workload not in cells:
        raise SystemExit(f"unknown workload {workload!r}; "
                         f"BENCHMARK.json has {sorted(cells)}")
    cell = cells[workload]
    conf = next(c for c in bench["configs"] if c["name"] == cell["config"])
    config = json.loads((ROOT / conf["file"]).read_text())
    traffic = json.loads(
        (BENCH / "traffic" / f"{cell['traffic']}.json").read_text())
    spec = Spec(cell, config, traffic, bench["end_to_end"],
                bench["per_layer"])
    layout(config["sizes"])
    train_fields(config["recipe"], spec.reference)
    spec.counts  # noqa: B018  a wrong path fails here
    return spec


def load_module(rel: str, what: str):
    """The Python file at ``bench/<rel>``, loaded once per process."""
    path = (BENCH / rel).resolve()
    if not path.is_file() or not path.is_relative_to(BENCH):
        raise FileNotFoundError(f"{what} {rel!r} is no file under bench/")
    name = "bench_file_" + re.sub(r"\W", "_", rel)
    if name not in sys.modules:
        mod_spec = importlib.util.spec_from_file_location(name, path)
        mod = importlib.util.module_from_spec(mod_spec)
        sys.modules[name] = mod
        try:
            mod_spec.loader.exec_module(mod)
        except BaseException:
            del sys.modules[name]
            raise
    return sys.modules[name]


def peaks_for(kind: str) -> dict:
    table = json.loads((BENCH / "peaks.json").read_text())
    if kind not in table["devices"]:
        raise NoAccelerator(f"no peaks for device kind {kind!r}; "
                            f"bench/peaks.json has {sorted(table['devices'])}")
    return table["devices"][kind]


def require_devices(chips: int):
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise NoAccelerator(f"found no TPU: JAX runs on {devs[0].platform}")
    if len(devs) < chips:
        raise NoAccelerator(f"the cell needs {chips} chips, JAX found "
                            f"{len(devs)}")
    return devs[:chips]


def key_for(seed: int):
    """A PRNG key that differs for every seed, beyond 32 bits too."""
    import jax
    key = jax.random.PRNGKey(seed & 0xFFFFFFFF)
    return jax.random.fold_in(key, seed >> 32)


# -- counters the benchmark keeps itself -----------------------------------


class CompileCounter:
    """Backend compiles of this process (a persistent-cache load counts)."""

    def __init__(self):
        import jax
        self.n = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, secs: float, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.n += 1


def _pool_bytes(mgr) -> int:
    if mgr is None or mgr.pool is None:
        return 0
    return sum(s.nbytes for s in list(mgr.pool.metrics.media.values()))


class Probe:
    """Stands in ``train()``'s ``ckpt_manager`` slot. Records when each
    step's loss returned (``on_step`` is called right after ``float(loss)``),
    times the real manager's ``on_step`` and delegates to it. Keeps the
    newest state and, at every dense save, the dense tier it saved."""

    def __init__(self, mgr, dense_interval: int):
        self.mgr = mgr
        self.every = dense_interval
        self.loss_t: dict[int, float] = {}
        self.on_step_s: dict[int, float] = {}
        self.commit_t: dict[int, float] = {}
        self.last = None                # (step, state)
        self.dense_at = None            # (step, dense tier)
        self.watch = None               # fn(step, state) in set-up
        if mgr is not None:
            mgr.add_commit_hook(self._on_commit)

    def _on_commit(self, step, idx):
        self.commit_t[step] = time.perf_counter()

    def on_step(self, step, state, feed_):
        import jax
        t = time.perf_counter()
        self.loss_t[step] = t
        if self.mgr is not None:
            with jax.profiler.TraceAnnotation("bench.on_step"):
                self.mgr.on_step(step, state, feed_)
            self.on_step_s[step] = time.perf_counter() - t
            self.last = (step, state)
            if self.every > 0 and step % self.every == 0:
                self.dense_at = (step, {k: state[k] for k in
                                        ("dense", "opt_dense", "opt_embed")})
        if self.watch is not None:
            self.watch(step, state)

    def flush(self):
        if self.mgr is not None:
            self.mgr.flush()


class SetupReadings:
    """The program's numbers from the first three steps of the warm call:
    losses, the first dense gradient as the reference module reads it from
    the optimizer state after step 1 (for AdamW m / (1 - beta1)), and every
    leaf's change after step 3. The table's change is taken over the rows
    the three batches touch, the only rows a row-local step on their
    gradients can move."""

    def __init__(self, state0, ring, sizes, recipe, ref):
        import jax
        import jax.numpy as jnp
        self.ref = ref
        self.recipe = recipe
        self.dense0 = state0["dense"]
        flat_ids = layout(sizes).flat_ids
        ids = np.unique(np.concatenate(
            [flat_ids(b["sparse"]).reshape(-1) for b in ring[:3]]))
        self.ids = jnp.asarray(ids.astype(np.int32))
        tab = state0["embed"]["emb_tables"]
        self.rows0 = jnp.take(tab.reshape(-1, tab.shape[-1]), self.ids,
                              axis=0)
        jax.block_until_ready(self.rows0)
        self.losses: list[float] = []
        self.grad_norms: dict = {}
        self.change_norms: dict = {}

    def on_metrics(self, n, metrics):
        if len(self.losses) < 3:
            self.losses.append(float(metrics["loss"]))

    def on_step(self, step, state):
        import jax.numpy as jnp
        if step == 0:
            self.grad_norms = self.ref.program_grad_norms(state["opt_dense"],
                                                          self.recipe)
        elif step == 2:
            now = self.ref.leaf_names(state["dense"])
            was = self.ref.leaf_names(self.dense0)
            self.change_norms = {n: float(self.ref.diff_norm(now[n], was[n]))
                                 for n in now}
            tab = state["embed"]["emb_tables"]
            rows = jnp.take(tab.reshape(-1, tab.shape[-1]), self.ids, axis=0)
            self.change_norms["table"] = float(
                self.ref.diff_norm(rows, self.rows0))
            self.dense0 = self.rows0 = self.ids = None

    def result(self) -> dict:
        return {"losses": self.losses, "grad_norms": self.grad_norms,
                "change_norms": self.change_norms}


class Window:
    """Opens at the measured call's first ``on_metrics``; records the end
    of every later step; raises ``WindowClosed`` once ``seconds`` passed."""

    def __init__(self, seconds: float, on_open=None):
        self.seconds = seconds
        self.on_open = on_open
        self.t_open = None
        self.wall_open = None
        self.step_end: list[float] = []
        self.steps: list[int] = []
        self.t_close = None

    def on_metrics(self, n, metrics):
        if self.t_open is None:
            if self.on_open is not None:
                self.on_open()
            self.t_open = time.perf_counter()
            self.wall_open = time.time()
            return
        now = time.perf_counter()
        self.step_end.append(now)
        self.steps.append(n)
        if now - self.t_open >= self.seconds:
            raise WindowClosed

    @property
    def length(self) -> float:
        return self.t_close - self.t_open

    def step_times(self) -> list[float]:
        ends = [self.t_open] + self.step_end
        return [b - a for a, b in zip(ends[:-1], ends[1:], strict=True)]


# -- the run -----------------------------------------------------------------


@dataclasses.dataclass
class Run:
    """Everything a metric reader may read. A reader returns a number, or
    None where the run holds nothing for it."""
    spec: Spec
    sizes: dict
    peaks: dict
    setup_s: float
    window: Window
    probe: Probe
    compiles_in_window: int
    pool_bytes_in_window: int
    resume_s: float | None
    ring: list
    trace: object = None           # traces.Reduced, with --trace 1

    @property
    def batch(self) -> int:
        return self.sizes["batch"]


def train_fields(recipe: dict, ref) -> dict:
    """The program's ``TrainConfig`` fields for ``recipe``, as the reference
    module's ``RECIPES`` says: keyed by the (dense, embedding) optimizers a
    recipe names, each entry maps every other key of the recipe to the
    field it sets (``None``: a constant of the program's optimizer that the
    reference alone reads) and holds the ``fixed`` fields the reference
    implements at one value. Raises ValueError where the reference
    implements no such recipe, or the recipe lacks a key that it uses or
    has one that reaches neither the program nor the reference."""
    opts = (recipe.get("optimizer"), recipe.get("embed_optimizer"))
    if opts not in ref.RECIPES:
        raise ValueError(f"the reference implements no recipe of the "
                         f"optimizers {opts}, only {list(ref.RECIPES)}")
    keys, fixed = ref.RECIPES[opts]["keys"], ref.RECIPES[opts]["fixed"]
    given = set(recipe) - {"optimizer", "embed_optimizer"}
    if given != set(keys):
        raise ValueError(f"the recipe of {opts} lacks "
                         f"{sorted(set(keys) - given)} and has "
                         f"{sorted(given - set(keys))} unused")
    return {"optimizer": opts[0], "embed_optimizer": opts[1], **fixed,
            **{f: recipe[k] for k, f in keys.items() if f}}


def _dlrm_fields(cfg) -> dict:
    """The sizes a file without ``program_fields`` is held to."""
    return {"num_tables": cfg.dlrm_num_tables,
            "rows_per_table": cfg.dlrm_rows_per_table,
            "embed_dim": cfg.dlrm_bottom_mlp[-1],
            "lookups_per_table": max(1, cfg.dlrm_num_sparse),
            "bottom_mlp": list(cfg.dlrm_bottom_mlp),
            "top_mlp": list(cfg.dlrm_top_mlp),
            "num_dense": cfg.dlrm_num_dense, "dtype": cfg.dtype}


def _program(spec: Spec, pool_dir: Path):
    """The program's model and training configuration for this cell. The
    file's ``program_fields`` map (size key -> attribute of the program's
    model config; tuples compare as lists) says which sizes are held to the
    program's; without it, those of ``_dlrm_fields``."""
    from repro.configs import get_arch
    from repro.configs.base import CheckpointConfig, TrainConfig
    c, t = spec.config, spec.traffic
    cfg = get_arch(c["arch"], smoke=bool(c.get("smoke_preset"))).model
    s = c["sizes"]
    if "program_fields" in c:
        have = {k: getattr(cfg, attr)
                for k, attr in c["program_fields"].items()}
        have = {k: list(v) if isinstance(v, tuple) else v
                for k, v in have.items()}
    else:
        have = _dlrm_fields(cfg)
    diff = {k: (v, s.get(k)) for k, v in have.items() if s.get(k) != v}
    if diff:
        raise ValueError(f"program config {c['arch']} differs from "
                         f"{c['name']}.json: {diff}")
    ckpt = CheckpointConfig(enabled=False)
    if t["checkpoint"]:
        ckpt = CheckpointConfig(
            directory=str(pool_dir), dense_interval=t["dense_interval"],
            pool_backend=t["pool_backend"], pool_compress=t["compress"])
    tc = TrainConfig(checkpoint=ckpt,
                     **train_fields(c["recipe"], spec.reference))
    return cfg, tc


def _start(spec: Spec, seed: int, pool_dir: Path):
    """The program's configuration, the ring of batches, and the initial
    state on the device, in one jitted call of the program's ``init_fn``."""
    import jax

    from repro.training import train_loop
    cfg, tc = _program(spec, pool_dir)
    ring = feed.make_ring(spec.config["sizes"], spec.traffic, seed)
    init_fn = train_loop.make_step_fns(cfg, tc)[0]
    box = {"state": jax.jit(init_fn)(key_for(seed))}
    jax.block_until_ready(box["state"])
    return cfg, tc, ring, feed.Feed(ring), box


def program_readings(spec: Spec, seed: int) -> dict:
    """The program's first three steps alone, as set-up takes them, with no
    checkpoint manager (the control's side of the comparison)."""
    from repro.training import train_loop
    cfg, tc, ring, batches, box = _start(spec, seed, SCRATCH / "unused")
    probe = Probe(None, 0)
    setup = SetupReadings(box["state"], ring, spec.config["sizes"],
                          spec.config["recipe"], spec.reference)
    probe.watch = setup.on_step
    train_loop.train(cfg, tc, batches, 3,
                     relaxed=spec.traffic["schedule"] == "relaxed",
                     state=box.pop("state"), ckpt_manager=probe,
                     on_metrics=setup.on_metrics)
    return setup.result()


def run_cell(spec: Spec, seed: int, seconds: float, trace: bool, *,
             t_start: float) -> dict:
    """One run of one cell. Returns the result line as a dict."""
    from repro.core.checkpoint.manager import CheckpointManager
    from repro.training import train_loop

    devs = require_devices(spec.cell["chips"])
    kind = devs[0].device_kind
    peaks = peaks_for(kind)
    compiles = CompileCounter()
    traffic, sizes = spec.traffic, spec.config["sizes"]
    pool_dir = SCRATCH / "pool" / spec.name
    trace_dir = SCRATCH / "trace" / spec.name
    for d in (pool_dir, trace_dir):
        shutil.rmtree(d, ignore_errors=True)
    pool_dir.mkdir(parents=True)
    image = _memory_image(pool_dir) if traffic["checkpoint"] else None
    try:
        log = lambda what: _log(t_start, what)  # noqa: E731
        log(f"device {kind} x{len(devs)}")
        cfg, tc, ring, batches, box = _start(spec, seed, pool_dir)
        log(f"weights on the device, ring of {len(ring)} batches")
        mgr = None
        if traffic["checkpoint"]:
            mgr = CheckpointManager(cfg, tc.checkpoint,
                                    embed_init=box["state"]["embed"])
            log("checkpoint pool holds the initial table")
        probe = Probe(mgr, tc.checkpoint.dense_interval)
        setup = SetupReadings(box["state"], ring, sizes,
                              spec.config["recipe"], spec.reference)
        probe.watch = setup.on_step
        relaxed = traffic["schedule"] == "relaxed"
        warm = traffic["warm_steps"]
        box["state"], _ = train_loop.train(
            cfg, tc, batches, warm, relaxed=relaxed, state=box.pop("state"),
            ckpt_manager=probe, on_metrics=setup.on_metrics)
        probe.watch = None
        if mgr is not None:
            _warm_row_gathers(box["state"], ring, sizes)
        log(f"warm call: {warm} steps, {compiles.n} compiles so far")

        tracer = _Tracer(trace_dir) if trace else None
        pool0 = compiles0 = 0

        def open_window():
            nonlocal pool0, compiles0
            if tracer:
                tracer.start()
            pool0, compiles0 = _pool_bytes(mgr), compiles.n

        window = Window(seconds, open_window)
        try:
            train_loop.train(
                cfg, tc, batches, 10**9, relaxed=relaxed,
                state=box.pop("state"), start_step=warm,
                ckpt_manager=probe if mgr is not None else None,
                on_metrics=window.on_metrics)
        except WindowClosed:
            pass
        probe.flush()
        window.t_close = time.perf_counter()
        if tracer:
            tracer.stop()
        log(f"window closed: {len(window.steps)} steps in "
            f"{window.length:.3f}s, longest step "
            f"{max(window.step_times(), default=0.0):.3f}s")
        n_compiles = compiles.n - compiles0
        pool_bytes = _pool_bytes(mgr) - pool0

        resume_s, ckpt_checks = None, {}
        if mgr is not None:
            resume_s, ckpt_checks = _resume_and_check(mgr, probe, pool_dir)
            log(f"resumed in {resume_s:.3f}s")
        peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
                   for d in devs)
        probe.last = probe.dense_at = None
        mgr = None
        gc.collect()

        prog = setup.result()
        refr = reference_readings(spec, seed, ring)
        log("reference done")
        train_checks = checks.training(prog, refr, spec.config["limits"])
        all_checks = {**train_checks, **ckpt_checks}
        run = Run(spec, sizes, peaks, window.wall_open - t_start, window,
                  probe, n_compiles, pool_bytes, resume_s, ring)
        if tracer:
            from bench import traces
            run.trace = traces.reduce_dir(trace_dir, window.length)
        return _result(spec, run, trace, devs, peak, all_checks)
    finally:
        shutil.rmtree(pool_dir, ignore_errors=True)
        if image is not None:
            os.close(image)


def _memory_image(pool_dir: Path) -> int:
    """Backs the pmem pool's image with memory: ``pool.img``, where the
    checkpoint manager and ``recovery.recover`` open it, links to a memfd
    of this process. The program's own ``PmemPool`` maps it and persists
    with msync + fsync as it would a file on persistent memory; nothing is
    written to disk. The image lives as long as the process."""
    fd = os.memfd_create("bench-pool-image")
    (pool_dir / "pool.img").symlink_to(f"/proc/self/fd/{fd}")
    return fd


def _warm_row_gathers(state, ring, sizes):
    """Compile, for every batch of the ring, the checkpoint manager's eager
    gather of the rows the batch touched (``jnp.take`` of its unique flat
    ids from the flat table): the one program of the loop whose shape
    follows the data. The same call on the same shapes, so the window finds
    each in the cache instead of running the writer through the whole ring
    in set-up."""
    import jax
    import jax.numpy as jnp
    tab = state["embed"]["emb_tables"]
    flat = tab.reshape(-1, tab.shape[-1])
    flat_ids = layout(sizes).flat_ids
    for b in ring:
        ids = np.unique(flat_ids(b["sparse"]).reshape(-1))
        jax.block_until_ready(jnp.take(flat, jnp.asarray(ids), axis=0))


class _Tracer:
    """The profiler over the window, which a host span of its own marks."""

    def __init__(self, path: Path):
        self.path = path
        self.span = None

    def start(self):
        import jax
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        jax.profiler.start_trace(str(self.path), profiler_options=opts)
        self.span = jax.profiler.TraceAnnotation("bench.window")
        self.span.__enter__()

    def stop(self):
        import jax
        self.span.__exit__(None, None, None)
        jax.profiler.stop_trace()


def _resume_and_check(mgr, probe, pool_dir: Path):
    """Close the manager and time recovery of the flushed checkpoint from
    the pool's image until the resumed state is on the device, then compare it with the trainer's own state at the steps
    the checkpoint names. Every comparison is exact."""
    import jax
    import jax.numpy as jnp

    from repro.core.checkpoint import recovery
    last_step, final = probe.last
    mgr.close()
    t0 = time.perf_counter()
    rec = recovery.recover(str(pool_dir))
    resumed, _ = recovery.resume_train_state(rec, final)
    jax.block_until_ready(resumed)
    resume_s = time.perf_counter() - t0
    rec.pool.close()

    tab_now = final["embed"]["emb_tables"]
    tab_rec = resumed["embed"].get("emb_tables")
    if tab_rec is None or tab_rec.shape != tab_now.shape:
        rows_off = int(tab_now.size)
    else:
        rows_off = int(jnp.sum(tab_rec != tab_now))
    held_step, held = probe.dense_at or (None, None)
    names = ("dense", "opt_dense", "opt_embed")
    n_leaves = sum(len(jax.tree.leaves(final[k])) for k in names)
    if rec.dense is None or held is None or rec.dense_step != held_step:
        dense_off = n_leaves
    else:
        dense_off = sum(
            int(not bool(jnp.array_equal(a, b)))
            for k in names
            for a, b in zip(jax.tree.leaves(resumed[k]),
                            jax.tree.leaves(held[k]), strict=True))
    return resume_s, {
        "ckpt_step_gap": {"value": abs(rec.mirror_step - last_step),
                          "limit": 0},
        "ckpt_table_elems_off": {"value": rows_off, "limit": 0},
        "ckpt_dense_leaves_off": {"value": dense_off, "limit": 0},
    }


def reference_readings(spec: Spec, seed: int, ring: list, *, store=None,
                       act="float32", rows=None) -> dict:
    """The plain reference's first three steps from the same seed; with
    ``store``/``act`` lowered it is the control, with ``rows`` a planted
    fault (part of each batch left out)."""
    sizes = spec.config["sizes"]
    return spec.reference.readings(
        key_for(seed), sizes, spec.config["recipe"], ring[:3],
        store=store or sizes["dtype"], act=act, rows=rows)


def load_reader(name: str):
    return load_module(f"metrics/{name}.py", "the metric reader").read


def _result(spec, run: Run, trace: bool, devs, peak: int,
            all_checks: dict) -> dict:
    metrics = {}
    for m in spec.metrics_for(trace):
        value = load_reader(m["name"])(run)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs), "memory_peak_bytes": int(peak)}
    out = {"correct": checks.passed(all_checks),
           "attempted": len(run.window.steps), "failed": 0,
           "metrics": metrics, "device": device}
    if trace and run.trace is not None:
        device["busy_s"] = run.trace.busy_s
        device["window_s"] = run.trace.window_s
        out["breakdown"] = run.trace.breakdown()
    out["checks"] = all_checks
    return out


def percentile(values: list[float], q: float) -> float:
    """The q-th percentile (0-100) by linear interpolation."""
    xs = sorted(values)
    if len(xs) == 1:
        return xs[0]
    pos = (len(xs) - 1) * q / 100.0
    lo = math.floor(pos)
    hi = min(lo + 1, len(xs) - 1)
    return xs[lo] + (xs[hi] - xs[lo]) * (pos - lo)
