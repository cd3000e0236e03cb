"""Reduction of a profiler trace to the numbers the per-layer metrics read.

A trace is the ``.xplane.pb`` that ``jax.profiler`` writes. From the device
planes (``/device:TPU:<n>``) it takes the operation events (line
``XLA Ops``) and the program events (line ``XLA Modules``); from the host
plane the benchmark's own spans (``bench.*``). Busy time is the union of a
device's operation intervals, averaged over the devices. An idle gap is a
stretch between two busy intervals of device 0, named after the host span
that overlaps it most, or after the loop (step dispatch and the loss read)
where none does. The harness marks the measured window with a host span
(``bench.window``); events are clipped to it and its length is the window.
"""
from __future__ import annotations

import dataclasses
import re
from collections import defaultdict
from pathlib import Path

OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
HOST_PREFIX = "bench."
LOOP = "loop: dispatch and loss read"
WINDOW = "bench.window"


@dataclasses.dataclass
class Reduced:
    busy_s: float
    window_s: float
    modules: dict           # program name -> [seconds per execution]
    op_seconds: dict        # operation name -> total device seconds
    gaps: list              # [(label, seconds)], longest first

    def module_times(self, prefix: str) -> list[float]:
        """Execution times of the programs whose name starts with prefix."""
        out = []
        for name, ts in self.modules.items():
            if name.startswith(prefix):
                out.extend(ts)
        return out

    def breakdown(self, n: int = 10) -> dict:
        ops = sorted(self.op_seconds.items(), key=lambda kv: -kv[1])[:n]
        return {"device_ops": [[k, v] for k, v in ops],
                "idle_gaps": [[k, v] for k, v in self.gaps[:n]]}


def union(intervals):
    """Merged, sorted [start, end) intervals."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def _label(gap, spans) -> str:
    best, cover = LOOP, 0
    for s, e, name in spans:
        c = min(e, gap[1]) - max(s, gap[0])
        if c > cover:
            best, cover = name, c
    return best


_OPCODE = re.compile(r"\s([a-z][\w\-]*)\(")


def short_name(hlo: str) -> str:
    """"%fusion.5 = f32[...] fusion(...), calls=..." -> "fusion.5 fusion"."""
    lhs, _, rhs = hlo.partition(" = ")
    m = _OPCODE.search(" " + rhs)
    return f"{lhs.lstrip('%')} {m.group(1)}" if m else lhs.lstrip("%")


def self_seconds(ops) -> dict:
    """Device seconds per operation, less the time of operations nested
    inside it (a while loop's body ops)."""
    out: dict = defaultdict(float)
    stack: list = []                 # [end, name]
    for s, e, name in sorted(ops, key=lambda o: (o[0], -o[1])):
        while stack and stack[-1][0] <= s:
            stack.pop()
        if stack:
            out[stack[-1][1]] -= (min(e, stack[-1][0]) - s) / 1e9
        out[name] += (e - s) / 1e9
        stack.append([e, name])
    return dict(out)


def _clip(events, lo, hi):
    return [(max(s, lo), min(e, hi), n) for s, e, n in events
            if e > lo and s < hi]


def reduce_events(device_ops: list, modules: list, host_spans: list,
                  window_s: float) -> Reduced:
    """device_ops: per device, [(start_ns, end_ns, name)]; modules: device
    0's [(start_ns, end_ns, name)]; host_spans: [(start_ns, end_ns, name)]
    on the same clock; window_s: the window's length where no
    ``bench.window`` span marks it."""
    marks = [(s, e) for s, e, n in host_spans if n == WINDOW]
    if marks:
        lo, hi = marks[0]
        window_s = (hi - lo) / 1e9
        device_ops = [_clip(ops, lo, hi) for ops in device_ops]
        modules = [m for m in modules if m[0] >= lo and m[1] <= hi]
        host_spans = [h for h in host_spans if h[2] != WINDOW]
    busy = []
    op_seconds, dev0 = {}, []
    for d, ops in enumerate(device_ops):
        merged = union([s, e] for s, e, _ in ops)
        busy.append(sum(e - s for s, e in merged) / 1e9)
        if d == 0:
            op_seconds, dev0 = self_seconds(ops), merged
    gaps = []
    if device_ops:
        spans = sorted(host_spans)
        for (_, e0), (s1, _) in zip(dev0[:-1], dev0[1:], strict=True):
            gaps.append((_label((e0, s1), spans), (s1 - e0) / 1e9))
        gaps.sort(key=lambda g: -g[1])
    mods: dict = defaultdict(list)
    for s, e, name in modules:
        mods[name].append((e - s) / 1e9)
    return Reduced(busy_s=sum(busy) / max(len(busy), 1), window_s=window_s,
                   modules=dict(mods), op_seconds=op_seconds,
                   gaps=gaps)


def _module_name(name: str) -> str:
    # "jit_relaxed_step(1234)" -> "jit_relaxed_step"
    return name.split("(", 1)[0]


def events_of(xspace_path: Path):
    """(device_ops, modules, host_spans) of one ``.xplane.pb``."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(str(xspace_path))
    devices, modules, host = {}, [], []
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:"):
            try:
                idx = int(plane.name.rsplit(":", 1)[1])
            except ValueError:
                continue                    # a core's own plane, not a chip
            for line in plane.lines:
                if line.name == OPS_LINE:
                    devices[idx] = [(e.start_ns, e.end_ns,
                                     short_name(e.name))
                                    for e in line.events]
                elif line.name == MODULES_LINE and idx == 0:
                    modules = [(e.start_ns, e.end_ns, _module_name(e.name))
                               for e in line.events]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                host.extend((e.start_ns, e.end_ns, e.name)
                            for e in line.events
                            if e.name.startswith(HOST_PREFIX))
    return [devices[k] for k in sorted(devices)], modules, host


def reduce_dir(trace_dir: Path, window_s: float) -> Reduced | None:
    files = sorted(Path(trace_dir).rglob("*.xplane.pb"))
    if not files:
        return None
    return reduce_events(*events_of(files[-1]), window_s=window_s)
