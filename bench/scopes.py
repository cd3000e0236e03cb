"""The step program's device time by named phase, and the loop's host time,
from the program's own spans in a profiler trace.

The step program names its phases with ``jax.named_scope``
(``training/train_loop.py``, ``models/dlrm.py``); the backward pass
inherits the forward's names. A TPU trace carries all that is needed to
read them back without compiling anything: the ``/host:metadata`` plane
holds each program's HLO (stat ``Hlo Proto``), whose instructions keep the
scope in their ``op_name`` metadata, and the device's ``XLA Ops`` line
names each executed instruction. An instruction takes the innermost phase
its ``op_name`` names; one without (a relayout loop XLA inserted, its body,
an async copy) takes the phase of the instruction that calls its
computation (its ``while`` or fusion), and otherwise that of the first
instruction that uses its result. What is still unattributed is
``(unscoped)``. Device time is each operation's self time (a loop's body
operations are taken out of the loop's own time), summed over the
executions of ``jit_relaxed_step`` that lie wholly inside the benchmark's
``bench.window`` span.

The host loop marks each iteration with ``repro.train.step`` and its parts
with ``repro.train.*`` spans (``training/train_loop.train``).
"""
from __future__ import annotations

import dataclasses
import functools
import re
from collections import defaultdict
from pathlib import Path

from bench import traces

PROGRAM = "jit_relaxed_step"
PHASES = ("bottom_mlp", "interaction", "top_mlp", "embed_grad",
          "dense_update", "embed_update", "prefetch", "ckpt_feed")
UNSCOPED = "(unscoped)"
STEP_SPAN = "repro.train.step"
LOSS_READ = "repro.train.loss_read"


# -- protobuf wire format ------------------------------------------------------


def _varint(buf, i: int):
    out = shift = 0
    while True:
        b = buf[i]
        i += 1
        out |= (b & 0x7F) << shift
        if b < 0x80:
            return out, i
        shift += 7


def _fields(buf):
    """(field number, value) of one message: an int for a varint or fixed
    field, a memoryview for a length-delimited one."""
    buf = memoryview(buf)
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        num, wire = key >> 3, key & 7
        if wire == 0:
            val, i = _varint(buf, i)
        elif wire == 1:
            val, i = int.from_bytes(buf[i:i + 8], "little"), i + 8
        elif wire == 5:
            val, i = int.from_bytes(buf[i:i + 4], "little"), i + 4
        elif wire == 2:
            size, i = _varint(buf, i)
            val, i = buf[i:i + size], i + size
        else:
            raise ValueError(f"unsupported protobuf wire type {wire}")
        yield num, val


def _ints(val) -> list[int]:
    """A repeated integer field's value, packed or not."""
    if isinstance(val, int):
        return [val]
    out, i = [], 0
    while i < len(val):
        v, i = _varint(val, i)
        out.append(v)
    return out


def _str(val) -> str:
    return bytes(val).decode("utf-8", "replace")


# -- the programs' HLO, from the trace -------------------------------------------


def hlo_protos(xspace: bytes) -> dict[str, bytes]:
    """Program name as the device's ``XLA Modules`` line gives it (e.g.
    ``jit_relaxed_step(1234)``) -> that program's serialized ``HloProto``."""
    out = {}
    for num, plane in _fields(xspace):                      # XSpace.planes
        if num != 1:
            continue
        name, entries, stat_names = None, [], {}
        for f, v in _fields(plane):
            if f == 2:                                      # XPlane.name
                name = _str(v)
            elif f == 4:                                    # event_metadata
                entries.append(v)
            elif f == 5:                                    # stat_metadata
                for k, sm in _fields(v):
                    if k == 2:
                        meta = dict(_fields(sm))
                        stat_names[meta.get(1, 0)] = _str(meta.get(2, b""))
        if name != "/host:metadata":
            continue
        for entry in entries:
            for k, em in _fields(entry):
                if k != 2:
                    continue
                prog, blob = None, None
                for f, v in _fields(em):                    # XEventMetadata
                    if f == 2:
                        prog = _str(v)
                    elif f == 5:                            # XStat
                        stat = dict(_fields(v))
                        if stat_names.get(stat.get(1)) == "Hlo Proto":
                            blob = bytes(stat.get(6, b""))
                if prog and blob:
                    out[prog] = blob
    return out


@dataclasses.dataclass
class Inst:
    """One HLO instruction, as far as attribution needs it."""
    op_name: str
    computation: int          # id of the computation that holds it
    operands: list            # names of the instructions it reads
    called: list              # ids of the computations it calls


def instructions(hlo_proto: bytes) -> dict[str, Inst]:
    """Instruction name -> its op_name, computation, operands and the
    computations it calls, for every computation of the module."""
    insts, by_id = {}, {}
    for num, module in _fields(hlo_proto):                  # HloProto
        if num != 1:
            continue
        for f, comp in _fields(module):                     # HloModuleProto
            if f != 3:
                continue
            cid, body = None, []
            for g, v in _fields(comp):                      # computation
                if g == 5:
                    cid = v
                elif g == 2:
                    body.append(v)
            for raw in body:                                # instruction
                name, iid, op_name, operands, called = "", None, "", [], []
                for h, v in _fields(raw):
                    if h == 1:
                        name = _str(v)
                    elif h == 7:                            # OpMetadata
                        op_name = _str(dict(_fields(v)).get(2, b""))
                    elif h == 35:
                        iid = v
                    elif h == 36:
                        operands.extend(_ints(v))
                    elif h == 38:
                        called.extend(_ints(v))
                insts[name] = Inst(op_name, cid, operands, called)
                by_id[iid] = name
    for inst in insts.values():
        inst.operands = [by_id[i] for i in inst.operands if i in by_id]
    return insts


_SPLIT = re.compile(r"[/()]")


def phase_of(op_name: str) -> str | None:
    """The innermost phase that an ``op_name`` path names, e.g.
    ``jit(relaxed_step)/transpose(jvp(bottom_mlp))/dot_general`` ->
    ``bottom_mlp``."""
    found = [p for p in _SPLIT.split(op_name) if p in PHASES]
    return found[-1] if found else None


def assign(insts: dict[str, Inst]) -> dict[str, str]:
    """Instruction name -> phase, or ``UNSCOPED``: its own op_name first,
    then the instruction that calls its computation, then the first
    instruction that uses its result. Each step leads up the call graph or
    forward in a computation, so the search ends."""
    callers, users = {}, defaultdict(list)
    for name, inst in insts.items():
        for c in inst.called:
            callers.setdefault(c, name)
        for o in inst.operands:
            users[o].append(name)
    memo: dict = {}

    def resolve(name: str):
        if name not in memo:
            inst = insts[name]
            got = phase_of(inst.op_name)
            caller = callers.get(inst.computation)
            for other in ([caller] if caller else []) + users[name]:
                if got is not None:
                    break
                got = resolve(other)
            memo[name] = got
        return memo[name]

    return {n: resolve(n) or UNSCOPED for n in insts}


# -- the trace's events ------------------------------------------------------------


@dataclasses.dataclass
class Scoped:
    """What one trace holds of the step program and the loop."""
    steps: int                  # executions of the program in the window
    module_s: float             # their device time
    phase_s: dict               # phase -> device self seconds
    has_scopes: bool            # the program names any phase at all
    op_s: dict                  # instruction -> (phase, self seconds)
    host: list                  # [(start_ns, end_ns, name, step)] in window

    def phase_ms(self, phases) -> float | None:
        """Device ms per step in ``phases``; None where the program names
        none of its phases or did not run."""
        if not self.has_scopes or not self.steps:
            return None
        return 1e3 * sum(self.phase_s.get(p, 0.0) for p in phases) / \
            self.steps

    def loop_host_ms(self) -> float | None:
        """Mean over the iterations inside the window of each
        ``repro.train.step`` span's length less its ``loss_read``: the host
        work the serial loop puts between two device steps."""
        steps = [h for h in self.host if h[2] == STEP_SPAN]
        reads = defaultdict(int)
        for s, e, name, n in self.host:
            if name == LOSS_READ:
                reads[n] += e - s
        if not steps:
            return None
        return sum((e - s) - reads[n] for s, e, _, n in steps) / \
            len(steps) / 1e6


def _short(name: str) -> str:
    # "%fusion.5 = f32[...] fusion(...)" -> "fusion.5"
    return name.partition(" = ")[0].lstrip("%").split(" ")[0]


def read_trace(path: Path) -> Scoped:
    """The phases of ``jit_relaxed_step`` and the program's host spans in
    one ``.xplane.pb``."""
    from jax.profiler import ProfileData
    raw = Path(path).read_bytes()
    ops, modules, host = [], [], []
    window = None
    for plane in ProfileData.from_serialized_xspace(raw).planes:
        if plane.name == "/device:TPU:0":
            for line in plane.lines:
                if line.name == traces.OPS_LINE:
                    ops = [(e.start_ns, e.end_ns, _short(e.name))
                           for e in line.events]
                elif line.name == traces.MODULES_LINE:
                    modules = [(e.start_ns, e.end_ns, e.name)
                               for e in line.events]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name == traces.WINDOW:
                        window = (e.start_ns, e.end_ns)
                    elif e.name.startswith("repro."):
                        st = dict(e.stats)
                        step = st.get("step", st.get("step_num"))
                        host.append((e.start_ns, e.end_ns, e.name, step))
    lo, hi = window or (float("-inf"), float("inf"))
    runs = [m for m in modules
            if traces._module_name(m[2]) == PROGRAM and m[0] >= lo
            and m[1] <= hi]
    host = [h for h in host if h[0] >= lo and h[1] <= hi]
    names = {m[2] for m in runs}
    protos = hlo_protos(raw)
    phase: dict = {}
    for prog in names:
        if prog in protos:
            phase.update(assign(instructions(protos[prog])))
    has_scopes = any(p != UNSCOPED for p in phase.values())
    inside = _inside(ops, runs)
    self_s = traces.self_seconds(inside)
    op_s = {n: (phase.get(n, UNSCOPED), s) for n, s in self_s.items()}
    phase_s: dict = defaultdict(float)
    for p, s in op_s.values():
        phase_s[p] += s
    return Scoped(steps=len(runs),
                  module_s=sum(e - s for s, e, _ in runs) / 1e9,
                  phase_s=dict(phase_s), has_scopes=has_scopes, op_s=op_s,
                  host=host)


def _inside(ops, runs):
    """The operations that start inside one of the program's executions."""
    runs = sorted(runs)
    out, j = [], 0
    for op in sorted(ops):
        while j < len(runs) and runs[j][1] <= op[0]:
            j += 1
        if j < len(runs) and runs[j][0] <= op[0]:
            out.append(op)
    return out


def of_run(run) -> Scoped | None:
    """The traced run's ``Scoped``, read once for all the readers; None in
    a run without a trace."""
    from bench import harness
    if run.trace is None:
        return None
    files = sorted((harness.SCRATCH / "trace" / run.spec.name)
                   .rglob("*.xplane.pb"))
    if not files:
        return None
    return _read_once(str(files[-1]), files[-1].stat().st_mtime_ns)


@functools.lru_cache(maxsize=1)
def _read_once(path: str, mtime_ns: int) -> Scoped:
    return read_trace(Path(path))
