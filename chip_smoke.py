#!/usr/bin/env python3
"""Smoke run of the failure-tolerant trainer on one TPU chip.

    python chip_smoke.py

Trains DLRM-RM1 at its published width (20 tables x 1M rows x 32, 80
lookups per table per sample, batch 256, random weights from a fixed seed)
through the normal entry point, ``python -m repro.launch.train``, with the
embedding mirror, the undo log and the dense snapshots checkpointed into
the pmem pool:

  Phase A  trains with checkpointing. Once it prints step 10, the script
           SIGKILLs it and waits for it to exit.
  Phase B  runs the same command with ``--resume`` for 10 steps. It must
           resume at step >= 1, print finite losses and finish.

Each phase is a child process with ``JAX_PLATFORMS=tpu``, so a child that
finds no chip fails instead of running on the CPU. This process touches no
JAX until every child has exited, so each child can open the chip. Both
children share one persistent compilation cache: Phase B should hit what
Phase A compiled, which the two compile readings show.

The lines before the last are smoke readings, not benchmark numbers. The
last line is one JSON object naming the device. The script exits non-zero,
and prints no such line, if a phase failed or the device is not a TPU.
"""
from __future__ import annotations

import json
import math
import os
import re
import shutil
import signal
import subprocess
import sys
import threading

REPO = os.path.dirname(os.path.abspath(__file__))
SRC = os.path.join(REPO, "src")
CKPT_DIR = os.path.join(REPO, ".chip_smoke", "ckpt")   # gitignored
TRAIN = ["--arch", "dlrm-rm1", "--full", "--batch", "256",
         "--pool-backend", "pmem"]
KILL_AT_STEP = 10
RESUME_STEPS = 10
PHASE_TIMEOUT_S = 540

_DEVICE = re.compile(r"^\[train\] device (\S+) (.+) x(\d+)$")
_FIRST = re.compile(r"^\[train\] first step: (\d+) programs, ([\d.]+)s "
                    r"backend compile, (\d+) persistent-cache hits")
_STEP = re.compile(r"^\[train\] step\s+(\d+) loss (\S+)")
_RESUMED = re.compile(r"^\[train\] resumed at step (\d+) .*"
                      r"rolled_back=(True|False)\)")
_DONE = re.compile(r"^\[train\] done: (\d+) steps, final loss (\S+)")
_PEAK = re.compile(r"^\[train\] device peak_bytes_in_use (\S+)")


class SmokeError(RuntimeError):
    pass


def _run_child(name: str, args: list, env: dict, kill_at_step=None) -> dict:
    """Run one trainer child, echo its output, and parse its readings.
    With ``kill_at_step``, SIGKILL it once it prints that step."""
    cmd = [sys.executable, "-u", "-m", "repro.launch.train", *args]
    print(f"[smoke] phase {name}: {' '.join(cmd[1:])}", flush=True)
    proc = subprocess.Popen(cmd, cwd=REPO, env=env, text=True,
                            stdout=subprocess.PIPE, stderr=subprocess.STDOUT)
    timer = threading.Timer(PHASE_TIMEOUT_S, proc.kill)
    timer.start()
    out = {"lines": [], "losses": [], "killed": False}
    try:
        for line in proc.stdout:
            line = line.rstrip("\n")
            out["lines"].append(line)
            print(f"  [{name}] {line}", flush=True)
            if m := _DEVICE.match(line):
                out["platform"], out["kind"] = m.group(1), m.group(2)
            elif m := _FIRST.match(line):
                out["compile_s"] = float(m.group(2))
                out["cache_hits"] = int(m.group(3))
            elif m := _RESUMED.match(line):
                out["resumed_at"] = int(m.group(1))
                out["rolled_back"] = m.group(2) == "True"
            elif m := _STEP.match(line):
                out["step"] = int(m.group(1))
                out["losses"].append(float(m.group(2)))
                if kill_at_step is not None and out["step"] >= kill_at_step:
                    proc.send_signal(signal.SIGKILL)
                    out["killed"] = True
                    break
            elif m := _DONE.match(line):
                out["final_loss"] = float(m.group(2))
            elif m := _PEAK.match(line):
                out["peak_bytes"] = m.group(1)
        proc.stdout.close()
        out["rc"] = proc.wait()
    finally:
        timer.cancel()
        if proc.poll() is None:
            proc.kill()
            proc.wait()
    return out


def _expect(cond: bool, name: str, what: str, out: dict):
    if not cond:
        tail = "\n".join(out["lines"][-15:])
        raise SmokeError(f"phase {name}: {what} (exit code {out['rc']}); "
                         f"last output:\n{tail}")


def _check_device(name: str, out: dict, platform: str):
    _expect("platform" in out, name,
            f"the trainer found no {platform.upper()} "
            f"(JAX_PLATFORMS={platform})", out)
    _expect(out["platform"] == platform, name,
            f"the trainer ran on {out['platform']}, not {platform}", out)


def run_phases(train_args: list, ckpt_dir: str, env: dict,
               platform: str = "tpu") -> dict:
    """Phase A (train, SIGKILL at step 10) then Phase B (resume, finish).
    Raises SmokeError on any failure; returns both phases' readings."""
    shutil.rmtree(ckpt_dir, ignore_errors=True)
    a = _run_child("A", [*train_args, "--steps", str(4 * KILL_AT_STEP),
                         "--ckpt-dir", ckpt_dir], env,
                   kill_at_step=KILL_AT_STEP)
    _check_device("A", a, platform)
    _expect(a["killed"], "A", f"exited before step {KILL_AT_STEP}", a)
    _expect(a["rc"] == -signal.SIGKILL, "A", "did not die of SIGKILL", a)

    b = _run_child("B", [*train_args, "--steps", str(RESUME_STEPS),
                         "--ckpt-dir", ckpt_dir, "--resume"], env)
    _check_device("B", b, platform)
    _expect(b["rc"] == 0, "B", "the resumed trainer failed", b)
    _expect(b.get("resumed_at", 0) >= 1, "B", "did not resume at step >= 1",
            b)
    losses = b["losses"] + [b.get("final_loss", math.nan)]
    _expect(all(math.isfinite(x) for x in losses), "B",
            f"non-finite or missing losses {losses}", b)
    return {"A": a, "B": b}


def main() -> int:
    if not os.path.isdir(os.path.join(SRC, "repro")):
        print(f"chip_smoke: FAILED: no repro package under {SRC}; run this "
              f"script from a checkout of the repository", file=sys.stderr)
        return 2
    sys.path.insert(0, SRC)
    from repro.utils.compile_cache import use_compile_cache
    cache = use_compile_cache()            # exported to both children
    env = {**os.environ, "JAX_PLATFORMS": "tpu",
           "PYTHONPATH": os.pathsep.join(
               p for p in (SRC, os.environ.get("PYTHONPATH")) if p)}
    print(f"[smoke] compile cache {cache}", flush=True)
    try:
        r = run_phases(TRAIN, CKPT_DIR, env)
    except SmokeError as e:
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr)
        return 1
    finally:
        shutil.rmtree(os.path.dirname(CKPT_DIR), ignore_errors=True)

    import jax                              # every child has exited
    devs = jax.devices()
    if devs[0].platform != "tpu":
        print(f"chip_smoke: FAILED: no TPU; JAX found {devs[0].platform}",
              file=sys.stderr)
        return 1
    a, b = r["A"], r["B"]
    print(f"[smoke reading] device {b['kind']}")
    print(f"[smoke reading] phase A: killed after step {a['step']}; first "
          f"step compile {a.get('compile_s')}s "
          f"({a.get('cache_hits')} persistent-cache hits)")
    print(f"[smoke reading] phase B: resumed at step {b['resumed_at']} "
          f"rolled_back={b['rolled_back']}; first step compile "
          f"{b.get('compile_s')}s ({b.get('cache_hits')} persistent-cache "
          f"hits); final loss {b['final_loss']}; "
          f"peak_bytes_in_use {b.get('peak_bytes')}")
    print(json.dumps({"ok": True, "device": {
        "platform": devs[0].platform, "kind": devs[0].device_kind,
        "count": len(devs)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
