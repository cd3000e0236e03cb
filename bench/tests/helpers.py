"""Shared set-up of the benchmark's CPU tests."""
import json
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]


PENDING = ROOT / "bench" / "tests" / "data" / "ckpt_cells.json"


def spec_of(cell: str):
    """The cell's spec from BENCHMARK.json or, for a checkpointing cell that
    no chip run has proved yet, from its entries in ``PENDING``."""
    from bench import harness
    b = benchmark()
    if cell not in {c["name"] for c in b["workloads"]}:
        extra = json.loads(PENDING.read_text())
        b = {**b, **{k: b[k] + v for k, v in extra.items()}}
    return harness.load_spec(cell, b)


def smoke_spec(cell: str):
    """The cell's spec at the program's smoke preset."""
    from bench import control
    spec = spec_of(cell)
    spec.config = control.smoke_config(spec.config)
    return spec


def run_smoke(cell: str, scratch: Path, seed: int = 11, seconds: float = 1.0,
              trace: bool = False, spec=None) -> dict:
    """One run of the cell at smoke size on the CPU: the look for a chip
    takes the CPU device and the peaks are left empty, so the readers that
    need them return nothing. Pool images and traces go under
    ``scratch``."""
    import jax

    from bench import harness
    spec = spec or smoke_spec(cell)
    saved = (harness.SCRATCH, harness.require_devices, harness.peaks_for)
    harness.SCRATCH = scratch
    harness.require_devices = lambda chips: jax.devices()[:chips]
    harness.peaks_for = lambda kind: {}
    try:
        return harness.run_cell(spec, seed, seconds, trace,
                                t_start=time.time())
    finally:
        harness.SCRATCH, harness.require_devices, harness.peaks_for = saved


def benchmark() -> dict:
    return json.loads((ROOT / "BENCHMARK.json").read_text())


def limits_for(config: str) -> dict:
    """The limits of ``correct`` that the configuration's file states."""
    conf = next(c for c in benchmark()["configs"] if c["name"] == config)
    return json.loads((ROOT / conf["file"]).read_text())["limits"]
