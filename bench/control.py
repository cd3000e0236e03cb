"""Readings that the limits of ``correct`` are set from, for one configuration.

    python3 bench/control.py --config dlrm-rm1 --seeds 12 [--first-seed N]

For each seed, in one process on the chip:

- the program's first three steps, taken as a run's set-up takes them, and
  their gaps to the float32 reference (the lower readings);
- the control: the reference itself computed in the configuration's
  ``control_dtype`` (parameters and activations), the next precision below
  the one the configuration states, and its gaps (the upper readings);
- a planted fault: the reference with half of every batch left out, the
  mean taken over the rest.

A step that returns its state unchanged reads 1 on ``change_gap`` by the
measure and needs no run. Prints one JSON line per seed and a summary line.
"""
import argparse
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--config", required=True)
    ap.add_argument("--seeds", type=int, default=12)
    ap.add_argument("--first-seed", type=int, default=7_000_000_000)
    args = ap.parse_args(argv)
    sys.path[:0] = [str(ROOT / "src"), str(ROOT)]
    import os
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(ROOT / "bench"
                                                  / ".jax_cache")
    from bench import checks, harness
    spec = spec_for(args.config)
    harness.require_devices(1)
    rows = spec.config["sizes"]["batch"] // 2
    worst = {}
    for i in range(args.seeds):
        seed = args.first_seed + 7919 * i
        t0 = time.time()
        ring = harness.feed.make_ring(spec.config["sizes"], spec.traffic,
                                      seed)
        prog = harness.program_readings(spec, seed)
        ref = harness.reference_readings(spec, seed, ring)
        ctl = harness.reference_readings(
            spec, seed, ring, store=spec.config["control_dtype"],
            act=spec.config["control_dtype"])
        half = harness.reference_readings(spec, seed, ring, rows=rows)
        line = {"seed": seed, "program": checks.gaps(prog, ref),
                "control": checks.gaps(ctl, ref),
                "half_batch": checks.gaps(half, ref),
                "step_loss_gaps": {"program": checks.loss_gaps(prog, ref),
                                   "control": checks.loss_gaps(ctl, ref)},
                "ref_losses": ref["losses"],
                "seconds": round(time.time() - t0, 2)}
        print(json.dumps(line), flush=True)
        for side in ("program", "control", "half_batch"):
            agg = min if side != "program" else max
            for k, v in line[side].items():
                key = f"{side}.{k}"
                worst[key] = agg(worst.get(key, v), v)
    print(json.dumps({"config": args.config, "seeds": args.seeds,
                      "program_max": {k[8:]: v for k, v in worst.items()
                                      if k.startswith("program.")},
                      "control_min": {k[8:]: v for k, v in worst.items()
                                      if k.startswith("control.")},
                      "half_batch_min": {k[11:]: v for k, v in worst.items()
                                         if k.startswith("half_batch.")}}),
          flush=True)
    return 0


def spec_for(config: str, smoke: bool = False):
    """A spec for the configuration with no checkpointing; at smoke size
    with ``smoke``."""
    from bench import harness
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    cell = next(c for c in bench["workloads"] if c["config"] == config)
    spec = harness.load_spec(cell["name"])
    spec.traffic = dict(spec.traffic, checkpoint=False)
    if smoke:
        spec.config = smoke_config(spec.config)
    return spec


def smoke_config(config: dict) -> dict:
    """The configuration at the program's smoke preset. The file's
    ``"smoke"`` block gives the sizes that change there. A file with
    scalar tables (``bench/layout.py``) that has none gets those of DLRM
    RM1 and RM3: 2,048 rows a table, a narrow MLP in float32, batch 16; a
    file with per-table lists has to bring its own."""
    from bench.layout import layout
    sizes = config["sizes"]
    if "smoke" in config:
        smoke = config["smoke"]
    elif layout(sizes).pooled:
        raise ValueError(f"{config['name']}.json lists its tables and has "
                         f"no \"smoke\" block")
    else:
        smoke = {"rows_per_table": 2048,
                 "bottom_mlp": [13, 64, sizes["embed_dim"]],
                 "top_mlp": [32, 1], "dtype": "float32", "batch": 16}
    return dict(config, smoke_preset=True, sizes=dict(sizes, **smoke))

if __name__ == "__main__":
    sys.exit(main())
