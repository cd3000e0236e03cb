"""Benchmark entry point.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Runs one cell of ``BENCHMARK.json`` on the machine it is started on and
prints one JSON line last on standard output. Exits non-zero, with no
result, where JAX finds no TPU or fewer chips than the cell asks for.
JAX's persistent compilation cache is kept in ``bench/.jax_cache`` inside
the checkout; pool images and traces go to ``bench/.scratch``.
"""
import time

T_START = time.time()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]


def _env():
    """Paths only: the compile cache inside the checkout (the path is part
    of the cache key, so it is fixed), and the program on the path."""
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(ROOT / "bench" / ".jax_cache")
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    for p in (ROOT / "src", ROOT):
        if str(p) not in sys.path:
            sys.path.insert(0, str(p))


def parse(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    if args.seed < 0:
        ap.error("--seed must be a whole number >= 0")
    return args


def main(argv=None) -> int:
    args = parse(argv)
    _env()
    import repro  # noqa: F401  the system under test must be present
    import jax
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    from bench import checks, harness
    spec = harness.load_spec(args.workload)
    try:
        out = harness.run_cell(spec, args.seed, args.seconds,
                               bool(args.trace), t_start=T_START)
    except harness.NoAccelerator as e:
        print(f"bench: {e}", file=sys.stderr)
        return 2
    print("\n".join(checks.lines(out["checks"])), file=sys.stderr,
          flush=True)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
