"""Serving entry point: batched greedy generation on a smoke config.

    PYTHONPATH=src python -m repro.launch.serve --arch tinyllama-1.1b \
        --batch 4 --prompt-len 16 --new-tokens 16

``--pool-backend`` routes the model's embedding lookups through the
pool-backed serving tier (``repro.serve.EmbeddingServeTier``): the table is
mirrored into the pool's ``embedding-mirror`` domain and every lookup the
jitted serve steps issue becomes a batched, hot-row-cached near-memory
gather. ``--pool-readonly`` connects remote backends as a read-only tenant —
the memory node denies every mutating op on that connection.
"""
from __future__ import annotations

import argparse
import os
import tempfile
import time

import jax
import numpy as np

from repro.configs import get_arch
from repro.data.synthetic import make_batches
from repro.models.registry import get_api
from repro.training.serve_loop import greedy_generate, pool_serving
from repro.utils.compile_cache import use_compile_cache


def _build_tier(args, params):
    from repro.pool import PoolAllocator, make_pool
    from repro.serve import EmbeddingServeTier

    root = args.pool_dir or tempfile.mkdtemp(prefix="serve_pool_")
    pool = make_pool(args.pool_backend,
                     path=os.path.join(root, "pool.img"),
                     capacity=1 << 22, addr=args.pool_addr,
                     shards=args.pool_shards,
                     readonly=args.pool_readonly)
    if not args.pool_readonly:
        table = np.asarray(jax.device_get(params["embed"]["table"]),
                           dtype=np.float32)
        alloc = PoolAllocator(pool)
        region = alloc.domain("embedding-mirror").alloc(
            "rows", shape=table.shape, dtype="float32")
        region.write_array(table, tag="mirror-load")
        region.persist(point="mirror-load")
    return EmbeddingServeTier(pool, cache_rows=args.pool_cache_rows)


def main():
    use_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="tinyllama-1.1b")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=16)
    ap.add_argument("--new-tokens", type=int, default=16)
    ap.add_argument("--pool-backend", default="",
                    help="dram|pmem|remote|sharded: serve embedding lookups "
                         "from the pool through the hot-row-cached tier")
    ap.add_argument("--pool-addr", default="",
                    help="remote backend: unix:/path or tcp:host:port")
    ap.add_argument("--pool-shards", default="",
                    help="sharded backend: comma list of node addrs")
    ap.add_argument("--pool-dir", default="",
                    help="pmem backend: directory for the pool image")
    ap.add_argument("--pool-cache-rows", type=int, default=4096)
    ap.add_argument("--pool-readonly", action="store_true",
                    help="connect remote backends as a read-only tenant "
                         "(assumes a trainer already materialised the "
                         "mirror)")
    args = ap.parse_args()

    bundle = get_arch(args.arch, smoke=True)
    cfg = bundle.model
    api = get_api(cfg)
    if api.decode_step is None:
        raise SystemExit(f"{args.arch} has no decode step")
    params = api.init(jax.random.PRNGKey(0), cfg)
    batch = make_batches(cfg, args.batch, args.prompt_len).next(0)
    extras = {k: v for k, v in batch.items()
              if k in ("frames", "vision_embeds", "positions3")}

    tier = _build_tier(args, params) if args.pool_backend else None

    def generate():
        return greedy_generate(cfg, params, batch["tokens"],
                               args.new_tokens,
                               max_seq=args.prompt_len + args.new_tokens,
                               extras=extras)

    t0 = time.time()
    if tier is not None:
        with pool_serving(tier):
            toks = generate()
    else:
        toks = generate()
    dt = time.time() - t0
    print(f"[serve] generated {toks.shape} in {dt:.2f}s "
          f"({args.batch * args.new_tokens / dt:.1f} tok/s)")
    print("[serve] sample:", toks[0].tolist())
    if tier is not None:
        s = tier.stats()
        print(f"[serve] pool tier: {s['requests']} lookups, "
              f"hit_rate={s['hit_rate']:.2f} p50={s['p50_ms']:.2f}ms "
              f"p99={s['p99_ms']:.2f}ms inval={s['invalidations']}")


if __name__ == "__main__":
    main()
