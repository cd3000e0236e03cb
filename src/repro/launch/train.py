"""Training entry point.

    PYTHONPATH=src python -m repro.launch.train --arch dlrm-rm1 --smoke \
        --steps 100 [--strict] [--ckpt-dir /tmp/ckpt] [--resume]

``--full --batch 256`` trains at the configuration's published width (RM1:
20 tables x 1M rows x 32, 80 lookups per table per sample).

Runs the relaxed (paper) schedule by default with the two-tier asynchronous
checkpoint manager; ``--resume`` recovers from the checkpoint directory
(works across device counts — elastic restart).
"""
from __future__ import annotations

import argparse
import os
import time

import jax

from repro.configs import get_arch
from repro.configs.base import CheckpointConfig, TrainConfig
from repro.core.checkpoint import recovery
from repro.core.checkpoint.manager import CheckpointManager
from repro.data.synthetic import make_batches
from repro.data.lookahead import LookaheadIterator
from repro.training import train_loop
from repro.utils.compile_cache import CompileStats, use_compile_cache


def main():
    use_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="dlrm-rm1")
    ap.add_argument("--smoke", action="store_true", default=True)
    ap.add_argument("--full", dest="smoke", action="store_false")
    ap.add_argument("--steps", type=int, default=50)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--strict", action="store_true")
    ap.add_argument("--ckpt-dir", default="")
    ap.add_argument("--pool-backend",
                    choices=["dram", "pmem", "remote", "sharded"],
                    default="pmem",
                    help="emulated memory-pool backend for checkpoints")
    ap.add_argument("--pool-addr", default="",
                    help="remote backend: pool-server address "
                         "(unix:/path or tcp:host:port)")
    ap.add_argument("--pool-shards", default="",
                    help="sharded backend: comma-separated pool-server "
                         "addresses (one per memory node)")
    ap.add_argument("--pool-placement", default="",
                    help="sharded backend: explicit domain pins, e.g. "
                         "'manifest=1,dense=1' (unpinned domains hash "
                         "deterministically over the shard list)")
    ap.add_argument("--pool-tenant", default="default",
                    help="remote backend: tenant namespace on the pool node")
    ap.add_argument("--pool-quota", type=int, default=0,
                    help="remote backend: byte quota (0 = unlimited)")
    ap.add_argument("--pool-compress", choices=["none", "zlib", "int8"],
                    default="zlib",
                    help="pool-side compression for undo payloads and dense "
                         "snapshot blobs (int8 is lossy: relaxed rollback)")
    ap.add_argument("--pool-rebalance", type=float, default=0.0,
                    metavar="HIGH",
                    help="sharded backend: enable capacity-watermark "
                         "rebalancing — when a node's used/capacity crosses "
                         "HIGH (e.g. 0.75), live-migrate its largest "
                         "unpinned domain group to the emptiest node "
                         "(0 = off)")
    ap.add_argument("--pool-replica", type=int, default=-1, metavar="SHARD",
                    help="sharded backend: keep a read replica of the "
                         "embedding mirror on this shard index, refreshed "
                         "at the commit watermark (-1 = off)")
    ap.add_argument("--pool-ckpt-replica", type=int, default=-1,
                    metavar="SHARD",
                    help="sharded backend: commit-coupled replica of the "
                         "checkpoint domains (undo-log + manifest) on this "
                         "shard index — survives permanent loss of the "
                         "primary via replica promotion (-1 = off)")
    ap.add_argument("--pool-manifest-quorum", action="store_true",
                    help="sharded backend (>=3 nodes): keep 3 manifest "
                         "copies on distinct shards; recovery takes the "
                         "2-of-3 majority by sealed seq")
    ap.add_argument("--pool-secret",
                    default=os.environ.get("REPRO_POOL_SECRET", ""),
                    help="shared secret for the memory-node tcp handshake "
                         "(HMAC challenge; env REPRO_POOL_SECRET; unix "
                         "sockets are exempt)")
    ap.add_argument("--dense-interval", type=int, default=10)
    ap.add_argument("--resume", action="store_true")
    ap.add_argument("--lr", type=float, default=1e-3)
    ap.add_argument("--embed-lr", type=float, default=0.05)
    args = ap.parse_args()
    if args.resume and args.pool_backend == "dram":
        ap.error("--resume needs a pool that survives process death; "
                 "the dram backend is volatile — use --pool-backend "
                 "pmem or remote")
    if args.pool_backend == "remote" and not args.pool_addr:
        ap.error("--pool-backend remote needs --pool-addr "
                 "(start one: python -m repro.pool.server --addr ...)")
    if args.pool_backend == "sharded" and not args.pool_shards:
        ap.error("--pool-backend sharded needs --pool-shards addr1,addr2,... "
                 "(one pool server per memory node)")

    devs = jax.devices()
    dev = devs[0]
    print(f"[train] device {dev.platform} {dev.device_kind} x{len(devs)}",
          flush=True)
    compiles = CompileStats()
    bundle = get_arch(args.arch, smoke=args.smoke)
    cfg = bundle.model
    ckpt = CheckpointConfig(enabled=bool(args.ckpt_dir),
                            directory=args.ckpt_dir or "/tmp/repro_ckpt",
                            dense_interval=args.dense_interval,
                            pool_backend=args.pool_backend,
                            pool_addr=args.pool_addr,
                            pool_shards=args.pool_shards,
                            pool_placement=args.pool_placement,
                            pool_tenant=args.pool_tenant,
                            pool_quota=args.pool_quota,
                            pool_compress=args.pool_compress,
                            pool_rebalance=args.pool_rebalance,
                            pool_replica=args.pool_replica,
                            pool_ckpt_replica=args.pool_ckpt_replica,
                            pool_manifest_quorum=args.pool_manifest_quorum,
                            pool_secret=args.pool_secret)
    tc = TrainConfig(learning_rate=args.lr, embed_learning_rate=args.embed_lr,
                     checkpoint=ckpt)
    raw = make_batches(cfg, args.batch, args.seq, seed=0)
    batches = LookaheadIterator(raw, cfg, depth=2)

    init_fn, _, _, _ = train_loop.make_step_fns(cfg, tc)
    state = init_fn(jax.random.PRNGKey(tc.seed))
    start = 0
    mgr = None
    if args.ckpt_dir:
        if args.resume:
            rec = recovery.recover(args.ckpt_dir)
            state, start = recovery.resume_train_state(rec, state)
            print(f"[train] resumed at step {start} "
                  f"(embed@{rec.mirror_step}, dense@{rec.dense_step}, "
                  f"gap={rec.gap}, rolled_back={rec.rolled_back})")
            mgr = CheckpointManager(cfg, ckpt, pool=rec.pool)
            mgr.init_mirror(state["embed"], step=rec.mirror_step)
        else:
            mgr = CheckpointManager(cfg, ckpt, embed_init=state["embed"])

    t0 = time.time()

    def on_metrics(n, m):
        if n == start:
            print(f"[train] first step: {compiles}", flush=True)
        if n % 10 == 0:
            print(f"[train] step {n:5d} loss {float(m['loss']):.4f} "
                  f"({(time.time()-t0):.1f}s)", flush=True)

    state, losses = train_loop.train(
        cfg, tc, batches, args.steps, relaxed=not args.strict, state=state,
        start_step=start, ckpt_manager=mgr, on_metrics=on_metrics)
    print(f"[train] done: {len(losses)} steps, final loss {losses[-1]:.4f}")
    mem = dev.memory_stats() or {}
    print(f"[train] device peak_bytes_in_use "
          f"{mem.get('peak_bytes_in_use', 'not reported')}")
    if mgr:
        print(f"[train] checkpoint stats: {mgr.stats}")
        print(mgr.pool.metrics.report())


if __name__ == "__main__":
    main()
