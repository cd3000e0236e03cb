"""Shared memory-node demo: two trainers, one pool, per-tenant accounting.

Starts a standalone pool-server (the memory node), then trains TWO models
against it as different tenants ("trainer-a", "trainer-b"), each with a
byte quota and its own ``CheckpointManager``. Both run in this one process
and step in turn: a chip belongs to one process at a time, so two trainer
processes could not share a one-chip host. When both finish, the demo
connects as an operator and prints the per-tenant traffic/energy the node
attributed to each trainer, then proves the isolation properties:

  * a third tenant ("eve") cannot read either trainer's domains — raw-offset
    access outside its owned regions raises ``TenantIsolationError``;
  * allocating past a tenant's byte quota raises ``QuotaExceededError``.

    PYTHONPATH=src python examples/shared_pool_demo.py
"""
import os
import shutil
import subprocess
import sys

ROOT = "/tmp/repro_shared_pool_demo"
REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
QUOTA = 64 << 20
STEPS = 8


def train_tenants(addr: str):
    """Two tenants, two managers on one memory node, stepping in turn."""
    import jax

    from repro.configs import get_arch
    from repro.configs.base import CheckpointConfig, TrainConfig
    from repro.core.checkpoint.manager import CheckpointManager
    from repro.data.synthetic import make_batches
    from repro.training import train_loop

    b = get_arch("dlrm-rm1", smoke=True)
    tc = TrainConfig(learning_rate=3e-4, embed_learning_rate=0.01)
    init_fn, _, relaxed_step, warmup = train_loop.make_step_fns(b.model, tc)
    step, warm = jax.jit(relaxed_step), jax.jit(warmup)
    tenants = []
    for seed, tenant in enumerate(("trainer-a", "trainer-b")):
        # max_undo_logs trimmed so the undo ring fits the per-tenant byte
        # budget (the default 64-slot ring alone would blow a 64 MiB quota
        # for this model)
        cc = CheckpointConfig(directory=os.path.join(ROOT, tenant),
                              dense_interval=4, pool_backend="remote",
                              pool_addr=addr, pool_tenant=tenant,
                              pool_quota=QUOTA, max_undo_logs=8)
        data = make_batches(b.model, 16, 0, seed=seed)
        st = warm(init_fn(jax.random.PRNGKey(seed)), data.next(0))
        mgr = CheckpointManager(b.model, cc, embed_init=st["embed"])
        tenants.append({"name": tenant, "state": st, "data": data,
                        "mgr": mgr})
    for n in range(STEPS):
        for t in tenants:
            t["state"], m = step(t["state"], t["data"].next(n),
                                 t["data"].next(n + 1))
            t["mgr"].on_step(n, t["state"], m["ckpt_feed"])
    for t in tenants:
        t["mgr"].flush()
        print(f"[{t['name']}] done: {t['mgr'].stats}", flush=True)
        t["mgr"].close()


def main():
    shutil.rmtree(ROOT, ignore_errors=True)
    os.makedirs(ROOT)
    addr = "unix:" + os.path.join(ROOT, "pool.sock")
    print(f"== starting memory node at {addr} ==")
    server = subprocess.Popen(
        [sys.executable, "-m", "repro.pool.server", "--addr", addr,
         "--backend", "pmem", "--path", os.path.join(ROOT, "pool.img")],
        stdout=subprocess.PIPE, text=True, cwd=REPO,
        env={**os.environ, "PYTHONPATH": "src"})
    line = server.stdout.readline().strip()
    print(" ", line)
    assert "listening" in line, f"server failed to start: {line}"

    sys.path.insert(0, os.path.join(REPO, "src"))
    print("== training two tenants in turn, one process ==")
    train_tenants(addr)

    from repro.pool import (PoolMetrics, QuotaExceededError, RemotePool,
                            TenantIsolationError)

    print("== per-tenant accounting (as attributed by the memory node) ==")
    op = RemotePool(addr, tenant="operator")
    for name, snap in sorted(op.metrics_snapshot(scope="all").items()):
        m = PoolMetrics.from_snapshot(snap)
        print(f"-- tenant {name!r}: media={m.media_bytes()}B "
              f"link={m.link_bytes()}B energy={m.energy()['total']:.6f}J")

    print("== isolation drill ==")
    eve = RemotePool(addr, tenant="eve", quota=1 << 16)
    from repro.pool.allocator import DATA_START, PoolAllocator
    try:
        eve.read(DATA_START, 64)
        raise SystemExit("FAILED: eve read another tenant's bytes")
    except TenantIsolationError as e:
        print(f"  cross-tenant read denied: {e}")
    try:
        PoolAllocator(eve).domain("grab").alloc("big", shape=(1 << 20,),
                                                dtype="uint8")
        raise SystemExit("FAILED: eve allocated past her quota")
    except QuotaExceededError as e:
        print(f"  over-quota alloc denied: {e}")

    server.terminate()
    server.wait()
    print("shared-pool demo PASSED")


if __name__ == "__main__":
    main()
