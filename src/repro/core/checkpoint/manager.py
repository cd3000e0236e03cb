"""Two-tier asynchronous checkpoint manager (the CXL-MEM checkpointing logic)
over the emulated memory pool (``repro.pool``).

All persistent state lives in named pool domains of one ``PoolDevice``:

    embedding-mirror/rows   the data region (host mirror of the table)
    undo-log/*              the log region (per-step undo ring, COMMIT flags)
    manifest/manifest       A/B crash-atomic manifest (mirror/dense steps)
    dense/slot{0,1}         double-buffered dense snapshot blobs

Tier-E (embedding pool, every step — paper: "the embedding log should be
permanently stored for every batch"):
    1-3. ONE fused near-memory op (``nmp.undo_log_append`` via
       ``UndoRing.log_and_apply``): the memory node snapshots the touched
       mirror rows straight into the log slot, compresses them pool-side,
       persists payload + COMMIT flag with the two paper barriers, then
       applies the new row values (idempotent row update + persist). Only
       (step, idx, new_rows) cross the link; the undo image never does —
       the paper's "active" checkpointing logic living next to the CXL
       controller.
    4. advance the manifest (A/B slot write).
The commit/apply boundary stays a named fault point (hit *inside* the node),
so tests still crash exactly between COMMIT and apply on every backend.

Tier-M (dense params, every K steps — the *relaxed batch-aware checkpoint*):
    the pytree is serialized to a CRC'd blob and written to the dense slot
    the manifest does NOT currently point at; the manifest flips to it only
    after the blob persists. May trail tier-E by up to K batches. An optional
    writer deadline emulates "MLP logging stops when the top-MLP completes".

All pool work runs on a background writer thread, off the critical path —
``on_step`` only enqueues. ``flush()`` drains (end of training / tests).

Tracing. ``on_step`` marks its phases with profiler spans
(``repro.ckpt.touched_to_host``, ``.row_gather``, ``.dense_to_host``,
``.enqueue``); the writer thread marks each item (``repro.ckpt.tier_e``
with ``.log_and_apply`` and ``.manifest``; ``repro.ckpt.tier_m`` with
``.serialize`` and ``.blob_put``). Every span carries ``step=`` of the loop
step that queued the work. ``stats["enqueue_wait_s"]`` sums the time the
loop blocked for room in the queue; ``stats["queue_wait_s"]`` sums, over
items, the time from the loop's hand-off (the ``put`` call) to the writer
starting on the item.
"""
from __future__ import annotations

import os
import queue
import threading
import time
from typing import Any, Optional

import jax
import numpy as np
from jax.profiler import TraceAnnotation

from repro.core.checkpoint import store
from repro.core.checkpoint.undo_log import UndoRing
from repro.pool import compress as pool_compress
from repro.pool.allocator import JsonRegion, PoolAllocator
from repro.pool.device import PoolDevice, PoolError, make_pool
from repro.pool.faults import FaultSchedule, InjectedCrash
from repro.pool.nmp import NmpQueue


def _table_of(embed: dict) -> tuple[str, Any]:
    if "table" in embed:
        return "table", embed["table"]
    return "emb_tables", embed["emb_tables"]


def flatten_touched(cfg, touched: np.ndarray) -> np.ndarray:
    """Unique flat row ids (DLRM tables get per-table offsets)."""
    touched = np.asarray(touched)
    if cfg.arch_type == "dlrm":
        T = cfg.dlrm_num_tables
        R = cfg.dlrm_rows_per_table
        flat = (np.arange(T)[None, :, None] * R + touched).reshape(-1)
    else:
        flat = touched.reshape(-1)
    return np.unique(flat)


class CheckpointManager:
    def __init__(self, cfg, ckpt_cfg, *, embed_init: Optional[dict] = None,
                 pool: Optional[PoolDevice] = None,
                 faults: Optional[FaultSchedule] = None):
        self.cfg = cfg
        self.ccfg = ckpt_cfg
        self.root = ckpt_cfg.directory
        os.makedirs(self.root, exist_ok=True)
        self.pool = pool
        self.faults = faults
        if pool is not None and faults is not None and pool.faults is None:
            pool.faults = faults
        self._alloc: Optional[PoolAllocator] = None
        self.ring: Optional[UndoRing] = None
        self.manifest: Optional[JsonRegion] = None
        self.nmp: Optional[NmpQueue] = None
        self._q: queue.Queue = queue.Queue(maxsize=8)
        self._err: Optional[BaseException] = None
        self._worker = threading.Thread(target=self._run, daemon=True)
        self._worker.start()
        self.stats = {"tier_e": 0, "tier_m": 0, "tier_m_skipped": 0,
                      "bytes_e": 0, "bytes_m": 0,
                      "undo_raw_bytes": 0, "undo_stored_bytes": 0,
                      "dense_stored_bytes": 0,
                      "migrations": 0, "migration_link_bytes": 0,
                      "replica_refreshes": 0, "replica_link_bytes": 0,
                      "replica_refresh_failures": 0,
                      "ship_steps": 0, "ship_link_bytes": 0,
                      "ship_full_refreshes": 0,
                      "manifest_witness_failures": 0,
                      "enqueue_wait_s": 0.0, "queue_wait_s": 0.0}
        self._commit_hooks: list = []
        self._man_witnesses: list = []
        self._ship_gen: Optional[int] = None
        self._degraded_warned = False
        if embed_init is not None:
            self.init_mirror(embed_init)

    # -- pool plumbing -------------------------------------------------------
    def _open_pool(self, capacity_hint: int):
        if self.pool is None:
            backend = getattr(self.ccfg, "pool_backend", "pmem")
            addr = getattr(self.ccfg, "pool_addr", "")
            tenant = getattr(self.ccfg, "pool_tenant", "default")
            self.pool = make_pool(
                backend, path=os.path.join(self.root, "pool.img"),
                capacity=capacity_hint, faults=self.faults, addr=addr,
                tenant=tenant, quota=getattr(self.ccfg, "pool_quota", 0),
                shards=getattr(self.ccfg, "pool_shards", ""),
                placement=getattr(self.ccfg, "pool_placement", ""),
                rebalance=float(getattr(self.ccfg, "pool_rebalance", 0.0)
                                or 0.0),
                secret=getattr(self.ccfg, "pool_secret", ""),
                timeout=getattr(self.ccfg, "pool_timeout", None))
            # POOL.json lets recovery reopen the same node(s): pmem by image
            # path, remote by reconnecting to the surviving server under
            # the same tenant AND quota (a server restart re-registers the
            # tenant from the reconnect handshake; the tcp shared secret is
            # re-read from the environment, never persisted). For a sharded
            # pool it records the RESOLVED placement — ordered shard list,
            # explicit pins, and the numbered placement-epoch records —
            # so recovery reconnects every node and replays the epochs to
            # the identical assignment (a domain is never re-placed or
            # re-hashed).
            info = {"backend": backend, "addr": addr, "tenant": tenant,
                    "quota": getattr(self.ccfg, "pool_quota", 0),
                    "manifest_quorum": bool(getattr(
                        self.ccfg, "pool_manifest_quorum", False)),
                    "ckpt_replica": int(getattr(
                        self.ccfg, "pool_ckpt_replica", -1))}
            store.write_json_atomic(
                os.path.join(self.root, "POOL.json"), info)
        if getattr(self.pool, "backend", "") == "sharded":
            # the durable half of every epoch flip routes through here
            self.pool.epoch_sink = self.record_placement
            reb = float(getattr(self.ccfg, "pool_rebalance", 0.0) or 0.0)
            if reb > 0 and self.pool.rebalance is None:
                from repro.pool.placement import RebalancePolicy
                self.pool.rebalance = RebalancePolicy(high=reb)
            self.record_placement()
        self._alloc = PoolAllocator(self.pool)
        self.manifest = JsonRegion.create(self._alloc.domain("manifest"),
                                          "manifest")
        self.compress = getattr(self.ccfg, "pool_compress", "zlib")
        self._open_witnesses()
        self.ring = UndoRing(self._alloc, self.ccfg.max_undo_logs,
                             compress=self.compress)
        self.nmp = NmpQueue(self.pool)
        self.dense_dom = self._alloc.domain("dense")

    def _open_witnesses(self):
        """2-of-3 manifest quorum (sharded, >=3 nodes): pin two witness
        copies of the manifest (``manifest@w1``/``manifest@w2``) on the two
        shards after the primary's, so the three copies land on distinct
        nodes and losing ANY single one leaves a majority. The pins ride in
        the published placement — recovery finds the witnesses there and
        elects the majority by sealed seq."""
        self._man_witnesses = []
        if not bool(getattr(self.ccfg, "pool_manifest_quorum", False)) \
                or getattr(self.pool, "backend", "") != "sharded" \
                or self.pool.nshards < 3:
            return
        primary = self.pool.placement.place("manifest")
        pinned = False
        for k in (1, 2):
            wdom = f"manifest@w{k}"
            if self.pool.placement.explicit(wdom) is None:
                self.pool.placement = self.pool.placement.with_pin(
                    wdom, (primary + k) % self.pool.nshards)
                pinned = True
            try:
                self._man_witnesses.append(
                    JsonRegion.create(self._alloc.domain(wdom), "manifest"))
            except PoolError as e:      # a lost witness shard: 2-of-3 holds
                self._degraded("manifest_witness_failures", e)
        if pinned:
            self.record_placement()

    def _man_write(self, man: dict, point: str):
        """Advance the manifest: the primary copy first (the image a
        quorum-less recovery elects), then the witness fan-out. A dead
        witness is counted and skipped — never fatal; the surviving 2-of-3
        majority is what recovery reads."""
        self.manifest.write(man, point=point)
        for w in self._man_witnesses:
            try:
                w.write(man, point="manifest-witness")
            except PoolError as e:
                self._degraded("manifest_witness_failures", e)

    def _degraded(self, key: str, err: BaseException):
        """A replication-side failure (dead replica destination, lost
        witness shard) must degrade the redundancy accounting, never kill
        training — the primary committed; only the extra copy is behind.
        Counted per occurrence, logged once."""
        self.stats[key] += 1
        if not self._degraded_warned:
            self._degraded_warned = True
            print(f"[ckpt] replication degraded (training continues): {err}")

    def _hit(self, point: str):
        """Manager-level fault point (between pipeline stages)."""
        if self.faults is not None:
            if self.faults.hit(point) == "crash-after":
                raise InjectedCrash(point, self.faults.counts[point])

    def record_placement(self, placement=None):
        """Durably publish the pool's placement map into POOL.json — the
        commit point of every epoch flip. Superblock-style: the whole new
        image is written beside the old one and swapped in a single atomic
        publish, and every epoch record carries its own CRC, so recovery
        always reads either the pre-flip or the post-flip placement (a torn
        tail record degrades to the previous epoch, never a re-hash)."""
        pm = placement if placement is not None else self.pool.placement
        path = os.path.join(self.root, "POOL.json")
        try:
            info = store.read_json(path)
        except (OSError, ValueError):
            info = {"backend": "sharded",
                    "tenant": getattr(self.ccfg, "pool_tenant", "default"),
                    "quota": getattr(self.ccfg, "pool_quota", 0)}
        pj = pm.to_json()
        info.update(shards=pj["shards"], placement=pj["pin"],
                    epochs=pj["epochs"])
        store.write_json_atomic(path, info)

    def _maybe_rebalance(self, step: int):
        """Capacity-watermark rebalancing (writer thread, between tier ops):
        poll the per-shard used/capacity gauges at the policy's cadence and
        execute any proposed migration — copy, epoch flip (recorded through
        ``record_placement``), source GC — then rebind the region handles
        the move invalidated."""
        pol = getattr(self.pool, "rebalance", None)
        if pol is None or not pol.due(step):
            return
        for mig in pol.propose(self.pool):
            info = self.pool.migrate_domain(mig.domain, mig.dst,
                                            compress=self.compress)
            self.rebind_domains(info["moved"])
            self.stats["migrations"] += 1
            self.stats["migration_link_bytes"] += info["link_bytes"]

    def add_commit_hook(self, fn):
        """Register fn(step, idx) to run on the writer thread right after a
        tier-E commit's manifest advance — the point at which step N's rows
        are durably applied to the mirror. The serving tier uses this to
        invalidate exactly the touched hot-cache rows."""
        self._commit_hooks.append(fn)

    def _maybe_replicate(self, step: int):
        """Refresh the read-replica of the embedding mirror (sharded only):
        export the mirror regions to the pinned replica shard and stamp the
        commit watermark. Runs on the writer thread at the configured
        cadence — the cadence IS the replica's declared staleness bound.
        A dead replica destination degrades (counted, logged once), never
        kills training: the primary's commit already landed. Injected
        crashes are NOT swallowed — they are the drill's power event."""
        if getattr(self.pool, "backend", "") != "sharded":
            return
        dst = int(getattr(self.ccfg, "pool_replica", -1))
        every = max(1, int(getattr(self.ccfg, "pool_replica_every", 1)))
        if dst >= 0 and step % every == 0:
            try:
                info = self.pool.replicate_domain("embedding-mirror", dst,
                                                  compress=self.compress,
                                                  watermark=step)
                self.stats["replica_refreshes"] += 1
                self.stats["replica_link_bytes"] += info["link_bytes"]
                self.pool.metrics.record_replica(info["link_bytes"])
            except PoolError as e:
                self._degraded("replica_refresh_failures", e)
        self._maybe_ship(step)

    def _maybe_ship(self, step: int):
        """Commit-coupled replication of the CHECKPOINT domains (sharded
        only): keep ``undo-log`` — and, when no manifest quorum stands,
        ``manifest`` — survivable on the ``pool_ckpt_replica`` shard. The
        first ship, and any ring regrowth, is a full ``replicate_domain``
        image; every commit after that ships ONLY the committed slot's
        verbatim bytes (plus the tiny manifest image), so the replica
        trails the primary by at most the in-flight step — lag bounded in
        committed steps, not wall time."""
        dst = int(getattr(self.ccfg, "pool_ckpt_replica", -1))
        if dst < 0 or getattr(self.pool, "backend", "") != "sharded":
            return
        try:
            if self._ship_gen != self.ring.gen:
                info = self.pool.replicate_domain("undo-log", dst,
                                                  compress=self.compress,
                                                  watermark=step)
                self.stats["ship_full_refreshes"] += 1
                self.stats["ship_link_bytes"] += info["link_bytes"]
                self._ship_gen = self.ring.gen
            else:
                img = self.ring.slot_image(step)
                if img is None:
                    raise PoolError(f"undo slot for step {step} vanished "
                                    f"before shipping")
                name, slot_off, buf = img
                self.stats["ship_link_bytes"] += \
                    self.pool.ship_slot("undo-log", name, slot_off, buf)
            if not self._man_witnesses:
                info = self.pool.replicate_domain("manifest", dst,
                                                  compress=self.compress,
                                                  watermark=step)
                self.stats["ship_link_bytes"] += info["link_bytes"]
            self.stats["ship_steps"] += 1
        except PoolError as e:
            self._degraded("replica_refresh_failures", e)

    def rebind_domains(self, moved):
        """Re-resolve region handles after `moved` domains changed shards —
        their global offsets now encode the destination node."""
        moved = set(moved)
        if "embedding-mirror" in moved \
                and getattr(self, "mirror_region", None) is not None:
            self.mirror_region = \
                self._alloc.domain("embedding-mirror").get("rows")
        if "undo-log" in moved and self.ring is not None:
            self.ring = UndoRing(self._alloc, self.ccfg.max_undo_logs,
                                 compress=self.compress)
        if "manifest" in moved and self.manifest is not None:
            region = self._alloc.domain("manifest").get("manifest")
            if region is not None:
                self.manifest = JsonRegion(region)

    @property
    def mirror_rows(self) -> np.ndarray:
        """Writable view of the data region (cache side)."""
        return self.mirror_region.view_array()

    # -- data region ---------------------------------------------------------
    def init_mirror(self, embed: dict, step: int = -1):
        """Materialise the persistent 'data region' from the initial pool."""
        name, tab = _table_of(embed)
        arr = np.asarray(jax.device_get(tab), dtype=np.float32)
        self.table_name = name
        self.table_shape = arr.shape
        flat = arr.reshape(-1, arr.shape[-1])
        if self._alloc is None:
            self._open_pool(2 * flat.nbytes + (1 << 20))
        dom = self._alloc.domain("embedding-mirror")
        # a PROMOTED mirror still carries the replica's watermark stamp; the
        # moment training re-anchors the mirror at `step` that stamp is
        # stale — left in place it would clamp a FUTURE recovery back to the
        # old promotion watermark
        if dom.get("watermark") is not None:
            dom.free_region("watermark")
        self.mirror_region = dom.alloc(
            "rows", shape=flat.shape, dtype="float32")
        self.mirror_region.write_array(flat, tag="mirror-load")
        self.mirror_region.persist(point="mirror-load")
        man = self.manifest.read() or {"dense_step": -1, "dense_slot": 0,
                                       "dense_len": 0}
        man.update(mirror_step=step, table_name=name,
                   table_shape=list(arr.shape),
                   max_undo_logs=self.ccfg.max_undo_logs)
        self._man_write(man, point="manifest-init")

    # -- hooks ---------------------------------------------------------------
    def _raise_writer_err(self):
        if self._err is not None:
            err = self._err
            if isinstance(err, InjectedCrash):
                raise err
            raise RuntimeError("checkpoint writer failed") from err

    def on_step(self, step: int, state: dict, feed: Optional[dict]):
        """Called by the train loop after step N. Non-blocking."""
        self._raise_writer_err()
        if feed is None:   # strict mode: derive touched rows from the batch
            return
        with TraceAnnotation("repro.ckpt.touched_to_host", step=step):
            idx = flatten_touched(self.cfg, jax.device_get(feed["touched"]))
        # new row values: small device gather of exactly the touched rows
        with TraceAnnotation("repro.ckpt.row_gather", step=step):
            name, tab = _table_of(state["embed"])
            flat_tab = tab.reshape(-1, tab.shape[-1])
            new_rows = np.asarray(
                jax.device_get(jnp_take(flat_tab, idx)), dtype=np.float32)
        self._enqueue("tier_e", step, (idx, new_rows))
        if (self.ccfg.dense_interval > 0
                and step % self.ccfg.dense_interval == 0):
            with TraceAnnotation("repro.ckpt.dense_to_host", step=step):
                dense_np = jax.device_get(
                    {"dense": state["dense"], "opt_dense": state["opt_dense"],
                     "opt_embed": state["opt_embed"]})
            self._enqueue("tier_m", step, dense_np)

    def _enqueue(self, kind: str, step: int, payload):
        """Hand an item to the writer; blocks while the queue is full."""
        t0 = time.monotonic()
        with TraceAnnotation("repro.ckpt.enqueue", step=step):
            self._q.put((kind, step, payload, t0))
        self.stats["enqueue_wait_s"] += time.monotonic() - t0

    def flush(self):
        self._q.join()
        self._raise_writer_err()

    def close(self):
        try:
            self.flush()
        finally:
            if self.pool is not None:
                self.pool.close()

    # -- writer thread -------------------------------------------------------
    def _run(self):
        while True:
            kind, step, payload, t_enq = self._q.get()
            try:
                if self._err is not None:
                    continue           # crashed: the machine is down
                self.stats["queue_wait_s"] += time.monotonic() - t_enq
                with TraceAnnotation(f"repro.ckpt.{kind}", step=step):
                    if kind == "tier_e":
                        self._do_tier_e(step, *payload)
                    else:
                        self._do_tier_m(step, payload, t_enq)
            except BaseException as e:  # surfaced on next on_step/flush
                self._err = e
            finally:
                self._q.task_done()

    def _do_tier_e(self, step: int, idx: np.ndarray, new_rows: np.ndarray):
        # 1-3: fused near-memory op — capture + compressed log + COMMIT +
        # apply, all inside the pool; only (step, idx, new_rows) crossed the
        # link to get here. The commit/apply crash window lives inside the
        # op (fault point "tier_e.between-commit-and-apply").
        with TraceAnnotation("repro.ckpt.log_and_apply", step=step):
            info = self.ring.log_and_apply(step, self.mirror_region, idx,
                                           new_rows)
        self._hit("tier_e.between-apply-and-manifest")
        # 4: persistent step flag
        with TraceAnnotation("repro.ckpt.manifest", step=step):
            man = self.manifest.read()
            man["mirror_step"] = step
            self._man_write(man, point="manifest-advance")
        self.ring.gc(step - self.ccfg.max_undo_logs)
        self.stats["tier_e"] += 1
        self.stats["bytes_e"] += idx.nbytes + new_rows.nbytes
        self.stats["undo_raw_bytes"] += info.get("raw", 0)
        self.stats["undo_stored_bytes"] += info.get("stored", 0)
        for hook in self._commit_hooks:
            hook(step, idx)
        self._maybe_replicate(step)
        self._maybe_rebalance(step)

    def _do_tier_m(self, step: int, dense_np: dict, t_enq: float):
        if (self.ccfg.writer_deadline_s
                and time.monotonic() - t_enq > self.ccfg.writer_deadline_s):
            self.stats["tier_m_skipped"] += 1      # relaxed ckpt: never block
            return
        with TraceAnnotation("repro.ckpt.serialize", step=step):
            blob = store.serialize_tree(dense_np, {"step": step})
        man = self.manifest.read()
        slot = 1 - man.get("dense_slot", 1)        # write the spare slot
        # the pool stores a framed (possibly compressed) image; size the
        # region for the frame's worst case (mode falls back to raw)
        need = pool_compress.framed_len(len(blob))
        cap = max(need, 1 << 12)
        region = self.dense_dom.get(f"slot{slot}")
        if region is None or region.nbytes < need:
            if region is not None:
                # same-name realloc would leak the old entry (and its quota
                # share) in the directory: free explicitly, then alloc
                self.dense_dom.free_region(f"slot{slot}")
            region = self.dense_dom.alloc(
                f"slot{slot}", shape=(int(cap * 1.5),), dtype="uint8")
        # compressed at the pool, persisted over exactly the written range
        with TraceAnnotation("repro.ckpt.blob_put", step=step):
            stored = self.nmp.blob_put(region, blob, compress=self.compress,
                                       point="dense-blob")
        man.update(dense_step=step, dense_slot=slot, dense_len=stored)
        self._man_write(man, point="manifest-dense")
        self.stats["tier_m"] += 1
        self.stats["bytes_m"] += len(blob)
        self.stats["dense_stored_bytes"] += stored


def jnp_take(flat_tab, idx: np.ndarray):
    import jax.numpy as jnp
    return jnp.take(flat_tab, jnp.asarray(idx), axis=0)
