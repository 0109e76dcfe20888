"""Raylet: per-node manager — lease scheduling, worker pool, object directory.

TPU-native analog of the reference raylet (ref: src/ray/raylet/node_manager.h,
HandleRequestWorkerLease node_manager.cc:2003; scheduling/
cluster_task_manager.h; worker_pool.h; wait_manager.h; local_object_manager.h).

Design deltas from the reference, driven by the TPU runtime model:
 * the object store is a shared tmpfs namespace per session (object_store.py),
   so the dependency manager's pull path degenerates to a directory lookup on
   one host — multi-host transfer rides the DCN object-transfer service
   (future native component) behind the same `wait_objects` contract;
 * scheduling understands TPU chips natively: every lease carrying "TPU"
   resources is assigned physical chip ids from a per-chip accounting pool
   (whole chips exclusive, fractional leases bin-packed onto shared chips —
   `_allocate_chips`), and the executing worker exports them as
   TPU_VISIBLE_CHIPS / RAY_TPU_CHIP_IDS before user code runs (ref:
   python/ray/_private/accelerators/tpu.py:31, promoted from env-var
   convention into scheduler state; tests/test_topology.py). Slice-spread
   placement-group gangs map onto one ICI slice in host_index order
   (gcs._plan_bundles_on_slice; SURVEY §5.8, §7.1.2);
 * hybrid scheduling policy: pack onto the local node below a utilization
   threshold, spread above it, spill to the best remote node otherwise
   (ref: policy/hybrid_scheduling_policy.h:50).
"""

from __future__ import annotations

import asyncio
import collections
import json
import os
import random
import signal
import subprocess
import sys
import time
from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional, Set, Tuple

from . import device_plane, failpoints
from .config import global_config, session_log_dir
from .ids import ActorID, NodeID, ObjectID, WorkerID
from .object_store import SharedObjectStore
from .rpc import (ConnectionLost, RpcClient, RpcError, RpcServer,
                  ServerConnection, background)
from .task_spec import (
    DefaultSchedulingStrategy,
    NodeAffinitySchedulingStrategy,
    NodeLabelSchedulingStrategy,
    PlacementGroupSchedulingStrategy,
    ResourceSet,
    SpreadSchedulingStrategy,
    label_expr_matches,
)


# how long a retired chip worker may take to exit before it is killed
# (_release_chips_when_gone)
_CHIP_EXIT_GRACE_S = 30.0


@dataclass
class WorkerHandle:
    worker_id: WorkerID
    pid: int
    address: str                      # the worker's own RPC socket
    conn: Optional[ServerConnection] = None
    idle_since: float = field(default_factory=time.monotonic)
    lease: Optional["Lease"] = None
    actor_id: Optional[ActorID] = None  # dedicated actor worker
    alive: bool = True
    # never leased yet: user code has not run here, so jax is not
    # imported. Only such a worker may take a lease that holds chips
    # (_pop_worker)
    fresh: bool = True


@dataclass
class Lease:
    lease_id: int
    worker: WorkerHandle
    resources: ResourceSet
    owner_address: str
    pg_key: Optional[tuple] = None    # (pg_id, bundle_idx) the lease lives in
    # fast-lane leases are preemptible when idle: under pending demand
    # the raylet pushes "reclaim_lease" to the owner, who returns the
    # worker if the lane has nothing in flight
    lane: bool = False
    conn: Optional[ServerConnection] = None
    reclaim_requested_at: float = 0.0
    # TPU chips granted to this lease as [(chip_id, fraction)] — the
    # worker sees them as TPU_VISIBLE_CHIPS (ref:
    # python/ray/_private/accelerators/tpu.py:31, promoted from env-var
    # convention to first-class per-lease accounting)
    chips: List[tuple] = field(default_factory=list)
    # CPU share temporarily given back while the worker blocks on object
    # resolution (ref: NotifyDirectCallTaskBlocked in node_manager.cc —
    # without this, a gang of dep-waiting workers deadlocks the node)
    blocked_cpu: Optional[ResourceSet] = None


@dataclass
class _ChipHold:
    """The TPU of a finished lease — chips and scalar — while the process
    that claimed it may still hold the device open
    (_retire_chip_worker)."""
    pid: int
    chips: List[tuple]
    tpu: float
    pg_key: Optional[tuple]


@dataclass
class _PendingLease:
    payload: dict
    future: asyncio.Future
    resources: ResourceSet
    queued_at: float = 0.0  # monotonic; damps queue->spillback bouncing


class NodeResources:
    """Per-node resource accounting. Backed by the native lease-scheduler
    engine when available (native/core_tables.cc — the C++ half of the
    reference's cluster_resource_scheduler/local_resource_manager pair);
    the Python ResourceSet arithmetic is the fallback."""

    _NODE = 1  # single-node handle inside the native engine

    def __init__(self, total: Dict[str, float]):
        self.total = ResourceSet(total)
        self._native = None
        try:
            from .._native import LeaseScheduler, native_unavailable_reason

            if native_unavailable_reason() is None:
                self._native = LeaseScheduler(local_node=self._NODE)
                self._native.node_upsert(self._NODE, self.total.to_dict(),
                                         self.total.to_dict())
        except Exception:
            self._native = None
        self._available = self.total.copy()  # fallback bookkeeping

    @property
    def available(self) -> ResourceSet:
        if self._native is not None:
            return ResourceSet({
                k: self._native.avail(self._NODE, k)
                for k in self.total.to_dict()})
        return self._available

    def try_allocate(self, req: ResourceSet) -> bool:
        if self._native is not None:
            return self._native.try_allocate(self._NODE, req.to_dict())
        if not req.fits(self._available):
            return False
        self._available.subtract(req)
        return True

    def force_allocate(self, req: ResourceSet) -> None:
        """Unconditional subtraction — availability may go transiently
        negative (a dep-blocked worker resuming re-takes its CPU even if
        the node is momentarily oversubscribed, matching the reference's
        unblock semantics)."""
        if self._native is not None:
            self._native.release(self._NODE,
                                 {k: -v for k, v in req.to_dict().items()})
            return
        self._available.subtract(req)

    def release(self, req: ResourceSet) -> None:
        if self._native is not None:
            self._native.release(self._NODE, req.to_dict())
            return
        self._available.add(req)
        # clamp against float drift
        for k, v in self._available.res.items():
            cap = self.total.get(k)
            if v > cap:
                self._available.res[k] = cap

    def utilization(self) -> float:
        avail = self.available
        best = 0.0
        for k, cap in self.total.res.items():
            if cap > 0:
                best = max(best, 1.0 - avail.get(k, 0.0) / cap)
        return best


class Raylet:
    def __init__(
        self,
        node_id: NodeID,
        session_name: str,
        socket_path: str,
        gcs_address: str,
        resources: Dict[str, float],
        store: SharedObjectStore,
        labels: Optional[Dict[str, str]] = None,
        advertise_host: Optional[str] = None,
    ):
        self.node_id = node_id
        self.session_name = session_name
        self.socket_path = socket_path
        self.gcs_address = gcs_address
        self.labels = labels or {}
        self.store = store
        self.resources = NodeResources(resources)
        self.server = RpcServer(socket_path, name=f"raylet-{node_id.hex()[:8]}",
                                advertise_host=advertise_host)
        self.server.register_all(self)
        self.server.on_disconnect = self._on_disconnect
        # constructed in start() from the (possibly port-resolved) gcs_address
        self.gcs: RpcClient = None  # type: ignore[assignment]
        self.transfer = None
        self.syncer = None

        cfg = global_config()
        self.cfg = cfg
        # bulk transfer plane: listener constructed in start() (needs the
        # resolved server address); the PullManager lives from birth so a
        # wait_objects arriving in the start() window can't hit None
        from .object_transfer import PullManager

        self.pulls = PullManager(
            cfg.object_transfer_max_inflight_bytes, self._pull)
        # worker pool
        self._workers: Dict[WorkerID, WorkerHandle] = {}
        self._idle: List[WorkerHandle] = []
        self._starting: int = 0
        self._register_waiters: List[asyncio.Future] = []
        max_workers = cfg.num_workers_soft_limit
        if max_workers > 0:
            self.max_workers = max_workers
        else:
            # The pool must be able to back every leasable CPU slot: the
            # node's ADVERTISED CPU resource, not the host core count —
            # a node faking num_cpus=8 on a 1-core box (tests, oversub-
            # scribed orchestration) would otherwise wedge the 5th
            # lease forever behind a 4-worker cap (actors hold workers
            # for life). (ref: worker_pool.h prestart/soft-limit ties
            # to num_cpus the same way.)
            ncpu = int(self.resources.total.get("CPU", 0))
            self.max_workers = max(4, ncpu, os.cpu_count() or 1)
        # leases
        self._leases: Dict[int, Lease] = {}
        self._next_lease_id = 1
        self._pending_leases: List[_PendingLease] = []
        self._worker_seq = 0  # names this node's worker log files
        # lease-request dedup by client request id, so a retried request
        # (reply lost, injected chaos, flaky DCN) returns the SAME grant
        # instead of leaking a second worker (ref: retryable_grpc_client.h +
        # lease idempotency in node_manager)
        self._lease_rid_grants: Dict[str, dict] = {}
        self._lease_rid_pending: Dict[str, asyncio.Future] = {}
        self._lease_id_to_rid: Dict[int, str] = {}
        # object directory + wait manager
        self._sealed: Dict[ObjectID, int] = {}          # oid -> size
        self._object_waiters: Dict[ObjectID, List[asyncio.Future]] = {}
        self._lost_objects: Set[ObjectID] = set()
        # inter-node object transfer (ref: object_manager/pull_manager.h:57,
        # push_manager.h:32 — chunked transfer over the control transport)
        self._peer_clients: Dict[str, RpcClient] = {}
        # broadcast-tree sender slots: oid -> {puller_hex: grant expiry}
        self._transfer_tokens: Dict[ObjectID, Dict[str, float]] = {}
        self._transfer_token_high: Dict[ObjectID, int] = {}  # high-water
        # grants per control connection, released the moment the puller's
        # connection drops (a crashed puller must not pin a sender slot
        # for the wall-clock TTL) — the TTL stays as the backstop
        self._token_conn_grants: Dict[object, set] = {}
        self._token_conn_watchers: Dict[object, asyncio.Task] = {}
        self._pull_sources: Dict[ObjectID, NodeID] = {}   # observability
        # cluster view (for spillback) — node_id -> (address, available)
        self._remote_nodes: Dict[NodeID, Tuple[str, ResourceSet]] = {}
        # hub-declared-dead nodes (node channel "removed"): the gossip
        # syncer cross-checks applied entries against this so a laggard
        # peer can't resurrect a dead node after its tombstone TTL
        # lapses; bounded so unbounded churn can't grow it forever
        self._dead_node_hexes: "collections.OrderedDict[str, None]" = (
            collections.OrderedDict())
        # node_id -> labels (incl. this node), for label-match scheduling
        self._node_labels: Dict[NodeID, Dict[str, str]] = {}
        self._worker_conns: Dict[ServerConnection, WorkerID] = {}
        self._spill_rr = 0
        self._resource_seq = 0
        self._subprocs: List[subprocess.Popen] = []
        # forkserver worker factory (see _spawn_via_factory)
        self._factory_proc: Optional[subprocess.Popen] = None
        self._factory_reader = None
        self._factory_writer = None
        self._factory_lock = asyncio.Lock()
        self._factory_pids: List[int] = []
        # (pg_id, bundle_idx) -> bundle-local resource accounting: reserved
        # total + what's still leasable within it (ref:
        # placement_group_resource_manager.h bundle resource bookkeeping)
        self._pg_bundles: Dict[tuple, NodeResources] = {}
        # per-chip TPU accounting: chip i carries a used fraction in
        # [0, 1]; whole-chip leases take exclusive chips, fractional
        # leases bin-pack onto shared ones (ref: accelerators/tpu.py
        # TPU_VISIBLE_CHIPS isolation + GPU fractional semantics)
        self._chip_used: List[float] = \
            [0.0] * int(self.resources.total.get("TPU", 0))
        # TPU of finished leases whose workers have not exited yet
        self._chip_holds: List[_ChipHold] = []
        # smoothed NTP-style estimate of (GCS clock - local clock);
        # None until the first clock-sync round completes
        self._clock_offset: Optional[float] = None
        # stall sentinel: per-scheduling-class EMA of completed task
        # durations (the adaptive RUNNING-too-long threshold's memory),
        # plus currently-flagged stalls so each hang alerts once
        self._class_ema: Dict[str, float] = {}
        self._stalled_tasks: Dict[str, dict] = {}
        self._stalled_transfers: Dict[str, dict] = {}
        # tail tolerance: node hex -> straggler score (EMA lateness over
        # cluster mean, from GCS straggler_scores), refreshed each
        # watchdog tick; scheduling deprioritizes nodes past threshold
        self._straggler_scores: Dict[str, float] = {}
        self._drained_workers: Set[int] = set()  # pids killed for draining
        # black-box plane: this raylet's own flight ring, plus the pids
        # whose exit we ORDERED (graceful shutdown pushes) — their
        # disconnect discards the flight file instead of bundling it
        self._blackbox = None
        self._expected_exits: Set[int] = set()
        from .config import TEMP_ROOT

        self._session_dir = os.path.join(TEMP_ROOT, session_name)

    # ------------------------------------------------------------------ setup
    async def start(self):
        await self.server.start()
        self.socket_path = self.server.address  # resolved (TCP port 0)
        # bulk transfer plane: its own listener so gigabyte chunk streams
        # never head-of-line-block control RPCs (object_transfer.py)
        from .object_transfer import TransferServer, _parse_addr

        kind = _parse_addr(self.server.address)
        if kind[0] == "unix":
            self.transfer = TransferServer(
                self.store, self.server.address + ".xfer",
                on_puller_gone=self._on_transfer_puller_gone)
        else:
            # bind-all, advertise the node's routable IP — same split the
            # control server uses (NAT/container hosts can't bind the
            # address they advertise)
            self.transfer = TransferServer(
                self.store, "0.0.0.0:0", advertise_host=kind[1],
                on_puller_gone=self._on_transfer_puller_gone)
        await self.transfer.start()
        self.gcs = RpcClient(self.gcs_address)
        await self.gcs.connect()
        self.gcs.on_push("pubsub:resources", self._on_remote_resources)
        self.gcs.on_push("pubsub:node", self._on_node_event)
        self.gcs.on_push("pubsub:object", self._on_object_event)
        reply = await self.gcs.call("register_node", {
            "node_id": self.node_id,
            "address": self.server.address,
            "resources_total": self.resources.total.to_dict(),
            "resources_available": self.resources.available.to_dict(),
            "labels": self.labels,
            "slice_name": self.labels.get("slice_name", ""),
            "host_index": int(self.labels.get("host_index", 0)),
            "store_dir": self.store.dir,
            "transfer_address": self.transfer.address,
        })
        self._node_labels[self.node_id] = dict(self.labels)
        for info in reply["nodes"]:
            if info.node_id != self.node_id and info.alive:
                self._remote_nodes[info.node_id] = (info.address, ResourceSet(info.resources_available))
                self._node_labels[info.node_id] = dict(info.labels or {})
        if self.cfg.resource_sync_mode == "gossip":
            # peer availability rides anti-entropy rounds, not a hub
            # fan-out: the GCS stays out of the O(N^2) broadcast path
            # (node/object events remain hub channels — membership and
            # the object directory are authoritative state, not gossip)
            from .syncer import ResourceSyncer

            self.syncer = ResourceSyncer(
                self, interval_s=self.cfg.resource_sync_interval_s,
                fanout=self.cfg.resource_sync_fanout)
            self.syncer.local_update(
                self.resources.available.to_dict(), [],
                self._resource_seq)
            self.syncer.start()
            await self.gcs.call(
                "subscribe", {"channels": ["node", "object"]})
        else:
            await self.gcs.call(
                "subscribe", {"channels": ["resources", "node", "object"]})
        self.gcs.on_reconnect.append(self._on_gcs_reconnect)
        if self.cfg.prestart_workers:
            for _ in range(min(2, self.max_workers)):
                self._spawn_worker()
        if self.cfg.memory_monitor_refresh_ms > 0:
            background(self._memory_monitor_loop())
        if self.cfg.clock_sync_interval_s > 0:
            background(self._clock_sync_loop())
        if self.cfg.task_watchdog_interval_s > 0:
            background(self._task_watchdog_loop())
        if self.cfg.blackbox_enabled:
            from . import blackbox

            self._blackbox = blackbox.FlightRecorder(
                "raylet", self._session_dir,
                ident=self.server.address,
                node_id=self.node_id.hex(),
                ring_size=self.cfg.blackbox_ring_size,
                flush_interval_s=self.cfg.blackbox_flush_interval_s,
                inflight_provider=self._blackbox_inflight)
            self._blackbox.start()

    def _blackbox_inflight(self):
        """Flight-ring view of what this raylet is holding right now:
        granted leases (the tasks a postmortem must implicate) plus the
        worker pool. Kept cheap — it runs on every flight flush."""
        items = []
        for lease_id, lease in list(self._leases.items())[:200]:
            items.append({
                "kind": "lease",
                "lease_id": lease_id,
                "worker_pid": lease.worker.pid,
                "actor_id": lease.worker.actor_id.hex()
                if lease.worker.actor_id else None,
                "owner": lease.owner_address,
            })
        for w in list(self._workers.values())[:200]:
            items.append({
                "kind": "worker",
                "worker_id": w.worker_id.hex(),
                "pid": w.pid,
                "alive": w.alive,
            })
        return items

    async def _clock_sync_loop(self):
        """Estimate this node's clock offset against the GCS clock by
        piggybacking on the ping RPC (NTP-style: offset = remote_time -
        local round-trip midpoint), EMA-smoothed so one congested RTT
        doesn't yank the whole node's timeline. The GCS stores it on the
        node table; timeline assembly applies it so per-node timestamps
        compose cluster-wide (corrected = local_ts + offset)."""
        period = self.cfg.clock_sync_interval_s
        # first few rounds run quickly so a fresh node's timestamps are
        # correctable almost immediately, then settle to the period
        warmup = 3
        while True:
            try:
                # chaos: a dropped/slow heartbeat must perturb only this
                # round — the loop itself neither dies nor wedges
                if await failpoints.afire("raylet.heartbeat") == "drop":
                    raise ConnectionError("heartbeat dropped (failpoint)")
                t0 = time.time()
                reply = await self.gcs.call("ping", {}, timeout=5)
                t1 = time.time()
                sample = reply["time"] - (t0 + t1) / 2.0
                if self._clock_offset is None:
                    self._clock_offset = sample
                else:
                    self._clock_offset = (0.8 * self._clock_offset
                                          + 0.2 * sample)
                await self.gcs.call("report_clock_offset", {
                    "node_id": self.node_id,
                    "offset": self._clock_offset,
                    "rtt": t1 - t0,
                })
            except Exception:
                pass  # next round reconnects/retries
            if warmup > 0:
                warmup -= 1
                await asyncio.sleep(min(1.0, period))
            else:
                await asyncio.sleep(period)

    async def _on_gcs_reconnect(self):
        """A restarted GCS lost every per-connection subscription (and,
        if its journal was cold, this node's registration): re-register
        idempotently, re-subscribe, and push a fresh resource report so
        the cluster view heals without operator action (ref:
        gcs_redis_failure_detector.h restart path)."""
        try:
            await self.gcs.call("register_node", {
                "node_id": self.node_id,
                "address": self.server.address,
                "resources_total": self.resources.total.to_dict(),
                "resources_available": self.resources.available.to_dict(),
                "labels": self.labels,
                "slice_name": self.labels.get("slice_name", ""),
                "host_index": int(self.labels.get("host_index", 0)),
                "store_dir": self.store.dir,
                "transfer_address": self.transfer.address,
            })
            await self.gcs.call(
                "subscribe",
                {"channels": (["node", "object"] if self.syncer is not None
                              else ["resources", "node", "object"])})
            await self._report_resources()
            if self._clock_offset is not None:
                # a cold-journal GCS restart lost the node table entry's
                # offset: re-seed it so timelines stay correctable
                await self.gcs.call("report_clock_offset", {
                    "node_id": self.node_id,
                    "offset": self._clock_offset, "rtt": 0.0})
        except Exception:
            pass  # next retrying call reconnects and refires this hook

    # ----------------------------------------------------- memory pressure
    def _memory_fraction(self) -> Optional[float]:
        """Host memory usage fraction (ref: memory_monitor.h:52). Tests
        inject a fraction through ``memory_monitor_test_file``."""
        tf = self.cfg.memory_monitor_test_file
        if tf:
            try:
                with open(tf) as f:
                    return float(f.read().strip())
            except (OSError, ValueError):
                return None
        try:
            total = avail = None
            with open("/proc/meminfo") as f:
                for line in f:
                    if line.startswith("MemTotal:"):
                        total = int(line.split()[1])
                    elif line.startswith("MemAvailable:"):
                        avail = int(line.split()[1])
                    if total is not None and avail is not None:
                        break
            if total and avail is not None:
                return 1.0 - avail / total
        except OSError:
            pass
        return None

    async def _memory_monitor_loop(self):
        """Kill workers under host memory pressure so retriable work is
        shed instead of the OS OOM-killer shooting randomly (ref:
        memory_monitor.h:52 + worker_killing_policy_retriable_fifo.h —
        newest non-actor lease dies first; its owner retries within the
        task's max_retries budget)."""
        period = self.cfg.memory_monitor_refresh_ms / 1000.0
        while True:
            await asyncio.sleep(period)
            frac = self._memory_fraction()
            if frac is None or frac < self.cfg.memory_usage_threshold:
                continue
            leases = [l for l in self._leases.values()
                      if l.worker.actor_id is None and l.worker.alive]
            if not leases:
                continue
            victim = max(leases, key=lambda l: l.lease_id)
            worker = victim.worker
            try:
                os.kill(worker.pid, 9)
            except (ProcessLookupError, PermissionError):
                continue
            worker.alive = False
            try:
                await self.gcs.call("report_task_events", {"events": [{
                    "task_id": f"oom_kill_{worker.worker_id.hex()[:12]}",
                    "name": "WORKER_OOM_KILLED",
                    "state": "WORKER_OOM_KILLED",
                    "node_id": self.node_id,
                    "memory_fraction": frac,
                }]})
            except Exception:
                pass

    # ------------------------------------------------------- stall sentinel
    async def _task_watchdog_loop(self):
        """Hang detector for the compute plane: each tick probes this
        node's workers for RUNNING-task ages and completed-duration
        samples, flags tasks past an adaptive per-scheduling-class
        threshold (EMA of past durations x task_stall_ema_factor,
        floored at task_stall_threshold_s), captures the implicated
        worker's Python stack over its dump_stacks RPC, and emits a
        WARNING cluster event with the stack attached. The transfer
        stall check (watermark registry, no byte progress) rides the
        same tick."""
        period = self.cfg.task_watchdog_interval_s
        while True:
            await asyncio.sleep(period)
            try:
                await self._task_watchdog_tick()
            except Exception:
                pass  # a failed tick must never kill the watchdog

    async def _task_watchdog_tick(self):
        floor = self.cfg.task_stall_threshold_s
        factor = self.cfg.task_stall_ema_factor
        seen = set()
        for worker in list(self._workers.values()):
            if not worker.alive or worker.conn is None:
                continue
            try:
                client = await self._peer_client(worker.address)
                probe = await client.call("stall_probe", {}, timeout=5)
            except Exception:
                continue  # worker busy dying; health plane owns that
            for fn, dur in probe.get("completed", []):
                prev = self._class_ema.get(fn)
                self._class_ema[fn] = (dur if prev is None
                                       else 0.8 * prev + 0.2 * dur)
            for rec in probe.get("running", []):
                seen.add(rec["task_id"])
                ema = self._class_ema.get(rec["fn"])
                threshold = max(floor, ema * factor) if ema else floor
                if rec["age_s"] < threshold:
                    continue
                if rec["task_id"] in self._stalled_tasks:
                    # already alerted; keep the record's age fresh and
                    # re-check mitigation — the drain trigger is an age
                    # multiple the task may only now have reached (the
                    # hint/report half ran once at flag time: one stall
                    # event must fold exactly one straggler sample)
                    self._stalled_tasks[rec["task_id"]]["age_s"] = \
                        rec["age_s"]
                    await self._mitigate_stalled_task(worker, rec,
                                                      threshold,
                                                      first=False)
                    continue
                await self._flag_stalled_task(worker, rec, threshold)
        # a flagged task that is no longer RUNNING resolved itself
        for tid in list(self._stalled_tasks):
            if tid not in seen:
                self._stalled_tasks.pop(tid, None)
        if self.cfg.transfer_stall_timeout_s > 0:
            await self._check_transfer_stalls()
        await self._refresh_straggler_scores()

    async def _refresh_straggler_scores(self):
        """Pull the cluster straggler scores so _pick_node can
        deprioritize persistently-late nodes without a per-lease RPC."""
        if self.cfg.straggler_deprioritize_threshold <= 0:
            return
        try:
            rows = await self.gcs.call("straggler_scores", {}, timeout=5)
        except (asyncio.TimeoutError, ConnectionLost, RpcError, OSError):
            return  # stale scores beat a dead watchdog
        scores: Dict[str, float] = {}
        for row in rows or []:
            nid = row.get("node_id")
            if nid:
                scores[nid] = float(row.get("score", 0.0))
        self._straggler_scores = scores

    async def _flag_stalled_task(self, worker: WorkerHandle, rec: dict,
                                 threshold: float):
        stack = ""
        try:
            client = await self._peer_client(worker.address)
            dump = await client.call("dump_stacks", {}, timeout=5)
            for th in dump.get("threads", []):
                if th.get("task_id") == rec["task_id"]:
                    stack = th["stack"]
                    break
            else:
                # interpreter-level hang (e.g. a wedged C extension):
                # attach every thread rather than nothing
                stack = "\n".join(th["stack"]
                                  for th in dump.get("threads", []))
        except Exception:
            stack = "<stack capture failed: worker unreachable>"
        record = {
            "kind": "task_stall",
            "task_id": rec["task_id"],
            "fn": rec["fn"],
            "age_s": rec["age_s"],
            "threshold_s": threshold,
            "node_id": self.node_id.hex(),
            "worker_id": worker.worker_id.hex(),
            "pid": worker.pid,
            "stack": stack,
            "detected_at": time.time(),
        }
        self._stalled_tasks[rec["task_id"]] = record
        try:
            await self.gcs.call("report_event", {
                "source": "stall_sentinel",
                "severity": "WARNING",
                "message": (
                    f"task {rec['task_id'][:12]} ({rec['fn']}) stalled: "
                    f"RUNNING for {rec['age_s']:.1f}s on node "
                    f"{self.node_id.hex()[:12]} worker pid {worker.pid} "
                    f"(threshold {threshold:.1f}s)"),
                "fields": record,
            })
        except Exception:
            pass
        await self._mitigate_stalled_task(worker, rec, threshold)

    async def _mitigate_stalled_task(self, worker: WorkerHandle, rec: dict,
                                     threshold: float, first: bool = True):
        """Tail-tolerance reactions to a flagged stall: nudge the task's
        owner to hedge NOW (it only acts if the task opted into
        speculation), feed the lateness into the GCS straggler stats —
        both once, at flag time — and, re-checked every tick, drain a
        wedged non-actor worker so its owner's retry lands on a healthy
        one before a gang times out."""
        if first:
            lease = worker.lease
            owner = lease.owner_address if lease is not None else ""
            if owner:
                background(self._send_hedge_hint(owner, rec["task_id"]))
            background(self.gcs.call("report_straggler", {
                "node_id": self.node_id.hex(),
                "late_s": max(0.0, rec["age_s"] - threshold),
                "source": "task_watchdog",
            }, timeout=5))
        if (self.cfg.straggler_drain_enabled
                and worker.actor_id is None
                and worker.pid not in self._drained_workers
                and rec["age_s"] >= threshold
                * max(1.0, self.cfg.straggler_drain_after_factor)):
            self._drained_workers.add(worker.pid)
            try:
                os.kill(worker.pid, 9)
            except (ProcessLookupError, PermissionError):
                return
            worker.alive = False
            try:
                await self.gcs.call("report_event", {
                    "source": "stall_sentinel",
                    "severity": "WARNING",
                    "message": (
                        f"drained wedged worker pid {worker.pid} on node "
                        f"{self.node_id.hex()[:12]} (task "
                        f"{rec['task_id'][:12]} RUNNING {rec['age_s']:.1f}s"
                        f"); owner retry will resubmit elsewhere"),
                    "fields": {"kind": "worker_drained",
                               "task_id": rec["task_id"],
                               "node_id": self.node_id.hex(),
                               "pid": worker.pid},
                }, timeout=5)
            except (asyncio.TimeoutError, ConnectionLost, RpcError, OSError):
                pass  # the drain itself already happened; event is best-effort

    async def _send_hedge_hint(self, owner: str, task_id_hex: str):
        try:
            client = await self._peer_client(owner)
            await client.call("hedge_hint", {"task_id": task_id_hex},
                              timeout=5)
        except (asyncio.TimeoutError, ConnectionLost, RpcError, OSError):
            pass  # owner gone or pre-hedging: the hint is best-effort

    async def _check_transfer_stalls(self):
        stalls = self.store.stalled_pulls(self.cfg.transfer_stall_timeout_s)
        current = set()
        for s in stalls:
            oid = s["object_id"]
            current.add(oid)
            src = self._pull_sources.get(ObjectID.from_hex(oid))
            s.update({"kind": "transfer_stall",
                      "node_id": self.node_id.hex(),
                      "source_node": src.hex() if src else None,
                      "detected_at": time.time()})
            if oid in self._stalled_transfers:
                self._stalled_transfers[oid].update(s)
                continue
            self._stalled_transfers[oid] = s
            try:
                await self.gcs.call("report_event", {
                    "source": "stall_sentinel",
                    "severity": "WARNING",
                    "message": (
                        f"pull {oid[:12]} stalled on node "
                        f"{self.node_id.hex()[:12]}: no byte progress for "
                        f"{s['stalled_for_s']:.1f}s "
                        f"({s['watermark']}/{s['size']} bytes)"),
                    "fields": s,
                })
            except Exception:
                pass
        for oid in list(self._stalled_transfers):
            if oid not in current:
                self._stalled_transfers.pop(oid, None)

    async def handle_list_stalls(self, payload, conn):
        """This node's currently-flagged stalls (state api / cli health)."""
        return {
            "tasks": list(self._stalled_tasks.values()),
            "transfers": list(self._stalled_transfers.values()),
        }

    async def handle_dump_worker_stacks(self, payload, conn):
        """Fan dump_stacks across this node's live workers (cli stacks,
        GCS hung-collective forensics). Unreachable workers report an
        error entry instead of wedging the whole dump."""
        out = []
        for worker in list(self._workers.values()):
            if not worker.alive:
                continue
            try:
                client = await self._peer_client(worker.address)
                dump = await client.call("dump_stacks", {}, timeout=5)
            except Exception as e:
                dump = {"pid": worker.pid, "error": str(e) or repr(e)}
            dump["worker_id"] = worker.worker_id.hex()
            dump["node_id"] = self.node_id.hex()
            out.append(dump)
        return {"node_id": self.node_id.hex(), "workers": out}

    async def handle_profile_start_workers(self, payload, conn):
        """Fan profile_start (burst sampler at ``hz``) across this
        node's live workers. Per-worker failures are reported, not
        raised — one dead worker must not kill a cluster profile."""
        hz = float(payload.get("hz", 100.0))
        started, errors = 0, []
        for worker in list(self._workers.values()):
            if not worker.alive:
                continue
            try:
                client = await self._peer_client(worker.address)
                if await client.call("profile_start", {"hz": hz},
                                     timeout=5):
                    started += 1
            except Exception as e:
                errors.append({"pid": worker.pid,
                               "error": str(e) or repr(e)})
        return {"node_id": self.node_id.hex(), "started": started,
                "errors": errors}

    async def handle_profile_stop_workers(self, payload, conn):
        """Collect each worker's folded-stack snapshot (burst if one is
        running, else the ambient accumulation)."""
        out = []
        for worker in list(self._workers.values()):
            if not worker.alive:
                continue
            try:
                client = await self._peer_client(worker.address)
                snap = await client.call("profile_stop", {}, timeout=10)
            except Exception as e:
                snap = {"pid": worker.pid, "error": str(e) or repr(e),
                        "wall": {}, "cpu": {}, "samples": 0}
            snap["node_id"] = self.node_id.hex()
            out.append(snap)
        return {"node_id": self.node_id.hex(), "workers": out}

    async def handle_node_memory_report(self, payload, conn):
        """This node's memory-attribution inputs: the shared store's
        object inventory (directory scan — node-global in both index
        modes) plus every live worker's reference claims / heap stats."""
        workers = []
        for worker in list(self._workers.values()):
            if not worker.alive:
                continue
            try:
                client = await self._peer_client(worker.address)
                rep = await client.call("memory_report", {}, timeout=10)
            except Exception as e:
                rep = {"pid": worker.pid, "error": str(e) or repr(e),
                       "claims": {}}
            rep["worker_id"] = worker.worker_id.hex()
            workers.append(rep)
        return {
            "node_id": self.node_id.hex(),
            "store": self.store.usage_report(),
            "workers": workers,
        }

    async def stop(self):
        for task in list(self._token_conn_watchers.values()):
            task.cancel()
        self._token_conn_watchers.clear()
        for worker in self._workers.values():
            self._expected_exits.add(worker.pid)
            if worker.conn is not None:
                await worker.conn.push("shutdown", {})
        if self._blackbox is not None:
            self._blackbox.close(clean=True)
            self._blackbox = None
        if self.syncer is not None:
            self.syncer.stop()
        await self.server.stop()
        if self.transfer is not None:
            await self.transfer.stop()
        await self.gcs.close()
        for client in self._peer_clients.values():
            await client.close()
        await self._factory_teardown()
        for proc in self._subprocs:
            try:
                proc.terminate()
            except Exception:
                pass
        self._signal_factory_workers(15)
        deadline = time.monotonic() + 3
        for proc in self._subprocs:
            try:
                proc.wait(timeout=max(0.1, deadline - time.monotonic()))
            except Exception:
                try:
                    proc.kill()
                except Exception:
                    pass
        await self._await_factory_workers(deadline)
        self._signal_factory_workers(9)

    async def die(self):
        """Abrupt node death for fault-injection tests (the cluster_utils
        `remove_node` analog): SIGKILL workers, drop connections ungracefully
        so the GCS health path — not a clean unregister — detects it."""
        if self.syncer is not None:
            # a "dead" node must stop gossiping, or it keeps re-opening
            # peer connections die() just severed
            self.syncer.stop()
        for proc in self._subprocs:
            try:
                proc.kill()
            except Exception:
                pass
        self._signal_factory_workers(9)
        if self._factory_proc is not None:
            try:
                self._factory_proc.kill()
            except Exception:
                pass
        # drop the GCS connection first — that's the death signal the GCS
        # health path turns into node-dead + object-lost events
        await self.gcs.close()
        await self.server.stop()
        if self.transfer is not None:
            await self.transfer.stop()
        for client in self._peer_clients.values():
            await client.close()

    def _on_remote_resources(self, payload):
        node_id, avail = payload["node_id"], payload["available"]
        if node_id == self.node_id:
            return
        entry = self._remote_nodes.get(node_id)
        if entry is not None:
            self._remote_nodes[node_id] = (entry[0], ResourceSet(avail))
            if self._pending_leases:  # capacity elsewhere: try spillback
                background(self._pump_pending())

    def _apply_peer_resources(self, node_hex: str,
                              available: dict) -> None:
        """Gossip-learned availability (syncer.py) feeding the same
        spillback view the hub pushes maintain. Availability ONLY:
        membership stays hub-authoritative (node channel), so a stale
        gossip entry can never resurrect a removed node into the
        spillback picker — unknown nodes are dropped here and evicted
        from the gossip view."""
        node_id = NodeID.from_hex(node_hex)
        entry = self._remote_nodes.get(node_id)
        if entry is None:
            if self.syncer is not None and node_id != self.node_id:
                self.syncer.evict(node_hex)
            return
        self._remote_nodes[node_id] = (entry[0], ResourceSet(available))
        if self._pending_leases:
            background(self._pump_pending())

    async def handle_syncer_sync(self, payload, conn):
        if self.syncer is None:
            return {"entries": {}, "want": []}
        return await self.syncer.handle_sync(payload)

    async def handle_syncer_push(self, payload, conn):
        if self.syncer is None:
            return 0
        return await self.syncer.handle_push(payload)

    async def handle_health(self, payload, conn):
        """Target of the GCS's ACTIVE health probe (gcs.py
        _node_health_loop; ref: gcs_health_check_manager.h). Answering
        requires THIS event loop to turn — a SIGSTOP'd or livelocked
        raylet keeps its socket open but fails the probe."""
        return True

    def _on_node_event(self, payload):
        if payload["event"] == "added":
            info = payload["node"]
            if info.node_id != self.node_id:
                self._remote_nodes[info.node_id] = (info.address, ResourceSet(info.resources_available))
                self._node_labels[info.node_id] = dict(info.labels or {})
                # a re-registered node is alive again by hub decree
                self._dead_node_hexes.pop(info.node_id.hex(), None)
                if self._pending_leases:  # a new node may fit queued work
                    background(self._pump_pending())
        elif payload["event"] == "removed":
            node_id = payload.get("node_id")
            self._remote_nodes.pop(node_id, None)
            if node_id is not None:
                self._dead_node_hexes[node_id.hex()] = None
                while len(self._dead_node_hexes) > 4096:
                    self._dead_node_hexes.popitem(last=False)
            if self.syncer is not None and node_id is not None:
                self.syncer.evict(node_id.hex())

    async def _report_resources(self):
        """Fire-and-forget availability report. Never awaited into the lease
        grant path — a lost frame must not stall granting. The sequence
        number lets the GCS drop late/stale reports (absolute values +
        last-writer-wins needs an order)."""
        self._resource_seq += 1
        payload = {
            "node_id": self.node_id,
            "available": self.resources.available.to_dict(),
            "seq": self._resource_seq,
            # queued lease shapes: the autoscaler's scale-up signal
            "pending": [p.resources.to_dict()
                        for p in self._pending_leases],
        }
        if self.syncer is not None:
            self.syncer.local_update(payload["available"],
                                     payload["pending"], payload["seq"])

        async def _send():
            try:
                await self.gcs.call_retrying("report_resources", payload,
                                             attempts=3, per_try_timeout=2.0)
            except Exception:
                pass

        background(_send())

    # ---------------------------------------------------------- worker pool
    def _spawn_worker(self) -> None:
        self._starting += 1
        env, log_path = self._worker_env()
        if self.cfg.worker_factory_enabled:
            background(self._spawn_via_factory(env, log_path))
        else:
            self._popen_worker(env, log_path)

    def _worker_env(self) -> tuple:
        env = dict(os.environ)
        # propagate the driver's import surface so by-reference pickles resolve
        # (the minimal working_dir runtime-env; ref: _private/runtime_env/working_dir.py)
        extra_path = [p for p in sys.path if p] + [os.getcwd()]
        env["PYTHONPATH"] = os.pathsep.join(
            extra_path + [p for p in env.get("PYTHONPATH", "").split(os.pathsep) if p])
        env["RAY_TPU_SESSION"] = self.session_name
        env["RAY_TPU_RAYLET_SOCKET"] = self.socket_path
        env["RAY_TPU_GCS_SOCKET"] = self.gcs_address
        env["RAY_TPU_NODE_ID"] = self.node_id.hex()
        env["RAY_TPU_STORE_DIR"] = self.store.dir
        # Every pool worker starts pinned to the CPU backend, whatever the
        # raylet's own environment asks for: a chip belongs to one process
        # at a time, so only the worker that runs a lease holding chips
        # may touch it. That worker un-pins itself before user code runs
        # (device_plane.claim_chips, from worker_main
        # _apply_chip_visibility) and is retired with its lease
        # (_retire_chip_worker).
        env.update(device_plane.pinned_worker_env(len(self._chip_used)))
        # worker stdout/stderr land in per-worker session log files (the
        # reference's log_monitor capture; surfaced via the state API's
        # list_logs/get_log raylet RPCs)
        log_dir = session_log_dir(self.session_name)
        os.makedirs(log_dir, exist_ok=True)
        # redirected-to-file stdout is block-buffered by default: a live
        # pooled worker's prints would sit in the 8KB buffer forever
        env["PYTHONUNBUFFERED"] = "1"
        self._worker_seq += 1
        log_path = os.path.join(
            log_dir, f"worker-{self.node_id.hex()[:8]}-{self._worker_seq}.log")
        return env, log_path

    def _popen_worker(self, env: dict, log_path: str) -> None:
        log_file = open(log_path, "ab")
        proc = subprocess.Popen(
            [sys.executable, "-m", "ray_tpu._private.worker_main"],
            env=env,
            stdout=log_file,
            stderr=log_file,
            start_new_session=True,
        )
        log_file.close()  # the child holds its own fd
        self._subprocs.append(proc)

    # ---------------------------------------------- worker factory (fork)
    # A cold worker pays ~0.7 s of interpreter+import startup; the factory
    # (worker_factory.py) imports once and forks per worker, which is what
    # makes envelope-depth actor counts (1k+ live actors on one host)
    # reachable (ref: worker_pool.h prestart amortization).
    async def _spawn_via_factory(self, env: dict, log_path: str) -> None:
        try:
            pid = await self._factory_request(
                {"cmd": "spawn", "log_path": log_path, "env": env})
            self._factory_pids.append(pid)
        except Exception as e:
            # factory unavailable (failed to start, died mid-request):
            # cold-start this worker and let the next spawn retry the
            # factory from scratch
            print(f"[raylet] worker factory spawn failed "
                  f"({type(e).__name__}: {e}); falling back to cold start",
                  file=sys.stderr)
            await self._factory_teardown()
            try:
                self._popen_worker(env, log_path)
            except Exception:
                self._starting = max(0, self._starting - 1)

    async def _factory_request(self, req: dict) -> int:
        async with self._factory_lock:
            if self._factory_writer is None:
                await self._factory_start_locked()
            writer = self._factory_writer
            reader = self._factory_reader
            writer.write(json.dumps(req).encode() + b"\n")
            await writer.drain()
            line = await asyncio.wait_for(
                reader.readline(), self.cfg.worker_startup_timeout_s)
        if not line:
            raise ConnectionLost("worker factory closed its socket")
        reply = json.loads(line)
        if "error" in reply:
            raise RuntimeError(f"worker factory: {reply['error']}")
        pid = reply.get("pid")
        if not isinstance(pid, int) or pid <= 0:
            # never let a malformed reply become pid 0/-1 — os.kill(0)
            # signals this whole process group at shutdown
            raise RuntimeError(f"worker factory: bad spawn reply {reply!r}")
        return pid

    async def _factory_start_locked(self) -> None:
        sock_path = os.path.join(
            session_log_dir(self.session_name),
            f"factory-{self.node_id.hex()[:8]}.sock")
        os.makedirs(os.path.dirname(sock_path), exist_ok=True)
        env, _ = self._worker_env()
        env["RAY_TPU_FACTORY_SOCKET"] = sock_path
        log_path = os.path.join(session_log_dir(self.session_name),
                                f"factory-{self.node_id.hex()[:8]}.log")
        log_file = open(log_path, "ab")
        self._factory_proc = subprocess.Popen(
            [sys.executable, "-m", "ray_tpu._private.worker_factory"],
            env=env, stdout=log_file, stderr=log_file)
        log_file.close()
        # the factory binds its socket only after the worker stack is
        # imported, so connect-success == ready
        deadline = time.monotonic() + self.cfg.worker_startup_timeout_s
        while True:
            try:
                reader, writer = await asyncio.open_unix_connection(sock_path)
                break
            except (FileNotFoundError, ConnectionRefusedError, OSError) as e:
                if (time.monotonic() > deadline
                        or self._factory_proc.poll() is not None):
                    proc, self._factory_proc = self._factory_proc, None
                    try:
                        proc.kill()
                    except Exception:
                        pass
                    raise TimeoutError(
                        "worker factory did not come up") from e
                await asyncio.sleep(0.05)
        self._factory_reader, self._factory_writer = reader, writer

    async def _factory_teardown(self) -> None:
        async with self._factory_lock:
            if self._factory_writer is not None:
                try:
                    self._factory_writer.write(b'{"cmd": "exit"}\n')
                    await self._factory_writer.drain()
                    self._factory_writer.close()
                except Exception:
                    pass
                self._factory_reader = self._factory_writer = None
            if self._factory_proc is not None:
                proc, self._factory_proc = self._factory_proc, None
                try:
                    proc.terminate()
                    await asyncio.get_event_loop().run_in_executor(
                        None, lambda: proc.wait(timeout=3))
                except Exception:
                    try:
                        proc.kill()
                    except Exception:
                        pass

    def _signal_factory_workers(self, sig: int) -> None:
        for pid in list(self._factory_pids):
            try:
                os.kill(pid, sig)
            except ProcessLookupError:
                self._factory_pids.remove(pid)
            except PermissionError:
                pass

    async def _await_factory_workers(self, deadline: float) -> None:
        """Give SIGTERM'd factory workers the same grace window Popen
        workers get before the SIGKILL pass (they are the factory's
        children, not ours — no waitpid, poll liveness instead).
        Async: this runs on the raylet's io loop during stop(), and a
        sleeping poll there would freeze every other connection for the
        full grace window (graftlint: blocking-call-on-loop)."""
        while self._factory_pids and time.monotonic() < deadline:
            for pid in list(self._factory_pids):
                try:
                    os.kill(pid, 0)
                except ProcessLookupError:
                    self._factory_pids.remove(pid)
                except PermissionError:
                    pass
            if self._factory_pids:
                await asyncio.sleep(0.05)

    async def handle_register_worker(self, payload, conn):
        worker = WorkerHandle(
            worker_id=payload["worker_id"],
            pid=payload["pid"],
            address=payload["address"],
            conn=conn,
        )
        self._workers[worker.worker_id] = worker
        self._worker_conns[conn] = worker.worker_id
        self._starting = max(0, self._starting - 1)
        self._idle.append(worker)
        await self._pump_pending()
        return {"node_id": self.node_id, "session": self.session_name}

    async def handle_worker_blocked(self, payload, conn):
        """The worker's current task is blocked resolving objects: hand
        its CPU share back so other work can run — withholding it
        deadlocks dependency chains once every worker waits (ref:
        node_manager.cc HandleNotifyDirectCallTaskBlocked →
        ReleaseCpuResourcesFromBlockedWorker)."""
        worker = self._workers.get(payload["worker_id"])
        if worker is None or worker.lease is None:
            return False
        lease = worker.lease
        if lease.blocked_cpu is not None:
            return True  # already released (re-entrant block)
        cpu = lease.resources.get("CPU", 0.0)
        if cpu <= 0:
            return True
        part = ResourceSet({"CPU": cpu})
        lease.blocked_cpu = part
        lease.resources = ResourceSet(
            {k: v for k, v in lease.resources.to_dict().items()
             if k != "CPU"})
        if lease.pg_key is not None:
            bundle = self._pg_bundles.get(lease.pg_key)
            if bundle is not None:
                bundle.release(part)
        else:
            self.resources.release(part)
        await self._report_resources()
        await self._pump_pending()
        return True

    async def handle_worker_unblocked(self, payload, conn):
        """Blocked worker resumed: re-take its CPU (forced — transient
        oversubscription beats starving the resumed task, matching the
        reference's ReturnCpuResourcesToUnblockedWorker)."""
        worker = self._workers.get(payload["worker_id"])
        if worker is None or worker.lease is None:
            return False
        lease = worker.lease
        part, lease.blocked_cpu = lease.blocked_cpu, None
        if part is None:
            return True
        if lease.pg_key is not None:
            bundle = self._pg_bundles.get(lease.pg_key)
            if bundle is not None:
                bundle.force_allocate(part)
        else:
            self.resources.force_allocate(part)
        lease.resources.add(part)
        await self._report_resources()
        return True

    async def _blackbox_worker_gone(self, worker: "WorkerHandle"):
        """Black-box disposition for a vanished worker: an exit this
        raylet ORDERED (shutdown push, drain kill marked expected)
        discards the flight file quietly; an unexpected death promotes
        it to a crash bundle — carrying the worker's own last-flushed
        in-flight tasks — and reports the crash to the GCS incident
        log. SIGKILL leaves no in-process hook, so the survivor doing
        the sweep is the only way those deaths get flight data."""
        if not self.cfg.blackbox_enabled:
            return
        from . import blackbox

        if worker.pid in self._expected_exits:
            self._expected_exits.discard(worker.pid)
            blackbox.discard_flight(self._session_dir, worker.pid)
            return
        reason = ("drain_kill" if worker.pid in self._drained_workers
                  else "worker_disconnect")
        try:
            promoted = blackbox.sweep(
                self._session_dir, reason=reason,
                bundled_by=f"raylet-{self.node_id.hex()[:12]}",
                pids=[worker.pid])
        except Exception:  # graftlint: ignore[swallow] — a failed sweep
            return  # must not break disconnect handling
        for snap in promoted:
            try:
                await self.gcs.call("report_crash", {
                    "role": snap.get("role", "worker"),
                    "pid": worker.pid,
                    "node_id": self.node_id.hex(),
                    "reason": reason,
                    "signal": snap.get("signal_name"),
                    "bundle_path": snap.get("path"),
                    "inflight": (snap.get("inflight") or [])[:5],
                }, timeout=5)
            except Exception:  # graftlint: ignore[swallow] — the bundle
                pass  # is on disk; losing the GCS event is tolerable

    async def _on_disconnect(self, conn):
        # reap exited worker subprocesses and drop them from tracking (dead
        # workers would otherwise linger as zombies until node stop)
        self._subprocs = [p for p in self._subprocs if p.poll() is None]
        worker_id = self._worker_conns.pop(conn, None)
        if worker_id is None:
            return
        worker = self._workers.pop(worker_id, None)
        if worker is None:
            return
        worker.alive = False
        await self._blackbox_worker_gone(worker)
        # a gone worker's pid may be recycled by the kernel — never keep
        # it on the factory kill list
        try:
            self._factory_pids.remove(worker.pid)
        except ValueError:
            pass
        if worker in self._idle:
            self._idle.remove(worker)
        if worker.lease is not None:
            lease = worker.lease
            self._forget_rid(lease.lease_id)
            self._release_lease_resources(lease)
            self._leases.pop(lease.lease_id, None)
            await self._report_resources()
        if worker.actor_id is not None:
            try:
                await self.gcs.call("actor_failed", {
                    "actor_id": worker.actor_id,
                    "cause": f"worker process {worker.pid} died",
                })
            except Exception:
                pass
        # leases the dead process OWNED (fast lanes it opened for its own
        # subtasks) must be reaped too, or their resources leak forever —
        # observed: a killed SplitCoordinator's 1-CPU lane lease wedging
        # every later data pipeline on the node (ref: the reference's
        # per-owner lease cleanup on worker death,
        # node_manager.cc HandleUnexpectedWorkerFailure)
        orphaned = [l for l in self._leases.values()
                    if l.owner_address == worker.address]
        for lease in orphaned:
            self._leases.pop(lease.lease_id, None)
            self._forget_rid(lease.lease_id)
            self._release_lease_resources(lease)
            held = lease.worker
            held.lease = None
            # disconnect rather than reuse: the orphaned worker may have
            # a lane-serve thread still polling the dead owner's ring
            held.alive = False
            self._expected_exits.add(held.pid)
            if held.conn is not None:
                try:
                    await held.conn.push("shutdown", {})
                except Exception:
                    pass
        if orphaned:
            await self._report_resources()
        await self._pump_pending()

    async def _pop_worker(self, dedicated: bool = False,
                          fresh: bool = False) -> Optional[WorkerHandle]:
        """An idle worker for a new lease, or None after asking for one
        to be spawned. ``fresh``: the lease holds chips, so its worker
        must not have run anything yet — a worker whose jax started under
        the CPU pin can never reach the chip (device_plane.claim_chips).
        Chip workers are retired with their lease; this makes them born
        with it too."""
        if fresh:
            worker = next((w for w in self._idle if w.alive and w.fresh),
                          None)
            if worker is not None:
                self._idle.remove(worker)
                return worker
        else:
            while self._idle:
                worker = self._idle.pop()
                if worker.alive:
                    return worker
        if dedicated or fresh:
            # an actor pins its worker for life and a chip worker dies
            # with its lease, so the pool soft limit must not gate them —
            # the limit sizes the REUSABLE pool, and neither ever returns
            # to it (ref: worker_pool.h — dedicated workers bypass the
            # soft cap). Spawns are bounded by actual demand (this
            # request + queued actor and chip leases) and burst-throttled
            # so 1k queued creations don't fork-storm — without the
            # demand bound, every pump pass during one worker's startup
            # window would fork another.
            demand = 1 + sum(
                1 for p in self._pending_leases
                if (p.payload.get("actor_id") is not None
                    or p.resources.get("TPU") > 0)
                and not p.future.done())
            if self._starting < min(self.cfg.worker_spawn_burst, demand):
                self._spawn_worker()
            return None
        # dep-blocked workers released their CPU but still sit in the
        # pool: they must not count against the cap, or the freed CPU is
        # ungrantable (no worker to run on) and dependency chains starve
        # (ref: worker_pool.h soft-limit exempting blocked workers)
        blocked = sum(1 for l in self._leases.values()
                      if l.blocked_cpu is not None)
        if len(self._workers) + self._starting - blocked < self.max_workers:
            self._spawn_worker()
        return None

    # -------------------------------------------------------------- leasing
    async def handle_request_worker_lease(self, payload, conn):
        """Grant a worker lease, spill to a remote node, or queue.

        payload: {resources, strategy, owner_address, actor_id?, pg?}
        reply:   {granted: bool, worker_address, lease_id, node_id}
               | {retry_at: (node_id, address)}
        """
        # a raise here rides the ERROR reply into the core_worker's
        # lease pipeline and lands in the task's return objects —
        # chaos asserts the driver's ray.get names this site
        await failpoints.afire("raylet.lease.grant")
        payload["_conn"] = conn  # reclaim push channel for lane leases
        rid = payload.get("request_id")
        if rid is not None:
            cached = self._lease_rid_grants.get(rid)
            if cached is not None and cached["lease_id"] in self._leases:
                return cached  # duplicate of an already-granted request
            pending = self._lease_rid_pending.get(rid)
            if pending is not None:
                # duplicate of a queued request; also covers the race where
                # the future resolved but the original handler hasn't
                # recorded the grant yet (awaiting a done future is a no-op)
                return await pending
        resources = ResourceSet(payload.get("resources", {}))
        strategy = payload.get("strategy")
        target = (None if payload.get("no_spill")
                  else self._pick_node(resources, strategy,
                                       avoid=payload.get("avoid_nodes")))
        if target is not None and target != self.node_id:
            addr, _ = self._remote_nodes[target]
            return {"granted": False, "retry_at": (target, addr)}
        if self._pg_key(strategy) is not None:
            pg_id = self._pg_key(strategy)[0]
            if not any(k[0] == pg_id for k in self._pg_bundles):
                raise ValueError("placement group bundle not reserved on this node")
        grant = await self._try_grant(resources, payload)
        if grant is not None:
            self._record_rid_grant(rid, grant)
            return grant
        # queue until a worker/resources free up; report immediately so
        # the GCS (and the autoscaler watching it) sees the new demand
        fut = asyncio.get_event_loop().create_future()
        self._pending_leases.append(
            _PendingLease(payload, fut, resources,
                          queued_at=time.monotonic()))
        await self._report_resources()
        if rid is not None:
            self._lease_rid_pending[rid] = fut
        try:
            grant = await fut
        finally:
            if self._lease_rid_pending.get(rid) is fut:
                self._lease_rid_pending.pop(rid, None)
        self._record_rid_grant(rid, grant)
        return grant

    def _record_rid_grant(self, rid: Optional[str], grant: dict) -> None:
        if rid is not None and grant.get("granted"):
            self._lease_rid_grants[rid] = grant
            self._lease_id_to_rid[grant["lease_id"]] = rid

    def _forget_rid(self, lease_id: int) -> None:
        rid = self._lease_id_to_rid.pop(lease_id, None)
        if rid is not None:
            self._lease_rid_grants.pop(rid, None)

    def _pg_key(self, strategy) -> Optional[tuple]:
        if isinstance(strategy, PlacementGroupSchedulingStrategy) and strategy.placement_group_id:
            return (strategy.placement_group_id, strategy.placement_group_bundle_index)
        return None

    def _pg_allocate(self, key: tuple, resources: ResourceSet) -> Optional[tuple]:
        """Allocate the lease's resources inside a reserved bundle; a -1 index
        is a wildcard over this node's bundles of that PG (reference
        semantics: `bundle_index=-1` = any bundle)."""
        pg_id, idx = key
        if idx >= 0:
            bundle = self._pg_bundles.get(key)
            if bundle is not None and bundle.try_allocate(resources):
                return key
            return None
        for k, bundle in self._pg_bundles.items():
            if k[0] == pg_id and bundle.try_allocate(resources):
                return k
        return None

    def _strategy_allows_local(self, strategy) -> bool:
        """Hard label expressions must hold for THIS node before a local
        grant; otherwise the lease stays queued for spillback/arrival."""
        if isinstance(strategy, NodeLabelSchedulingStrategy):
            return label_expr_matches(
                self._node_labels.get(self.node_id, dict(self.labels)),
                strategy.hard)
        return True

    async def _try_grant(self, resources: ResourceSet, payload):
        if not self._strategy_allows_local(payload.get("strategy")):
            return None
        pg_key = self._pg_key(payload.get("strategy"))
        alloc_key = None
        if pg_key is not None:
            # bundle resources were deducted from the node at reservation;
            # the lease draws from the bundle's own pool
            alloc_key = self._pg_allocate(pg_key, resources)
            if alloc_key is None:
                return None
        elif not self.resources.try_allocate(resources):
            return None
        worker = await self._pop_worker(
            dedicated=payload.get("actor_id") is not None,
            fresh=resources.get("TPU") > 0)
        if worker is None:
            if alloc_key is not None:
                self._pg_bundles[alloc_key].release(resources)
            else:
                self.resources.release(resources)
            return None
        chips = self._allocate_chips(resources.get("TPU", 0.0))
        if chips is None:
            # resource math admitted the lease but chips are exhausted
            # (should not diverge; defensive): give everything back
            if alloc_key is not None:
                self._pg_bundles[alloc_key].release(resources)
            else:
                self.resources.release(resources)
            self._return_worker_to_pool(worker)
            return None
        lease = Lease(self._next_lease_id, worker, resources,
                      payload.get("owner_address", ""), pg_key=alloc_key,
                      lane=bool(payload.get("lane")),
                      conn=payload.get("_conn"), chips=chips)
        self._next_lease_id += 1
        worker.lease = lease
        worker.fresh = False
        if payload.get("actor_id") is not None:
            worker.actor_id = payload["actor_id"]
        self._leases[lease.lease_id] = lease
        await self._report_resources()
        return {
            "granted": True,
            "worker_address": worker.address,
            "worker_id": worker.worker_id,
            "lease_id": lease.lease_id,
            "node_id": self.node_id,
            # the leased worker's chip visibility set (TPU leases only)
            "chip_ids": sorted(i for i, _ in lease.chips),
        }

    async def handle_cancel_lease_request(self, payload, conn):
        """Fail a queued lease request for a cancelled task so the owner's
        submit path unblocks (ref: node_manager.cc HandleCancelWorkerLease).
        Races with a grant are benign: the owner re-checks its cancel flag
        before pushing the task and returns the worker unused."""
        from .. import exceptions as exc

        task_id = payload["task_id"]
        hit = False
        for pending in self._pending_leases[:]:
            if pending.payload.get("task_id") == task_id and not pending.future.done():
                pending.future.set_exception(
                    exc.TaskCancelledError("lease request cancelled"))
                hit = True
        return hit

    async def handle_list_logs(self, payload, conn):
        """THIS node's captured worker logs (log-monitor surface). The
        session log dir is shared by co-hosted raylets, so filter to our
        own node-id prefix."""
        prefix = f"worker-{self.node_id.hex()[:8]}-"
        try:
            return sorted(n for n in os.listdir(
                session_log_dir(self.session_name))
                if n.startswith(prefix))
        except FileNotFoundError:
            return []

    async def handle_tail_log(self, payload, conn):
        """Last ``tail_bytes`` of one captured log (basename only — no
        path traversal out of the session log dir)."""
        name = os.path.basename(payload["name"])
        tail_bytes = int(payload.get("tail_bytes", 1 << 16))
        path = os.path.join(session_log_dir(self.session_name), name)
        try:
            with open(path, "rb") as f:
                f.seek(0, os.SEEK_END)
                size = f.tell()
                f.seek(max(0, size - tail_bytes))
                return f.read()
        except OSError:  # missing, or '.'/'..' resolving to a directory
            return b""

    async def handle_return_worker(self, payload, conn):
        lease = self._leases.pop(payload["lease_id"], None)
        if lease is None:
            return False
        self._forget_rid(lease.lease_id)
        self._release_lease_resources(lease)
        worker = lease.worker
        worker.lease = None
        if payload.get("disconnect_worker"):
            worker.alive = False
            self._expected_exits.add(worker.pid)
            if worker.conn is not None:
                await worker.conn.push("shutdown", {})
        elif worker.alive and worker.actor_id is None:
            worker.idle_since = time.monotonic()
            self._idle.append(worker)
        await self._report_resources()
        await self._pump_pending()
        return True

    async def _pump_pending(self):
        """Grant queued lease requests as capacity frees up.

        Non-reentrant: _try_grant awaits, during which new requests may queue
        or another pump may trigger — a flag serializes pumps and a re-run bit
        picks up arrivals, so no request is double-granted or dropped.
        """
        if getattr(self, "_pumping", False):
            self._pump_again = True
            return
        self._pumping = True
        try:
            rerun = True
            while rerun:
                self._pump_again = False
                i = 0
                while i < len(self._pending_leases):
                    pending = self._pending_leases[i]
                    if pending.future.done():
                        self._pending_leases.pop(i)
                        continue
                    grant = await self._try_grant(pending.resources, pending.payload)
                    if grant is None:
                        await self._request_lane_reclaims()
                        # spillback: a node that joined (autoscaler) or
                        # freed up since this lease queued may fit it
                        # now. Damped: never for no_spill leases (chain
                        # cap reached) and only after a settle period so
                        # two saturated raylets with stale views of each
                        # other don't bounce a lease back and forth.
                        if (pending.payload.get("no_spill")
                                or time.monotonic() - pending.queued_at
                                < self.cfg.lease_spill_min_queue_s):
                            i += 1
                            continue
                        target = self._pick_node(
                            pending.resources,
                            pending.payload.get("strategy"),
                            avoid=pending.payload.get("avoid_nodes"))
                        if (target is not None and target != self.node_id
                                and target in self._remote_nodes):
                            addr, _ = self._remote_nodes[target]
                            self._pending_leases.pop(i)
                            if not pending.future.done():
                                pending.future.set_result(
                                    {"granted": False,
                                     "retry_at": (target, addr)})
                            continue
                        i += 1
                        continue
                    self._pending_leases.pop(i)
                    if pending.future.done():  # caller gave up mid-grant
                        await self.handle_return_worker(
                            {"lease_id": grant["lease_id"]}, None)
                    else:
                        pending.future.set_result(grant)
                rerun = self._pump_again
        finally:
            self._pumping = False

    # ------------------------------------------------------ scheduling policy
    def _pick_node(self, resources: ResourceSet, strategy,
                   avoid: Optional[List[str]] = None) -> Optional[NodeID]:
        """Returns the node the lease should run on; None means "queue here".

        Hybrid default (ref: hybrid_scheduling_policy.h:50): prefer local while
        local utilization < threshold; otherwise least-utilized feasible node.

        Tail tolerance: nodes in ``avoid`` (a hedge steering off its
        primary's node) and nodes whose straggler score crossed
        ``straggler_deprioritize_threshold`` are soft-excluded — skipped
        while any clean feasible node exists, used as a last resort
        rather than failing the lease.
        """
        bad = set(avoid or ())
        thresh = self.cfg.straggler_deprioritize_threshold
        if thresh > 0:
            for nhex, score in self._straggler_scores.items():
                if score >= thresh:
                    bad.add(nhex)

        def _prefer(feasible):
            good = [(nid, a) for nid, a in feasible
                    if nid.hex() not in bad]
            return good or feasible

        if isinstance(strategy, NodeAffinitySchedulingStrategy) and strategy.node_id:
            target = NodeID.from_hex(strategy.node_id)
            if target == self.node_id or target in self._remote_nodes:
                return target
            if not strategy.soft:
                raise ValueError(f"node {strategy.node_id} not available (hard affinity)")
            return None
        if self._pg_key(strategy) is not None:
            return self.node_id  # caller already directed to the bundle's node
        local_fits = resources.fits(self.resources.available)
        if isinstance(strategy, NodeLabelSchedulingStrategy):
            # hard expressions gate feasibility; soft ones rank the
            # feasible set (ref: node_label_scheduling_policy.h + A.2)
            def _labels(nid):
                return self._node_labels.get(nid, {})

            candidates = [(self.node_id, self.resources.available)] + [
                (nid, avail) for nid, (_, avail) in self._remote_nodes.items()
            ]
            feasible = [
                (nid, a) for nid, a in candidates
                if resources.fits(a)
                and label_expr_matches(_labels(nid), strategy.hard)]
            if not feasible:
                return None  # queue: a matching node may join/free up
            soft_ok = [(nid, a) for nid, a in feasible
                       if label_expr_matches(_labels(nid), strategy.soft)]
            pool = _prefer(soft_ok or feasible)
            for nid, _ in pool:
                if nid == self.node_id:
                    return nid  # local preferred within the match set
            return pool[0][0]
        if isinstance(strategy, SpreadSchedulingStrategy):
            candidates = [(self.node_id, self.resources.available)] + [
                (nid, avail) for nid, (_, avail) in self._remote_nodes.items()
            ]
            feasible = [(nid, a) for nid, a in candidates if resources.fits(a)]
            if not feasible:
                return None
            feasible = _prefer(feasible)
            self._spill_rr += 1
            return feasible[self._spill_rr % len(feasible)][0]
        # default / hybrid
        local_bad = self.node_id.hex() in bad
        if (local_fits and not local_bad
                and self.resources.utilization()
                < self.cfg.scheduler_spread_threshold):
            return self.node_id
        best, best_util = None, None
        best_bad = None  # least-utilized feasible node among the avoided
        for nid, (_, avail) in self._remote_nodes.items():
            if resources.fits(avail):
                util = 1.0 - min(
                    (avail.get(k, 0.0) / v) for k, v in resources.res.items() if v > 0
                ) if resources.res else 0.0
                if nid.hex() in bad:
                    if best_bad is None:
                        best_bad = nid
                    continue
                if best_util is None or util < best_util:
                    best, best_util = nid, util
        if (local_fits and not local_bad
                and (best is None
                     or self.resources.utilization() <= (best_util or 1.0))):
            return self.node_id
        if best is not None:
            return best
        # only avoided/straggler options remain: degrade rather than fail
        if local_bad and best_bad is not None:
            return best_bad
        return self.node_id if local_fits else best_bad

    # ------------------------------------------------- placement group bundles
    def _release_lease_resources(self, lease: Lease) -> None:
        """Return a finished lease's resources to the bundle it drew from, or
        to the node pool. A canceled bundle already released its whole
        reservation, so its leases return nothing. The TPU of a lease
        that held chips follows once its worker is gone
        (_retire_chip_worker)."""
        resources = lease.resources
        if lease.chips:
            resources = self._retire_chip_worker(lease)
        self._release_resources(lease.pg_key, resources)

    def _release_resources(self, pg_key: Optional[tuple],
                           resources: ResourceSet) -> None:
        if pg_key is not None:
            bundle = self._pg_bundles.get(pg_key)
            if bundle is not None:
                bundle.release(resources)
            return
        self.resources.release(resources)

    # -------------------------------------------------- per-lease TPU chips
    def _allocate_chips(self, amount: float) -> Optional[List[tuple]]:
        """Assign physical chips to a TPU lease: whole units take
        exclusive free chips; a fractional tail bin-packs onto the most-
        loaded chip it still fits (so shards share one chip, not many).
        Returns [(chip_id, fraction)], [] for non-TPU leases, None when
        chip accounting can't satisfy the amount."""
        if amount <= 0 or not self._chip_used:
            return []
        eps = 1e-9
        whole = int(amount + eps)
        frac = amount - whole
        alloc: List[tuple] = []
        free = [i for i, u in enumerate(self._chip_used) if u <= eps]
        if len(free) < whole:
            return None
        for i in free[:whole]:
            alloc.append((i, 1.0))
        if frac > eps:
            taken = {i for i, _ in alloc}
            best = None
            for i, used in enumerate(self._chip_used):
                if i in taken or used + frac > 1.0 + eps:
                    continue
                if used > eps and (best is None
                                   or used > self._chip_used[best]):
                    best = i  # most-loaded shared chip that still fits
            if best is None:  # no partially-used chip fits: take a free one
                rest = free[whole:]
                if not rest:
                    return None  # nothing reserved yet: clean failure
                best = rest[0]
            alloc.append((best, frac))
        for i, f in alloc:
            self._chip_used[i] += f
        return alloc

    def _release_chips(self, chips: List[tuple]) -> None:
        for i, f in chips:
            if 0 <= i < len(self._chip_used):
                self._chip_used[i] = max(0.0, self._chip_used[i] - f)

    def _retire_chip_worker(self, lease: Lease) -> ResourceSet:
        """End of a lease that held chips. Its worker claimed the TPU
        backend (device_plane.claim_chips) and may hold libtpu and the
        device until its process exits, so it never returns to the idle
        pool, and the lease's TPU — the chips and the scalar resource
        together — is granted again only once that process is gone: the
        next holder's libtpu would otherwise fail or hang on a busy
        device. Returns what of the lease's resources is free now."""
        worker = lease.worker
        hold = _ChipHold(worker.pid, lease.chips, lease.resources.get("TPU"),
                         lease.pg_key)
        lease.chips = []
        self._chip_holds.append(hold)
        background(self._release_chips_when_gone(hold))
        if worker.alive:
            worker.alive = False
            self._expected_exits.add(worker.pid)
            if worker.conn is not None:
                background(worker.conn.push("shutdown", {}))
        rest = lease.resources.to_dict()
        rest.pop("TPU", None)
        return ResourceSet(rest)

    async def _release_chips_when_gone(self, hold: _ChipHold) -> None:
        """Nothing is released under a live process: a holder that has
        not exited ``_CHIP_EXIT_GRACE_S`` after it was told to is
        killed, and the wait goes on until the kernel has closed its
        files, the device among them."""
        kill_at = time.monotonic() + _CHIP_EXIT_GRACE_S
        while device_plane.process_alive(hold.pid):
            if kill_at is not None and time.monotonic() > kill_at:
                kill_at = None
                try:
                    os.kill(hold.pid, signal.SIGKILL)
                except OSError:
                    pass   # gone between the two looks
            await asyncio.sleep(0.05)
        self._chip_holds.remove(hold)
        self._release_chips(hold.chips)
        self._release_resources(hold.pg_key, ResourceSet({"TPU": hold.tpu}))
        await self._report_resources()
        await self._pump_pending()

    def _return_worker_to_pool(self, worker: WorkerHandle) -> None:
        worker.lease = None
        if worker.alive and worker.actor_id is None:
            worker.idle_since = time.monotonic()
            self._idle.append(worker)

    async def _request_lane_reclaims(self) -> None:
        """Pending demand (queued lease / PG reservation) cannot fit:
        ask fast-lane owners to hand back idle lanes. Rate-limited per
        lease; actual release is the owner's call (a busy lane stays)."""
        now = time.monotonic()
        for lease in self._leases.values():
            if not lease.lane or lease.conn is None:
                continue
            if now - lease.reclaim_requested_at < 2.0:
                continue
            lease.reclaim_requested_at = now
            try:
                await lease.conn.push("reclaim_lease",
                                      {"lease_id": lease.lease_id})
            except Exception:
                pass

    async def handle_reserve_bundle(self, payload, conn):
        """Two-phase commit, phase 1: reserve resources for a PG bundle
        (ref: placement_group_resource_manager.h)."""
        resources = ResourceSet(payload["resources"])
        key = (payload["pg_id"], payload["bundle_index"])
        if key in self._pg_bundles:
            return True
        if not self.resources.try_allocate(resources):
            # idle fast lanes may be squatting on exactly this capacity;
            # the GCS retries the reservation after the release lands
            await self._request_lane_reclaims()
            return False
        self._pg_bundles[key] = NodeResources(resources.to_dict())
        await self._report_resources()
        return True

    async def handle_commit_bundle(self, payload, conn):
        return (payload["pg_id"], payload["bundle_index"]) in self._pg_bundles

    async def handle_cancel_bundle(self, payload, conn):
        key = (payload["pg_id"], payload["bundle_index"])
        reserved = self._pg_bundles.pop(key, None)
        if reserved is None:
            return True
        # evict leases living inside the bundle: their workers are killed so
        # PG removal reclaims the processes (ref: gcs_placement_group_scheduler
        # DestroyPlacementGroupCommittedBundleResources kills bundle workers)
        for lease in list(self._leases.values()):
            if lease.pg_key == key:
                self._leases.pop(lease.lease_id, None)
                self._forget_rid(lease.lease_id)
                if lease.chips:
                    self._retire_chip_worker(lease)
                worker = lease.worker
                worker.lease = None
                worker.alive = False
                self._expected_exits.add(worker.pid)
                if worker.conn is not None:
                    await worker.conn.push("shutdown", {})
        # the reservation goes back to the node, less the TPU that chip
        # workers of this bundle still hold: that follows them, straight
        # to the node pool
        freed = reserved.total.copy()
        for hold in self._chip_holds:
            if hold.pg_key == key:
                hold.pg_key = None
                freed.subtract(ResourceSet({"TPU": hold.tpu}))
        self.resources.release(freed)
        # queued leases waiting on this PG with no bundle left here would wait
        # forever: fail them so the submitter re-resolves (and learns of
        # removal from the GCS directory)
        if not any(k[0] == key[0] for k in self._pg_bundles):
            for pending in self._pending_leases[:]:
                pgk = self._pg_key(pending.payload.get("strategy"))
                if pgk is not None and pgk[0] == key[0] and not pending.future.done():
                    pending.future.set_exception(
                        ValueError("placement group bundle canceled"))
        await self._report_resources()
        await self._pump_pending()
        return True

    # ------------------------------------------------------- object directory
    def _mark_local_sealed(self, oid: ObjectID, size: int) -> None:
        self._sealed[oid] = size
        self._lost_objects.discard(oid)
        for fut in self._object_waiters.pop(oid, []):
            if not fut.done():
                fut.set_result(True)

    async def handle_object_sealed(self, payload, conn):
        oid, size = payload["object_id"], payload["size"]
        self._mark_local_sealed(oid, size)
        background(self._report_location(oid))
        return True

    async def handle_objects_sealed_batch(self, payload, conn):
        """Coalesced seal notifications (fast-lane executors batch their
        per-return reports; one frame covers a flush window)."""
        oids = []
        for oid, size in payload["objects"]:
            self._mark_local_sealed(oid, size)
            oids.append(oid)
        background(self._report_locations(oids))
        return True

    async def _report_locations(self, oids: List[ObjectID]):
        try:
            await self.gcs.call("add_object_locations", {
                "object_ids": oids, "node_id": self.node_id})
        except Exception:
            pass

    async def _report_location(self, oid: ObjectID):
        try:
            await self.gcs.call("add_object_location", {
                "object_id": oid, "node_id": self.node_id})
        except Exception:
            pass

    async def _drop_location(self, oid: ObjectID):
        try:
            await self.gcs.call("remove_object_location", {
                "object_id": oid, "node_id": self.node_id})
        except Exception:
            pass

    def _on_object_event(self, payload):
        if payload.get("event") != "lost":
            return
        oid = payload["object_id"]
        if self.store.contains(oid):
            return  # we hold a copy; not lost here
        self._lost_objects.add(oid)
        for fut in self._object_waiters.pop(oid, []):
            if not fut.done():
                fut.set_result(False)  # False = lost

    # ------------------------------------------------ inter-node object pull
    async def _peer_client(self, address: str) -> RpcClient:
        client = self._peer_clients.get(address)
        if client is None or client.closed:
            client = RpcClient(address)
            await client.connect(timeout=10)
            self._peer_clients[address] = client
        return client

    def _start_pull(self, oid: ObjectID, prio: int = 1) -> None:
        """Idempotently request a pull of oid to the local store through
        the admission-controlled PullManager (ref: pull_manager.h:57 —
        byte budget + priority classes; retries while waiters exist)."""
        self.pulls.request(oid, prio, size_hint=self._sealed.get(oid, 0))

    async def _pull(self, oid: ObjectID) -> Optional[int]:
        backoff = 0.02
        denials = 0
        while True:
            if self.store.contains(oid) or oid in self._lost_objects:
                return self._sealed.get(oid, 0)
            if oid not in self._object_waiters:
                return None  # nobody waiting anymore
            try:
                locs = await self.gcs.call(
                    "get_object_locations", {"object_ids": [oid]})
            except Exception:
                locs = {oid: []}
            transfer_map = locs.get("__transfer__", {})
            candidates = [loc for loc in locs.get(oid, [])
                          if loc[0] != self.node_id]
            # broadcast tree: spread pullers over ALL current holders
            # instead of piling onto the list head (each completed pull
            # registers a new location, so the source set grows as the
            # broadcast progresses — ref: push_manager.h:32)
            random.shuffle(candidates)
            denied = False
            for loc in candidates:
                node_id, address = loc[0], loc[1]
                xfer_address = transfer_map.get(node_id.hex(), "")
                token = await self._acquire_transfer_token(oid, address)
                if token is False:
                    denied = True   # holder at sender cap: try another
                    continue
                try:
                    size = await self._fetch_via(oid, address, xfer_address)
                    if size is not None:
                        self._sealed[oid] = size
                        self._mark_local_sealed(oid, size)
                        self._pull_sources[oid] = node_id
                        # bounded observability maps (free also prunes)
                        for book in (self._pull_sources,
                                     self._transfer_token_high):
                            while len(book) > 4096:
                                book.pop(next(iter(book)))
                        background(self._report_location(oid))
                        return size
                    # holder no longer has it: drop the stale location
                    await self.gcs.call("remove_object_location", {
                        "object_id": oid, "node_id": node_id})
                except Exception:
                    continue
                finally:
                    if token:
                        background(self._release_transfer_token(
                            oid, address))
            if denied:
                # every holder is saturated: a fresh copy registers soon
                # — re-poll faster than the cold backoff, but with
                # jittered exponential growth so a 50-node broadcast's
                # denied majority doesn't hammer the GCS/holders at a
                # synchronized 20 Hz for the whole transfer
                denials += 1
                wait = min(0.25, 0.05 * (2 ** min(denials, 4)))
                await asyncio.sleep(wait * (0.5 + random.random()))
                continue
            denials = 0
            await asyncio.sleep(backoff)
            # cap grows to 2s: pending-local objects (task still running
            # here) shouldn't hammer the GCS with location polls
            backoff = min(2.0, backoff * 2)

    async def _acquire_transfer_token(self, oid: ObjectID, address: str):
        """Ask a holder for a sender slot. True = granted, False =
        holder saturated, None = holder predates tokens / unreachable
        (proceed ungated — the pull itself will fail if the holder is
        really gone)."""
        if self.cfg.object_transfer_max_senders_per_object <= 0:
            return None
        try:
            client = await self._peer_client(address)
            ok = await client.call("transfer_token", {
                "object_id": oid, "node_id": self.node_id.hex(),
            }, timeout=5)
        except Exception:
            return None
        return bool(ok)

    async def _release_transfer_token(self, oid: ObjectID, address: str):
        try:
            client = await self._peer_client(address)
            await client.call("transfer_token_release", {
                "object_id": oid, "node_id": self.node_id.hex(),
            }, timeout=5)
        except Exception:
            pass

    # sender-slot grants per local object: {oid: {puller_hex: expiry}}
    _TRANSFER_TOKEN_TTL_S = 120.0

    async def handle_transfer_token(self, payload, conn):
        cap = self.cfg.object_transfer_max_senders_per_object
        if cap <= 0:
            return True
        oid = payload["object_id"]
        puller = payload["node_id"]
        now = time.monotonic()
        if len(self._transfer_tokens) > 4096:
            # sweep grants of crashed pullers across ALL objects (the
            # per-oid sweep below only fires on a repeat acquire)
            for stale_oid in [o for o, g in self._transfer_tokens.items()
                              if all(exp < now for exp in g.values())]:
                del self._transfer_tokens[stale_oid]
        grants = self._transfer_tokens.setdefault(oid, {})
        for stale in [p for p, exp in grants.items() if exp < now]:
            del grants[stale]
        if puller in grants or len(grants) < cap:
            grants[puller] = now + self._TRANSFER_TOKEN_TTL_S
            high = self._transfer_token_high.get(oid, 0)
            self._transfer_token_high[oid] = max(high, len(grants))
            self._track_token_conn(conn, oid, puller)
            return True
        return False

    def _track_token_conn(self, conn, oid: ObjectID, puller: str) -> None:
        """Tie a sender-slot grant to the puller's control connection:
        when the connection closes (crash, shutdown) the grant is
        released immediately instead of pinning one of the default 2
        slots until the 120 s TTL sweep."""
        if conn is None or not hasattr(conn, "closed"):
            return
        self._token_conn_grants.setdefault(conn, set()).add((oid, puller))
        if conn not in self._token_conn_watchers:
            self._token_conn_watchers[conn] = asyncio.ensure_future(
                self._watch_token_conn(conn))

    async def _watch_token_conn(self, conn) -> None:
        try:
            await conn.closed.wait()
        except asyncio.CancelledError:
            raise  # watcher cancelled at teardown: keep the task CANCELLED
        for oid, puller in self._token_conn_grants.pop(conn, ()):
            grants = self._transfer_tokens.get(oid)
            if grants is not None:
                grants.pop(puller, None)
                if not grants:
                    self._transfer_tokens.pop(oid, None)
        self._token_conn_watchers.pop(conn, None)

    def _on_transfer_puller_gone(self, oid: ObjectID, puller: str) -> None:
        """Data-plane conn-close hook (TransferServer on_puller_gone):
        the puller's last transfer connection for `oid` closed, so its
        sender-slot grant is over — whether the transfer finished or the
        puller crashed. Releasing here means a crashed puller (whose
        release RPC never arrives) frees the slot immediately instead of
        pinning it for the 120 s TTL."""
        grants = self._transfer_tokens.get(oid)
        if grants is not None:
            grants.pop(puller, None)
            if not grants:
                self._transfer_tokens.pop(oid, None)

    async def handle_transfer_token_release(self, payload, conn):
        grants = self._transfer_tokens.get(payload["object_id"])
        if grants is not None:
            grants.pop(payload["node_id"], None)
            if not grants:
                self._transfer_tokens.pop(payload["object_id"], None)
        tracked = self._token_conn_grants.get(conn)
        if tracked is not None:
            tracked.discard((payload["object_id"], payload["node_id"]))
        return True

    async def _fetch_via(self, oid: ObjectID, address: str,
                         xfer_address: str) -> Optional[int]:
        """Pull one object from one holder: parallel raw-frame streams on
        the transfer plane when the holder advertises one, control-RPC
        chunks otherwise. A transfer-plane transport failure retries once
        through the RPC path before the holder is given up on — a dropped
        stream must not fail the pull while the holder is still alive
        (chaos: tests/test_chaos.py transfer-drop)."""
        if xfer_address:
            from .object_transfer import fetch_object

            if self.store.contains(oid):
                return self._sealed.get(oid, 0)
            holder = {}

            def _create(size: int):
                buf, entry = self.store.create_streaming(oid, size)
                holder["entry"] = entry
                # cut-through relay: advertise this IN-PROGRESS copy in
                # the directory now — downstream pullers stream behind
                # our watermark instead of waiting for our seal, so a
                # broadcast tree pipelines across its depth (retracted
                # below if the pull dies)
                background(self._report_location(oid))
                return buf

            try:
                return await fetch_object(
                    xfer_address, oid, _create,
                    streams=self.cfg.object_transfer_streams,
                    chunk_bytes=self.cfg.object_transfer_chunk_bytes,
                    seal=lambda: self.store.seal(oid),
                    abort=lambda: self.store.abort(oid),
                    admit_bytes=lambda n: self.pulls.acquire_bytes(oid, n),
                    on_progress=lambda wm: holder["entry"].advance(wm),
                    puller=self.node_id.hex())
            except Exception:
                if "entry" in holder:
                    # the early advertisement is stale — retract it
                    # BEFORE the RPC fallback can re-add it on success
                    await self._drop_location(oid)
                pass  # plane unreachable/dropped: fall through to RPC
            finally:
                self.pulls.release_bytes(oid)
        if await self._fetch_from(oid, address):
            return self._sealed.get(oid, 0)
        return None

    async def _fetch_from(self, oid: ObjectID, address: str) -> bool:
        """Chunked fetch of a sealed object from a peer raylet into the local
        store. Returns False if the peer no longer holds the object."""
        client = await self._peer_client(address)
        chunk = self.cfg.object_transfer_chunk_bytes
        first = await client.call("pull_object", {
            "object_id": oid, "offset": 0, "length": chunk}, timeout=60)
        if first is None:
            return False
        size = first["size"]
        if self.store.contains(oid):
            return True
        buf = self.store.create(oid, size)
        try:
            data = first["data"]
            buf[: len(data)] = data
            offset = len(data)
            while offset < size:
                part = await client.call("pull_object", {
                    "object_id": oid, "offset": offset, "length": chunk}, timeout=60)
                if part is None:
                    raise ConnectionError("holder dropped object mid-transfer")
                pdata = part["data"]
                buf[offset: offset + len(pdata)] = pdata
                offset += len(pdata)
        except BaseException:
            self.store.abort(oid)
            raise
        self.store.seal(oid)
        self._sealed[oid] = size
        return True

    async def handle_forget_lost(self, payload, conn):
        """Clear lost markers so a recovery attempt (lineage reconstruction
        re-creating the object elsewhere) can be awaited afresh; without this
        the lost flag is sticky and recovery could never be observed."""
        for oid in payload["object_ids"]:
            self._lost_objects.discard(oid)
        return True

    async def handle_pull_object(self, payload, conn):
        """Serve one chunk of a sealed local object to a peer raylet
        (ref: push_manager.h:32 — chunked sends on the control transport).
        An object still being received/restored here serves from behind
        its watermark (bounded wait), so the RPC fallback path cuts
        through in-progress creations the same way the transfer plane
        does."""
        oid = payload["object_id"]
        offset, length = payload["offset"], payload["length"]
        view = self.store.get(oid)
        if view is None:
            entry = self.store.inprogress(oid)
            if entry is not None:
                total = entry.size
                off = min(offset, total)
                ln = min(length, total - off)
                if not ln or await entry.wait_for(off + ln, 30.0):
                    return {"size": total,
                            "data": bytes(entry.buf[off:off + ln])}
            return None
        return {"size": len(view), "data": bytes(view[offset: offset + length])}

    async def handle_wait_objects(self, payload, conn):
        """Block until `num_returns` of `object_ids` are sealed locally, an
        object is declared lost cluster-wide, or timeout (ref: wait_manager.h).
        Missing objects trigger background pulls from remote holders."""
        oids: List[ObjectID] = payload["object_ids"]
        num_returns = payload.get("num_returns", len(oids))
        timeout = payload.get("timeout")
        # the store is authoritative: a directory entry whose file was evicted
        # must not be reported ready (get would ObjectLostError)
        ready, lost = [], []
        for oid in oids:
            if self.store.contains(oid):
                self._sealed.setdefault(oid, 0)
                ready.append(oid)
            elif oid in self._lost_objects:
                lost.append(oid)
            else:
                self._sealed.pop(oid, None)
        if len(ready) >= num_returns or len(ready) + len(lost) >= len(oids):
            return {"ready": ready, "lost": lost}
        futures = {}
        prio = payload.get("prio", 1)  # 0 = a worker is blocked on args
        for oid in oids:
            if oid not in self._sealed and oid not in self._lost_objects:
                fut = asyncio.get_event_loop().create_future()
                self._object_waiters.setdefault(oid, []).append(fut)
                futures[oid] = fut
                self._start_pull(oid, prio)
        deadline = None if timeout is None else asyncio.get_event_loop().time() + timeout
        while len(ready) < num_returns and len(ready) + len(lost) < len(oids):
            remaining = None if deadline is None else max(0.0, deadline - asyncio.get_event_loop().time())
            pending = [f for f in futures.values() if not f.done()]
            if not pending:
                break
            # bound each wait so we also poll the local store (seal paths that
            # bypass this raylet's directory, e.g. a co-located process)
            poll = 0.05 if remaining is None else min(0.05, remaining)
            done, _ = await asyncio.wait(pending, timeout=poll,
                                         return_when=asyncio.FIRST_COMPLETED)
            for oid, fut in futures.items():
                if not fut.done() and oid not in self._sealed and self.store.contains(oid):
                    self._sealed.setdefault(oid, 0)
                    fut.set_result(True)
            ready = [oid for oid in oids if oid in self._sealed]
            lost = [oid for oid in oids if oid in self._lost_objects and oid not in self._sealed]
            if not done and remaining is not None and remaining <= poll \
                    and len(ready) < num_returns:
                break  # timeout
        for oid, fut in futures.items():
            if not fut.done():
                try:
                    self._object_waiters.get(oid, []).remove(fut)
                except ValueError:
                    pass
                fut.cancel()
            if oid in self._object_waiters and not self._object_waiters[oid]:
                del self._object_waiters[oid]
        return {"ready": ready, "lost": lost}

    async def handle_free_objects(self, payload, conn):
        for oid in payload["object_ids"]:
            if self._sealed.pop(oid, None) is not None or self.store.contains(oid):
                background(self._drop_location(oid))
            self.store.delete(oid)
            self._transfer_tokens.pop(oid, None)
            self._transfer_token_high.pop(oid, None)
            self._pull_sources.pop(oid, None)
        return True

    async def handle_pin_objects(self, payload, conn):
        for oid in payload["object_ids"]:
            self.store.pin(oid)
        return True

    async def handle_unpin_objects(self, payload, conn):
        for oid in payload["object_ids"]:
            self.store.unpin(oid)
        return True

    # ------------------------------------------------------------ state api
    async def handle_node_stats(self, payload, conn):
        return {
            "node_id": self.node_id,
            "resources_total": self.resources.total.to_dict(),
            "resources_available": self.resources.available.to_dict(),
            "num_workers": len(self._workers),
            "num_idle_workers": len(self._idle),
            "num_leases": len(self._leases),
            "num_pending_leases": len(self._pending_leases),
            "num_objects": len(self._sealed),
            "store_used_bytes": self.store.used_bytes(),
            "store_capacity_bytes": self.store.capacity,
            # per-lease detail: who holds this node's resources (the
            # `ray memory`-style leak-hunting view)
            "leases": [{
                "lease_id": lease.lease_id,
                "resources": lease.resources.to_dict(),
                "owner": lease.owner_address,
                "lane": lease.lane,
                "actor_id": (lease.worker.actor_id.hex()
                             if lease.worker.actor_id else None),
            } for lease in self._leases.values()],
        }
