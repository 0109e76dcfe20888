"""Global Control Service: cluster metadata + control plane.

TPU-native analog of the reference GCS (ref: src/ray/gcs/gcs_server/
gcs_server.h, gcs_actor_manager.cc:394,480,858, gcs_node_manager.h,
gcs_kv_manager.h, gcs_job_manager.h) with its pubsub (ref: src/ray/pubsub/
publisher.h:300) collapsed into push frames on the same RPC server. Storage is
pluggable like the reference store_client (ref: gcs/store_client/
store_client.h:33): in-memory by default, file-backed journal for
fault-tolerant restart (the Redis-persistence analog).

Tables: nodes, actors, jobs, KV (function blobs, named refs), placement
groups. All mutating handlers publish deltas on pubsub channels so raylets and
core workers keep eventually-consistent views (the RaySyncer role, ref:
src/ray/common/ray_syncer/ray_syncer.h:73).
"""

from __future__ import annotations

import asyncio
import json
import os
import pickle
import time
from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional, Set, Tuple

from .gcs_storage import RemoteStoreClient, Storage
from .ids import ActorID, JobID, NodeID, ObjectID, PlacementGroupID
from .rpc import RpcServer, ServerConnection, background

# Actor lifecycle states (ref: gcs.proto ActorTableData.ActorState)
DEPENDENCIES_UNREADY = "DEPENDENCIES_UNREADY"
PENDING_CREATION = "PENDING_CREATION"
ALIVE = "ALIVE"
RESTARTING = "RESTARTING"
DEAD = "DEAD"
# the states in which an actor's constructor has not returned yet. An
# actor no worker has been leased for is in them too:
# ``ActorInfo.constructor_running`` tells the two apart. Who waits behind
# a running constructor (a call, in core_worker._wait_actor_alive; the
# serve controller's health check) gives it this long: weights made on a
# chip plus cold compiles take minutes, never this long
CONSTRUCTING = (PENDING_CREATION, RESTARTING)
CONSTRUCTOR_TIMEOUT_S = 1800.0


@dataclass
class NodeInfo:
    node_id: NodeID
    address: str                      # raylet socket path
    resources_total: Dict[str, float]
    resources_available: Dict[str, float]
    labels: Dict[str, str] = field(default_factory=dict)
    alive: bool = True
    # TPU slice topology (ICI coordinates of this host's chips)
    slice_name: str = ""
    host_index: int = 0
    resource_seq: int = 0     # last-applied availability report sequence
    store_dir: str = ""       # shm namespace (same-host drivers attach to it)
    # resource shapes of leases queued on this raylet (the autoscaler's
    # demand signal; ref: autoscaler v2 cluster-status resource demands)
    pending_demands: list = field(default_factory=list)
    # bulk object-transfer listener (object_transfer.py); "" = peer
    # predates the transfer plane, pulls fall back to control-RPC chunks
    # (wire schema rule: appended field, decode fills the default)
    transfer_address: str = ""
    # NTP-style estimate of (GCS clock - this node's clock), seconds,
    # reported by the raylet's clock-sync loop; timestamps from this
    # node compose cluster-wide as local_ts + clock_offset
    clock_offset: float = 0.0
    # GCS wall clock of the last sign of life from this node (successful
    # health probe or resource report) — heartbeat age in `cli status` /
    # dashboard is now - last_heartbeat_t (wire schema rule: appended
    # field, decode fills the default)
    last_heartbeat_t: float = 0.0


@dataclass
class ActorInfo:
    actor_id: ActorID
    state: str
    name: str = ""
    namespace: str = ""
    detached: bool = False    # survives its creating driver (ref: detached
    #                           lifetime, gcs_actor_manager job cleanup)
    owner_is_driver: bool = True  # created by a driver (vs by another actor)
    address: str = ""                 # worker socket when ALIVE
    node_id: Optional[NodeID] = None
    class_name: str = ""
    max_restarts: int = 0
    num_restarts: int = 0
    death_cause: str = ""
    creation_spec: Any = None         # pickled TaskSpec for restarts
    # a leased worker has begun the constructor (handle_actor_constructing)
    constructor_running: bool = False


class GcsServer:
    def __init__(self, socket_path: str, journal_path: Optional[str] = None,
                 advertise_host: Optional[str] = None,
                 external_store_address: Optional[str] = None,
                 on_storage_failure=None):
        self.server = RpcServer(socket_path, name="gcs",
                                advertise_host=advertise_host)
        self.server.register_all(self)
        self.server.on_disconnect = self._on_disconnect
        # persistence ladder (gcs_storage.py): external store > local
        # journal > memory-only. With an external store the head node's
        # DISK is expendable — a replacement GCS anywhere re-seeds from
        # the store (ref: redis_store_client.h:111 + gcs_init_data.h)
        self._remote_store: Optional[RemoteStoreClient] = None
        self._on_storage_failure = on_storage_failure
        self._storage_health_task: Optional[asyncio.Task] = None
        self._node_health_task: Optional[asyncio.Task] = None
        if external_store_address:
            self._remote_store = RemoteStoreClient(external_store_address)
            self.storage = Storage(journal_path, remote=self._remote_store)
        else:
            self.storage = Storage(journal_path)
        self.nodes: Dict[NodeID, NodeInfo] = {}
        self.actors: Dict[ActorID, ActorInfo] = {}
        self.named_actors: Dict[Tuple[str, str], ActorID] = {}  # (namespace, name)
        self.jobs: Dict[JobID, dict] = {}
        self.placement_groups: Dict[PlacementGroupID, dict] = {}
        self._pg_tasks: Dict[PlacementGroupID, asyncio.Task] = {}
        self._pg_raylet_clients: Dict[str, Any] = {}
        self._pg_waiters: Dict[PlacementGroupID, List[asyncio.Future]] = {}
        # object directory: oid -> set of node ids holding a sealed copy
        # (the ownership-based-object-directory role, ref:
        # src/ray/object_manager/ownership_based_object_directory.h — here the
        # GCS keeps the authoritative location view; owners cache it)
        self.object_locations: Dict[ObjectID, Set[NodeID]] = {}
        # pubsub: channel -> set of subscribed connections
        self._subs: Dict[str, Set[ServerConnection]] = {}
        self._node_conns: Dict[ServerConnection, NodeID] = {}
        self._driver_conns: Dict[ServerConnection, JobID] = {}
        self._driver_cleanup_timers: Dict[JobID, asyncio.Task] = {}
        # observability tables (in-memory, bounded; not journaled)
        self.metrics: Dict[tuple, dict] = {}
        self.task_events: Dict[Any, dict] = {}
        self.MAX_TASK_EVENTS = 10_000
        self.MAX_METRICS = 10_000
        # structured cluster events (ref: src/ray/util/event.h +
        # _private/event/export_event_logger.py — severity-tagged
        # lifecycle records the dashboard event module surfaces)
        import collections as _collections

        self.events: "_collections.deque" = _collections.deque(maxlen=5000)
        # stall sentinel: collective/barrier arrival tables. Key
        # (group, step) -> record with per-rank clock-corrected arrival
        # timestamps; the collective watchdog flags records with
        # some-but-not-all arrivals past the deadline, and completed
        # steps roll their arrival-skew histogram into per-host
        # straggler scores.
        self.collectives: Dict[tuple, dict] = {}
        self.MAX_COLLECTIVES = 2000
        self._collective_waiters: Dict[tuple, list] = {}
        # host key (node hex, or reported host name) -> skew aggregates
        self.straggler_stats: Dict[str, dict] = {}
        self._collective_watchdog_task: Optional[asyncio.Task] = None
        # SLO observability plane (ray_tpu/slo.py): ring-buffered time
        # series of the aggregated metrics view + burn-rate monitor,
        # both fed by _slo_loop on the evaluation tick. Built lazily in
        # start() so config overrides applied at init are honored.
        self.series_store = None
        self.slo_monitor = None
        self._slo_task: Optional[asyncio.Task] = None
        # training goodput plane (ray_tpu/train/telemetry.py): per-job
        # ledgers folding rank step reports into productive vs badput
        # chip-seconds; fed by handle_train_report, surfaced through
        # handle_train_status and the _train_metrics synthetics
        self.train_ledgers: Dict[str, Any] = {}
        self.MAX_TRAIN_JOBS = 64
        # black-box plane (_private/blackbox.py): session dir derived
        # from the journal location (flight files / bundles / event
        # journal live next to it); the GCS keeps its own flight ring,
        # checkpoints durable observability state, and sweeps corpse
        # flight files when it declares a node dead.
        self.session_dir: Optional[str] = (
            os.path.dirname(journal_path) if journal_path else None)
        self.started_at = time.time()
        self._blackbox = None
        self._events_journal = None
        self._obs_task: Optional[asyncio.Task] = None
        # per-(node, role, reason, signal) crash counter — the
        # process_crashes_total Prometheus series
        self.crash_counts: Dict[tuple, int] = {}
        # clock offsets recovered from the last obs checkpoint (nodes
        # are not restored across restarts; postmortem still needs the
        # dead fleet's offsets to clock-correct its timeline)
        self._restored_clock_offsets: Dict[str, float] = {}
        self._last_diag_t = 0.0
        # node registration times (process_uptime_seconds source; a
        # raylet restart re-registers and resets its clock)
        self._node_first_seen: Dict[str, float] = {}
        self._next_job = 1
        if self._remote_store is None:
            self._restore_tables()
        # else: tables restore in start(), after the async snapshot load

    # ---- journal-backed table persistence (the Redis-persistence analog:
    #      gcs_table_storage.h + gcs_init_data.h restart rebuild) ----
    def _persist(self, table: str, key: str, obj: Any) -> None:
        self.storage.put("__table_" + table, key, pickle.dumps(obj))

    def _unpersist(self, table: str, key: str) -> None:
        self.storage.delete("__table_" + table, key)

    def _restore_tables(self) -> None:
        """Rebuild actor/PG/job tables from the journal on restart. Nodes
        are NOT restored — raylets re-register and their liveness is
        re-derived from fresh connections. Restored actor addresses may be
        stale; callers re-resolve through actor_failed on first contact."""
        for key in self.storage.keys("__table_actors"):
            info: ActorInfo = pickle.loads(
                self.storage.get("__table_actors", key))
            self.actors[info.actor_id] = info
            if info.name:
                self.named_actors[(info.namespace, info.name)] = info.actor_id
        for key in self.storage.keys("__table_pgs"):
            pg = pickle.loads(self.storage.get("__table_pgs", key))
            self.placement_groups[pg["pg_id"]] = pg
        for key in self.storage.keys("__table_jobs"):
            job_id, job = pickle.loads(self.storage.get("__table_jobs", key))
            self.jobs[job_id] = job
            self._next_job = max(self._next_job, int(key) + 1)

    async def start(self):
        if self._remote_store is not None:
            # seed tables from the external store BEFORE listening — a
            # client must never observe a half-restored GCS
            await self._remote_store.connect()
            await self.storage.load_remote()
            self._restore_tables()
            self._storage_health_task = asyncio.ensure_future(
                self._storage_failure_detector())
        await self.server.start()
        from .config import global_config

        if global_config().health_check_timeout_ms > 0:
            self._node_health_task = asyncio.ensure_future(
                self._node_health_loop())
        if global_config().collective_watchdog_interval_s > 0:
            self._collective_watchdog_task = asyncio.ensure_future(
                self._collective_watchdog_loop())
        cfg = global_config()
        if cfg.metrics_series_enabled and cfg.slo_eval_interval_s > 0:
            from ..slo import (SeriesStore, SloMonitor, default_policies,
                               parse_specs)

            self.series_store = SeriesStore(
                max_samples=cfg.metrics_series_max_samples,
                min_interval_s=cfg.metrics_series_min_interval_s,
                max_series=cfg.metrics_series_max_series)
            try:
                specs = parse_specs(cfg.slo_specs)
            except Exception as e:
                specs = []
                self._event("slo", "ERROR",
                            f"invalid slo_specs config, monitor empty: {e}")
            self.slo_monitor = SloMonitor(specs, default_policies(cfg))
            self._slo_task = asyncio.ensure_future(self._slo_loop())
        # durable observability: reload the last checkpoint (series
        # rings, SLO alert state, cumulative metrics table, task events)
        # so `cli slo`/`cli timeline` span the restart, then start
        # checkpointing ourselves
        self._restore_obs_checkpoint(cfg)
        if cfg.obs_checkpoint_interval_s > 0:
            self._obs_task = asyncio.ensure_future(
                self._obs_checkpoint_loop())
        if cfg.blackbox_enabled and self.session_dir:
            from . import blackbox

            self._blackbox = blackbox.FlightRecorder(
                "gcs", self.session_dir,
                ident=self.server.address,
                ring_size=cfg.blackbox_ring_size,
                flush_interval_s=cfg.blackbox_flush_interval_s,
                inflight_provider=self._blackbox_inflight,
            ).start()
        # restored placement groups that never finished reserving resume
        # scheduling now that the loop is live (restart recovery)
        for pg in self.placement_groups.values():
            if pg["state"] in ("PENDING", "RESCHEDULING"):
                self._kick_pg_scheduler(pg["pg_id"])

    # ---- black-box plane: flight ring + durable observability ----
    def _blackbox_inflight(self) -> list:
        """The GCS's in-flight view for its own flight ring: RUNNING
        tasks and non-terminal actors (what a head-death postmortem
        needs to implicate)."""
        out = []
        for rec in self.task_events.values():
            if rec.get("state") == "RUNNING":
                out.append({"kind": "task",
                            "task_id": str(rec.get("task_id")),
                            "name": rec.get("name", "")})
        for actor in self.actors.values():
            if actor.state in (ALIVE, PENDING_CREATION, RESTARTING):
                out.append({"kind": "actor",
                            "actor_id": actor.actor_id.hex(),
                            "class_name": actor.class_name,
                            "state": actor.state})
        return out[:200]

    def _restore_obs_checkpoint(self, cfg) -> None:
        raw = self.storage.get("__obs", "checkpoint")
        if not raw:
            return
        try:
            snap = pickle.loads(raw)
        except Exception as e:
            self._event("blackbox", "WARNING",
                        f"obs checkpoint unreadable, starting cold: {e!r}")
            return
        now = time.time()
        # cumulative per-worker metric values: restoring them means the
        # next worker report lands as a normal delta on top, so the
        # aggregated counters never step backwards across the restart
        # (no windowed_increase reset artifact)
        for key, entry in (snap.get("metrics") or {}).items():
            if len(self.metrics) >= self.MAX_METRICS:
                break
            self.metrics.setdefault(key, entry)
        for task_id, rec in (snap.get("task_events") or {}).items():
            if len(self.task_events) >= self.MAX_TASK_EVENTS:
                break
            self.task_events.setdefault(task_id, rec)
        self._restored_clock_offsets = dict(
            snap.get("clock_offsets") or {})
        # goodput ledgers: cumulative badput/rework accounting must
        # survive a head restart like every other counter here
        for job, state in (snap.get("train") or {}).items():
            try:
                from ..train.telemetry import GoodputLedger

                ledger = GoodputLedger(job)
                ledger.load(state)
                self.train_ledgers.setdefault(job, ledger)
            except Exception:  # graftlint: ignore[swallow] — one bad
                continue  # ledger must not poison the restore
        restored_series = 0
        if self.series_store is not None and snap.get("series"):
            restored_series = self.series_store.load(snap["series"])
        if self.slo_monitor is not None and snap.get("slo"):
            self.slo_monitor.load(snap["slo"], now=now,
                                  grace_s=cfg.slo_restore_grace_s)
        self._event(
            "blackbox", "INFO",
            f"observability state restored from checkpoint "
            f"(written {now - snap.get('written_at', now):.1f}s ago: "
            f"{restored_series} series, "
            f"{len(snap.get('task_events') or {})} task events)",
            kind="obs_restore", written_at=snap.get("written_at"))

    def _obs_checkpoint_once(self):
        """Persist the observability plane through the storage seam
        (journal or remote store — whatever the GCS already trusts)."""
        from .blackbox import ObsCheckpointInfo

        now = time.time()
        snap = {
            "version": 1,
            "written_at": now,
            "series": (self.series_store.dump()
                       if self.series_store is not None else None),
            "slo": (self.slo_monitor.dump()
                    if self.slo_monitor is not None else None),
            "metrics": dict(self.metrics),
            "task_events": dict(self.task_events),
            "clock_offsets": {
                info.node_id.hex(): info.clock_offset
                for info in self.nodes.values()},
            "train": {job: ledger.dump()
                      for job, ledger in self.train_ledgers.items()},
        }
        self.storage.put("__obs", "checkpoint", pickle.dumps(snap))
        return ObsCheckpointInfo(
            written_at=now,
            series=len(self.series_store or ()),
            slo_specs=(len(self.slo_monitor.specs)
                       if self.slo_monitor is not None else 0),
            task_events=len(self.task_events),
            metrics=len(self.metrics))

    async def _obs_checkpoint_loop(self):
        from .config import global_config

        period = max(1.0, global_config().obs_checkpoint_interval_s)
        while True:
            await asyncio.sleep(period)
            try:
                self._obs_checkpoint_once()
            except Exception:  # graftlint: ignore[swallow] — a failed
                pass  # checkpoint must not kill the periodic loop

    async def handle_obs_checkpoint(self, payload, conn):
        """Force a checkpoint now (tests, pre-restart flushes)."""
        return self._obs_checkpoint_once()

    async def handle_list_incidents(self, payload, conn):
        """Crash-bundle summaries + recent crash/blackbox events (the
        dashboard Incidents panel / `cli postmortem --live` source)."""
        from . import blackbox

        bundles = (blackbox.bundle_infos(self.session_dir)
                   if self.session_dir else [])
        limit = int(payload.get("limit", 100))
        events = [e for e in self.events
                  if e.get("source") in ("blackbox", "NODE")
                  or e.get("kind") in ("fast_burn", "slow_burn")]
        return {
            "session_dir": self.session_dir or "",
            "bundles": bundles[-limit:],
            "events": events[-limit:],
            "crash_counts": [
                {"node": k[0], "role": k[1], "reason": k[2],
                 "signal": k[3], "count": n}
                for k, n in self.crash_counts.items()],
        }

    async def handle_report_crash(self, payload, conn):
        """A raylet swept a worker corpse: count it, log it, and name
        the in-flight work in the event stream."""
        node = str(payload.get("node_id", ""))[:12]
        key = (node, payload.get("role", "worker"),
               payload.get("reason", "unknown"),
               payload.get("signal", ""))
        self.crash_counts[key] = self.crash_counts.get(key, 0) + 1
        inflight = payload.get("inflight") or []
        names = ", ".join(
            f"{str(r.get('task_id') or r.get('request_id') or '?')[:12]}"
            f" ({r.get('fn') or r.get('kind') or '?'})"
            for r in inflight[:5]) or "nothing in flight"
        self._event(
            "blackbox", "ERROR",
            f"{payload.get('role', 'worker')} pid "
            f"{payload.get('pid')} on node {node} crashed "
            f"({payload.get('reason', 'unknown')}): {names}",
            kind="process_crash", **{
                k: payload.get(k) for k in
                ("role", "pid", "node_id", "reason", "signal",
                 "bundle_path", "inflight")})
        return True

    async def _node_health_loop(self):
        """ACTIVE node liveness probing (ref: gcs_health_check_manager.h:45
        — periodic per-node probe + consecutive-failure threshold).
        Socket disconnect alone misses wedged-but-connected raylets
        (SIGSTOP, half-open TCP, a livelocked event loop): each round
        calls ``health`` on every alive raylet with a timeout; after
        health_check_failure_threshold consecutive misses the node is
        declared dead through the same _mark_node_dead path a disconnect
        takes (actors failed, objects reaped/lineage-rebuilt, PG bundles
        rescheduled)."""
        from .config import global_config

        cfg = global_config()
        period = max(0.05, cfg.health_check_period_ms / 1000.0)
        timeout = max(0.05, cfg.health_check_timeout_ms / 1000.0)
        misses: Dict[NodeID, int] = {}
        inflight: Dict[NodeID, asyncio.Task] = {}
        while True:
            await asyncio.sleep(period)
            for node_id in [n for n in inflight if n not in self.nodes]:
                inflight.pop(node_id).cancel()
            for node_id, info in list(self.nodes.items()):
                if not info.alive:
                    misses.pop(node_id, None)
                    continue
                prev = inflight.get(node_id)
                if prev is not None and not prev.done():
                    # at most ONE probe in flight per node: when this
                    # loop stalls (~5 s GC pause, saturated loop), the
                    # backlog of rounds must not fire as a burst of
                    # already-timed-out probes that alone cross the
                    # failure threshold and declare a live raylet dead
                    continue

                async def _probe(node_id=node_id, info=info):
                    try:
                        client = await asyncio.wait_for(
                            self._raylet_client(info.address), timeout)
                        ok = await client.call("health", {}, timeout=timeout)
                    except Exception:
                        ok = False
                    if ok:
                        misses.pop(node_id, None)
                        info.last_heartbeat_t = time.time()
                        return
                    n = misses.get(node_id, 0) + 1
                    misses[node_id] = n
                    if n >= cfg.health_check_failure_threshold:
                        misses.pop(node_id, None)
                        # drop AND close the cached client: a later
                        # reconnect must not reuse a half-open transport,
                        # and a wedged peer never closes its end — without
                        # close() the recv task and fd leak per death
                        stale = self._pg_raylet_clients.pop(
                            info.address, None)
                        if stale is not None:
                            try:
                                await stale.close()
                            except Exception:
                                pass
                        await self._mark_node_dead(
                            node_id, f"health check failed ({n} probes)")

                # probes run concurrently so one wedged node cannot
                # stretch the round for the others
                inflight[node_id] = asyncio.ensure_future(_probe())

    async def _storage_failure_detector(self):
        """Ping the external store; a sustained outage is fatal for the
        GCS (its writes are no longer durable), so after the threshold
        it reports and — like the reference — dies for a supervisor to
        restart it against a healthy store (ref:
        gcs_redis_failure_detector.h). Tests inject on_storage_failure
        to observe the trip without losing the process."""
        from .config import global_config

        cfg = global_config()
        period = max(0.2, cfg.health_check_period_ms / 1000.0)
        strikes = 0
        while True:
            await asyncio.sleep(period)
            if await self._remote_store.ping():
                strikes = 0
                continue
            strikes += 1
            if strikes >= cfg.health_check_failure_threshold:
                self._event("GCS_STORAGE", "ERROR",
                            "external store unreachable; GCS writes are "
                            "no longer durable",
                            address=self._remote_store.address)
                if self._on_storage_failure is not None:
                    self._on_storage_failure()
                    strikes = 0  # injected handler chose to continue
                else:
                    os._exit(1)

    async def stop(self):
        for task in list(self._pg_tasks.values()):
            task.cancel()
        if self._storage_health_task is not None:
            self._storage_health_task.cancel()
        if self._node_health_task is not None:
            self._node_health_task.cancel()
        if self._collective_watchdog_task is not None:
            self._collective_watchdog_task.cancel()
        if self._slo_task is not None:
            self._slo_task.cancel()
        if self._obs_task is not None:
            self._obs_task.cancel()
            try:
                self._obs_checkpoint_once()  # final flush before exit
            except Exception:  # graftlint: ignore[swallow] — shutdown
                pass  # path: best-effort durability only
        if self._blackbox is not None:
            self._blackbox.close(clean=True)
            self._blackbox = None
        if self._events_journal is not None:
            try:
                self._events_journal.close()
            except Exception:  # graftlint: ignore[swallow] — shutdown
                pass  # path: journal fd close is best-effort
            self._events_journal = None
        for client in self._pg_raylet_clients.values():
            try:
                await client.close()
            except Exception:
                pass
        await self.server.stop()
        if self._remote_store is not None:
            try:
                await self._remote_store.close()
            except Exception:
                pass
        self.storage.close()

    # ---- structured events (ref: util/event.h EventManager) ----
    def _event(self, source: str, severity: str, message: str,
               **fields) -> None:
        rec = {"timestamp": time.time(), "source": source,
               "severity": severity, "message": message, **fields}
        self.events.append(rec)
        self._journal_event(rec)
        if self._blackbox is not None:
            self._blackbox.record_event(rec)
        # streamed to subscribers too (dashboard live tail)
        background(self._publish("events", rec))

    def _journal_event(self, rec: dict) -> None:
        """Append-only JSONL event journal in the session dir: the
        dead-cluster source for `cli events --follow` and postmortem."""
        if self._events_journal is None:
            from .config import global_config

            if (not global_config().event_journal_enabled
                    or not self.session_dir):
                return
            from . import blackbox

            try:
                path = blackbox.events_journal_path(self.session_dir)
                os.makedirs(os.path.dirname(path), exist_ok=True)
                self._events_journal = open(path, "a")
            except OSError:
                return
        try:
            self._events_journal.write(
                json.dumps(rec, default=str) + "\n")
            self._events_journal.flush()
        except (OSError, ValueError):
            pass  # closed mid-shutdown / disk full: in-memory deque wins

    async def handle_list_events(self, payload, conn):
        source = payload.get("source")
        severity = payload.get("severity")
        limit = int(payload.get("limit", 1000))
        out = [e for e in self.events
               if (not source or e["source"] == source)
               and (not severity or e["severity"] == severity)]
        return out[-limit:]

    async def handle_report_event(self, payload, conn):
        """Application/library events enter the same stream."""
        self._event(payload.get("source", "APP"),
                    payload.get("severity", "INFO"),
                    payload.get("message", ""),
                    **payload.get("fields", {}))
        return True

    # ---- stall sentinel: collective arrivals + straggler scores ----
    def _corrected_time(self, node_hex: str, t_local: float) -> float:
        """Apply the reporting node's NTP-style clock offset so arrival
        timestamps from different hosts compose on the GCS clock."""
        if node_hex:
            try:
                info = self.nodes.get(NodeID.from_hex(node_hex))
            except Exception:
                info = None
            if info is not None:
                return t_local + info.clock_offset
        return t_local

    def _prune_collectives(self) -> None:
        if len(self.collectives) <= self.MAX_COLLECTIVES:
            return
        done = [k for k, r in self.collectives.items()
                if r.get("completed_t") is not None]
        for k in done[:len(self.collectives) - self.MAX_COLLECTIVES]:
            self.collectives.pop(k, None)

    async def handle_collective_arrival(self, payload, conn):
        """One participant reached a collective/barrier step. Arrival
        timestamps are clock-corrected via the node table; a step whose
        arrivals complete rolls its skew histogram into the per-host
        straggler scores, and one left incomplete past its deadline is
        the collective watchdog's hung-collective signal."""
        group = payload["group"]
        step = int(payload["step"])
        rank = int(payload["rank"])
        size = int(payload["size"])
        node_hex = payload.get("node_id") or ""
        t = self._corrected_time(
            node_hex, float(payload.get("t") or time.time()))
        key = (group, step)
        rec = self.collectives.get(key)
        if rec is None:
            self._prune_collectives()
            rec = self.collectives[key] = {
                "group": group, "step": step,
                "op": payload.get("op", "barrier"), "size": size,
                "arrivals": {}, "first_t": t, "flagged": False,
                "completed_t": None,
                "deadline_s": float(payload.get("deadline_s") or 0.0),
            }
        rec["size"] = max(rec["size"], size)
        if payload.get("deadline_s"):
            dl = float(payload["deadline_s"])
            rec["deadline_s"] = (min(rec["deadline_s"], dl)
                                 if rec["deadline_s"] else dl)
        rec["arrivals"][rank] = {
            "t": t, "node_id": node_hex,
            "host": payload.get("host") or node_hex or f"rank{rank}",
        }
        rec["first_t"] = min(rec["first_t"], t)
        if (rec["completed_t"] is None
                and len(rec["arrivals"]) >= rec["size"]):
            rec["completed_t"] = time.time()
            self._roll_straggler_stats(rec)
        # wake collective_wait blockers (complete or not — they re-check)
        for fut in self._collective_waiters.pop(key, []):
            if not fut.done():
                fut.set_result(None)
        return {"arrived": len(rec["arrivals"]), "size": rec["size"],
                "complete": rec["completed_t"] is not None}

    async def handle_collective_wait(self, payload, conn):
        """Block until every rank reached (group, step) or timeout_s
        passes; the reply names missing ranks so the caller can raise a
        CollectiveTimeoutError that points at the hung participants."""
        key = (payload["group"], int(payload["step"]))
        deadline = time.monotonic() + float(payload.get("timeout_s", 30.0))
        while True:
            rec = self.collectives.get(key)
            if rec is not None and rec["completed_t"] is not None:
                return {"complete": True, "missing": [],
                        "arrived": len(rec["arrivals"]),
                        "size": rec["size"]}
            remaining = deadline - time.monotonic()
            if remaining <= 0:
                arrivals = rec["arrivals"] if rec else {}
                size = rec["size"] if rec else int(payload.get("size", 0))
                missing = sorted(set(range(size)) - set(arrivals))
                return {"complete": False, "missing": missing,
                        "arrived": len(arrivals), "size": size}
            fut = asyncio.get_event_loop().create_future()
            self._collective_waiters.setdefault(key, []).append(fut)
            try:
                await asyncio.wait_for(fut, min(remaining, 0.5))
            except asyncio.TimeoutError:
                pass
            finally:
                waiters = self._collective_waiters.get(key, [])
                if fut in waiters:
                    waiters.remove(fut)

    @staticmethod
    def _skew_bucket(late_s: float) -> str:
        for limit, label in ((0.001, "<1ms"), (0.01, "1-10ms"),
                             (0.1, "10-100ms"), (1.0, "100ms-1s"),
                             (10.0, "1-10s")):
            if late_s < limit:
                return label
        return ">10s"

    def _roll_straggler_stats(self, rec: dict) -> None:
        """Completed step: fold each rank's lateness (arrival - earliest
        arrival, clock-corrected) into its host's running aggregates.
        The straggler score read off these is the host's EMA lateness
        relative to the cluster mean — persistently-late hosts float to
        the top regardless of absolute step cadence."""
        arrivals = rec["arrivals"]
        if not arrivals:
            return
        t0 = min(a["t"] for a in arrivals.values())
        span = max(a["t"] for a in arrivals.values()) - t0
        rec["skew_s"] = span
        worst_rank = max(arrivals, key=lambda r: arrivals[r]["t"])
        for rank, a in arrivals.items():
            late = a["t"] - t0
            st = self._straggler_entry(a["host"], a.get("node_id"))
            self._fold_lateness(st, late)
            # only count "worst in step" when the skew is material —
            # someone is always last even in a perfectly healthy step
            if rank == worst_rank and span >= 0.005:
                st["worst_count"] += 1

    def _straggler_entry(self, host: str, node_id: Optional[str]) -> dict:
        st = self.straggler_stats.setdefault(host, {
            "host": host, "node_id": node_id or "", "steps": 0,
            "sum_lateness_s": 0.0, "max_lateness_s": 0.0,
            "ema_lateness_s": 0.0, "worst_count": 0, "hist": {}})
        if node_id:
            # scheduling deprioritization keys on node ids; collective
            # arrivals and direct reports both refresh the mapping
            st["node_id"] = node_id
        return st

    def _fold_lateness(self, st: dict, late: float) -> None:
        st["steps"] += 1
        st["sum_lateness_s"] += late
        st["max_lateness_s"] = max(st["max_lateness_s"], late)
        st["ema_lateness_s"] = (late if st["steps"] == 1
                                else 0.8 * st["ema_lateness_s"]
                                + 0.2 * late)
        bucket = self._skew_bucket(late)
        st["hist"][bucket] = st["hist"].get(bucket, 0) + 1

    async def handle_report_straggler(self, payload, conn):
        """Direct lateness sample outside the collective plane: a raylet
        watchdog flagging a RUNNING task past threshold, or an owner
        whose hedge beat the primary copy. Folds into the same per-host
        aggregates that drive straggler_scores, so task-plane stragglers
        deprioritize scheduling exactly like collective-skew ones."""
        node_id = payload.get("node_id") or ""
        # host key matches what collective arrivals use (node hex when no
        # host name rides the payload) so both planes fold into one entry
        host = payload.get("host") or node_id
        if not host:
            return False  # unattributable sample
        st = self._straggler_entry(host, node_id)
        self._fold_lateness(st, max(0.0, float(payload.get("late_s", 0.0))))
        if payload.get("source"):
            st.setdefault("sources", {})
            st["sources"][payload["source"]] = \
                st["sources"].get(payload["source"], 0) + 1
        return True

    async def handle_straggler_scores(self, payload, conn):
        stats = list(self.straggler_stats.values())
        if not stats:
            return []
        mean_ema = (sum(s["ema_lateness_s"] for s in stats)
                    / len(stats)) or 1e-9
        out = []
        for s in stats:
            rec = dict(s)
            rec["score"] = s["ema_lateness_s"] / max(mean_ema, 1e-9)
            out.append(rec)
        out.sort(key=lambda s: s["score"], reverse=True)
        return out

    async def handle_list_collectives(self, payload, conn):
        out = []
        for rec in self.collectives.values():
            r = {k: v for k, v in rec.items() if k != "arrivals"}
            r["arrived_ranks"] = sorted(rec["arrivals"])
            r["missing_ranks"] = sorted(
                set(range(rec["size"])) - set(rec["arrivals"]))
            out.append(r)
        return out

    async def _collective_watchdog_loop(self):
        """Flag collectives with some-but-not-all arrivals past their
        deadline: emit a WARNING "hung collective" event naming the
        missing ranks/hosts and pull Python stacks from the implicated
        nodes' workers."""
        from .config import global_config

        cfg = global_config()
        period = cfg.collective_watchdog_interval_s
        while True:
            await asyncio.sleep(period)
            now = time.time()
            for key, rec in list(self.collectives.items()):
                if rec["completed_t"] is not None or rec["flagged"]:
                    continue
                deadline = rec["deadline_s"] or cfg.collective_stall_timeout_s
                if now - rec["first_t"] < deadline:
                    continue
                rec["flagged"] = True
                try:
                    await self._flag_hung_collective(rec, deadline)
                except Exception:
                    pass  # forensics must never kill the watchdog

    def _rank_host_map(self, group: str) -> Dict[int, dict]:
        """rank -> {node_id, host} learned from every observed step of
        this group (a missing rank never arrived THIS step, but earlier
        steps tell us where it lives)."""
        mapping: Dict[int, dict] = {}
        for (g, _), rec in self.collectives.items():
            if g != group:
                continue
            for rank, a in rec["arrivals"].items():
                mapping[rank] = {"node_id": a["node_id"],
                                 "host": a["host"]}
        return mapping

    async def _flag_hung_collective(self, rec: dict, deadline: float):
        missing = sorted(set(range(rec["size"])) - set(rec["arrivals"]))
        known = self._rank_host_map(rec["group"])
        missing_hosts = {r: known.get(r, {}).get("host", "?")
                         for r in missing}
        # pull stacks from the missing ranks' nodes; when a rank's home
        # is unknown (it never arrived in any step), sweep all alive
        # nodes — the hung worker is on one of them
        node_hexes = {known[r]["node_id"] for r in missing
                      if r in known and known[r]["node_id"]}
        if not node_hexes:
            node_hexes = {n.node_id.hex() for n in self.nodes.values()
                          if n.alive}
        stacks = {}
        for node_hex in list(node_hexes)[:16]:
            info = None
            try:
                info = self.nodes.get(NodeID.from_hex(node_hex))
            except Exception:
                pass
            if info is None or not info.alive:
                continue
            try:
                client = await self._raylet_client(info.address)
                dump = await client.call("dump_worker_stacks", {},
                                         timeout=5)
                stacks[node_hex] = dump.get("workers", [])
            except Exception as e:
                stacks[node_hex] = [{"error": str(e) or repr(e)}]
        age = time.time() - rec["first_t"]
        self._event(
            "stall_sentinel", "WARNING",
            (f"hung collective {rec['group']} step {rec['step']} "
             f"({rec['op']}): {len(missing)}/{rec['size']} ranks missing "
             f"after {age:.1f}s — missing ranks {missing} "
             f"(hosts: {missing_hosts})"),
            kind="collective_stall", group=rec["group"],
            step=rec["step"], op=rec["op"], size=rec["size"],
            missing_ranks=missing, missing_hosts=missing_hosts,
            arrived_ranks=sorted(rec["arrivals"]), age_s=age,
            deadline_s=deadline, stacks=stacks)

    async def handle_list_stalls(self, payload, conn):
        """Cluster-wide stall view: hung collectives from this table,
        task/transfer stalls fanned in from every alive raylet."""
        out = {"tasks": [], "transfers": [], "collectives": []}
        for rec in self.collectives.values():
            if rec["flagged"] and rec["completed_t"] is None:
                out["collectives"].append({
                    "kind": "collective_stall",
                    "group": rec["group"], "step": rec["step"],
                    "op": rec["op"], "size": rec["size"],
                    "arrived_ranks": sorted(rec["arrivals"]),
                    "missing_ranks": sorted(
                        set(range(rec["size"])) - set(rec["arrivals"])),
                    "age_s": time.time() - rec["first_t"],
                })
        for info in list(self.nodes.values()):
            if not info.alive:
                continue
            try:
                client = await self._raylet_client(info.address)
                local = await client.call("list_stalls", {}, timeout=5)
            except Exception:
                continue
            out["tasks"].extend(local.get("tasks", []))
            out["transfers"].extend(local.get("transfers", []))
        return out

    async def handle_dump_all_stacks(self, payload, conn):
        """Fan dump_worker_stacks across every alive node (cli stacks
        without a node filter)."""
        out = []
        for info in list(self.nodes.values()):
            if not info.alive:
                continue
            try:
                client = await self._raylet_client(info.address)
                dump = await client.call("dump_worker_stacks", {},
                                         timeout=10)
            except Exception as e:
                dump = {"node_id": info.node_id.hex(),
                        "workers": [], "error": str(e) or repr(e)}
            out.append(dump)
        return out

    async def handle_profile_cluster(self, payload, conn):
        """Cluster-wide sampling burst (cli profile / dashboard
        flamegraph): start per-worker samplers on every matching alive
        raylet, sleep the window on the GCS loop, stop them, and merge
        the folded stacks — overall, per node, and per scheduling class
        (the ``task:<fn>`` roots the workers annotate)."""
        duration_s = float(payload.get("duration_s", 5.0))
        hz = float(payload.get("hz", 100.0))
        prefix = str(payload.get("node_id") or "")
        errors: List[dict] = []
        started = []
        for info in list(self.nodes.values()):
            if not info.alive:
                continue
            if prefix and not info.node_id.hex().startswith(prefix):
                continue
            try:
                client = await self._raylet_client(info.address)
                res = await client.call("profile_start_workers",
                                        {"hz": hz}, timeout=10)
                errors.extend({"node_id": info.node_id.hex(), **err}
                              for err in res.get("errors", []))
                started.append(info)
            except Exception as e:
                errors.append({"node_id": info.node_id.hex(),
                               "error": str(e) or repr(e)})
        await asyncio.sleep(max(0.0, duration_s))
        wall: Dict[str, int] = {}
        cpu: Dict[str, int] = {}
        per_node: Dict[str, Dict[str, int]] = {}
        samples = 0
        workers = 0
        for info in started:
            try:
                client = await self._raylet_client(info.address)
                dump = await client.call("profile_stop_workers", {},
                                         timeout=15)
            except Exception as e:
                errors.append({"node_id": info.node_id.hex(),
                               "error": str(e) or repr(e)})
                continue
            node_hex = dump.get("node_id", info.node_id.hex())
            node_wall = per_node.setdefault(node_hex, {})
            for snap in dump.get("workers", []):
                if snap.get("error"):
                    errors.append({"node_id": node_hex,
                                   "pid": snap.get("pid"),
                                   "error": snap["error"]})
                    continue
                workers += 1
                samples += int(snap.get("samples", 0))
                w = snap.get("wall", {})
                for key, n in w.items():
                    wall[key] = wall.get(key, 0) + n
                    node_wall[key] = node_wall.get(key, 0) + n
                for key, n in snap.get("cpu", {}).items():
                    cpu[key] = cpu.get(key, 0) + n
        # scheduling-class rollup: the worker annotates task-executing
        # threads with a ``task:<fn>`` root frame; everything else is
        # runtime/idle machinery.
        by_class: Dict[str, int] = {}
        for key, n in wall.items():
            root = key.split(";", 1)[0]
            cls = root[5:] if root.startswith("task:") else "(runtime)"
            by_class[cls] = by_class.get(cls, 0) + n
        return {"duration_s": duration_s, "hz": hz, "samples": samples,
                "workers": workers, "wall": wall, "cpu": cpu,
                "per_node": per_node, "by_class": by_class,
                "errors": errors}

    async def handle_memory_report(self, payload, conn):
        """Cluster memory attribution: fan ``node_memory_report`` to
        every alive raylet, merge the per-worker reference claims (plus
        the driver's, passed in the payload — the driver is not raylet-
        registered), and classify every live store object by ref-type:
        spilled > pending_task_arg > pinned > local_ref > borrowed >
        unreferenced. Pinned objects nobody claims that have out-aged
        ``memory_leak_age_s`` are flagged as leak suspects."""
        from .config import global_config

        leak_age_s = float(payload.get(
            "leak_age_s", global_config().memory_leak_age_s))
        limit = int(payload.get("limit", 200))
        errors: List[dict] = []
        node_reports = []
        for info in list(self.nodes.values()):
            if not info.alive:
                continue
            try:
                client = await self._raylet_client(info.address)
                rep = await client.call("node_memory_report", {},
                                        timeout=15)
                node_reports.append(rep)
            except Exception as e:
                errors.append({"node_id": info.node_id.hex(),
                               "error": str(e) or repr(e)})

        # ---- merge reference claims across every worker + the driver
        merged: Dict[str, dict] = {}

        def _absorb(label: str, claims: dict):
            for oid, c in (claims or {}).items():
                m = merged.setdefault(oid, {
                    "local_refs": 0, "task_deps": 0,
                    "owners": [], "borrowers": 0})
                m["local_refs"] += int(c.get("local_refs", 0))
                m["task_deps"] += int(c.get("task_deps", 0))
                if c.get("owned"):
                    m["owners"].append(label)
                if c.get("borrowed_from"):
                    m["borrowers"] += 1

        worker_summaries = []
        for rep in node_reports:
            node_hex = rep.get("node_id", "")
            for wrep in rep.get("workers", []):
                if wrep.get("error"):
                    errors.append({"node_id": node_hex,
                                   "pid": wrep.get("pid"),
                                   "error": wrep["error"]})
                label = (wrep.get("address")
                         or "pid:%s" % wrep.get("pid"))
                _absorb(label, wrep.get("claims"))
                worker_summaries.append({
                    "node_id": node_hex,
                    "worker_id": wrep.get("worker_id", ""),
                    "address": wrep.get("address", ""),
                    "pid": wrep.get("pid"),
                    "mode": wrep.get("mode", ""),
                    "num_inflight_tasks": wrep.get(
                        "num_inflight_tasks", 0),
                    "heap": wrep.get("heap", {}),
                    "hbm": wrep.get("hbm", []),
                    "memory_store": wrep.get("memory_store", {}),
                })
        driver = payload.get("driver") or {}
        if driver:
            _absorb("driver", driver.get("claims"))
            worker_summaries.append({
                "node_id": "", "worker_id": driver.get("worker_id", ""),
                "address": driver.get("address", "driver"),
                "pid": driver.get("pid"), "mode": "driver",
                "num_inflight_tasks": driver.get("num_inflight_tasks", 0),
                "heap": driver.get("heap", {}),
                "hbm": driver.get("hbm", []),
                "memory_store": driver.get("memory_store", {}),
            })

        # ---- classify every store object
        def _ref_type(meta: dict, claim: Optional[dict]) -> str:
            if meta.get("spilled"):
                return "spilled"
            if claim and claim.get("task_deps", 0) > 0:
                return "pending_task_arg"
            if meta.get("pinned", 0) > 0:
                return "pinned"
            if claim and claim.get("local_refs", 0) > 0:
                return "local_ref"
            if claim and claim.get("borrowers", 0) > 0:
                return "borrowed"
            return "unreferenced"

        nodes_out = []
        objects: List[dict] = []
        leak_suspects: List[dict] = []
        cluster_by_type: Dict[str, int] = {}
        cluster_used = 0
        cluster_spill = 0
        cluster_attr = 0
        for rep in node_reports:
            node_hex = rep.get("node_id", "")
            store = rep.get("store", {})
            by_type: Dict[str, int] = {}
            for oid, meta in store.get("objects", {}).items():
                claim = merged.get(oid)
                rtype = _ref_type(meta, claim)
                size = int(meta.get("size", 0))
                by_type[rtype] = by_type.get(rtype, 0) + size
                entry = {
                    "object_id": oid, "node_id": node_hex,
                    "size": size,
                    "age_s": round(float(meta.get("age_s", 0.0)), 1),
                    "pinned": int(meta.get("pinned", 0)),
                    "spilled": bool(meta.get("spilled")),
                    "ref_type": rtype,
                    "owners": list(claim["owners"]) if claim else [],
                }
                # leak suspect: pinned by the control plane, claimed by
                # nobody, and older than the leak threshold — the owner
                # likely died or dropped the ref without unpinning.
                unclaimed = (not claim
                             or (claim["local_refs"] == 0
                                 and claim["task_deps"] == 0))
                if (entry["pinned"] > 0 and unclaimed
                        and not entry["spilled"]
                        and entry["age_s"] > leak_age_s):
                    entry["leak_suspect"] = True
                    leak_suspects.append(entry)
                else:
                    entry["leak_suspect"] = False
                objects.append(entry)
            used = int(store.get("used_bytes", 0))
            spill = int(store.get("spill_bytes", 0))
            attr = sum(b for t, b in by_type.items()
                       if t not in ("unreferenced", "spilled"))
            cluster_used += used
            cluster_spill += spill
            cluster_attr += attr
            for t, b in by_type.items():
                cluster_by_type[t] = cluster_by_type.get(t, 0) + b
            nodes_out.append({
                "node_id": node_hex,
                "used_bytes": used,
                "capacity_bytes": int(store.get("capacity_bytes", 0)),
                "spill_bytes": spill,
                "num_objects": int(store.get("num_objects", 0)),
                "by_ref_type": by_type,
            })
        objects.sort(key=lambda o: o["size"], reverse=True)
        return {
            "nodes": nodes_out,
            "workers": worker_summaries,
            "objects": objects[:limit] if limit > 0 else objects,
            "leak_suspects": leak_suspects,
            "cluster": {
                "used_bytes": cluster_used,
                "spill_bytes": cluster_spill,
                "attributed_bytes": cluster_attr,
                "by_ref_type": cluster_by_type,
                "num_objects": len(objects),
                "attributed_fraction": (
                    cluster_attr / cluster_used
                    if cluster_used > 0 else 1.0),
            },
            "errors": errors,
        }

    # ---- pubsub ----
    async def _publish(self, channel: str, payload: Any):
        for conn in list(self._subs.get(channel, ())):
            await conn.push("pubsub:" + channel, payload)

    async def handle_subscribe(self, payload, conn):
        for channel in payload["channels"]:
            self._subs.setdefault(channel, set()).add(conn)
        return True

    async def handle_unsubscribe(self, payload, conn):
        for channel in payload["channels"]:
            conns = self._subs.get(channel)
            if conns is not None:
                conns.discard(conn)
                if not conns:
                    self._subs.pop(channel, None)
        return True

    async def _publish_actor(self, actor):
        """Actor updates go to per-actor subscribers (``actor:<hex>``)
        plus any blanket ``actor`` subscribers (dashboard, state API).
        Blanket delivery to every core worker would be O(actors x
        workers) pushes through this one loop at envelope depth (1k+
        actors); the reference pubsub indexes subscriptions per entity
        key for the same reason (ref: src/ray/pubsub/publisher.h
        SubscriptionIndex)."""
        payload = {"actor": actor}
        blanket = self._subs.get("actor", set())
        for conn in list(blanket):
            await conn.push("pubsub:actor", payload)
        key = "actor:" + actor.actor_id.hex()
        for conn in list(self._subs.get(key, ())):
            if conn not in blanket:
                await conn.push("pubsub:actor", payload)
        if actor.state == DEAD:
            # terminal: nobody will see another update on this key
            self._subs.pop(key, None)

    async def handle_publish(self, payload, conn):
        """Application-level pubsub fan-out (the reference's long-poll
        broadcast role, ref: python/ray/serve/_private/long_poll.py:66
        LongPollClient — here a plain push to every subscriber of the
        channel; Serve uses it to push config versions to routers and
        handles instead of having them poll)."""
        await self._publish(payload["channel"], payload["message"])
        return True

    async def _on_disconnect(self, conn):
        for subs in self._subs.values():
            subs.discard(conn)
        node_id = self._node_conns.pop(conn, None)
        if node_id is not None:
            await self._mark_node_dead(node_id, "raylet disconnected")
        job_id = self._driver_conns.pop(conn, None)
        if job_id is not None:
            # a dropped connection is only a HINT of driver death (network
            # blip, reconnect in flight): grant a grace window and cancel
            # if the driver re-registers. Clean exits send driver_exit
            # explicitly and skip the grace.
            self._schedule_driver_cleanup(job_id)

    def _schedule_driver_cleanup(self, job_id: JobID, grace_s: float = 10.0):
        if job_id in self._driver_cleanup_timers:
            return

        async def _later():
            try:
                await asyncio.sleep(grace_s)
                await self._on_driver_exit(job_id)
            finally:
                self._driver_cleanup_timers.pop(job_id, None)

        self._driver_cleanup_timers[job_id] = asyncio.ensure_future(_later())

    async def handle_register_driver(self, payload, conn):
        """Bind this connection to a driver's job: when the driver goes
        away, its non-detached actors are torn down (ref:
        gcs_actor_manager.cc OnJobFinished)."""
        job_id = payload["job_id"]
        self._driver_conns[conn] = job_id
        timer = self._driver_cleanup_timers.pop(job_id, None)
        if timer is not None:
            timer.cancel()  # driver reconnected within the grace window
        return True

    async def handle_driver_exit(self, payload, conn):
        """Explicit clean driver detach: immediate cleanup, no grace."""
        timer = self._driver_cleanup_timers.pop(payload["job_id"], None)
        if timer is not None:
            timer.cancel()
        self._driver_conns.pop(conn, None)
        await self._on_driver_exit(payload["job_id"])
        return True

    async def _on_driver_exit(self, job_id: JobID):
        for actor in list(self.actors.values()):
            if (actor.actor_id.job_id() == job_id and not actor.detached
                    and actor.state != DEAD):
                address = actor.address
                actor.max_restarts = 0
                actor.state = DEAD
                actor.death_cause = "creating driver exited"
                self._persist("actors", actor.actor_id.hex(), actor)
                await self._publish_actor(actor)
                if address:
                    background(self._kill_actor_process(address))

    async def _kill_actor_process(self, address: str):
        from .rpc import RpcClient

        try:
            client = RpcClient(address)
            await client.connect(timeout=2)
            await client.call("kill_self", {}, timeout=2)
            await client.close()
        except Exception:
            pass  # worker already gone

    # ---- nodes ----
    async def handle_register_node(self, payload, conn):
        info = NodeInfo(**payload)
        info.last_heartbeat_t = time.time()
        # re-registration (raylet restart) resets the uptime clock
        self._node_first_seen[info.node_id.hex()] = info.last_heartbeat_t
        self.nodes[info.node_id] = info
        self._node_conns[conn] = info.node_id
        await self._publish("node", {"event": "added", "node": info})
        self._event("NODE", "INFO", "node registered",
                    node_id=info.node_id.hex(), address=info.address)
        return {"nodes": list(self.nodes.values())}

    async def handle_get_all_nodes(self, payload, conn):
        return list(self.nodes.values())

    async def handle_report_resources(self, payload, conn):
        node_id = payload["node_id"]
        info = self.nodes.get(node_id)
        if info is not None:
            seq = payload.get("seq", 0)
            if seq and seq <= info.resource_seq:
                return True  # stale retry of an older report — ignore
            info.last_heartbeat_t = time.time()
            info.resource_seq = seq
            info.resources_available = payload["available"]
            info.pending_demands = payload.get("pending", [])
            await self._publish("resources", {
                "node_id": node_id, "available": payload["available"],
            })
        return True

    async def handle_drain_node(self, payload, conn):
        await self._mark_node_dead(payload["node_id"], payload.get("reason", "drained"))
        return True

    async def _mark_node_dead(self, node_id: NodeID, reason: str):
        info = self.nodes.get(node_id)
        if info is None or not info.alive:
            return
        info.alive = False
        await self._publish("node", {"event": "removed", "node_id": node_id, "reason": reason})
        self._event("NODE", "ERROR" if "died" in reason or "lost" in reason
                    else "INFO", f"node dead: {reason}",
                    node_id=node_id.hex())
        self._sweep_node_corpses(node_id, reason)
        # Fail actors on the dead node (ref: gcs_actor_manager OnNodeDead)
        for actor in list(self.actors.values()):
            if actor.node_id == node_id and actor.state in (ALIVE, PENDING_CREATION):
                await self._actor_failed(actor, f"node {node_id} died: {reason}")
        # Objects whose last sealed copy lived on the dead node are lost;
        # consumers surface ObjectLostError (or reconstruct via lineage).
        lost = []
        for oid, nodes in list(self.object_locations.items()):
            nodes.discard(node_id)
            if not nodes:
                del self.object_locations[oid]
                lost.append(oid)
        for oid in lost:
            await self._publish("object", {"event": "lost", "object_id": oid})
        # Bundles reserved on the dead node are gone: put their placement
        # groups back on the scheduler to re-reserve elsewhere (ref:
        # gcs_placement_group_manager OnNodeDead -> RESCHEDULING)
        for pg in list(self.placement_groups.values()):
            hit = [i for i, nid in enumerate(pg["bundle_nodes"]) if nid == node_id]
            if hit:
                for i in hit:
                    pg["bundle_nodes"][i] = None
                if pg["state"] == "CREATED":
                    pg["state"] = "RESCHEDULING"
                await self._publish("placement_group", pg)
                self._kick_pg_scheduler(pg["pg_id"])

    def _sweep_node_corpses(self, node_id: NodeID, reason: str) -> None:
        """Heartbeat loss / disconnect declared a node dead: promote
        every flight file the corpse's processes left into crash bundles
        (a SIGKILL'd or silently-lost process dumps nothing itself —
        the survivor does it). Same-host sessions share the session
        dir, so the head can read the corpse's files directly."""
        if not self.session_dir:
            return
        from . import blackbox

        node_hex = node_id.hex()
        try:
            promoted = blackbox.sweep(
                self.session_dir, reason=f"node_death: {reason}",
                bundled_by="gcs", node_id=node_hex)
        except Exception:  # graftlint: ignore[swallow] — a failed sweep
            return  # must not break node-death handling
        for snap in promoted:
            key = (node_hex[:12], snap.get("role", "proc"),
                   "node_death", str(snap.get("signal", "")))
            self.crash_counts[key] = self.crash_counts.get(key, 0) + 1
            inflight = snap.get("inflight") or []
            names = ", ".join(
                str(r.get("task_id", r.get("request_id", "?")))[:12]
                for r in inflight[:5]) or "nothing in flight"
            self._event(
                "blackbox", "ERROR",
                f"swept crash bundle for {snap.get('role')} pid "
                f"{snap.get('pid')} on dead node {node_hex[:12]} "
                f"(in flight: {names})",
                kind="process_crash", role=snap.get("role"),
                pid=snap.get("pid"), node_id=node_hex,
                reason="node_death", bundle_path=snap.get("path"),
                inflight=inflight)

    # ---- jobs ----
    async def handle_register_job(self, payload, conn):
        job_id = JobID.from_int(self._next_job)
        job_num = self._next_job
        self._next_job += 1
        self.jobs[job_id] = {"config": payload.get("config", {}), "start_time": time.time(),
                             "driver_address": payload.get("driver_address", "")}
        self._persist("jobs", str(job_num), (job_id, self.jobs[job_id]))
        self._event("JOB", "INFO", "job registered", job_id=job_id.hex())
        return job_id

    async def handle_get_all_jobs(self, payload, conn):
        return self.jobs

    # ---- KV (function table etc.; ref: gcs_kv_manager.h) ----
    async def handle_kv_put(self, payload, conn):
        self.storage.put(payload["ns"], payload["key"], payload["value"])
        return True

    async def handle_kv_get(self, payload, conn):
        return self.storage.get(payload["ns"], payload["key"])

    async def handle_kv_del(self, payload, conn):
        return self.storage.delete(payload["ns"], payload["key"])

    async def handle_kv_keys(self, payload, conn):
        return self.storage.keys(payload["ns"], payload.get("prefix", ""))

    # ---- actors (ref: gcs_actor_manager.cc) ----
    async def handle_register_actor(self, payload, conn):
        info = ActorInfo(
            actor_id=payload["actor_id"],
            state=PENDING_CREATION,
            name=payload.get("name", ""),
            namespace=payload.get("namespace", ""),
            detached=payload.get("detached", False),
            owner_is_driver=payload.get("owner_is_driver", True),
            class_name=payload.get("class_name", ""),
            max_restarts=payload.get("max_restarts", 0),
            creation_spec=payload.get("creation_spec"),
        )
        if info.name:
            key = (info.namespace, info.name)
            existing = self.named_actors.get(key)
            if existing is not None and self.actors[existing].state != DEAD:
                raise ValueError(f"Actor name '{info.name}' already taken")
            self.named_actors[key] = info.actor_id
        if payload.get("subscribe"):
            # owner registers + subscribes to the keyed lifecycle channel
            # in one hop (half the creation-path RPCs; the subscription
            # is live before the PENDING_CREATION publish below)
            self._subs.setdefault(
                "actor:" + info.actor_id.hex(), set()).add(conn)
        self.actors[info.actor_id] = info
        self._persist("actors", info.actor_id.hex(), info)
        await self._publish_actor(info)
        self._event("ACTOR", "INFO", "actor registered",
                    actor_id=info.actor_id.hex(),
                    class_name=info.class_name, name=info.name)
        return True

    async def handle_actor_constructing(self, payload, conn):
        """The worker leased for the actor begins its constructor: from
        here on a silent PENDING_CREATION or RESTARTING is a constructor
        that runs, not a lease that is waited for."""
        actor = self.actors.get(payload["actor_id"])
        if actor is None or actor.state not in CONSTRUCTING:
            return False
        actor.constructor_running = True
        return True

    async def handle_actor_alive(self, payload, conn):
        actor = self.actors.get(payload["actor_id"])
        if actor is None:
            return False
        actor.constructor_running = False
        if actor.state == DEAD:
            # killed while still creating (driver exited, explicit kill):
            # do NOT resurrect — put the late-arriving worker down instead
            background(
                self._kill_actor_process(payload["address"]))
            return False
        actor.state = ALIVE
        actor.address = payload["address"]
        actor.node_id = payload.get("node_id")
        self._persist("actors", actor.actor_id.hex(), actor)
        await self._publish_actor(actor)
        return True

    async def handle_actor_failed(self, payload, conn):
        actor = self.actors.get(payload["actor_id"])
        if actor is not None:
            await self._actor_failed(actor, payload.get("cause", "worker died"))
        return True

    async def _actor_failed(self, actor: ActorInfo, cause: str):
        # restarts are owner-driven: an actor created DIRECTLY by a driver
        # that has since exited has nobody to resubmit its creation task, so
        # leaving it RESTARTING would hang every caller forever — mark it
        # DEAD instead. Actors created by other actors keep their worker
        # process as a live owner and restart normally. (GCS-driven restart
        # of orphaned detached actors is future work.)
        if (actor.owner_is_driver
                and actor.actor_id.job_id() not in self._driver_conns.values()
                and actor.num_restarts < actor.max_restarts):
            cause += " (creating driver exited; restart impossible)"
            actor.num_restarts = actor.max_restarts
        actor.constructor_running = False
        if actor.num_restarts < actor.max_restarts:
            actor.num_restarts += 1
            actor.state = RESTARTING
            actor.address = ""
            self._persist("actors", actor.actor_id.hex(), actor)
            await self._publish_actor(actor)
            self._event("ACTOR", "WARNING",
                        f"actor restarting ({actor.num_restarts}/"
                        f"{actor.max_restarts}): {cause}",
                        actor_id=actor.actor_id.hex(),
                        class_name=actor.class_name)
            # restart is driven by the owning core worker, which subscribes
            # to RESTARTING transitions and resubmits the creation task
        else:
            actor.state = DEAD
            actor.death_cause = cause
            actor.address = ""
            self._persist("actors", actor.actor_id.hex(), actor)
            await self._publish_actor(actor)
            self._event("ACTOR", "ERROR", f"actor died: {cause}",
                        actor_id=actor.actor_id.hex(),
                        class_name=actor.class_name)

    async def handle_kill_actor(self, payload, conn):
        actor = self.actors.get(payload["actor_id"])
        if actor is None:
            return False
        actor.max_restarts = 0  # no_restart
        if actor.state != DEAD:
            actor.state = DEAD
            actor.death_cause = payload.get("cause", "ray_tpu.kill")
            self._persist("actors", actor.actor_id.hex(), actor)
            await self._publish_actor(actor)
        return True

    async def handle_get_actor(self, payload, conn):
        if "actor_id" in payload:
            return self.actors.get(payload["actor_id"])
        key = (payload.get("namespace", ""), payload["name"])
        actor_id = self.named_actors.get(key)
        return self.actors.get(actor_id) if actor_id is not None else None

    async def handle_list_actors(self, payload, conn):
        return list(self.actors.values())

    # ---- placement groups (ref: gcs_placement_group_manager.h +
    #      gcs_placement_group_scheduler.h: the GCS owns bundle placement and
    #      drives the raylets' two-phase reserve/commit protocol) ----
    async def handle_create_placement_group(self, payload, conn):
        pg_id = payload["pg_id"]
        bundles = payload["bundles"]
        if not bundles or any(not b for b in bundles):
            raise ValueError("placement group bundles must be non-empty dicts")
        self.placement_groups[pg_id] = {
            "pg_id": pg_id, "bundles": bundles,
            "strategy": payload["strategy"], "state": "PENDING",
            "name": payload.get("name", ""),
            # one entry per bundle: NodeID once reserved, None while pending
            "bundle_nodes": [None] * len(bundles),
        }
        self._persist("pgs", pg_id.hex(), self.placement_groups[pg_id])
        await self._publish("placement_group", self.placement_groups[pg_id])
        self._kick_pg_scheduler(pg_id)
        return True

    def _kick_pg_scheduler(self, pg_id: PlacementGroupID) -> None:
        task = self._pg_tasks.get(pg_id)
        if task is not None and not task.done():
            return
        self._pg_tasks[pg_id] = asyncio.ensure_future(self._schedule_pg_loop(pg_id))

    async def _schedule_pg_loop(self, pg_id: PlacementGroupID) -> None:
        """Retry placement until the PG is fully reserved or removed (ref:
        gcs_placement_group_manager.h pending queue + retry on resource change;
        here a per-PG task with a short poll — cluster views are tiny)."""
        try:
            while True:
                pg = self.placement_groups.get(pg_id)
                if pg is None or pg["state"] in ("CREATED", "REMOVED"):
                    return
                ok = await self._try_schedule_pg(pg)
                if self.placement_groups.get(pg_id) is not pg:
                    # removed while the 2PC was in flight: the remove handler
                    # could not see these fresh reservations — roll them back
                    # here so no raylet resources leak
                    for i, nid in enumerate(pg["bundle_nodes"]):
                        if nid is not None:
                            await self._cancel_bundle(pg_id, i, nid)
                    return
                if ok:
                    pg["state"] = "CREATED"
                    self._persist("pgs", pg_id.hex(), pg)
                    self._wake_pg_waiters(pg_id)
                    await self._publish("placement_group", pg)
                    return
                await asyncio.sleep(0.1)
        finally:
            self._pg_tasks.pop(pg_id, None)

    def _wake_pg_waiters(self, pg_id) -> None:
        for fut in self._pg_waiters.pop(pg_id, []):
            if not fut.done():
                fut.set_result(None)

    def _plan_bundles(self, pg: dict) -> Optional[List[NodeID]]:
        """Pick a node per unplaced bundle per strategy, against the current
        resource view (ref: policy/bundle_scheduling_policy.h:82-106). Returns
        a full bundle->node list, or None if infeasible right now. The plan is
        validated authoritatively by reserve_bundle on each raylet."""
        from .task_spec import ResourceSet

        avail = {nid: ResourceSet(dict(info.resources_available))
                 for nid, info in self.nodes.items() if info.alive}
        placed: List[Optional[NodeID]] = list(pg["bundle_nodes"])
        # already-reserved bundles keep their node; their resources are
        # already deducted from the reporting raylet's availability
        strategy = pg["strategy"]
        used_nodes = {n for n in placed if n is not None}
        todo = [i for i, n in enumerate(placed) if n is None or n not in avail]
        if strategy == "STRICT_PACK":
            # every bundle on one node (respect any existing reservation)
            candidates = list(used_nodes) if used_nodes else list(avail)
            for nid in candidates:
                if nid not in avail:
                    continue
                trial = avail[nid].copy()
                ok = True
                for i in todo:
                    req = ResourceSet(pg["bundles"][i])
                    if not req.fits(trial):
                        ok = False
                        break
                    trial.subtract(req)
                if ok:
                    for i in todo:
                        placed[i] = nid
                    return placed  # type: ignore[return-value]
            return None
        # TPU slice-aware placement (the TPU-first substitution of
        # SURVEY §7.1.2): a spread PG whose bundles all request TPU maps
        # onto ONE ICI slice, bundle k on the slice's k-th host in
        # host_index order — the gang becomes a physical sub-cube whose
        # collectives ride ICI, not DCN (ref:
        # policy/bundle_scheduling_policy.h:82-106 +
        # accelerators/tpu.py:401-403's slice-head gang resource,
        # promoted from resource-string convention into the scheduler).
        if (todo and strategy in ("SPREAD", "STRICT_SPREAD")
                and all(ResourceSet(pg["bundles"][i]).get("TPU") > 0
                        for i in todo)):
            sliced = self._plan_bundles_on_slice(pg, avail, placed, todo)
            if sliced is not None:
                return sliced
            # no slice can host the whole gang: generic placement below
        # place most-constrained bundles first (fewest feasible nodes) so a
        # bundle needing a rare resource isn't starved by flexible ones
        todo.sort(key=lambda i: sum(
            1 for a in avail.values() if ResourceSet(pg["bundles"][i]).fits(a)))
        for i in todo:
            req = ResourceSet(pg["bundles"][i])
            feasible = [nid for nid, a in avail.items() if req.fits(a)]
            if strategy == "STRICT_SPREAD":
                feasible = [nid for nid in feasible if nid not in used_nodes]
            if not feasible:
                return None
            if strategy == "PACK":
                # prefer nodes already carrying bundles, then most-utilized
                feasible.sort(key=lambda nid: (
                    nid not in used_nodes,
                    sum(avail[nid].res.values())))
            elif strategy in ("SPREAD", "STRICT_SPREAD"):
                # prefer fresh, least-loaded nodes
                feasible.sort(key=lambda nid: (
                    nid in used_nodes,
                    -sum(avail[nid].res.values())))
            nid = feasible[0]
            placed[i] = nid
            avail[nid].subtract(req)
            used_nodes.add(nid)
        return placed  # type: ignore[return-value]

    def _plan_bundles_on_slice(self, pg: dict, avail: dict,
                               placed: list, todo: list):
        """Assign the unplaced bundles of a TPU gang to the hosts of one
        ICI slice in host_index order. Prefers the smallest slice that
        fits (tight sub-cubes leave big slices for big gangs). Returns
        the full placement list or None."""
        from .task_spec import ResourceSet

        used = {n for n in placed if n is not None}
        slices: Dict[str, list] = {}
        for nid, info in self.nodes.items():
            if info.alive and info.slice_name and nid in avail:
                slices.setdefault(info.slice_name, []).append(
                    (info.host_index, nid))
        if used:
            # bundles already reserved pin the gang to their slice
            names = {self.nodes[n].slice_name for n in used
                     if n in self.nodes}
            if len(names) != 1 or "" in names:
                return None
            slices = {k: v for k, v in slices.items() if k in names}
        best = None
        for name in sorted(slices):
            hosts = sorted(slices[name])
            free_hosts = [nid for _, nid in hosts if nid not in used]
            if len(free_hosts) < len(todo):
                continue
            trial = {nid: avail[nid].copy() for nid in free_hosts}
            assign = {}
            ok = True
            for k, i in enumerate(sorted(todo)):
                nid = free_hosts[k]  # bundle k -> k-th host by host_index
                req = ResourceSet(pg["bundles"][i])
                if not req.fits(trial[nid]):
                    ok = False
                    break
                trial[nid].subtract(req)
                assign[i] = nid
            if ok and (best is None or len(hosts) < best[0]):
                best = (len(hosts), assign)
        if best is None:
            return None
        out = list(placed)
        for i, nid in best[1].items():
            out[i] = nid
        return out

    async def _try_schedule_pg(self, pg: dict) -> bool:
        plan = self._plan_bundles(pg)
        if plan is None:
            return False
        pg_id = pg["pg_id"]
        newly = [(i, nid) for i, nid in enumerate(plan)
                 if pg["bundle_nodes"][i] != nid]
        # phase 1: reserve every new bundle; roll back all of them on any miss
        reserved: List[Tuple[int, NodeID]] = []
        ok = True
        for i, nid in newly:
            info = self.nodes.get(nid)
            if info is None or not info.alive:
                ok = False
                break
            try:
                granted = await self._raylet_call(
                    info.address, "reserve_bundle", {
                        "pg_id": pg_id, "bundle_index": i,
                        "resources": pg["bundles"][i]})
            except Exception:
                granted = False
            if not granted:
                ok = False
                break
            reserved.append((i, nid))
        if not ok:
            for i, nid in reserved:
                await self._cancel_bundle(pg_id, i, nid)
            return False
        # phase 2: commit (ref: placement_group_resource_manager.h 2PC);
        # a failed commit means the raylet lost the reservation — do not
        # record the bundle as placed, retry the whole group
        all_committed = True
        for i, nid in newly:
            committed = False
            info = self.nodes.get(nid)
            if info is not None:
                try:
                    committed = bool(await self._raylet_call(
                        info.address, "commit_bundle",
                        {"pg_id": pg_id, "bundle_index": i}))
                except Exception:
                    committed = False
            if committed:
                pg["bundle_nodes"][i] = nid
            else:
                await self._cancel_bundle(pg_id, i, nid)
                all_committed = False
        return all_committed

    async def _cancel_bundle(self, pg_id, bundle_index, node_id) -> None:
        info = self.nodes.get(node_id)
        if info is None or not info.alive:
            return
        try:
            await self._raylet_call(info.address, "cancel_bundle", {
                "pg_id": pg_id, "bundle_index": bundle_index})
        except Exception:  # graftlint: ignore[swallow]
            # rollback best-effort: the raylet may already be dead, and
            # its bundle ledger resets with it — nothing to unwind
            pass

    async def handle_remove_placement_group(self, payload, conn):
        # NOTE: the scheduler task is NOT canceled — interrupting it mid-2PC
        # would strand reservations; _schedule_pg_loop detects the removal
        # after its in-flight attempt and rolls back itself
        pg = self.placement_groups.pop(payload["pg_id"], None)
        if pg is not None:
            for i, nid in enumerate(pg["bundle_nodes"]):
                if nid is not None:
                    await self._cancel_bundle(pg["pg_id"], i, nid)
            pg["state"] = "REMOVED"
            self._unpersist("pgs", pg["pg_id"].hex())
            self._wake_pg_waiters(pg["pg_id"])
            await self._publish("placement_group", pg)
        return True

    async def handle_get_placement_group(self, payload, conn):
        if "pg_id" in payload:
            return self.placement_groups.get(payload["pg_id"])
        for pg in self.placement_groups.values():
            if pg["name"] and pg["name"] == payload.get("name"):
                return pg
        return None

    async def handle_list_placement_groups(self, payload, conn):
        return list(self.placement_groups.values())

    async def handle_wait_placement_group_ready(self, payload, conn):
        """Block until the PG is fully reserved, removed, or timeout (the
        driver-side `pg.ready()` / `pg.wait()` backend). Waiters park on a
        future resolved at state transitions — no polling."""
        pg_id = payload["pg_id"]
        timeout = payload.get("timeout")
        deadline = None if timeout is None else asyncio.get_event_loop().time() + timeout
        while True:
            pg = self.placement_groups.get(pg_id)
            if pg is None:
                return {"status": "removed"}
            if pg["state"] == "CREATED":
                nodes = []
                for nid in pg["bundle_nodes"]:
                    info = self.nodes.get(nid)
                    nodes.append((nid, info.address if info else ""))
                return {"status": "ready", "bundle_nodes": nodes}
            fut = asyncio.get_event_loop().create_future()
            self._pg_waiters.setdefault(pg_id, []).append(fut)
            try:
                remaining = (None if deadline is None
                             else deadline - asyncio.get_event_loop().time())
                if remaining is not None and remaining <= 0:
                    return {"status": "timeout"}
                await asyncio.wait_for(fut, remaining)
            except asyncio.TimeoutError:
                return {"status": "timeout"}
            finally:
                waiters = self._pg_waiters.get(pg_id, [])
                if fut in waiters:
                    waiters.remove(fut)

    async def _raylet_client(self, address: str):
        from .rpc import RpcClient

        client = self._pg_raylet_clients.get(address)
        if client is None or client.closed:
            client = RpcClient(address)
            await client.connect(timeout=10)
            self._pg_raylet_clients[address] = client
        return client

    async def _raylet_call(self, address: str, method: str, payload: dict):
        """Outbound raylet RPC bounded by gcs_rpc_timeout_s.

        The GCS event loop serves every control-plane handler; one
        unresponsive raylet (wedged host, partitioned network) must
        surface as GcsTimeoutError at the call site — never park a
        scheduler loop forever."""
        from ..exceptions import GcsTimeoutError
        from .config import global_config

        timeout = global_config().gcs_rpc_timeout_s
        client = await self._raylet_client(address)
        try:
            return await client.call(
                method, payload, timeout=timeout if timeout > 0 else None)
        except asyncio.TimeoutError as e:
            raise GcsTimeoutError(method, address, timeout) from e

    # ---- object directory ----
    async def handle_add_object_location(self, payload, conn):
        self.object_locations.setdefault(payload["object_id"], set()).add(payload["node_id"])
        return True

    async def handle_add_object_locations(self, payload, conn):
        """Batched location adds (raylets coalesce seal reports — the
        directory write amortizes to one frame per flush window)."""
        node_id = payload["node_id"]
        for oid in payload["object_ids"]:
            self.object_locations.setdefault(oid, set()).add(node_id)
        return True

    async def handle_remove_object_location(self, payload, conn):
        """Drop one node's copy (evicted/freed/stale). The last copy vanishing
        via eviction is NOT a loss event — the object may be recreated; loss is
        declared only on node death (see _mark_node_dead)."""
        nodes = self.object_locations.get(payload["object_id"])
        if nodes is not None:
            nodes.discard(payload["node_id"])
            if not nodes:
                del self.object_locations[payload["object_id"]]
        return True

    async def handle_list_object_locations(self, payload, conn):
        return {oid: set(nodes)
                for oid, nodes in self.object_locations.items()}

    async def handle_get_object_locations(self, payload, conn):
        """oid -> [(node_id, raylet_address)] for live holders, plus a
        "__transfer__" side map {node_hex: transfer_address}. The holder
        tuples stay 2-wide on purpose: a pre-transfer-plane raylet
        unpacks `for node_id, address in ...` and a widened tuple would
        break ITS pulls, while an extra top-level key is invisible to
        it (wire-compat: additive only)."""
        out = {}
        transfer = {}
        for oid in payload["object_ids"]:
            holders = []
            for node_id in self.object_locations.get(oid, ()):
                info = self.nodes.get(node_id)
                if info is not None and info.alive:
                    holders.append((node_id, info.address))
                    if info.transfer_address:
                        transfer[node_id.hex()] = info.transfer_address
            out[oid] = holders
        out["__transfer__"] = transfer
        return out

    # ---- metrics (ref: stats/metric.h registry + metrics agent; the GCS
    #      is the aggregation point the state API reads) ----
    async def handle_report_metrics(self, payload, conn):
        worker = payload["worker_id"]
        for entry in payload["metrics"]:
            key = (entry["name"], tuple(sorted(entry["tags"].items())), worker)
            # bounded like task_events: worker churn + high-cardinality tags
            # must not grow the GCS without limit (FIFO eviction)
            if key not in self.metrics and len(self.metrics) >= self.MAX_METRICS:
                self.metrics.pop(next(iter(self.metrics)))
            self.metrics[key] = {
                "name": entry["name"], "kind": entry["kind"],
                "tags": entry["tags"], "value": entry["value"],
                "worker_id": worker,
                "description": entry.get("description", ""),
            }
        return True

    def _aggregate_metrics(self, name_filter=None) -> List[dict]:
        """Aggregated across workers: counters/histogram buckets sum,
        gauges report per-worker last values summed (the common scrape
        semantic for distributed gauges of additive quantities)."""
        out: Dict[tuple, dict] = {}
        for (name, tags, _worker), entry in self.metrics.items():
            if name_filter and name != name_filter:
                continue
            agg_key = (name, tags)
            if agg_key in out:
                out[agg_key]["value"] += entry["value"]
            else:
                out[agg_key] = dict(entry)
                out[agg_key].pop("worker_id", None)
        result = list(out.values())
        result.extend(self._process_metrics(name_filter))
        result.extend(self._train_metrics(name_filter))
        return result

    def _process_metrics(self, name_filter=None) -> List[dict]:
        """Synthetic per-process liveness series the GCS mints itself:
        process_uptime_seconds (head + every alive raylet, from
        registration time) and process_crashes_total (per node, with
        reason/signal labels, fed by the crash sweeps). They ride the
        normal aggregation so Prometheus, the series store and `cli
        status` all see them with no extra plumbing."""
        now = time.time()
        entries: List[dict] = []
        if not name_filter or name_filter == "process_uptime_seconds":
            entries.append({
                "name": "process_uptime_seconds", "kind": "gauge",
                "tags": {"role": "gcs", "node": "head"},
                "value": now - self.started_at,
                "description": "seconds since this process came up"})
            for info in self.nodes.values():
                if not info.alive:
                    continue
                first = self._node_first_seen.get(info.node_id.hex())
                if first is None:
                    continue
                entries.append({
                    "name": "process_uptime_seconds", "kind": "gauge",
                    "tags": {"role": "raylet",
                             "node": info.node_id.hex()[:12]},
                    "value": now - first,
                    "description": "seconds since this process came up"})
        if not name_filter or name_filter == "process_crashes_total":
            for (node, role, reason, sig), n in self.crash_counts.items():
                entries.append({
                    "name": "process_crashes_total", "kind": "counter",
                    "tags": {"node": node, "role": role,
                             "reason": reason, "signal": sig},
                    "value": float(n),
                    "description": "abnormal process exits (bundled)"})
        return entries

    async def handle_get_metrics(self, payload, conn):
        return self._aggregate_metrics(payload.get("name"))

    # ---- SLO observability plane (ray_tpu/slo.py; ROADMAP item 4's
    #      sensing layer: series retention -> quantiles -> burn alerts) ----
    async def _slo_loop(self):
        """Each tick: snapshot the aggregated metrics view into the
        per-series ring buffers, then evaluate every SLO spec against
        the fresh series (attainment + multi-window burn rates). Alert
        transitions land in the cluster-event log through _event, so
        `cli.py events`/`cli.py slo` and the dashboard see them with no
        extra plumbing."""
        from .config import global_config

        period = max(0.25, global_config().slo_eval_interval_s)
        last_err = None
        while True:
            await asyncio.sleep(period)
            try:
                now = time.time()
                self.series_store.sample(self._aggregate_metrics(), now)
                self.slo_monitor.tick(self.series_store, now,
                                      emit=self._slo_emit)
                last_err = None
            except asyncio.CancelledError:
                raise
            except Exception as e:
                # surface once per distinct failure, not once per tick —
                # a persistent bug must not flood the event deque
                msg = f"{type(e).__name__}: {e}"
                if msg != last_err:
                    last_err = msg
                    self._event("slo", "ERROR",
                                f"SLO evaluation tick failed: {msg}")

    def _slo_emit(self, severity: str, message: str, **fields) -> None:
        """SLO alert-transition sink: the event lands in the stream as
        before, and a fast-burn ERROR additionally self-diagnoses —
        profile burst + stack sweep + memory report captured NOW, while
        the burn is live, with the artifact paths attached to the alert
        event (the on-call reads the page and the evidence together)."""
        self._event("slo", severity, message, **fields)
        if severity != "ERROR" or fields.get("kind") != "fast_burn":
            return
        now = time.time()
        if now - self._last_diag_t < 30.0:
            return  # one burst per page storm, not one per spec
        self._last_diag_t = now
        alert_rec = self.events[-1]  # the event just appended above
        background(self._self_diagnose(alert_rec))

    async def _self_diagnose(self, alert_rec: dict) -> None:
        """Capture the three forensic views and attach their paths to
        the triggering alert (mutating the deque'd record: later
        list_events readers see the artifacts on the alert itself)."""
        if not self.session_dir:
            return
        from . import blackbox

        out_dir = os.path.join(blackbox.incident_dir(self.session_dir),
                               str(int(time.time() * 1000)))
        artifacts: Dict[str, str] = {}

        async def _capture(name, coro):
            try:
                result = await coro
            except Exception as e:
                result = {"error": repr(e)}
            path = os.path.join(out_dir, f"{name}.json")
            try:
                os.makedirs(out_dir, exist_ok=True)
                with open(path, "w") as f:
                    json.dump(result, f, default=str)
                artifacts[name] = path
            except OSError:
                pass

        await _capture("profile", self.handle_profile_cluster(
            {"duration_s": 1.0, "hz": 50.0}, None))
        await _capture("stacks", self.handle_dump_all_stacks({}, None))
        await _capture("memory", self.handle_memory_report({}, None))
        alert_rec["artifacts"] = dict(artifacts)
        self._event("blackbox", "INFO",
                    f"self-diagnosis captured for '{alert_rec.get('slo')}'"
                    f" fast-burn: {', '.join(sorted(artifacts))}",
                    kind="self_diagnosis", slo=alert_rec.get("slo"),
                    artifacts=artifacts)

    async def handle_get_metric_series(self, payload, conn):
        """Ring-buffered samples for one metric (dashboard sparklines,
        loadgen reports). Selector is a tag-subset match."""
        if self.series_store is None:
            return []
        return self.series_store.query(
            payload["name"], payload.get("selector") or {})

    async def handle_slo_status(self, payload, conn):
        """Per-spec attainment/burn/alert records + the policy windows
        (so clients can render thresholds without re-reading config)."""
        if self.slo_monitor is None:
            return {"enabled": False, "specs": []}
        return {
            "enabled": True,
            "specs": self.slo_monitor.status(),
            "policies": [
                {"kind": p.kind, "severity": p.severity,
                 "short_window_s": p.short_window_s,
                 "long_window_s": p.long_window_s,
                 "threshold": p.threshold}
                for p in self.slo_monitor.policies],
        }

    async def handle_set_slo_specs(self, payload, conn):
        """Install/replace SLO specs at runtime (loadgen and tests use
        this; config slo_specs seeds the initial set). Malformed specs
        reject the whole batch — never half-install."""
        if self.slo_monitor is None:
            raise RuntimeError(
                "SLO monitor disabled (metrics_series_enabled=False or "
                "slo_eval_interval_s=0)")
        from ..slo import parse_specs

        specs = parse_specs(payload.get("specs") or [])
        self.slo_monitor.set_specs(specs)
        return [s.describe() for s in specs]

    # ---- training goodput plane (ray_tpu/train/telemetry.py ledger) ----
    def _train_ledger(self, job: str, world_size: int = 0):
        from ..train.telemetry import GoodputLedger
        from .config import global_config

        ledger = self.train_ledgers.get(job)
        if ledger is None:
            while len(self.train_ledgers) >= self.MAX_TRAIN_JOBS:
                self.train_ledgers.pop(next(iter(self.train_ledgers)))
            ledger = self.train_ledgers[job] = GoodputLedger(
                job, world_size=world_size or 1,
                peak_flops_per_chip=(
                    global_config().train_peak_flops_per_chip))
        if world_size:
            ledger.world_size = max(1, int(world_size))
        return ledger

    async def handle_train_report(self, payload, conn):
        """Fold a batch of per-rank TrainStepTelemetry records — or a
        controller restart notice — into the job's goodput ledger.
        Rank timestamps are clock-corrected here (NodeInfo.clock_offset,
        the collective-watchdog path), so straggler skew measured across
        hosts is real skew, not NTP noise."""
        job = str(payload.get("job") or "default")
        ledger = self._train_ledger(job,
                                    int(payload.get("world_size") or 0))
        if payload.get("kind") == "restart":
            restore_step = int(payload.get("restore_step") or 0)
            expected = ledger.restart(restore_step)
            self._event(
                "train", "WARNING",
                f"train job '{job}' gang restart #{ledger.restarts} from "
                f"checkpoint step {restore_step}: ~{expected} step(s) will "
                f"be re-executed (rework badput)",
                kind="train_restart", job=job, restore_step=restore_step,
                expected_rework=expected,
                failure=str(payload.get("failure") or "")[:500])
            return True
        from ..train.telemetry import TrainStepTelemetry

        for rec in payload.get("records") or []:
            if isinstance(rec, dict):       # tolerate dict-shaped reports
                rec = TrainStepTelemetry(**{
                    k: v for k, v in rec.items()
                    if k in TrainStepTelemetry.__dataclass_fields__})
            if not isinstance(rec, TrainStepTelemetry):
                continue
            rec.start_t = self._corrected_time(rec.node_id, rec.start_t)
            rec.end_t = self._corrected_time(rec.node_id, rec.end_t)
            ledger.add(rec)
        return True

    async def handle_train_status(self, payload, conn):
        """Per-job goodput snapshots (TrainJobLedger records) for
        `cli train`, the dashboard Train panel and state.train_status()."""
        job = payload.get("job")
        ledgers = ([self.train_ledgers[job]]
                   if job and job in self.train_ledgers
                   else list(self.train_ledgers.values()))
        return {"jobs": [ledger.to_record() for ledger in ledgers]}

    def _train_metrics(self, name_filter=None) -> List[dict]:
        """Synthetic per-job goodput series minted from the ledgers:
        they ride the normal aggregation, so Prometheus, the SeriesStore
        and the SLO engine (mfu floor specs, burn-rate alerts) see them
        with no extra plumbing."""
        entries: List[dict] = []

        def want(name):
            return not name_filter or name_filter == name

        for job, ledger in self.train_ledgers.items():
            tags = {"job": job}
            goodput = ledger.goodput_fraction()
            if want("train_goodput_fraction") and goodput is not None:
                entries.append({
                    "name": "train_goodput_fraction", "kind": "gauge",
                    "tags": tags, "value": goodput,
                    "description": "productive / total attributed "
                                   "chip-seconds"})
            if want("train_mfu") and ledger.mfu > 0.0:
                entries.append({
                    "name": "train_mfu", "kind": "gauge", "tags": tags,
                    "value": ledger.mfu,
                    "description": "model flops utilization (EMA over "
                                   "recent steps)"})
            if (want("train_tokens_per_s_per_chip")
                    and ledger.tok_per_s_per_chip > 0.0):
                entries.append({
                    "name": "train_tokens_per_s_per_chip", "kind": "gauge",
                    "tags": tags, "value": ledger.tok_per_s_per_chip,
                    "description": "training throughput per chip (EMA)"})
            if want("train_badput_seconds_total"):
                for cause, secs in sorted(ledger.badput_s.items()):
                    entries.append({
                        "name": "train_badput_seconds_total",
                        "kind": "counter",
                        "tags": {"job": job, "cause": cause},
                        "value": secs,
                        "description": "non-productive chip-seconds by "
                                       "cause (MegaScale taxonomy)"})
            if want("train_rework_steps_total") and ledger.rework_steps:
                entries.append({
                    "name": "train_rework_steps_total", "kind": "counter",
                    "tags": tags, "value": float(ledger.rework_steps),
                    "description": "steps re-executed after checkpoint "
                                   "restores"})
            if want("train_compile_total"):
                for kind, n in (("cold", ledger.compile_count),
                                ("cache_hit", ledger.cache_hit_count)):
                    if n:
                        entries.append({
                            "name": "train_compile_total",
                            "kind": "counter",
                            "tags": {"job": job, "kind": kind},
                            "value": float(n),
                            "description": "step-fn compiles by kind"})
        return entries

    # ---- task events (ref: gcs_task_manager.h — the state API backend) ----
    _TERMINAL_STATES = ("FINISHED", "FAILED")

    def _evict_task_event(self) -> None:
        """Make room for one record: prefer the oldest TERMINAL record —
        evicting a still-RUNNING task's record would lose live state the
        moment the table fills with completed history."""
        victim = None
        for key, rec in self.task_events.items():
            if rec.get("state") in self._TERMINAL_STATES:
                victim = key
                break
        if victim is None:
            victim = next(iter(self.task_events))
        self.task_events.pop(victim)

    async def handle_report_task_events(self, payload, conn):
        for event in payload["events"]:
            task_id = event["task_id"]
            record = self.task_events.get(task_id)
            if record is None:
                if len(self.task_events) >= self.MAX_TASK_EVENTS:
                    self._evict_task_event()
                record = self.task_events[task_id] = {
                    "task_id": task_id, "name": "", "state": "",
                    "start_time": None, "end_time": None, "error": "",
                    "state_transitions": [],
                }
            # lifecycle transitions accumulate (append-merge); every
            # other field is last-writer-wins as before
            transitions = event.get("transitions")
            record.update({k: v for k, v in event.items()
                           if v is not None and k != "transitions"})
            if transitions:
                record.setdefault("state_transitions",
                                  []).extend(transitions)
        return True

    async def handle_list_task_events(self, payload, conn):
        return list(self.task_events.values())

    # ---- health / introspection ----
    async def handle_ping(self, payload, conn):
        return {"time": time.time()}

    async def handle_report_clock_offset(self, payload, conn):
        """Store a node's smoothed clock offset (raylet clock-sync loop;
        NTP-style offset = GCS time - node-local midpoint)."""
        node_id = payload["node_id"]
        if isinstance(node_id, str):
            node_id = NodeID.from_hex(node_id)
        info = self.nodes.get(node_id)
        if info is None:
            return False
        info.clock_offset = float(payload["offset"])
        return True

    async def handle_cluster_status(self, payload, conn):
        return {
            "nodes": list(self.nodes.values()),
            "num_actors": len(self.actors),
            "num_jobs": len(self.jobs),
        }
