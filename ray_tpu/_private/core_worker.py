"""Core worker: ownership, task submission, object access.

TPU-native analog of the reference core worker (ref: src/ray/core_worker/
core_worker.h:165, transport/normal_task_submitter.h, actor_task_submitter.h,
reference_count.h:66, task_manager.h). One CoreWorker per process (driver or
worker), bridging sync user code onto a dedicated asyncio IO thread.

Submission paths:
 * normal tasks — lease-based: acquire a worker lease from the raylet for the
   task's SchedulingKey (scheduling class), then push the task directly to the
   leased worker over its own socket (worker->worker direct push, the
   steady-state hot path; ref: normal_task_submitter.h:227). Leases are pooled
   per scheduling class and returned when the backlog drains.
 * actor tasks — pushed directly to the actor's worker with per-caller
   sequence numbers; the executing side replays them in order (ref:
   transport/sequential_actor_submit_queue.h, actor_scheduling_queue.h).

Ownership: this process owns every object its tasks return and everything it
`put`s. Local+borrowed reference counts drive plasma frees; submitted-task
argument deps pin refs until the task completes (ref: reference_count.h:66).
Lineage-based reconstruction is recorded (resubmittable task specs are kept
while their returns are referenced) — re-execution lands in the recovery
manager in a later milestone.
"""

from __future__ import annotations

import asyncio
import collections
import concurrent.futures
import os
import threading
import time
from dataclasses import dataclass, field
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import cloudpickle

from .config import global_config
from . import gcs as gcs_states
from . import locking
from .ids import ActorID, JobID, NodeID, ObjectID, TaskID, WorkerID
from .object_ref import ObjectRef, ObjectRefGenerator, _set_ref_registry
from .object_store import MemoryStore, SharedObjectStore
from .rpc import (ConnectionLost, EventLoopThread, RpcClient, RpcError,
                  background)
from . import serialization as ser
from .task_spec import (
    ArgKind,
    DefaultSchedulingStrategy,
    FunctionDescriptor,
    PlacementGroupSchedulingStrategy,
    ResourceSet,
    TaskArg,
    TaskSpec,
)
from .. import exceptions as exc

_SMALL = None  # resolved from config at init

# per-coroutine task binding for async actors (thread-locals cannot
# distinguish coroutines interleaving on one loop thread)
import contextvars

_task_ctx_var: "contextvars.ContextVar[Optional[TaskID]]" = \
    contextvars.ContextVar("ray_tpu_task_ctx", default=None)


@dataclass
class _ActorState:
    actor_id: ActorID
    address: str = ""
    state: str = "PENDING_CREATION"
    seq_no: int = 0
    client: Optional[RpcClient] = None
    waiters: List[asyncio.Future] = field(default_factory=list)
    death_cause: str = ""
    owned: bool = False                 # this process registered the actor
    creation_spec: Optional["TaskSpec"] = None
    restart_in_flight: bool = False


_STREAM_DONE = object()


def _rss_bytes() -> int:
    """Resident set size (the heap stat when tracemalloc is off)."""
    try:
        with open("/proc/self/statm") as f:
            return int(f.read().split()[1]) * os.sysconf("SC_PAGE_SIZE")
    except Exception:  # graftlint: ignore[swallow] — non-Linux /proc
        return 0       # miss: heap stat degrades to 0, never a fault

# tail-tolerance hedge counters, created lazily: metric construction
# spins up the flusher thread, which only processes that actually hedge
# should pay for
_hedge_counters: Dict[str, Any] = {}


def _hedge_counter(name: str):
    c = _hedge_counters.get(name)
    if c is None:
        from ..util.metrics import Counter
        c = _hedge_counters.setdefault(name, Counter(
            name, "tail-tolerance hedged-execution counter"))
    return c


# Submit-path stage timers (ROADMAP item 2's measured baseline): one
# histogram family, submit_stage_seconds{stage=...}, µs-resolution
# buckets (the stages live in the 1µs-1ms range — LATENCY_BUCKETS'
# 0.5ms floor would flatten them all into one bucket). Created lazily
# like the hedge counters so non-submitting processes never spin up
# the metrics flusher.
SUBMIT_STAGE_BUCKETS = [
    1e-6, 2.5e-6, 5e-6, 1e-5, 2.5e-5, 5e-5, 1e-4, 2.5e-4, 5e-4,
    1e-3, 2.5e-3, 5e-3, 1e-2, 0.1, 1.0]
_stage_hist_box: list = []


def _stage_hist():
    if not _stage_hist_box:
        from ..util.metrics import Histogram
        _stage_hist_box.append(Histogram(
            "submit_stage_seconds",
            "driver submit hot-path stage latency",
            boundaries=SUBMIT_STAGE_BUCKETS))
    return _stage_hist_box[0]


class _StageClock:
    """Consecutive perf_counter marks PARTITIONING submit_task into
    submit_stage_seconds{stage=...} observations — no gaps between
    marks, so the per-stage sums add up to the `total` stage minus
    observe overhead (the invariant tests/test_profiling.py and the
    bench_envelope submit family hold this family to)."""

    __slots__ = ("hist", "t0", "t")

    def __init__(self, hist):
        self.hist = hist
        self.t0 = self.t = time.perf_counter()

    def mark(self, stage: str) -> None:
        now = time.perf_counter()
        self.hist.observe(now - self.t, tags={"stage": stage})
        self.t = now

    def total(self) -> None:
        self.hist.observe(time.perf_counter() - self.t0,
                          tags={"stage": "total"})


@dataclass
class _StreamState:
    """Owner-side view of one streaming task's item queue (ref:
    task_manager.h ObjectRefStream)."""

    queue: asyncio.Queue = field(default_factory=asyncio.Queue)
    worker_address: str = ""
    consumed: int = 0
    received: int = 0
    total: Optional[int] = None


class _LeasePool:
    """Pooled worker leases for one scheduling class (ref: SchedulingKey lease
    pool, normal_task_submitter.h:58-65)."""

    def __init__(self):
        self.idle: List[dict] = []          # granted leases not executing
        self.in_flight = 0                  # lease requests outstanding
        self.waiters: List[asyncio.Future] = []

    def wake_one(self) -> None:
        while self.waiters:
            waiter = self.waiters.pop(0)
            if not waiter.done():
                waiter.set_result(None)
                return


class CoreWorker:
    def __init__(
        self,
        *,
        mode: str,                      # "driver" | "worker"
        session_name: str,
        gcs_address: str,
        raylet_address: str,
        job_id: JobID,
        node_id: NodeID,
        store: SharedObjectStore,
        io: Optional[EventLoopThread] = None,
        worker_id: Optional[WorkerID] = None,
    ):
        self.mode = mode
        self.session_name = session_name
        self.job_id = job_id
        self.node_id = node_id
        self.worker_id = worker_id or WorkerID.from_random()
        self.store = store
        self.memory_store = MemoryStore()
        self.io = io or EventLoopThread(name=f"ray_tpu_io_{mode}")
        self.cfg = global_config()
        global _SMALL
        _SMALL = self.cfg.object_store_small_object_threshold

        self.gcs = RpcClient(gcs_address)
        self.raylet = RpcClient(raylet_address)
        self._worker_clients: Dict[str, RpcClient] = {}
        self._worker_clients_lock = asyncio.Lock()

        self._default_task_id = (TaskID.for_driver(job_id) if mode == "driver"
                                 else TaskID.for_normal_task(job_id))
        self._task_local = threading.local()  # per-execution-thread task context
        self._put_index = 0
        self._put_lock = locking.make_lock("CoreWorker._put_lock")
        self._subscribed_channels: set = set()
        self._actor_sub_tasks: Dict[str, asyncio.Task] = {}
        self._block_depth = 0          # worker dep-block nesting
        self._block_lock = locking.make_lock("CoreWorker._block_lock")

        # reference counting — native C++ table by default (ref:
        # reference_count.h:66; native/core_tables.cc), Python dicts as
        # the fallback when the toolchain can't build the lib
        self._rc = None
        try:
            from .._native import RefTable, native_unavailable_reason

            if native_unavailable_reason() is None:
                self._rc = RefTable()
        except Exception:
            self._rc = None
        self._local_refs: Dict[ObjectID, int] = {}
        self._borrowed: Dict[ObjectID, str] = {}
        self._task_deps: Dict[ObjectID, int] = {}
        self._ref_lock = locking.make_lock("CoreWorker._ref_lock")
        self._owned_in_plasma: set = set()

        # submission state
        self._lease_pools: Dict[int, _LeasePool] = {}
        self._actors: Dict[ActorID, _ActorState] = {}
        self._function_cache: Dict[str, Any] = {}
        self._exported_blobs: set = set()
        # id(func) -> (func, FunctionDescriptor); func kept so the id
        # cannot be recycled by a different object
        self._descriptor_cache: Dict[int, tuple] = {}
        # lineage: resubmittable specs for owned objects (recorded, replayed by
        # the recovery manager milestone)
        self._lineage: Dict[TaskID, TaskSpec] = {}
        self._runtime_env_cache: Dict[Any, Optional[dict]] = {}
        self._pg_rr = 0  # round-robin over bundles for wildcard PG leases
        self._pg_cache: Dict[Any, list] = {}  # pg_id -> bundle (node, addr)
        # object recovery (ref: object_recovery_manager.h): reconstruction
        # attempts consumed per lineage task
        self._reconstructions: Dict[TaskID, int] = {}
        # cancellation: in-flight normal tasks (ref: core_worker.cc Cancel)
        self._inflight: Dict[TaskID, dict] = {}
        # tail tolerance (The Tail at Scale): per-fn EMA of push->reply
        # durations (the owner-side latency profile hedge delays derive
        # from) + per-task events the raylet watchdog's hedge_hint RPC
        # sets to trigger an immediate hedge of a flagged task
        self._hedge_ema: Dict[str, float] = {}
        self._hedge_hints: Dict[str, asyncio.Event] = {}  # task hex -> event
        # object-locality hints: oid -> (node_hex, bytes) for sealed
        # plasma objects this owner knows about (its puts + its tasks'
        # large returns). Feeds locality-aware leasing (ref:
        # core_worker/lease_policy.h LocalityAwareLeasePolicy +
        # scheduling/policy/scorer.h): lease where the argument bytes
        # already live. Bounded FIFO — a hint, not a directory.
        self._obj_locality: "collections.OrderedDict" = (
            collections.OrderedDict())
        self._node_addr_cache: Dict[str, str] = {}
        self._node_addr_ts = 0.0
        # streaming generators (ref: task_manager.h ObjectRefStream)
        self._streams: Dict[TaskID, _StreamState] = {}
        # task events buffered toward the GCS (ref: task_event_buffer.h)
        self._task_events: List[dict] = []
        self._task_events_lock = locking.make_lock("CoreWorker._task_events_lock")
        self._task_event_flusher_armed = False
        self.address = ""  # worker-mode processes set their push address
        self._owner_server = None  # drivers: serves owned small objects

        # fast-lane submission plane (ray_tpu/_private/fastlane.py):
        # shm-ring task streaming to leased workers, asyncio as fallback
        from .fastlane import LanePool, lanes_enabled

        self._lane_events: Dict[ObjectID, threading.Event] = {}
        self._actor_lanes: Dict[ActorID, Any] = {}
        # serializes lane CREATION only (submission is lock-free):
        # constructing an ActorLane has side effects (spawns _attach,
        # registers shm rings named by (actor, worker, pid)) — two
        # threads racing the first call to an actor must not construct
        # two lanes whose identically-named rings clobber each other
        self._actor_lane_create_lock = locking.make_lock(
            "CoreWorker._actor_lane_create_lock")
        self._actor_lane_blocked: set = set()
        if lanes_enabled():
            # more lanes than cores just adds context-switch thrash: each
            # lane is a busy worker process (plus its reply thread here)
            width = max(1, min(self.cfg.fastlane_width,
                               os.cpu_count() or 1))
            self._lane_pool = LanePool(
                self, width=width, window=self.cfg.fastlane_window)
            self.io.spawn(self._lane_maintenance_loop())
        else:
            self._lane_pool = None

        # always-on sampling profiler for the DRIVER process (workers
        # start theirs in worker_main with task annotation); drained by
        # state.profile_cluster into the merged profile as "driver"
        self._driver_sampler = None
        if mode == "driver" and self.cfg.profiling_sample_hz > 0:
            from ..util import stacks as _stacks

            self._driver_sampler = _stacks.StackSampler(
                self.cfg.profiling_sample_hz,
                max_depth=self.cfg.profiling_max_stack_depth,
                name="stack_sampler").start()

        _set_ref_registry(self)

    def _on_reclaim_lease(self, payload):
        """Raylet push under pending demand: give back the named lane's
        lease if it has nothing in flight."""
        if self._lane_pool is not None:
            self._lane_pool.reclaim(payload.get("lease_id"))

    async def _lane_maintenance_loop(self):
        while True:
            await asyncio.sleep(2.0)
            if self._lane_pool is not None:
                self._lane_pool.maintain()

    # ------------------------------------------------------- task context
    @property
    def current_task_id(self) -> TaskID:
        ctx = _task_ctx_var.get()
        if ctx is not None:
            return ctx
        return getattr(self._task_local, "task_id", None) or self._default_task_id

    @current_task_id.setter
    def current_task_id(self, task_id: TaskID) -> None:
        self._default_task_id = task_id

    def set_task_context(self, task_id: TaskID) -> None:
        """Bind the executing task to this thread (concurrent actor methods
        each get their own context, so put-object lineage stays correct)."""
        self._task_local.task_id = task_id

    def clear_task_context(self) -> None:
        self._task_local.task_id = None

    def set_async_task_context(self, task_id: TaskID) -> None:
        """Bind the executing task to the current coroutine context: async
        actor methods interleave on ONE loop thread, so thread-locals
        cannot tell them apart — contextvars can."""
        _task_ctx_var.set(task_id)

    # ------------------------------------------------------------- lifecycle
    def connect(self):
        self.io.run(self._connect())

    async def _connect(self):
        await self.gcs.connect()
        await self.raylet.connect()
        self.gcs.on_push("pubsub:actor", self._on_actor_update)
        self.raylet.on_push("reclaim_lease", self._on_reclaim_lease)
        # actor updates are subscribed PER ACTOR (actor:<hex>) on first
        # contact with a handle — a blanket "actor" subscription from
        # every worker makes each lifecycle event an O(workers) fan-out
        # (quadratic at 1k-actor envelope depth)
        self.gcs.on_reconnect.append(self._resubscribe_gcs)
        if self.mode == "driver" and not self.address:
            await self._start_owner_server()

    async def _start_owner_server(self):
        """Drivers serve their owned in-memory objects to borrowers
        (ref: core_worker.proto GetObject — the owner is the source of
        truth for small objects, which never touch plasma). Workers
        register the same handler on their existing task server."""
        from .rpc import RpcServer, parse_address

        kind = parse_address(self.raylet.address)
        if kind[0] == "unix":
            base = os.path.dirname(kind[1])
            addr = os.path.join(
                base, f"driver_{self.worker_id.hex()[:12]}.sock")
        else:
            addr = "127.0.0.1:0"
        self._owner_server = RpcServer(
            addr, name=f"owner-{self.worker_id.hex()[:8]}")
        self._owner_server.register("fetch_object", self._handle_fetch_object)
        self._owner_server.register("hedge_hint", self.handle_hedge_hint)
        await self._owner_server.start()
        self.address = self._owner_server.address

    async def handle_hedge_hint(self, payload, conn=None):
        """Raylet watchdog push: the named task is flagged as stalled —
        hedge it now instead of waiting out the owner-side delay. Workers
        register this on their task server, drivers on the owner server
        (the same split as fetch_object)."""
        tid = payload.get("task_id")
        if hasattr(tid, "hex"):
            tid = tid.hex()
        ev = self._hedge_hints.get(tid)
        if ev is not None:
            ev.set()
        return True

    async def _handle_fetch_object(self, payload, conn):
        """Serve one owned object: {"status": ok|in_plasma|pending|gone}.
        pending = the creating task is still in flight here, the
        borrower should retry. in_plasma = the object is sealed in this
        node's store and too large to pickle through the control RPC —
        the borrower pulls it through its raylet (the bulk transfer
        plane), landing it sealed in ITS node store where every local
        worker shares it."""
        oid = payload["object_id"]
        # first, for the reason _get gives: a reply landing between a
        # "not stored" and a "not pending" read would answer "gone", and
        # a borrower resolving task arguments then parks on the raylet
        # directory, which a small object never enters
        pending = bool(self._pending_here([oid]))
        data = self.memory_store.get(oid)
        if data is None:
            view = self.store.get(oid)
            if view is not None:
                if len(view) > self.cfg.object_store_small_object_threshold:
                    return {"status": "in_plasma", "size": len(view),
                            "data": None}
                data = bytes(view)
        if data is not None:
            return {"status": "ok", "data": data}
        if pending:
            return {"status": "pending", "data": None}
        return {"status": "gone", "data": None}

    def shutdown(self):
        if self._driver_sampler is not None:
            self._driver_sampler.stop(timeout=2.0)
            self._driver_sampler = None
        if self._lane_pool is not None:
            self._lane_pool.close()
        for lane in list(self._actor_lanes.values()):
            lane.close()
        self._actor_lanes.clear()
        try:
            self.io.run(self._shutdown(), timeout=5)
        except Exception:
            pass
        self.io.stop()
        _set_ref_registry(None)
        # The native RefTable is deliberately NOT closed: ObjectRef
        # finalizers and lane reply threads may still race a call into
        # it during interpreter teardown, and close() would free the C++
        # table under them (use-after-free). It is in-process memory —
        # process exit reclaims it.

    async def _shutdown(self):
        # final task-event drain: events recorded moments before
        # shutdown would otherwise miss the 250ms flusher and vanish
        # from the state API / `timeline` (observed: a short driver's
        # FINISHED events lost)
        with self._task_events_lock:
            flush, self._task_events = self._task_events, []
        if flush and not self.gcs.closed:
            try:
                # 1s cap: this whole coroutine runs under a 5s budget
                # and driver_exit + connection closes must still fit
                await asyncio.wait_for(self._send_task_events(flush), 1)
            except Exception:
                pass
        if self.mode == "driver" and not self.gcs.closed:
            try:
                # clean detach: the GCS tears down this job's non-detached
                # actors immediately instead of waiting out the
                # connection-drop grace window
                await self.gcs.call("driver_exit", {"job_id": self.job_id},
                                    timeout=3)
            except Exception:
                pass
        for task in list(self._worker_clients.values()):
            try:
                client = await asyncio.wait_for(asyncio.shield(task), 1.0)
                await client.close()
            except Exception:
                pass
        if self._owner_server is not None:
            try:
                await self._owner_server.stop()
            except Exception:
                pass
        await self.gcs.close()
        await self.raylet.close()

    async def _resubscribe_gcs(self):
        """A restarted GCS dropped this connection's subscriptions;
        re-establish every channel this core ever subscribed."""
        try:
            await self.gcs.call("subscribe", {
                "channels": sorted(self._subscribed_channels)})
        except Exception:
            pass

    # --------------------------------------------------- app-level pubsub
    def subscribe_channel(self, channel: str, callback) -> None:
        """Receive pushes on an application pubsub channel (the long-poll
        replacement surface — ref: serve/_private/long_poll.py:66; here
        pushes ride the standing GCS connection)."""
        self.gcs.on_push("pubsub:" + channel, callback)
        self._subscribed_channels.add(channel)
        self.io.run(self.gcs.call("subscribe", {"channels": [channel]}),
                    timeout=10)

    def publish_channel(self, channel: str, message) -> None:
        self.io.run(self.gcs.call("publish", {
            "channel": channel, "message": message}), timeout=10)

    # ------------------------------------------------- blocked notification
    def _notify_blocked(self):
        """Worker mode: tell the raylet this worker's task is blocked on
        object resolution so the lease's CPU is released back (ref:
        NotifyDirectCallTaskBlocked — see raylet.handle_worker_blocked).
        Re-entrant; no-op for drivers."""
        if self.mode != "worker":
            return
        with self._block_lock:
            self._block_depth += 1
            first = self._block_depth == 1
        if first:
            try:
                self.io.run(self.raylet.call(
                    "worker_blocked", {"worker_id": self.worker_id},
                    timeout=5), timeout=6)
            except Exception:
                pass

    def _notify_unblocked(self):
        if self.mode != "worker":
            return
        with self._block_lock:
            self._block_depth = max(0, self._block_depth - 1)
            last = self._block_depth == 0
        if last:
            try:
                self.io.run(self.raylet.call(
                    "worker_unblocked", {"worker_id": self.worker_id},
                    timeout=5), timeout=6)
            except Exception:
                pass

    # -------------------------------------------------------- ref counting
    # Native C++ table when available (self._rc, native/core_tables.cc);
    # the table returns the free decision: 0 keep, 1 free (owned),
    # 2 drop local state only (borrowed).
    def add_local_ref(self, oid: ObjectID):
        if self._rc is not None:
            self._rc.add_local(oid.binary())
            return
        with self._ref_lock:
            self._local_refs[oid] = self._local_refs.get(oid, 0) + 1

    def remove_local_ref(self, oid: ObjectID):
        if self._rc is not None:
            self._apply_free_decision(oid, self._rc.remove_local(oid.binary()))
            return
        with self._ref_lock:
            count = self._local_refs.get(oid, 0) - 1
            if count <= 0:
                self._local_refs.pop(oid, None)
                if self._task_deps.get(oid, 0) <= 0:
                    self._maybe_free(oid)
            else:
                self._local_refs[oid] = count

    def add_borrowed_ref(self, oid: ObjectID, owner_address: str):
        self._borrowed[oid] = owner_address
        if self._rc is not None:
            self._rc.set_borrowed(oid.binary())
            return
        with self._ref_lock:
            self._local_refs[oid] = self._local_refs.get(oid, 0) + 1

    def _pin_task_dep(self, oid: ObjectID):
        if self._rc is not None:
            self._rc.pin_dep(oid.binary())
            return
        with self._ref_lock:
            self._task_deps[oid] = self._task_deps.get(oid, 0) + 1

    def _unpin_task_dep(self, oid: ObjectID):
        if self._rc is not None:
            self._apply_free_decision(oid, self._rc.unpin_dep(oid.binary()))
            return
        with self._ref_lock:
            count = self._task_deps.get(oid, 0) - 1
            if count <= 0:
                self._task_deps.pop(oid, None)
                if self._local_refs.get(oid, 0) <= 0:
                    self._maybe_free(oid)
            else:
                self._task_deps[oid] = count

    def _apply_free_decision(self, oid: ObjectID, decision: int):
        if decision == 0:
            return
        if decision == 2:  # borrowed: drop local state, owner frees
            self._borrowed.pop(oid, None)
            return
        self._free_owned(oid)

    def _maybe_free(self, oid: ObjectID):
        # only the owner frees plasma copies; borrowers just drop local state
        if oid in self._borrowed:
            self._borrowed.pop(oid, None)
            return
        self._free_owned(oid)

    def _free_owned(self, oid: ObjectID):
        self.memory_store.delete(oid)
        if oid in self._owned_in_plasma:
            self._owned_in_plasma.discard(oid)
            spec = self._lineage.pop(oid.task_id(), None)
            del spec
            if not self.gcs.closed:
                self.io.spawn(self._free_remote([oid]))

    async def _free_remote(self, oids: List[ObjectID]):
        try:
            await self.raylet.call("free_objects", {"object_ids": oids})
        except Exception:
            pass

    # -------------------------------------------------- memory attribution
    def local_memory_report(self) -> dict:
        """This process's object-reference claims + heap stats: the
        per-process half of state.memory_report (the GCS merges claims
        from every worker — plus the driver's, passed through the call
        payload — against each node's store inventory to attribute
        bytes per owner/ref-type)."""
        import sys as _sys
        import tracemalloc

        claims: Dict[str, dict] = {}

        def _claim(oid: ObjectID) -> dict:
            rec = claims.get(oid.hex())
            if rec is None:
                rec = claims[oid.hex()] = {
                    "local_refs": 0, "task_deps": 0, "owned": False,
                    "borrowed_from": None}
            return rec

        with self._ref_lock:
            owned = set(self._owned_in_plasma)
            borrowed = dict(self._borrowed)
            local_refs = dict(self._local_refs)
            task_deps = dict(self._task_deps)
        for oid in owned:
            _claim(oid)["owned"] = True
        for oid, owner in borrowed.items():
            _claim(oid)["borrowed_from"] = owner
        if self._rc is not None:
            # native RefTable: counts are queryable per oid but the
            # table is not enumerable — owned/borrowed sets bound the
            # plasma-relevant oids (everything else is memory-store)
            for oid in set(owned) | set(borrowed):
                rec = _claim(oid)
                try:
                    rec["local_refs"] = self._rc.local_count(oid.binary())
                    if rec["local_refs"] == 0 and \
                            self._rc.contains(oid.binary()):
                        # alive with zero local refs: held by a task-dep
                        # pin (the table has no per-kind count getter)
                        rec["task_deps"] = 1
                except Exception:  # graftlint: ignore[swallow] — native
                    pass           # table probe is advisory enrichment
        else:
            for oid, n in local_refs.items():
                _claim(oid)["local_refs"] = n
            for oid, n in task_deps.items():
                _claim(oid)["task_deps"] = n
        report = {
            "address": self.address,
            "worker_id": self.worker_id.hex(),
            "pid": os.getpid(),
            "mode": self.mode,
            "num_inflight_tasks": len(self._inflight),
            "memory_store": self.memory_store.usage_report(),
            "claims": claims,
        }
        if tracemalloc.is_tracing():
            current, peak = tracemalloc.get_traced_memory()
            report["heap"] = {"kind": "tracemalloc",
                              "current_bytes": current,
                              "peak_bytes": peak}
        else:
            report["heap"] = {"kind": "rss", "current_bytes": _rss_bytes()}
        try:
            from ..util import hbm

            report["hbm"] = (hbm.collect_hbm_stats()
                             if "jax" in _sys.modules else [])
        except Exception:
            report["hbm"] = []
        return report

    # ----------------------------------------------------------- task events
    def _record_task_event(self, task_id: TaskID, **fields) -> None:
        """Buffer a task state transition; a standing periodic flusher
        ships batches to the GCS (ref: task_event_buffer.h →
        gcs_task_manager.h). Nothing is spawned on the submit path —
        at 10k tasks/s even one run_coroutine_threadsafe per event
        would dominate."""
        event = {"task_id": task_id}
        event.update(fields)
        with self._task_events_lock:
            # lifecycle transitions coalesce into the tail event when it
            # is for the same task (one merged GCS record update instead
            # of N) — everything else appends
            if ("transitions" in event and self._task_events
                    and self._task_events[-1]["task_id"] == task_id):
                tail = self._task_events[-1]
                tail.setdefault("transitions", []).extend(
                    event.pop("transitions"))
                tail.update({k: v for k, v in event.items()
                             if k != "task_id"})
                return
            # bounded buffer: a submit burst must not build an unbounded
            # flush payload that then monopolizes the GCS loop (observed
            # r4: flush backlog starving actor creations). Oldest events
            # drop first, like the reference's ring buffer
            # (task_event_buffer.h kMaxBufferedTaskEvents).
            if len(self._task_events) >= self._TASK_EVENT_BUFFER_MAX:
                del self._task_events[:self._TASK_EVENT_FLUSH_MAX]
                self._task_events_dropped += self._TASK_EVENT_FLUSH_MAX
            self._task_events.append(event)
            arm = not self._task_event_flusher_armed
            if arm:
                self._task_event_flusher_armed = True
        if arm:
            self.io.spawn(self._task_event_flusher())

    def _record_transition(self, task_id: TaskID, to_state: str,
                           ts: Optional[float] = None, **fields) -> None:
        """Append one lifecycle transition {state, ts, node_id} to the
        task's state_transitions list in the GCS task table (the flight
        recorder's unit record). Extra fields ride the same event as
        last-writer-wins record fields (e.g. state/node_id/worker_id —
        hence the positional name: ``state=`` means the record field)."""
        entry = {"state": to_state,
                 "ts": time.time() if ts is None else ts,
                 "node_id": self.node_id.hex()}
        self._record_task_event(task_id, transitions=[entry], **fields)

    _TASK_EVENT_FLUSH_MAX = 2000     # events per report RPC
    _TASK_EVENT_BUFFER_MAX = 100_000
    _task_event_flusher_armed = False
    _task_events_dropped = 0

    async def _task_event_flusher(self):
        """Standing flusher; exits after an idle period so short-lived
        cores don't keep a wakeup loop alive. Flushes in BOUNDED chunks:
        each chunk is one awaited GCS RPC, so control-plane traffic
        (lease grants, actor registration) interleaves between chunks
        instead of queueing behind one giant report."""
        idle = 0
        while idle < 20:
            await asyncio.sleep(0.25)
            with self._task_events_lock:
                flush, self._task_events = self._task_events, []
            if flush:
                idle = 0
                for i in range(0, len(flush), self._TASK_EVENT_FLUSH_MAX):
                    await self._send_task_events(
                        flush[i:i + self._TASK_EVENT_FLUSH_MAX])
            else:
                idle += 1
        with self._task_events_lock:
            if self._task_events:
                # an event landed between the last empty swap and now;
                # disarming here would strand it — let a fresh flusher
                # take over
                self.io.spawn(self._task_event_flusher())
            else:
                self._task_event_flusher_armed = False

    async def _send_task_events(self, events: List[dict]):
        try:
            await self.gcs.call("report_task_events", {"events": events})
        except Exception:
            pass

    # --------------------------------------------------------------- put/get
    def put(self, value: Any) -> ObjectRef:
        with self._put_lock:
            self._put_index += 1
            oid = ObjectID.for_put(self.current_task_id, self._put_index)
        parts = ser.serialize_parts(value)
        if parts.total <= _SMALL:
            self._store_object(oid, parts.to_bytes())
        else:
            # large objects serialize straight into the shm mapping —
            # one write pass instead of assemble + bytes() + store copy
            buf = self.store.create(oid, parts.total)
            try:
                parts.write_into(buf)
            except BaseException:
                self.store.abort(oid)
                raise
            self.store.seal(oid)
            self._owned_in_plasma.add(oid)
            self._note_locality(oid, self.node_id.hex(), parts.total)
            self.io.spawn(self._notify_sealed(oid, parts.total))
        return ObjectRef(oid, self.address)

    def _store_object(self, oid: ObjectID, data: bytes, memory_only: bool = False):
        if len(data) <= _SMALL or memory_only:
            self.memory_store.put(oid, data)
            if not memory_only:
                # small objects also become visible cluster-wide via plasma so
                # other processes can fetch them (inline-on-reply covers the
                # common path; this covers puts)
                self.store.put(oid, data)
                self._owned_in_plasma.add(oid)
                self.io.spawn(self._notify_sealed(oid, len(data)))
        else:
            self.store.put(oid, data)
            self._owned_in_plasma.add(oid)
            self._note_locality(oid, self.node_id.hex(), len(data))
            self.io.spawn(self._notify_sealed(oid, len(data)))

    async def _notify_sealed(self, oid: ObjectID, size: int):
        try:
            # idempotent: retried on loss so the object directory cannot
            # silently miss a sealed object (chaos/unreliable transports)
            await self.raylet.call_retrying(
                "object_sealed", {"object_id": oid, "size": size},
                attempts=5, per_try_timeout=2.0)
        except Exception:
            pass

    def get(self, refs: Sequence[ObjectRef], timeout: Optional[float] = None) -> List[Any]:
        oids = [r.id() for r in refs]
        # Fast path: every object is already local, or is the pending
        # return of a fast-lane task (completed by the lane reply thread
        # setting a threading.Event) — no event-loop hop, no raylet RPC.
        fast = []
        for oid in oids:
            ev = self._lane_events.get(oid)
            if ev is not None:
                fast.append((oid, ev))
            elif self.memory_store.contains(oid) or self.store.contains(oid):
                fast.append((oid, None))
            else:
                fast = None
                break
        if fast is not None:
            deadline = (None if timeout is None
                        else time.monotonic() + timeout)
            # notify only for a REAL wait: pre-set events / already-
            # completed results must not cost two raylet RPCs
            waiting = any(
                ev is not None and not ev.is_set()
                and not (self.memory_store.contains(oid)
                         or self.store.contains(oid))
                for oid, ev in fast)
            if waiting:
                self._notify_blocked()
            try:
                out = []
                for oid, ev in fast:
                    if ev is not None and not (
                            self.memory_store.contains(oid)
                            or self.store.contains(oid)):
                        left = (None if deadline is None
                                else max(0.0, deadline - time.monotonic()))
                        if not ev.wait(left):
                            raise exc.GetTimeoutError(
                                "Get timed out: fast-lane task not finished")
                    out.append(self._load_object(oid))
                return out
            finally:
                if waiting:
                    self._notify_unblocked()
        owners = {r.id(): r.owner_address for r in refs if r.owner_address}
        # fast==None means at least one object is neither local nor an
        # in-flight lane return: a real wait — give the CPU back
        self._notify_blocked()
        try:
            return self.io.run(
                self._get(oids, timeout, owners),
                timeout=None if timeout is None else timeout + 30)
        finally:
            self._notify_unblocked()

    async def _probe_owner(self, owner: str, oid: ObjectID,
                           rpc_timeout: float = 10.0) -> str:
        """One non-blocking probe of an object's owner. "ok" lands the
        bytes in the local memory store; "pending" means the creating
        task is still running there. Returns
        "ok" | "pending" | "gone" | "unreachable"."""
        try:
            client = await self._client_for(owner)
            reply = await client.call("fetch_object",
                                      {"object_id": oid},
                                      timeout=rpc_timeout)
        except Exception:
            return "unreachable"  # owner dead, hung, or not serving
        if reply is None or reply.get("status") == "gone":
            return "gone"
        if reply["status"] == "ok":
            self.memory_store.put(oid, reply["data"])
            return "ok"
        if reply["status"] == "in_plasma":
            return "in_plasma"  # caller routes through the raylet pull
        return "pending"

    async def _owner_gone_policy(self, oid: ObjectID,
                                 gone_strikes: Dict[ObjectID, int]) -> str:
        """Shared _get/_wait policy when an owner reports gone or is
        unreachable: the owner holds nothing IN MEMORY, but a large
        result seals into plasma on the EXECUTING node, so give the
        raylet directory a few passes (with a grace window for the
        batched seal report) before attempting lineage recovery.
        Returns "directory" (keep consulting the directory),
        "recovered", or "lost"."""
        strikes = gone_strikes.get(oid, 0) + 1
        gone_strikes[oid] = strikes
        if strikes < 4:
            return "directory"
        if await self._try_recover([oid]):
            gone_strikes.pop(oid, None)
            return "recovered"
        return "lost"

    async def _fetch_from_owner(self, owner: str, oid: ObjectID,
                                deadline: Optional[float]) -> str:
        """Pull one object from its owner into the local memory store
        (small objects never seal into plasma — the owner serves them).
        Retries while the owner reports the creating task pending.
        Returns "ok" | "in_plasma" | "gone" | "unreachable" | "timeout"."""
        delay = 0.005
        while True:
            status = await self._probe_owner(owner, oid)
            if status != "pending":
                return status
            if (deadline is not None
                    and asyncio.get_event_loop().time() > deadline):
                return "timeout"
            await asyncio.sleep(delay)
            delay = min(delay * 2, 0.1)

    def _pending_here(self, oids) -> set:
        """Objects whose creating task is in flight from THIS worker
        (fast lane, asyncio path or stream): they complete into the
        local memory store."""
        return {oid for oid in oids
                if oid in self._lane_events
                or oid.task_id() in self._inflight
                or oid.task_id() in self._streams}

    async def _get(self, oids: List[ObjectID], timeout: Optional[float],
                   owners: Optional[Dict[ObjectID, str]] = None) -> List[Any]:
        """Resolution order per object: local stores → (owned, task in
        flight here) poll local completion → (borrowed, owner known)
        fetch from owner → raylet directory wait + lineage recovery.
        Small objects never seal into plasma, so the directory only
        covers large/sealed ones."""
        loop = asyncio.get_event_loop()
        deadline = None if timeout is None else loop.time() + timeout
        owners = owners or {}
        delay = 0.002
        gone_strikes: Dict[ObjectID, int] = {}
        while True:
            # snapshot BEFORE looking in the stores: a fast-lane reply
            # thread stores the value and only then un-registers the
            # task, so read in the other order a reply landing between
            # the two reads is neither stored nor pending, and the get
            # parks on the raylet directory for an object that never
            # enters it
            pending_here = self._pending_here(oids)
            missing = [oid for oid in oids
                       if not self.memory_store.contains(oid)
                       and not self.store.contains(oid)]
            if not missing:
                return [self._load_object(oid) for oid in oids]
            pending_here.intersection_update(missing)
            foreign = [oid for oid in missing if oid not in pending_here]
            progressed = False
            plasma_wait = []
            for oid in foreign:
                owner = owners.get(oid)
                if owner and owner != self.address:
                    status = await self._fetch_from_owner(owner, oid,
                                                          deadline)
                    if status == "ok":
                        progressed = True
                        continue
                    if status == "in_plasma":
                        # sealed + large at the owner's node: pull it
                        # through the raylet (bulk transfer plane)
                        plasma_wait.append(oid)
                        continue
                    if status in ("gone", "unreachable"):
                        verdict = await self._owner_gone_policy(
                            oid, gone_strikes)
                        if verdict == "recovered":
                            continue
                        if verdict == "lost":
                            raise exc.ObjectLostError(oid)
                        plasma_wait.append(oid)
                        continue
                    raise exc.GetTimeoutError(
                        f"Get timed out waiting on owner {owner}")
                plasma_wait.append(oid)
            if plasma_wait:
                left = (None if deadline is None
                        else max(0.0, deadline - loop.time()))
                # bounded slices when owned work is also pending here or
                # an owner said gone (the directory may never learn of a
                # small object), so local completions / strikes progress
                slice_t = left
                if pending_here or gone_strikes:
                    slice_t = 0.2 if left is None else min(0.2, left)
                reply = await self.raylet.call("wait_objects", {
                    "object_ids": plasma_wait,
                    "num_returns": len(plasma_wait),
                    "timeout": slice_t,
                })
                lost = reply.get("lost", [])
                if lost:
                    recovered = await self._try_recover(lost)
                    if not recovered:
                        raise exc.ObjectLostError(lost[0])
                    continue
                if len(reply["ready"]) >= len(plasma_wait):
                    progressed = True
                elif not pending_here and timeout is not None and (
                        deadline is None or loop.time() >= deadline):
                    raise exc.GetTimeoutError(
                        f"Get timed out: "
                        f"{len(plasma_wait) - len(reply['ready'])} "
                        f"object(s) not ready")
            if deadline is not None and loop.time() >= deadline:
                still = [oid for oid in oids
                         if not self.memory_store.contains(oid)
                         and not self.store.contains(oid)]
                if still:
                    raise exc.GetTimeoutError(
                        f"Get timed out: {len(still)} object(s) not ready")
                continue
            if not progressed:
                await asyncio.sleep(delay)
                delay = min(delay * 2, 0.05)
            else:
                delay = 0.002

    async def _try_recover(self, oids: List[ObjectID]) -> bool:
        """Lineage reconstruction (ref: object_recovery_manager.h,
        task_manager.h resubmit): re-execute the recorded creating task of
        each lost object, recursively recovering lost arguments first.
        Bounded by the task's max_retries. False = any object unrecoverable
        (no lineage: ray_tpu.put data, actor returns, exhausted budget)."""
        for oid in dict.fromkeys(oids):
            if not await self._recover_object(oid):
                return False
        return True

    async def _recover_object(self, oid: ObjectID, depth: int = 0) -> bool:
        if depth > 16:
            return False
        if self.memory_store.contains(oid) or self.store.contains(oid):
            return True
        spec = self._lineage.get(oid.task_id())
        if spec is None or spec.actor_id is not None or spec.streaming:
            return False
        if spec.max_retries <= 0:
            return False
        used = self._reconstructions.get(spec.task_id, 0)
        if used >= spec.max_retries:
            return False
        self._reconstructions[spec.task_id] = used + 1
        # lost args must be rebuilt before the task can run again; args that
        # are merely remote are pulled by the executing raylet as usual
        for arg in spec.args:
            if arg.kind != ArgKind.OBJECT_REF:
                continue
            reply = await self.raylet.call("wait_objects", {
                "object_ids": [arg.object_id], "num_returns": 1, "timeout": 0})
            if reply.get("lost"):
                await self.raylet.call(
                    "forget_lost", {"object_ids": [arg.object_id]})
                if not await self._recover_object(arg.object_id, depth + 1):
                    return False
        # clear sticky lost markers so the fresh copy can be awaited
        await self.raylet.call("forget_lost", {"object_ids": spec.return_ids()})
        try:
            await self._run_on_leased_worker(spec)
        except asyncio.CancelledError:
            raise  # recovery itself cancelled: don't report "lost"
        except Exception:  # any resubmit failure surfaces as "lost"
            return False
        return True

    def _load_object(self, oid: ObjectID) -> Any:
        data = self.memory_store.get(oid)
        if data is None:
            view = self.store.get(oid)
            if view is None:
                raise exc.ObjectLostError(oid)
            data = view
        value, metadata = ser.deserialize(data)
        if metadata == ser.META_ERROR:
            err, tb = value
            if isinstance(err, (exc.TaskCancelledError, exc.ActorDiedError,
                                exc.WorkerCrashedError, exc.ObjectLostError)):
                raise err
            raise exc.TaskError(err, tb)
        return value

    def wait(self, refs: Sequence[ObjectRef], num_returns: int,
             timeout: Optional[float]) -> Tuple[List[ObjectRef], List[ObjectRef]]:
        oids = [r.id() for r in refs]
        owners = {r.id(): r.owner_address for r in refs if r.owner_address}
        ready_ids = self.io.run(self._wait(oids, num_returns, timeout, owners))
        ready_set = set(ready_ids[:num_returns]) if len(ready_ids) > num_returns else set(ready_ids)
        ready, not_ready = [], []
        for ref in refs:
            (ready if ref.id() in ready_set and len(ready) < num_returns else not_ready).append(ref)
        return ready, not_ready

    async def _wait(self, oids, num_returns, timeout, owners=None):
        """Readiness: local stores first; owned in-flight tasks (fast
        lane / asyncio) complete into the memory store, so they are
        polled locally — small returns never reach the plasma
        directory; borrowed refs with a known foreign owner are probed
        at that owner (small objects never get a directory entry, so
        the raylet wait manager alone would never report them ready);
        everything else blocks on the raylet wait manager. Lost
        objects count as ready: their get() surfaces ObjectLostError
        (matches the reference, where a failed reconstruction stores
        an error object)."""
        loop = asyncio.get_event_loop()
        deadline = None if timeout is None else loop.time() + timeout
        owners = owners or {}
        delay = 0.002
        lost_here: set = set()
        gone_strikes: Dict[ObjectID, int] = {}
        while True:
            pending_here = self._pending_here(oids)  # first: see _get
            ready = [oid for oid in oids
                     if oid in lost_here
                     or self.memory_store.contains(oid)
                     or self.store.contains(oid)]
            if len(ready) >= num_returns:
                return ready
            ready_set = set(ready)
            pending_here.difference_update(ready_set)
            owner_served = [oid for oid in oids
                            if oid not in ready_set
                            and oid not in pending_here
                            and owners.get(oid) not in (None, self.address)]
            owner_set = set(owner_served)
            remote = [oid for oid in oids
                      if oid not in ready_set and oid not in pending_here
                      and oid not in owner_set]
            progressed = False
            for oid in owner_served:
                # cap each probe RPC by the caller's remaining budget so
                # a hung owner cannot make wait(timeout=0.5) take 10 s
                left = (None if deadline is None
                        else max(0.0, deadline - loop.time()))
                rpc_t = 10.0 if left is None else max(0.05, min(10.0, left))
                status = await self._probe_owner(owners[oid], oid,
                                                 rpc_timeout=rpc_t)
                if status == "ok":
                    progressed = True
                elif status == "in_plasma":
                    remote.append(oid)  # directory wait pulls it locally
                elif status in ("gone", "unreachable"):
                    # lost counts as ready; get() raises there
                    verdict = await self._owner_gone_policy(
                        oid, gone_strikes)
                    if verdict in ("recovered", "lost"):
                        if verdict == "lost":
                            lost_here.add(oid)
                        progressed = True
                    else:
                        remote.append(oid)
                if deadline is not None and loop.time() >= deadline:
                    break
            if progressed:
                continue
            if remote and not pending_here and not owner_served:
                left = (None if deadline is None
                        else max(0.0, deadline - loop.time()))
                reply = await self.raylet.call("wait_objects", {
                    "object_ids": remote,
                    "num_returns": num_returns - len(ready),
                    "timeout": left if timeout is not None else None,
                })
                return ready + reply["ready"] + reply.get("lost", [])
            if remote:
                reply = await self.raylet.call("wait_objects", {
                    "object_ids": remote, "num_returns": len(remote),
                    "timeout": 0})
                combined = ready + reply["ready"] + reply.get("lost", [])
                if len(combined) >= num_returns:
                    return combined
            if deadline is not None and loop.time() >= deadline:
                return ready
            await asyncio.sleep(delay)
            # owner-probe-only passes may spin for a task's whole
            # runtime (no blocking park exists for borrowed pending
            # objects) — back off further so a minutes-long wait costs
            # ~4 RPCs/s, not ~20; local in-flight completion still
            # polls at the tight cap.
            cap = 0.25 if (owner_served and not pending_here) else 0.05
            delay = min(delay * 2, cap)

    def as_future(self, ref: ObjectRef) -> concurrent.futures.Future:
        fut: concurrent.futures.Future = concurrent.futures.Future()

        async def _resolve():
            try:
                values = await self._get([ref.id()], None)
                fut.set_result(values[0])
            except BaseException as e:  # noqa: BLE001
                fut.set_exception(e)

        self.io.spawn(_resolve())
        return fut

    # ------------------------------------------------------ function export
    def export_function(self, func_or_class: Any) -> FunctionDescriptor:
        # Descriptor memoized per function OBJECT: cloudpickling the
        # function on every submit would dominate the trivial-task path
        # (~130us each). Keyed by identity in a WeakKeyDictionary-like
        # id map so redefinition (new object) re-exports; the closure
        # caveat (mutated captured state is not re-shipped) matches the
        # reference's once-per-function export via function_manager.py.
        key = id(func_or_class)
        cached = self._descriptor_cache.get(key)
        if cached is not None and cached[0] is func_or_class:
            return cached[1]
        if len(self._descriptor_cache) >= 4096:
            # bound the cache: drivers minting closures in a loop must
            # not pin every one (plus its captures) forever
            for old in list(self._descriptor_cache)[:2048]:
                self._descriptor_cache.pop(old, None)
        pickled = cloudpickle.dumps(func_or_class)
        blob_id = FunctionDescriptor.blob_id_for(pickled)
        if blob_id not in self._exported_blobs:
            self.io.run(self.gcs.call("kv_put", {
                "ns": "functions", "key": blob_id, "value": pickled,
            }))
            self._exported_blobs.add(blob_id)
        name = getattr(func_or_class, "__qualname__", repr(func_or_class))
        descriptor = FunctionDescriptor(blob_id=blob_id, repr_name=name)
        self._descriptor_cache[key] = (func_or_class, descriptor)
        return descriptor

    def load_function(self, blob_id: str) -> Any:
        cached = self._function_cache.get(blob_id)
        if cached is not None:
            return cached
        pickled = self.io.run(self.gcs.call("kv_get", {"ns": "functions", "key": blob_id}))
        if pickled is None:
            raise exc.RayTpuError(f"function blob {blob_id} not found in GCS")
        func = cloudpickle.loads(pickled)
        self._function_cache[blob_id] = func
        return func

    # ------------------------------------------------------- arg resolution
    def _pack_args(self, args: tuple, kwargs: dict) -> Tuple[List[TaskArg], List[ObjectID]]:
        packed: List[TaskArg] = []
        dep_ids: List[ObjectID] = []
        flat = list(args) + [("__kw__", k, v) for k, v in (kwargs or {}).items()]
        for item in flat:
            actual = item[2] if isinstance(item, tuple) and len(item) == 3 and item[0] == "__kw__" else item
            kw = item[1] if actual is not item else None
            if isinstance(actual, ObjectRef):
                # Inline small owned values the owner already holds
                # (ref: transport/dependency_resolver.h inlines small
                # in-memory objects): the consuming worker skips the
                # whole dependency wait. Error payloads stay by-ref so
                # the dependency failure surfaces as a task error, not
                # as a (err, tb) tuple argument.
                inline = self.memory_store.get(actual.id())
                if (inline is not None and len(inline) <= _SMALL
                        and ser.get_metadata(inline) == ser.META_PLAIN):
                    packed.append(TaskArg(ArgKind.VALUE,
                                          value=(kw, inline)))
                    continue
                packed.append(TaskArg(
                    ArgKind.OBJECT_REF, value=kw, object_id=actual.id(),
                    owner=actual.owner_address or self.address))
                dep_ids.append(actual.id())
                self._pin_task_dep(actual.id())
            else:
                data = ser.serialize(actual)
                if len(data) > _SMALL:
                    ref = self.put(actual)
                    packed.append(TaskArg(
                        ArgKind.OBJECT_REF, value=kw, object_id=ref.id(),
                        owner=self.address))
                    dep_ids.append(ref.id())
                    self._pin_task_dep(ref.id())
                else:
                    packed.append(TaskArg(ArgKind.VALUE, value=(kw, data)))
        return packed, dep_ids

    @staticmethod
    def _resolve_strategy(opts: dict):
        """scheduling_strategy option, with the `placement_group=` and
        `accelerator_type=` shorthands folded in (ref:
        ray_option_utils.py option groups; accelerator_type maps to a
        hard node-label match like the reference's
        accelerator-type-to-label resolution)."""
        strategy = opts.get("scheduling_strategy")
        pg = opts.get("placement_group")
        acc = opts.get("accelerator_type")
        if sum(x is not None for x in (strategy, pg, acc)) > 1:
            raise ValueError(
                "scheduling_strategy=, placement_group= and "
                "accelerator_type= are mutually exclusive")
        if strategy is not None:
            return strategy
        if pg is not None:
            return PlacementGroupSchedulingStrategy(
                placement_group_id=getattr(pg, "id", pg),
                placement_group_bundle_index=opts.get(
                    "placement_group_bundle_index", -1))
        if acc is not None:
            from ..util.scheduling_strategies import (
                In, NodeLabelSchedulingStrategy)

            return NodeLabelSchedulingStrategy(
                hard={"accelerator_type": In(str(acc))})
        return DefaultSchedulingStrategy()

    @staticmethod
    def _build_resources(opts: dict) -> ResourceSet:
        res = dict(opts.get("resources") or {})
        if opts.get("num_cpus") is not None:
            res["CPU"] = opts["num_cpus"]
        elif "CPU" not in res:
            res["CPU"] = 1
        if opts.get("num_tpus"):
            res["TPU"] = opts["num_tpus"]
        return ResourceSet(res)

    # ------------------------------------------------------ normal tasks
    def _prepare_runtime_env(self, opts: dict,
                             allow_container: bool = True) -> Optional[dict]:
        """Pack a runtime_env option for the wire (ref: runtime envs,
        SURVEY §2.2). Cached per (env-spec, content fingerprint):
        re-tarring a working_dir on every one of thousands of
        submissions would dominate the submit path. The fingerprint is
        a shallow walk of every file's (relpath, size, mtime) — editing
        a file's CONTENTS bumps its mtime, so re-submitting from the
        same driver ships fresh code (the reference re-hashes directory
        contents per upload; a directory-level mtime would miss edits
        inside existing files)."""
        env = opts.get("runtime_env")
        if not env:
            return None
        if not allow_container and isinstance(env, dict) \
                and env.get("container"):
            # the per-task-body container model cannot seal a long-lived
            # actor or a streaming generator — reject LOUDLY at
            # submission instead of silently running on the host
            raise ValueError(
                "container runtime_env supports plain tasks only; "
                "actors and streaming generators run on the host "
                "worker (use pip/conda/working_dir envs for those)")
        import json
        import os as _os

        def _dir_fingerprint(d: str):
            if not d:
                return 0.0
            sig = []
            try:
                for root, subdirs, files in _os.walk(d):
                    subdirs.sort()
                    for f in sorted(files):
                        p = _os.path.join(root, f)
                        try:
                            st = _os.stat(p)
                        except OSError:
                            continue
                        sig.append((_os.path.relpath(p, d),
                                    st.st_size, st.st_mtime))
            except OSError:
                return 0.0
            return tuple(sig)

        dirs = [env.get("working_dir") or ""] + list(
            env.get("py_modules") or [])
        try:
            mtimes = tuple(_dir_fingerprint(d) for d in dirs)
        except OSError:
            mtimes = ()
        try:
            cache_key = (json.dumps(env, sort_keys=True, default=str),
                         mtimes)
        except TypeError:
            cache_key = None
        if cache_key is not None and cache_key in self._runtime_env_cache:
            return self._runtime_env_cache[cache_key]  # may be None
        from .runtime_env import prepare_runtime_env

        wire = prepare_runtime_env(self, env)
        if cache_key is not None:
            self._runtime_env_cache[cache_key] = wire
        return wire

    def submit_task(self, func: Any, args: tuple, kwargs: dict, opts: dict):
        clock = (_StageClock(_stage_hist())
                 if self.cfg.submit_stage_timers_enabled else None)
        # validate options BEFORE packing args: _pack_args pins dependencies
        # that are only released through the submit coroutine's finally
        strategy = self._resolve_strategy(opts)
        if opts.get("speculation", "") not in ("", "auto", "off"):
            raise ValueError(
                f"speculation must be 'auto' or 'off', got "
                f"{opts.get('speculation')!r}")
        descriptor = self.export_function(func)
        if clock:
            clock.mark("export_fn")
        packed, deps = self._pack_args(args, kwargs)
        if clock:
            clock.mark("serialize")
        num_returns = opts.get("num_returns", 1)
        streaming = num_returns == "streaming"
        spec = TaskSpec(
            task_id=TaskID.for_normal_task(self.job_id),
            job_id=self.job_id,
            function=descriptor,
            args=packed,
            num_returns=0 if streaming else num_returns,
            resources=self._build_resources(opts),
            scheduling_strategy=strategy,
            # streaming tasks never auto-retry: a replay would re-emit items
            # the consumer already saw (the failure rides the stream instead)
            max_retries=0 if streaming else opts.get(
                "max_retries", self.cfg.task_max_retries_default),
            retry_exceptions=opts.get("retry_exceptions", False),
            streaming=streaming,
            backpressure_items=opts.get(
                "generator_backpressure_num_objects", 0) or 0,
            owner_address=self.address,
            runtime_env=self._prepare_runtime_env(
                opts, allow_container=not streaming),
            idempotent=bool(opts.get("idempotent", False)),
            speculation=opts.get("speculation", "") or "",
        )
        from ..util.tracing import inject_trace_ctx

        inject_trace_ctx(spec)
        if clock:
            clock.mark("spec_mint")
        # registered before the submit coroutine runs, so an immediate
        # cancel() cannot race past the bookkeeping
        self._inflight[spec.task_id] = {"canceled": False, "worker_address": None}
        if self.cfg.lineage_pinning_enabled and not streaming:
            self._lineage[spec.task_id] = spec
        if clock:
            clock.mark("bookkeeping")
        submit_t = time.time()
        self._record_transition(spec.task_id, "SUBMITTED", ts=submit_t,
                                name=spec.function.repr_name,
                                state="SUBMITTED", start_time=submit_t)
        if clock:
            clock.mark("task_event")
        if streaming:
            self._streams[spec.task_id] = _StreamState()
            self.io.spawn(self._submit_normal(spec, deps))
            if clock:
                clock.mark("dispatch")
                clock.total()
            return ObjectRefGenerator(spec.task_id, self)
        refs = [ObjectRef(oid, self.address) for oid in spec.return_ids()]
        if self._lane_eligible(spec, deps) and self._lane_submit(spec):
            if clock:
                clock.mark("dispatch")
                clock.total()
            return refs
        self.io.spawn(self._submit_normal(spec, deps))
        if clock:
            clock.mark("dispatch")
            clock.total()
        return refs

    def _lane_eligible(self, spec: TaskSpec, deps: List[ObjectID]) -> bool:
        """Fast-lane tasks: default-shaped, dependency-free, one return.
        Everything else — including hedge-eligible tasks, whose backup
        copy management lives on the asyncio control plane — takes the
        normal submit path."""
        return (self._lane_pool is not None
                and not self._hedge_eligible(spec)
                and not deps
                and spec.num_returns == 1
                and spec.runtime_env is None
                and isinstance(spec.scheduling_strategy,
                               DefaultSchedulingStrategy)
                and spec.resources.key() == (("CPU", 1.0),))

    def _lane_submit(self, spec: TaskSpec) -> bool:
        event = threading.Event()
        oid = ObjectID.for_return(spec.task_id, 1)
        self._lane_events[oid] = event
        if self._lane_pool.try_submit(spec, event):
            return True
        self._lane_events.pop(oid, None)
        return False

    async def _submit_normal(self, spec: TaskSpec, deps: List[ObjectID]):
        info = self._inflight.setdefault(spec.task_id, {
            "canceled": False, "worker_address": None})
        try:
            attempts = spec.max_retries + 1
            last_error: Optional[BaseException] = None
            for attempt in range(attempts):
                if info["canceled"]:
                    raise exc.TaskCancelledError(
                        f"task {spec.function.repr_name} was cancelled")
                try:
                    app_errored = await self._run_on_leased_worker(spec, info)
                    last_error = None
                    break
                except (ConnectionLost, exc.WorkerCrashedError) as e:
                    if info["canceled"]:
                        # the lease loss is incidental — the user asked
                        # for cancellation; don't chain the crash noise
                        raise exc.TaskCancelledError(
                            f"task {spec.function.repr_name} was "
                            "cancelled") from None
                    last_error = e
                    await asyncio.sleep(0.02 * (2 ** attempt))
            if last_error is not None:
                self._store_error(spec, exc.WorkerCrashedError(
                    f"task {spec.function.repr_name} failed after {attempts} attempts: {last_error}"))
                self._record_transition(spec.task_id, "FAILED",
                                        state="FAILED",
                                        end_time=time.time(),
                                        error=str(last_error))
            else:
                # a task whose body raised is FAILED in the state API even
                # though submission completed cleanly (its returns hold the
                # serialized error)
                terminal = "FAILED" if app_errored else "FINISHED"
                self._record_transition(
                    spec.task_id, terminal,
                    state=terminal,
                    end_time=time.time(),
                    error="application error" if app_errored else None)
        except BaseException as e:  # noqa: BLE001
            self._store_error(spec, e)
            self._record_transition(spec.task_id, "FAILED", state="FAILED",
                                    end_time=time.time(), error=str(e))
        finally:
            self._inflight.pop(spec.task_id, None)
            for oid in deps:
                self._unpin_task_dep(oid)

    def _store_error(self, spec: TaskSpec, error: BaseException):
        data = ser.serialize_error(error)
        if spec.streaming:
            # submission-level failure becomes the next (final) stream item
            state = self._streams.get(spec.task_id)
            if state is not None:
                index = state.received + 1
                oid = ObjectID.for_return(spec.task_id, index)
                self.memory_store.put(oid, data)
                state.queue.put_nowait(ObjectRef(oid, self.address))
                state.queue.put_nowait(_STREAM_DONE)
            return
        for oid in spec.return_ids():
            self.memory_store.put(oid, data)
            try:
                self.store.put(oid, data)
                self.io.spawn(self._notify_sealed(oid, len(data)))
            except OSError:
                pass  # store already destroyed (shutdown race)

    async def _run_on_leased_worker(self, spec: TaskSpec, info: Optional[dict] = None):
        if self._hedge_eligible(spec):
            return await self._run_hedged(spec, info)
        return await self._run_attempt(spec, info)

    # ------------------------------------------- hedged speculative execution
    # (The Tail at Scale: issue a backup copy of a slow idempotent task on
    #  a different node, first reply wins, loser is cancelled)
    def _hedge_eligible(self, spec: TaskSpec) -> bool:
        return (self.cfg.task_speculation_enabled
                and spec.idempotent
                and spec.speculation != "off"
                and not spec.streaming
                and spec.actor_id is None
                and not spec.actor_creation)

    def _hedge_delay(self, spec: TaskSpec) -> Optional[float]:
        """Owner-side hedge trigger delay: the per-fn latency profile
        (EMA of past push->reply durations) times the hedge factor. None
        when no profile exists yet — then only a raylet watchdog
        hedge_hint triggers the backup."""
        ema = self._hedge_ema.get(spec.function.repr_name)
        if ema is None:
            return None
        return max(self.cfg.task_hedge_min_delay_s,
                   ema * self.cfg.task_hedge_ema_factor)

    async def _run_hedged(self, spec: TaskSpec, info: Optional[dict]):
        state = {"published": False, "publishes": 0}
        hint = asyncio.Event()
        self._hedge_hints[spec.task_id.hex()] = hint
        hedge: Optional[asyncio.Future] = None
        primary = asyncio.ensure_future(
            self._run_attempt(spec, info, publish_state=state,
                              role="primary"))
        try:
            hint_task = asyncio.ensure_future(hint.wait())
            try:
                await asyncio.wait({primary, hint_task},
                                   timeout=self._hedge_delay(spec),
                                   return_when=asyncio.FIRST_COMPLETED)
            finally:
                # a bare Event.wait() holds no resource: safe to cancel
                if not hint_task.done():
                    hint_task.cancel()
            if primary.done() or (info is not None and info["canceled"]):
                return await primary
            _hedge_counter("task_hedges_launched").inc()
            hedge = asyncio.ensure_future(
                self._run_attempt(spec, info, publish_state=state,
                                  avoid_node=state.get("primary_node"),
                                  role="hedge"))
            # first reply to publish wins (an attempt that aborted because
            # the other copy sealed returns None); an attempt dying with an
            # infra error (ConnectionLost/WorkerCrashed) defers to the
            # other copy, and only if BOTH fail does the error escape into
            # _submit_normal's retry loop
            pending = {primary, hedge}
            winner: Optional[asyncio.Future] = None
            first_exc: Optional[BaseException] = None
            while pending and winner is None:
                done, pending = await asyncio.wait(
                    pending, return_when=asyncio.FIRST_COMPLETED)
                for fut in done:
                    if fut.exception() is not None:
                        if first_exc is None:
                            first_exc = fut.exception()
                    # fut came out of asyncio.wait's done set: result()
                    # is an immediate read, not a blocking future wait
                    elif fut.result() is not None:  # graftlint: ignore[blocking]
                        winner = fut
                        break
            if winner is None:
                if first_exc is not None:
                    raise first_exc
                raise exc.RayTpuError(
                    f"hedged task {spec.function.repr_name}: no attempt "
                    "published a result")
            loser = hedge if winner is primary else primary
            if winner is hedge:
                _hedge_counter("task_hedges_won").inc()
                self._report_primary_straggler(spec, state)
            if not loser.done():
                background(self._finalize_hedge_loser(
                    spec, loser,
                    state.get("hedge_addr" if winner is primary
                              else "primary_addr")))
            else:
                loser.exception()  # retrieved: both replies are in
            return winner.result()
        finally:
            self._hedge_hints.pop(spec.task_id.hex(), None)

    async def _finalize_hedge_loser(self, spec: TaskSpec,
                                    loser: asyncio.Future,
                                    address: Optional[str]):
        """Cancel the losing copy through the normal cancel_task path and
        drain its attempt coroutine (which skips publication — the winner
        already sealed — and releases its own lease)."""
        if address:
            try:
                client = await self._client_for(address)
                await client.call("cancel_task", {
                    "task_id": spec.task_id, "force": False}, timeout=5)
                _hedge_counter("task_hedges_cancelled").inc()
            except (asyncio.TimeoutError, ConnectionLost, RpcError, OSError):
                pass  # loser's worker already gone — nothing to cancel
        try:
            await loser
        except (exc.RayTpuError, ConnectionLost, RpcError,
                asyncio.TimeoutError, OSError):
            pass  # loser infra errors are moot once the winner published

    def _report_primary_straggler(self, spec: TaskSpec, state: dict) -> None:
        """A won hedge is a measured straggle of the primary's node: feed
        it into the GCS straggler stats so scheduling deprioritization
        sees task-plane stragglers, not just collective skew."""
        node = state.get("primary_node")
        push_t = state.get("primary_push_t")
        if not node or push_t is None:
            return
        ema = self._hedge_ema.get(spec.function.repr_name) or 0.0
        late = max(0.0, time.monotonic() - push_t - ema)
        background(self.gcs.call("report_straggler", {
            "node_id": node, "late_s": late,
            "source": "task_hedge"}, timeout=self.cfg.gcs_rpc_timeout_s or None))

    async def _run_attempt(self, spec: TaskSpec, info: Optional[dict] = None,
                           publish_state: Optional[dict] = None,
                           avoid_node: Optional[str] = None,
                           role: str = "primary"):
        sched_class = spec.scheduling_class()
        pool = self._lease_pools.setdefault(sched_class, _LeasePool())
        self._record_transition(spec.task_id, "PENDING_NODE_ASSIGNMENT")
        # lease-queue stage: async-side (pool pop or raylet round trip +
        # spillback chain), so it reports alongside — not inside — the
        # synchronous submit partition
        timed = self.cfg.submit_stage_timers_enabled
        t_lease = time.perf_counter() if timed else 0.0
        grant = await self._acquire_lease(pool, spec, avoid_node=avoid_node)
        if timed:
            _stage_hist().observe(time.perf_counter() - t_lease,
                                  tags={"stage": "lease_acquire"})
        keep = False
        try:
            if publish_state is not None and publish_state["published"]:
                # the other copy won while this lease was in flight:
                # never cancel mid-acquisition (rid-deduped grants would
                # leak) — take the grant, skip the push, return it clean
                keep = True
                return None
            if info is not None:
                if info["canceled"]:
                    keep = True  # lease unused; return it to the pool clean
                    raise exc.TaskCancelledError(
                        f"task {spec.function.repr_name} was cancelled")
                info["worker_address"] = grant["worker_address"]
            if grant.get("chip_ids"):
                spec.chip_ids = grant["chip_ids"]
            gnode_id = grant.get("node_id")
            gworker = grant.get("worker_id")
            if publish_state is not None:
                publish_state[f"{role}_node"] = (
                    gnode_id.hex() if gnode_id else "")
                publish_state[f"{role}_addr"] = grant["worker_address"]
                publish_state[f"{role}_push_t"] = time.monotonic()
            self._record_transition(
                spec.task_id, "SUBMITTED_TO_WORKER",
                node_id=gnode_id.hex() if gnode_id else "",
                worker_id=gworker.hex() if gworker else "")
            client = await self._client_for(grant["worker_address"])
            t_push = time.monotonic()
            # the reply arrives when the task finishes — unbounded by
            # design (tasks may run for hours); the stall sentinel and
            # hedging bound the wait instead of a wire timeout
            reply = await client.call(  # graftlint: ignore[rpc-timeout]
                "push_task", cloudpickle.dumps(spec))
            if publish_state is not None:
                if publish_state["published"]:
                    keep = True  # loser replied after the winner: discard
                    return None
                publish_state["published"] = True
                publish_state["publishes"] += 1
                if publish_state["publishes"] > 1:  # defensive: must stay 0
                    _hedge_counter("task_hedge_duplicate_publishes").inc()
            gnode = grant.get("node_id")
            errored = self._handle_task_reply(
                spec, reply, node_id=gnode.hex() if gnode else "")
            if self.cfg.task_speculation_enabled and not errored:
                fn = spec.function.repr_name
                dur = time.monotonic() - t_push
                prev = self._hedge_ema.get(fn)
                self._hedge_ema[fn] = (dur if prev is None
                                       else 0.8 * prev + 0.2 * dur)
            keep = True
            return errored
        finally:
            await self._release_lease(pool, grant, spec, reusable=keep)

    async def _acquire_lease(self, pool: _LeasePool, spec: TaskSpec,
                             avoid_node: Optional[str] = None) -> dict:
        while True:
            if pool.idle:
                if avoid_node is None:
                    return pool.idle.pop()
                # hedge attempts must land off the primary's node: take the
                # first idle grant elsewhere, else fall through to a fresh
                # lease request carrying avoid_nodes
                for i, g in enumerate(pool.idle):
                    gnode = g.get("node_id")
                    if (gnode.hex() if gnode else "") != avoid_node:
                        return pool.idle.pop(i)
            if pool.in_flight < self.cfg.max_pending_lease_requests_per_scheduling_class:
                pool.in_flight += 1
                try:
                    return await self._request_lease(spec, avoid_node=avoid_node)
                finally:
                    pool.in_flight -= 1
                    # the freed request slot must wake a queued submission:
                    # an actor-creation grant is pinned for life and never
                    # passes through _release_lease, so without this wake
                    # the 11th+ queued creation in a scheduling class waits
                    # forever (envelope: 1k actors of one class)
                    pool.wake_one()
            # saturated: wait for a slot, then retry the whole acquisition
            fut = asyncio.get_event_loop().create_future()
            pool.waiters.append(fut)
            await fut

    async def _request_lease(self, spec: TaskSpec,
                             avoid_node: Optional[str] = None) -> dict:
        import uuid

        payload = {
            "resources": spec.resources.to_dict(),
            "strategy": spec.scheduling_strategy,
            "owner_address": self.address,
            "actor_id": spec.actor_id if spec.actor_creation else None,
            "task_id": spec.task_id,
            # lane leases are preemptible-when-idle (reclaim_lease push)
            "lane": spec.function.repr_name == "__lane__",
            # stable across retries: the raylet dedups grants by this id, so
            # a lost reply cannot leak a second worker lease
            "request_id": uuid.uuid4().hex,
        }
        if avoid_node:
            # hedge placement: the serving raylet excludes these nodes
            # when picking (spilling elsewhere if the local node is one)
            payload["avoid_nodes"] = [avoid_node]
        info = self._inflight.get(spec.task_id)
        strategy = spec.scheduling_strategy
        pg_strategy = (isinstance(strategy, PlacementGroupSchedulingStrategy)
                       and strategy.placement_group_id is not None)
        # locality-aware leasing (DEFAULT strategy only — explicit
        # strategies encode the user's placement intent): start the lease
        # chain at the node holding the task's argument bytes; its raylet
        # still applies the hybrid policy and may spill back out
        locality_raylet = None
        from .task_spec import DefaultSchedulingStrategy

        if (strategy is None
                or isinstance(strategy, DefaultSchedulingStrategy)) and spec.args:
            target = self._locality_node(spec)
            if target is not None and target != self.node_id.hex():
                addr = await self._node_raylet_address(target)
                if addr:
                    try:
                        locality_raylet = await self._raylet_client_for(addr)
                    except Exception:
                        locality_raylet = None
        for pg_attempt in range(8):
            raylet = locality_raylet or self.raylet
            if pg_strategy:
                address = await self._pg_bundle_address(strategy)
                raylet = await self._raylet_client_for(address)
            # a fresh attempt gets a fresh spillback budget — no_spill
            # sticking from a previous attempt's chain cap would pin the
            # lease to a saturated raylet forever
            payload.pop("no_spill", None)
            try:
                for hop in range(16):  # bounded spillback chain
                    if info is not None:
                        # remembered so cancel() can reach the raylet
                        # currently queueing this lease request
                        info["lease_raylet"] = raylet
                    if hop == 15:
                        # mutually-stale availability views can bounce a
                        # lease between saturated raylets; pin it to the
                        # current raylet's queue instead of erroring (it
                        # waits exactly as it would have pre-spillback)
                        payload["no_spill"] = True
                    reply = await self._lease_call(raylet, payload)
                    if reply.get("granted"):
                        reply["_raylet"] = raylet
                        return reply
                    node_id, address = reply["retry_at"]
                    raylet = await self._raylet_client_for(address)
                raise exc.RayTpuError("lease spillback chain too long")
            except (ValueError, ConnectionLost):
                # the bundle moved (node died, PG rescheduling) between the
                # directory lookup and the lease request — re-resolve
                if not pg_strategy:
                    if locality_raylet is not None:
                        # the locality hint pointed at a dead/stale node:
                        # degrade to the local raylet, don't fail the task
                        locality_raylet = None
                        continue
                    raise
                self._pg_cache.pop(strategy.placement_group_id, None)
                await asyncio.sleep(0.05 * (pg_attempt + 1))
        raise exc.RayTpuError(
            f"could not lease into placement group "
            f"{strategy.placement_group_id} (bundle unavailable)")

    async def _lease_call(self, raylet: RpcClient, payload: dict):
        """One lease RPC. With `lease_rpc_timeout_s` set (chaos tests,
        unreliable transports), lost frames time out and retry; the
        request_id makes retries idempotent at the raylet."""
        per_try = self.cfg.lease_rpc_timeout_s
        if per_try <= 0:
            return await raylet.call("request_worker_lease", payload)
        last: Optional[BaseException] = None
        for _ in range(10):
            try:
                return await raylet.call("request_worker_lease", payload,
                                         timeout=per_try)
            except asyncio.TimeoutError as e:
                last = e
                # a queued lease legitimately takes as long as the cluster
                # is busy — escalate the per-try window so retries (cheap,
                # deduped) only fire fast when loss is likely
                per_try = min(per_try * 2, 60.0)
        raise exc.RayTpuError(
            f"lease request timed out after retries: {last}")

    async def _pg_bundle_address(self, strategy) -> str:
        """Resolve the raylet address of the bundle the lease targets,
        blocking until the PG is reserved (this is what makes `pg.ready()` —
        a trivial task scheduled into the PG — resolve exactly when the
        reservation lands, matching the reference's
        bundle_reservation_check_func trick)."""
        nodes = self._pg_cache.get(strategy.placement_group_id)
        if nodes is None:
            reply = await self.gcs.call("wait_placement_group_ready", {
                "pg_id": strategy.placement_group_id})
            if reply["status"] != "ready":
                raise exc.RayTpuError(
                    f"placement group {strategy.placement_group_id} was removed")
            nodes = reply["bundle_nodes"]
            # cached so steady-state submissions skip the GCS hop; the lease
            # retry path invalidates on ValueError/ConnectionLost
            self._pg_cache[strategy.placement_group_id] = nodes
        index = strategy.placement_group_bundle_index
        if index >= 0:
            if index >= len(nodes):
                raise ValueError(
                    f"bundle index {index} out of range ({len(nodes)} bundles)")
            return nodes[index][1]
        self._pg_rr += 1
        return nodes[self._pg_rr % len(nodes)][1]

    async def _release_lease(self, pool: _LeasePool, grant: dict, spec: TaskSpec,
                             reusable: bool):
        if not spec.actor_creation:
            if reusable and pool.waiters:
                pool.idle.append(grant)  # hand the leased worker to the backlog
            else:
                raylet = grant.get("_raylet", self.raylet)
                try:
                    await raylet.call("return_worker", {
                        "lease_id": grant["lease_id"],
                        "disconnect_worker": not reusable,
                    })
                except Exception:
                    pass
        # always wake one waiter — even on the failure path, so queued
        # submissions retry instead of stranding
        pool.wake_one()

    _raylet_clients: Dict[str, RpcClient]

    async def _raylet_client_for(self, address: str) -> RpcClient:
        if not hasattr(self, "_raylet_clients_map"):
            self._raylet_clients_map = {}
        client = self._raylet_clients_map.get(address)
        if client is None or client.closed:
            client = RpcClient(address)
            await client.connect()
            self._raylet_clients_map[address] = client
        return client

    async def _client_for(self, address: str) -> RpcClient:
        """One connection per peer. The connect task is cached synchronously so
        concurrent callers share a single connection — per-caller actor task
        ordering relies on all pushes riding one ordered stream."""
        task = self._worker_clients.get(address)
        if task is not None:
            client = await asyncio.shield(task)
            if not client.closed:
                return client
            self._worker_clients.pop(address, None)

        async def _make():
            client = RpcClient(address)
            # streaming tasks report items as PUSH frames on this connection
            client.on_push("generator_item", self._on_generator_item)
            # target workers are already registered (their server is up), so a
            # dead socket means death, not startup: fail fast so in-flight
            # actor calls surface ActorDiedError promptly instead of burning
            # the whole startup window re-dialing a corpse
            await client.connect(timeout=self.cfg.worker_dial_timeout_s)
            return client

        task = asyncio.ensure_future(_make())
        self._worker_clients[address] = task
        try:
            return await asyncio.shield(task)
        except BaseException:
            if self._worker_clients.get(address) is task:
                self._worker_clients.pop(address, None)
            raise

    def _handle_task_reply(self, spec: TaskSpec, reply: dict,
                           node_id: str = "") -> bool:
        """reply: {results: [(oid, data|None)], error: bytes|None,
        sealed?: [(oid, size)]}. Returns True when the task raised (its
        returns hold the error)."""
        if reply.get("error") is not None:
            for oid in spec.return_ids():
                self.memory_store.put(oid, reply["error"])
            return True
        for oid, data in reply["results"]:
            if data is not None:
                self.memory_store.put(oid, data)
            # else: large result sealed in plasma by the executor
        if node_id:
            for oid, size in reply.get("sealed", ()):
                self._note_locality(oid, node_id, size)
        return False

    # ------------------------------------------------ locality-aware leasing
    _LOCALITY_CAP = 65536  # hint entries kept (FIFO)

    def _note_locality(self, oid: ObjectID, node_hex: str, size: int) -> None:
        loc = self._obj_locality
        loc[oid] = (node_hex, size)
        loc.move_to_end(oid)
        while len(loc) > self._LOCALITY_CAP:
            loc.popitem(last=False)

    def _locality_node(self, spec: TaskSpec) -> Optional[str]:
        """Node holding the most known dependency bytes, when that beats
        the threshold (ref: LocalityAwareLeasePolicy::GetBestNodeForTask)."""
        if self.cfg.scheduler_locality_min_bytes <= 0:
            return None
        by_node: Dict[str, int] = {}
        for arg in spec.args:
            if arg.object_id is None:
                continue
            hint = self._obj_locality.get(arg.object_id)
            if hint is not None:
                by_node[hint[0]] = by_node.get(hint[0], 0) + hint[1]
        if not by_node:
            return None
        best = max(by_node, key=by_node.get)
        if by_node[best] < self.cfg.scheduler_locality_min_bytes:
            return None
        return best

    async def _node_raylet_address(self, node_hex: str) -> Optional[str]:
        """node_id -> raylet address, via a TTL-cached GCS node listing
        (locality leases are for big-data tasks; one listing per 10 s is
        noise next to the transfers it avoids)."""
        now = time.monotonic()
        # staleness alone gates the refresh: a hint pointing at a dead
        # node must NOT turn every submission into a GCS listing — a
        # fresh-cache miss just skips the locality lease this time
        if now - self._node_addr_ts > 10.0:
            try:
                infos = await self.gcs.call("get_all_nodes", {})
            except Exception:
                return None
            self._node_addr_cache = {
                i.node_id.hex(): i.address for i in infos if i.alive}
            self._node_addr_ts = now
        return self._node_addr_cache.get(node_hex)

    # ------------------------------------------------- streaming generators
    def _on_generator_item(self, payload):
        """PUSH from the executing worker: one yielded object, or the end
        marker (ref: _raylet.pyx streaming_generator_returns). Runs on the
        io loop inside the client recv loop."""
        state = self._streams.get(payload["task_id"])
        if state is None:
            return
        if payload.get("worker_address"):
            state.worker_address = payload["worker_address"]
        if payload.get("done"):
            state.total = payload.get("total", 0)
            state.queue.put_nowait(_STREAM_DONE)
            return
        oid = payload["object_id"]
        data = payload.get("data")
        if data is not None:
            self.memory_store.put(oid, data)
        self._owned_in_plasma.add(oid)
        state.received += 1
        state.queue.put_nowait(ObjectRef(oid, self.address))

    def next_stream_item(self, task_id: TaskID,
                         timeout: Optional[float]) -> Optional[ObjectRef]:
        """Block for the next yielded ObjectRef; None = stream exhausted."""
        return self.io.run(self._next_stream_item(task_id), timeout)

    async def _next_stream_item(self, task_id: TaskID) -> Optional[ObjectRef]:
        state = self._streams.get(task_id)
        if state is None:
            return None
        item = await state.queue.get()
        if item is _STREAM_DONE:
            self._streams.pop(task_id, None)
            return None
        state.consumed += 1
        if state.worker_address:
            background(self._send_stream_ack(task_id, state))
        return item

    async def _send_stream_ack(self, task_id: TaskID, state: _StreamState):
        """Consumption ack driving producer backpressure (the
        generator_waiter.h role)."""
        try:
            client = await self._client_for(state.worker_address)
            await client.call("generator_ack", {
                "task_id": task_id, "consumed": state.consumed})
        except Exception:
            pass  # producer gone (stream finished/worker died) — no ack needed

    def stream_completed(self, task_id: TaskID) -> bool:
        state = self._streams.get(task_id)
        return state is None or (state.total is not None
                                 and state.consumed >= state.total)

    def release_stream(self, task_id: TaskID) -> None:
        self._streams.pop(task_id, None)

    # ------------------------------------------------------------ cancel
    def cancel(self, ref_or_gen, force: bool = False) -> None:
        """Cancel an in-flight normal task (ref: core_worker.cc CancelTask,
        _raylet.pyx cancel paths). Queued tasks are dropped before dispatch;
        running tasks get TaskCancelledError raised in their executing
        thread; force kills the worker process."""
        if isinstance(ref_or_gen, ObjectRefGenerator):
            task_id = ref_or_gen.task_id
        else:
            task_id = ref_or_gen.id().task_id()
        self.io.run(self._cancel(task_id, force))

    async def _cancel(self, task_id: TaskID, force: bool):
        info = self._inflight.get(task_id)
        if info is None:
            return  # already finished (or not a task this worker submitted)
        info["canceled"] = True
        address = info.get("worker_address")
        if address:
            try:
                client = await self._client_for(address)
                await client.call("cancel_task", {
                    "task_id": task_id, "force": force}, timeout=5)
            except Exception:
                pass  # worker already gone — the retry loop sees `canceled`
            # lane tasks dispatched into a ring may sit behind long
            # tasks on the lane's serial worker: finalize promptly
            # owner-side (the worker's eventual skip-reply is dropped)
            if self._lane_pool is not None:
                self._lane_pool.cancel_pending(task_id)
        else:
            # queued on the fast-lane feeder: fail it immediately (a
            # dispatch-time check alone could be a full task-runtime
            # away when the lane window is occupied)
            if self._lane_pool is not None and \
                    self._lane_pool.cancel_queued(task_id):
                return
            # no worker yet: the lease request may be queued at a raylet
            # behind resources that never free — fail it there so the submit
            # coroutine wakes up (ref: node_manager CancelWorkerLease)
            raylet = info.get("lease_raylet") or self.raylet
            try:
                await raylet.call("cancel_lease_request",
                                  {"task_id": task_id}, timeout=5)
            except Exception:
                pass
            # fast-lane window: the task may still DISPATCH right after
            # this cancel (feeder re-checks the flag, but a ring push
            # already in flight sets worker_address moments later).
            # Chase it: deliver the cancel once an address appears.
            self.io.spawn(self._chase_cancel(task_id, force))

    async def _chase_cancel(self, task_id: TaskID, force: bool):
        for _ in range(50):
            await asyncio.sleep(0.1)
            info = self._inflight.get(task_id)
            if info is None:
                return  # finished or errored meanwhile
            address = info.get("worker_address")
            if address:
                try:
                    client = await self._client_for(address)
                    await client.call("cancel_task", {
                        "task_id": task_id, "force": force}, timeout=5)
                except Exception:
                    pass
                if self._lane_pool is not None:
                    self._lane_pool.cancel_pending(task_id)
                return

    # ------------------------------------------------------------- actors
    def submit_actor_creation(self, cls: Any, args: tuple, kwargs: dict, opts: dict) -> ActorID:
        # all option validation BEFORE any state mutation/arg pinning
        strategy = self._resolve_strategy(opts)
        detached = opts.get("lifetime") == "detached"
        if detached and not opts.get("name"):
            raise ValueError("detached actors must be named (lookup is the "
                             "only way to reach them after the driver exits)")
        actor_id = ActorID.of(self.job_id)
        descriptor = self.export_function(cls)
        packed, deps = self._pack_args(args, kwargs)
        spec = TaskSpec(
            task_id=TaskID.for_actor_task(actor_id),
            job_id=self.job_id,
            function=descriptor,
            args=packed,
            num_returns=0,
            resources=self._build_resources(opts),
            scheduling_strategy=strategy,
            actor_id=actor_id,
            actor_creation=True,
            actor_max_restarts=opts.get("max_restarts", self.cfg.actor_max_restarts_default),
            # 0 = unset: sync actors default to 1 thread, async actors to
            # 1000 slots; an EXPLICIT max_concurrency=1 stays serialized
            actor_max_concurrency=opts.get("max_concurrency") or 0,
            actor_name=opts.get("name") or "",
            owner_address=self.address,
            runtime_env=self._prepare_runtime_env(
                opts, allow_container=False),
        )
        state = _ActorState(actor_id=actor_id)
        state.creation_spec = spec
        state.owned = True
        self._actors[actor_id] = state
        register_payload = {
            "actor_id": actor_id,
            "name": spec.actor_name,
            "namespace": opts.get("namespace", ""),
            "detached": detached,
            "owner_is_driver": self.mode == "driver",
            "class_name": spec.function.repr_name,
            "max_restarts": spec.actor_max_restarts,
            "creation_spec": cloudpickle.dumps(spec),
            # register + keyed lifecycle subscription in ONE GCS hop
            # (the subscription is installed server-side before the
            # registered state publishes, so no transition is missed)
            "subscribe": True,
        }
        # restartable actors keep creation args pinned for their lifetime so
        # the creation spec can be resubmitted
        pinned_deps = [] if spec.actor_max_restarts > 0 else deps
        if spec.actor_name:
            # named: registration stays synchronous so a duplicate-name
            # ValueError surfaces at .remote() itself
            self.io.run(self.gcs.call("register_actor", register_payload))
            self._subscribed_channels.add("actor:" + actor_id.hex())
            self.io.spawn(self._submit_actor_creation(spec, pinned_deps))
        else:
            # unnamed: the whole register->lease->push chain runs async,
            # so creations PIPELINE — .remote() costs no GCS round trip
            # (the r4 envelope measured 90-183 ms/actor, nearly all of
            # it these two blocking hops queued behind a busy GCS; ref
            # gcs_actor_manager.cc:394 RegisterActor is async there too)
            self.io.spawn(self._register_and_create(
                spec, register_payload, pinned_deps))
        return actor_id

    async def _register_and_create(self, spec: TaskSpec, payload: dict,
                                   deps: List[ObjectID]):
        try:
            await self.gcs.call("register_actor", payload)
        except asyncio.CancelledError:
            raise  # loop teardown — not a registration verdict
        except Exception as e:
            state = self._actors.get(spec.actor_id)
            if state is not None:
                state.state = "DEAD"
                state.death_cause = f"actor registration failed: {e!r}"
                for fut in state.waiters:
                    if not fut.done():
                        fut.set_result("DEAD")
                state.waiters.clear()
            return
        self._subscribed_channels.add("actor:" + spec.actor_id.hex())
        await self._submit_actor_creation(spec, deps)

    async def _submit_actor_creation(self, spec: TaskSpec, deps: List[ObjectID]):
        try:
            sched_class = spec.scheduling_class()
            pool = self._lease_pools.setdefault(sched_class, _LeasePool())
            grant = await self._acquire_lease(pool, spec)
            if grant.get("chip_ids"):
                # the actor owns its lease's chips for life; the worker
                # exports them before __init__ runs
                spec.chip_ids = grant["chip_ids"]
            client = await self._client_for(grant["worker_address"])
            reply = await client.call("push_task", cloudpickle.dumps(spec), timeout=None)
            if reply.get("error") is not None:
                try:
                    (err, tb), _ = ser.deserialize(reply["error"])
                    cause = f"creation task failed: {type(err).__name__}: {err}"
                except Exception:
                    cause = "creation task failed"
                await self.gcs.call("actor_failed", {
                    "actor_id": spec.actor_id, "cause": cause,
                })
                state = self._actors.get(spec.actor_id)
                if state is not None:
                    state.death_cause = cause
        except BaseException as e:  # noqa: BLE001
            try:
                await self.gcs.call("actor_failed", {
                    "actor_id": spec.actor_id,
                    "cause": f"creation failed: {type(e).__name__}: {e}",
                })
            except Exception:
                pass
        finally:
            for oid in deps:
                self._unpin_task_dep(oid)

    def _on_actor_update(self, payload):
        info = payload["actor"]
        state = self._actors.get(info.actor_id)
        if state is None:
            state = self._actors[info.actor_id] = _ActorState(actor_id=info.actor_id)
        state.state = info.state
        state.address = info.address
        state.death_cause = info.death_cause
        if info.state in ("DEAD", "RESTARTING"):
            # tear down the fast lane: buffered calls flush through the
            # asyncio path, which owns death/restart semantics
            lane = self._actor_lanes.pop(info.actor_id, None)
            if lane is not None:
                lane.close()
        if info.state == "DEAD":
            self._drop_actor_sub(info.actor_id)
        if info.state in ("ALIVE", "DEAD"):
            state.restart_in_flight = False
            for fut in state.waiters:
                if not fut.done():
                    fut.set_result(info.state)
            state.waiters.clear()
        elif (info.state == "RESTARTING" and state.owned
              and state.creation_spec is not None and not state.restart_in_flight):
            # the owner drives restarts: resubmit the creation task on a fresh
            # lease (ref: gcs_actor_manager.cc:858 RestartActor — here the
            # owner, not the GCS, re-runs the creation path)
            state.restart_in_flight = True
            spec = state.creation_spec
            spec.task_id = TaskID.for_actor_task(info.actor_id)
            self.io.spawn(self._submit_actor_creation(spec, []))

    async def _ensure_actor_sub(self, actor_id: ActorID) -> None:
        """Per-actor keyed subscription (gcs.py _publish_actor).
        Concurrent callers share one in-flight subscribe task, so a
        failure is seen by ALL of them (a flag-only guard would let the
        second caller proceed unsubscribed and stall out its alive-wait
        when the first caller's RPC failed)."""
        channel = "actor:" + actor_id.hex()
        if channel in self._subscribed_channels:
            return
        task = self._actor_sub_tasks.get(channel)
        if task is None:
            async def _sub():
                await self.gcs.call("subscribe", {"channels": [channel]})
                self._subscribed_channels.add(channel)

            task = self._actor_sub_tasks[channel] = \
                asyncio.ensure_future(_sub())
            task.add_done_callback(
                lambda _: self._actor_sub_tasks.pop(channel, None))
        await asyncio.shield(task)

    def _drop_actor_sub(self, actor_id: ActorID) -> None:
        """DEAD is terminal: release the keyed subscription on both
        sides (the GCS pops its index when it PUBLISHES the death, but a
        borrower that subscribed after that publish re-created it)."""
        channel = "actor:" + actor_id.hex()
        if channel in self._subscribed_channels:
            self._subscribed_channels.discard(channel)
            self.io.spawn(self.gcs.call(
                "unsubscribe", {"channels": [channel]}))

    async def _wait_actor_alive(self, actor_id: ActorID, timeout: float = 120.0) -> _ActorState:
        # subscribe-then-read: the authoritative get_actor below runs
        # AFTER the subscription is live, so no transition is missed
        await self._ensure_actor_sub(actor_id)
        state = self._actors.get(actor_id)
        if state is None:
            info = await self.gcs.call("get_actor", {"actor_id": actor_id})
            state = self._actors[actor_id] = _ActorState(actor_id=actor_id)
            if info is not None:
                state.state, state.address = info.state, info.address
                state.death_cause = info.death_cause
        waited = 0.0
        while state.state != "ALIVE":
            if state.state == "DEAD":
                # covers the borrow-after-death path, where no DEAD
                # update will ever arrive to trigger the drop
                self._drop_actor_sub(actor_id)
                raise exc.ActorDiedError(actor_id, state.death_cause)
            fut = asyncio.get_event_loop().create_future()
            state.waiters.append(fut)
            try:
                await asyncio.wait_for(fut, timeout)
            except asyncio.TimeoutError:
                # a constructor may run longer than ``timeout`` (a serve
                # replica that compiles its programs before it is ready,
                # with an empty compile cache): while the GCS says a
                # worker runs it the call waits behind it, as long as the
                # serve controller's health check does (``gcs.py
                # CONSTRUCTOR_TIMEOUT_S``); an actor no worker was leased
                # for, or any other state that stays silent, is an actor
                # nobody will bring up, and the timeout is raised
                waited += timeout
                if fut in state.waiters:
                    state.waiters.remove(fut)
                info = await self.gcs.call(
                    "get_actor", {"actor_id": actor_id}, timeout=30)
                if info is not None:
                    state.state, state.address = info.state, info.address
                    state.death_cause = info.death_cause
                constructing = (
                    info is not None and info.constructor_running
                    and info.state in gcs_states.CONSTRUCTING
                    and waited < gcs_states.CONSTRUCTOR_TIMEOUT_S)
                if state.state not in ("ALIVE", "DEAD") and not constructing:
                    raise
        return state

    def submit_actor_task(self, actor_id: ActorID, method_name: str, args: tuple,
                          kwargs: dict, opts: dict) -> List[ObjectRef]:
        packed, deps = self._pack_args(args, kwargs)
        num_returns = opts.get("num_returns", 1)
        spec = TaskSpec(
            task_id=TaskID.for_actor_task(actor_id),
            job_id=self.job_id,
            function=FunctionDescriptor(blob_id="", repr_name=method_name,
                                        method_name=method_name),
            args=packed,
            num_returns=num_returns,
            actor_id=actor_id,
            max_retries=opts.get("max_task_retries", 0),
            owner_address=self.address,
        )
        from ..util.tracing import inject_trace_ctx

        inject_trace_ctx(spec)
        return_ids = spec.return_ids()
        refs = [ObjectRef(oid, self.address) for oid in return_ids]
        # registered so borrower fetch_object sees in-flight returns as
        # pending rather than gone
        self._inflight.setdefault(spec.task_id,
                                  {"canceled": False, "worker_address": None})
        if self._actor_lane_submit(spec, deps, return_ids):
            return refs
        self._actor_lane_blocked.add(actor_id)
        self.io.spawn(self._submit_actor_task(spec, deps))
        return refs

    def _actor_lane_submit(self, spec: TaskSpec, deps: List[ObjectID],
                           return_ids: List[ObjectID]) -> bool:
        """Route the call through the actor's fast lane. Once a lane
        exists ALL calls from this owner must ride it (ring FIFO is the
        ordering guarantee). A lane may only OPEN on the first-ever call
        to the actor from this owner — if any call already took the
        asyncio path, opening a lane later could reorder around the
        in-flight stream, so the actor is lane-blocked for good."""
        if self._lane_pool is None:  # native plane disabled
            return False
        known = self._actors.get(spec.actor_id)
        if known is not None and known.state == "DEAD":
            # the asyncio path raises ActorDiedError with the cause;
            # the ring would just see a dead socket
            return False
        lane = self._actor_lanes.get(spec.actor_id)
        if lane is None:
            if deps or spec.actor_id in self._actor_lane_blocked:
                return False
            if len(self._actor_lanes) >= self.cfg.actor_lane_max:
                # each lane costs two shm rings + a flusher/reply thread
                # pair; at envelope actor counts (1k+) that is thousands
                # of threads — beyond the cap, calls stay on the asyncio
                # path (the lane is a hot-actor latency optimization,
                # not a correctness feature)
                return False
            from .fastlane import ActorLane

            # double-checked under the create lock: ActorLane() is
            # side-effecting (attach coroutine + shm rings keyed by
            # (actor, worker, pid)), so a lost setdefault race would
            # leave an orphan lane attached to the SAME rings as the
            # winner — its reply thread then steals replies it has no
            # pending entry for, and the caller's get() times out
            with self._actor_lane_create_lock:
                lane = self._actor_lanes.get(spec.actor_id)
                if lane is None:
                    lane = self._actor_lanes[spec.actor_id] = ActorLane(
                        self, spec.actor_id)
        event = threading.Event()
        for oid in return_ids:
            self._lane_events[oid] = event
        if lane.submit(spec, event):
            return True
        for oid in return_ids:
            self._lane_events.pop(oid, None)
        return False

    async def _submit_actor_task(self, spec: TaskSpec, deps: List[ObjectID]):
        try:
            state = await self._wait_actor_alive(spec.actor_id)
            spec.seq_no = state.seq_no
            state.seq_no += 1
            retries_left = spec.max_retries  # actor default: in-flight tasks
            while True:                      # fail on death (ref: max_task_retries)
                try:
                    client = await self._client_for(state.address)
                    reply = await client.call("push_task", cloudpickle.dumps(spec), timeout=None)
                    self._handle_task_reply(spec, reply)
                    return
                except ConnectionLost:
                    prev_address = state.address
                    state.state = "RESTARTING" if state.state == "ALIVE" else state.state
                    if retries_left <= 0:
                        self._store_error(spec, exc.ActorDiedError(
                            spec.actor_id,
                            "the actor died while this call was in flight "
                            "(set max_task_retries to retry on restart)"))
                        return
                    retries_left -= 1
                    try:
                        state = await self._wait_actor_alive(spec.actor_id)
                    except exc.ActorDiedError as e:
                        self._store_error(spec, e)
                        return
                    if state.address == prev_address:
                        self._store_error(spec, exc.ActorDiedError(spec.actor_id, "unreachable"))
                        return
        except BaseException as e:  # noqa: BLE001
            self._store_error(spec, e)
        finally:
            self._inflight.pop(spec.task_id, None)
            for oid in deps:
                self._unpin_task_dep(oid)

    # ---------------------------------------------------- placement groups
    def create_placement_group(self, bundles: List[Dict[str, float]],
                               strategy: str, name: str = "") -> "PlacementGroupID":
        from .ids import PlacementGroupID

        pg_id = PlacementGroupID.of(self.job_id)
        self.io.run(self.gcs.call("create_placement_group", {
            "pg_id": pg_id, "bundles": bundles, "strategy": strategy,
            "name": name,
        }))
        return pg_id

    def remove_placement_group(self, pg_id) -> None:
        self.io.run(self.gcs.call("remove_placement_group", {"pg_id": pg_id}))

    def wait_placement_group(self, pg_id, timeout: Optional[float]) -> bool:
        reply = self.io.run(
            self.gcs.call("wait_placement_group_ready",
                          {"pg_id": pg_id, "timeout": timeout}),
            timeout=None if timeout is None else timeout + 30)
        return reply["status"] == "ready"

    def get_placement_group_info(self, pg_id=None, name: str = "") -> Optional[dict]:
        payload = {"pg_id": pg_id} if pg_id is not None else {"name": name}
        return self.io.run(self.gcs.call("get_placement_group", payload))

    def list_placement_groups(self) -> List[dict]:
        return self.io.run(self.gcs.call("list_placement_groups", {}))

    def kill_actor(self, actor_id: ActorID, no_restart: bool = True):
        async def _kill():
            state = self._actors.get(actor_id)
            await self.gcs.call("kill_actor", {"actor_id": actor_id,
                                               "cause": "ray_tpu.kill"})
            if state is not None and state.address:
                try:
                    client = await self._client_for(state.address)
                    await client.call("kill_self", {}, timeout=2)
                except Exception:
                    pass
        if threading.current_thread() is self.io.thread:
            # kill() can be reached from a destructor GC runs on the io
            # loop thread itself (e.g. a dataset coordinator handle);
            # blocking there would deadlock the loop — fire and forget
            self.io.spawn(_kill())
        else:
            self.io.run(_kill())

    def get_named_actor(self, name: str, namespace: str = "") -> ActorID:
        info = self.io.run(self.gcs.call("get_actor", {"name": name, "namespace": namespace}))
        if info is None or info.state == "DEAD":
            raise ValueError(f"Failed to look up actor '{name}'")
        state = self._actors.setdefault(info.actor_id, _ActorState(actor_id=info.actor_id))
        state.state, state.address = info.state, info.address
        return info.actor_id
