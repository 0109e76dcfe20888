"""Worker process: registers with its raylet, executes pushed tasks.

TPU-native analog of the reference worker runtime (ref: src/ray/core_worker/
core_worker_process.cc:98 RunTaskExecutionLoop, transport/task_receiver.h,
actor_scheduling_queue.h; python/ray/_private/workers/default_worker.py).

Execution model: the process's RpcServer accepts `push_task` directly from
submitting core workers (no raylet hop on the hot path). Normal tasks run on a
small thread pool; an actor promotes the worker to a dedicated actor runtime —
a single ordered execution thread fed FIFO (per-caller order is preserved by
the connection stream), with `max_concurrency > 1` widening the pool.

Every return value is sealed into the shared object store (so any process can
resolve it via the raylet directory) and small values are additionally inlined
in the reply as the owner's fast path.
"""

from __future__ import annotations

import asyncio
import collections
import logging
import os
import queue
import sys
import threading
import time
from concurrent.futures import ThreadPoolExecutor
from typing import Any, List, Optional, Tuple

import cloudpickle

from .config import global_config
from . import failpoints
from . import locking
from .core_worker import CoreWorker
from .ids import JobID, NodeID, ObjectID, WorkerID
from .object_store import SharedObjectStore
from .rpc import RpcClient, RpcServer
from . import serialization as ser
from .task_spec import ArgKind, TaskSpec
from .. import exceptions as exc
from ..util import stacks


def _cheap_size_bound(value, limit: int, _depth: int = 2) -> bool:
    """Heuristic (not a proof): True when ``value`` looks small enough
    to serialize on the actor's event loop without stalling it. Arrays
    expose nbytes, strings/bytes their length; narrow containers are
    inspected two levels deep (so [big_array, big_array] offloads).
    Opaque custom objects pass — they serialize on the loop, matching
    the reference's async actors (whose returns also serialize on the
    loop thread that ran the task)."""
    nb = getattr(value, "nbytes", None)
    # int check matters: objects with dynamic __getattr__ (actor
    # handles) synthesize a non-numeric .nbytes
    if isinstance(nb, int):
        return nb <= limit
    if isinstance(value, (bytes, bytearray, str)):
        return len(value) <= limit
    if isinstance(value, (list, tuple, set, frozenset, dict)):
        if len(value) > 256:
            return False  # wide containers: size unknowable cheaply
        if _depth <= 0:
            return True
        items = value.values() if isinstance(value, dict) else value
        return all(_cheap_size_bound(v, limit, _depth - 1)
                   for v in items)
    return True


def _maybe_span(spec: TaskSpec):
    """Execution span when the spec carries a trace context (tracing
    enabled at the driver); a no-op context otherwise."""
    import contextlib

    ctx = getattr(spec, "trace_ctx", None)
    if ctx is None:
        return contextlib.nullcontext()
    from ..util.tracing import task_span

    return task_span(ctx, spec.function.repr_name)


def _resolve_actor_method(instance, name: str):
    """Bound method lookup with a fallback for the injected dynamic-call
    entry point: classes pickled BY REFERENCE re-import without the
    driver-side ActorClass injection, so the compiled-DAG loop method
    must resolve from ray_tpu.actor here."""
    try:
        return getattr(instance, name)
    except AttributeError:
        if name == "_rtpu_dyn_call":
            from ..actor import _rtpu_dyn_call

            return lambda *a, **k: _rtpu_dyn_call(instance, *a, **k)
        raise


class _GenBudget:
    """Producer-side backpressure (ref: generator_waiter.h): the generator
    thread blocks while produced - consumed >= threshold."""

    def __init__(self, threshold: int):
        self.threshold = threshold
        self.consumed = 0
        self._cond = locking.make_condition("_GenBudget._cond")

    def ack(self, consumed: int) -> None:
        with self._cond:
            self.consumed = max(self.consumed, consumed)
            self._cond.notify_all()

    def wait_for_budget(self, produced: int) -> None:
        if self.threshold <= 0:
            return
        with self._cond:
            while produced - self.consumed >= self.threshold:
                self._cond.wait(timeout=1.0)


class SealBatcher:
    """Coalesces seal notifications into one ``objects_sealed_batch``
    RPC per flush window. Per-return round trips to the raylet dominate
    trivial-task latency otherwise (ref: task_event_buffer.h applies the
    same batching idea to task events)."""

    def __init__(self, core: CoreWorker, raylet: RpcClient,
                 window_s: float = 0.002):
        self.core = core
        self.raylet = raylet
        self.window_s = window_s
        self._q: List[Tuple[ObjectID, int]] = []
        self._lock = locking.make_lock("SealBatcher._lock")
        self._event = threading.Event()
        self._thread = threading.Thread(target=self._loop, daemon=True,
                                        name="seal_batcher")
        self._thread.start()

    def add(self, oid: ObjectID, size: int) -> None:
        with self._lock:
            self._q.append((oid, size))
        self._event.set()

    def _loop(self) -> None:
        import time as _time

        while True:
            self._event.wait()
            _time.sleep(self.window_s)  # coalesce a burst
            with self._lock:
                batch, self._q = self._q, []
                self._event.clear()
            if not batch:
                continue
            try:
                self.core.io.run(self.raylet.call_retrying(
                    "objects_sealed_batch", {"objects": batch},
                    attempts=5, per_try_timeout=2.0))
            except Exception:
                # a lost seal notification would strand every consumer
                # of these objects in the directory: REQUEUE and keep
                # trying (the raylet being down this long usually means
                # the node is dying anyway — but never silently drop)
                with self._lock:
                    self._q = batch + self._q
                self._event.set()
                _time.sleep(1.0)


class TaskExecutor:
    def __init__(self, core: CoreWorker, raylet: RpcClient):
        self.core = core
        self.raylet = raylet
        self.seal_batcher: Optional[SealBatcher] = None
        # the worker's flight recorder (blackbox.py), if enabled — the
        # deliberate-exit paths close it so an ORDERED kill (force
        # cancel, kill_self) never masquerades as a crash bundle
        self.blackbox_rec = None
        self.pool = ThreadPoolExecutor(max_workers=4, thread_name_prefix="task_exec")
        self._applied_env: dict = {}  # runtime-env hash this worker adopted
        # actor runtime
        self.actor_instance: Any = None
        self.actor_id = None
        self.actor_async = False
        self._actor_loop_obj = None
        self._actor_sem = None
        self._actor_queue: "queue.Queue" = queue.Queue()
        self._actor_threads: List[threading.Thread] = []
        # cancellation: task_id -> executing thread (ref: _raylet.pyx
        # execute_task_with_cancellation_handler); requests arriving before
        # the task registers (still loading its function) are parked
        self._running: dict = {}
        self._cancel_requested: set = set()
        # tasks that already finished here, kept briefly so a late
        # cancel() — e.g. a hedge loser whose reply raced the winner's
        # cancel RPC — is a silent no-op instead of parking forever in
        # _cancel_requested (bounded: deque evicts, set membership-tests)
        self._recently_done: "collections.deque" = collections.deque(
            maxlen=1024)
        self._recently_done_set: set = set()
        # streaming: task_id -> producer budget
        self._gen_budgets: dict = {}
        # stall sentinel: task_id -> (thread ident, fn name, started at);
        # feeds dump_stacks (stack annotation) and stall_probe (the
        # raylet watchdog's RUNNING-age / per-class EMA inputs)
        self._running_since: dict = {}
        # (fn name, duration) of completions since the last stall_probe
        self._completed_durations: List[Tuple[str, float]] = []
        self._durations_lock = locking.make_lock("TaskExecutor._durations_lock")
        # profiling plane (util/stacks.py): an always-on ambient sampler
        # (profiling_sample_hz > 0) plus an on-demand burst sampler the
        # profile_start/profile_stop RPCs drive; task-thread samples are
        # rooted "task:<fn>" so the GCS can merge per scheduling class
        self._ambient_sampler: Optional[stacks.StackSampler] = None
        self._burst_sampler: Optional[stacks.StackSampler] = None
        self._hbm_last_report = 0.0

    def _register_running(self, task_id, fn_name: str = "") -> None:
        """Bind the executing thread; honor a cancel that raced startup."""
        self._running[task_id] = threading.current_thread()
        self._running_since[task_id] = (
            threading.get_ident(), fn_name, time.time())
        if task_id in self._cancel_requested:
            self._cancel_requested.discard(task_id)
            raise exc.TaskCancelledError("task cancelled before start")

    def _unregister_running(self, task_id) -> None:
        self._running.pop(task_id, None)
        if len(self._recently_done) == self._recently_done.maxlen:
            self._recently_done_set.discard(self._recently_done[0])
        self._recently_done.append(task_id)
        self._recently_done_set.add(task_id)
        entry = self._running_since.pop(task_id, None)
        if entry is not None:
            with self._durations_lock:
                self._completed_durations.append(
                    (entry[1], time.time() - entry[2]))
                # bound the backlog if no watchdog ever drains it
                if len(self._completed_durations) > 512:
                    del self._completed_durations[:256]

    # ------------------------------------------------------ stall sentinel
    def stall_probe(self) -> dict:
        """Cheap watchdog input: tasks currently RUNNING on this worker
        (with age) plus completed (fn, duration) samples drained since
        the last probe — the raylet's per-scheduling-class EMA feed."""
        now = time.time()
        with self._durations_lock:
            completed, self._completed_durations = \
                self._completed_durations, []
        running = [
            {"task_id": tid.hex(), "fn": fn, "age_s": now - t0}
            for tid, (_, fn, t0) in list(self._running_since.items())
        ]
        self._maybe_report_hbm()
        return {"pid": os.getpid(), "running": running,
                "completed": completed}

    def dump_stacks(self) -> dict:
        """sys._current_frames() snapshot, each thread annotated with the
        task it is executing (if any) and its time-in-state. The remote
        half of `cli.py stacks` and the watchdogs' hang forensics.
        Capture/annotation lives in util/stacks.py, shared with the
        sampling profiler (one format, one annotation path)."""
        now = time.time()
        return {
            "pid": os.getpid(),
            "worker_id": self.core.worker_id.hex(),
            "actor_id": self.actor_id.hex() if self.actor_id else None,
            "time": now,
            "threads": stacks.capture_threads(self._running_since, now=now),
        }

    # -------------------------------------------------- sampling profiler
    def _annotate_thread(self, ident: int) -> Optional[str]:
        """Root label for a sampled thread: the task it is executing (the
        sampler's per-scheduling-class merge handle), None otherwise."""
        for _tid, (tident, fn, _t0) in list(self._running_since.items()):
            if tident == ident:
                return f"task:{fn or '?'}"
        return None

    def start_ambient_sampler(self, hz: float) -> None:
        """Always-on low-rate mode (profiling_sample_hz knob)."""
        if hz <= 0 or self._ambient_sampler is not None:
            return
        self._ambient_sampler = stacks.StackSampler(
            hz, annotate=self._annotate_thread,
            max_depth=global_config().profiling_max_stack_depth,
            name="stack_sampler").start()

    def profile_start(self, hz: float) -> bool:
        """On-demand burst capture; a second start supersedes the first
        (the previous burst's thread is joined, its samples dropped)."""
        if self._burst_sampler is not None:
            self._burst_sampler.stop(timeout=1.0)
        self._burst_sampler = stacks.StackSampler(
            hz, annotate=self._annotate_thread,
            max_depth=global_config().profiling_max_stack_depth,
            name="stack_sampler_burst").start()
        return True

    def profile_stop(self) -> dict:
        """End the burst (or drain the ambient accumulation when no
        burst is running) and return the folded-stack snapshot."""
        burst, self._burst_sampler = self._burst_sampler, None
        if burst is not None:
            burst.stop(timeout=2.0)
            snap = burst.snapshot()
        elif self._ambient_sampler is not None:
            snap = self._ambient_sampler.snapshot(reset=True)
        else:
            snap = {"pid": os.getpid(), "hz": 0.0, "samples": 0,
                    "duration_s": 0.0, "wall": {}, "cpu": {}}
        snap["worker_id"] = self.core.worker_id.hex()
        snap["actor_id"] = self.actor_id.hex() if self.actor_id else None
        return snap

    def _maybe_report_hbm(self) -> None:
        """Rate-limited HBM gauge publication, piggybacked on the
        watchdog's stall_probe tick (no extra thread, no RPC). Inert
        until task code actually initializes jax in this process."""
        if "jax" not in sys.modules:
            return
        interval = global_config().hbm_gauge_interval_s
        if interval <= 0:
            return
        now = time.monotonic()
        if now - self._hbm_last_report < interval:
            return
        self._hbm_last_report = now
        try:
            from ..util import hbm

            hbm.publish_hbm_gauges(node=self.core.node_id.hex()[:12])
        except Exception:  # graftlint: ignore[swallow] — HBM gauges are
            pass           # best-effort; a backend hiccup can't kill
            # the worker main loop that publishes them

    # ---------------------------------------------------------- arg loading
    def _resolve_args(self, spec: TaskSpec) -> Tuple[list, dict]:
        args, kwargs = [], {}
        # gather deps first so we wait once; small objects come from
        # their owner (never sealed into plasma), the rest through the
        # raylet directory/pull path
        ref_args = [a for a in spec.args if a.kind == ArgKind.OBJECT_REF]
        missing = [a for a in ref_args
                   if not self.core.store.contains(a.object_id)
                   and not self.core.memory_store.contains(a.object_id)]
        if missing:
            # dep wait: release the lease's CPU for the duration, or a
            # gang of dep-blocked workers deadlocks the node (ref:
            # NotifyDirectCallTaskBlocked)
            self.core._notify_blocked()
        try:
            plasma_wait = []
            for a in missing:
                if a.owner and a.owner != self.core.address:
                    status = self.core.io.run(self.core._fetch_from_owner(
                        a.owner, a.object_id, None))
                    if status == "ok":
                        continue
                    # "gone"/"unreachable": the object may still be
                    # sealed in plasma on a third node — directory wait
                plasma_wait.append(a.object_id)
            if plasma_wait:
                self.core.io.run(self.core.raylet.call("wait_objects", {
                    "object_ids": plasma_wait,
                    "num_returns": len(plasma_wait),
                    "timeout": None,
                    "prio": 0,  # this worker is blocked on its task args
                }))
        finally:
            if missing:
                self.core._notify_unblocked()
        for arg in spec.args:
            if arg.kind == ArgKind.VALUE:
                kw, data = arg.value
                value, _ = ser.deserialize(data)
            else:
                kw = arg.value
                value = self.core._load_object(arg.object_id)
            if kw is None:
                args.append(value)
            else:
                kwargs[kw] = value
        return args, kwargs

    # -------------------------------------------------------- result sealing
    def _ok_reply(self, spec: TaskSpec, values: Any) -> dict:
        results, sealed = self._seal_results(spec, values)
        if not spec.is_actor_task():
            # actor calls don't flow through the task table (no SUBMITTED
            # record exists for them) — don't create orphan records
            self.core._record_transition(spec.task_id, "OUTPUT_SEALED")
        return {"results": results, "sealed": sealed, "error": None}

    def _seal_results(self, spec: TaskSpec, values: Any) -> tuple:
        small_limit = global_config().object_store_small_object_threshold
        if spec.num_returns == 0:
            return [], []
        if spec.num_returns == 1:
            values = (values,)
        elif not isinstance(values, tuple):
            values = tuple(values)
        results = []
        sealed = []
        for i, value in enumerate(values[: spec.num_returns]):
            oid = ObjectID.for_return(spec.task_id, i + 1)
            data = ser.serialize(value)
            if len(data) <= small_limit:
                # small returns ride the reply into the owner's memory
                # store and are served from there (fetch_object); no
                # plasma write, no directory entry (ref: the reference's
                # in-process store for inlined returns)
                results.append((oid, data))
            else:
                self.core.store.put(oid, data)
                self._notify_sealed(oid, len(data))
                results.append((oid, None))
                # rides the reply so the owner learns where (and how big)
                # its large returns are — locality-aware leasing input
                sealed.append((oid, len(data)))
        return results, sealed

    def _notify_sealed(self, oid: ObjectID, size: int) -> None:
        # idempotent + retried: a lost seal notification would strand every
        # consumer waiting on this object in the directory
        if self.seal_batcher is not None:
            self.seal_batcher.add(oid, size)
            return
        self.core.io.run(self.raylet.call_retrying(
            "object_sealed", {"object_id": oid, "size": size},
            attempts=5, per_try_timeout=2.0))

    def _seal_error(self, spec: TaskSpec, error: BaseException) -> bytes:
        data = ser.serialize_error(error)
        for oid in spec.return_ids():
            self.core.store.put(oid, data)
            self._notify_sealed(oid, len(data))
        return data

    # ------------------------------------------------------------ execution
    def _ensure_runtime_env(self, spec: TaskSpec) -> None:
        from .runtime_env import apply_runtime_env

        self._apply_chip_visibility(spec)
        apply_runtime_env(self.core, spec.runtime_env, self._applied_env)

    def _apply_chip_visibility(self, spec: TaskSpec) -> None:
        """The one rule for who gets the device plane, applied before
        user code runs: a lease that holds chips gets the TPU backend,
        confined to its chips (the ids come from the raylet's per-lease
        chip accounting, so two leases on one host see disjoint chips);
        a lease that holds none keeps the raylet's CPU pin
        (device_plane.py)."""
        from . import device_plane

        if spec.chip_ids is None:
            device_plane.release_chips()
        else:
            device_plane.claim_chips(spec.chip_ids)

    def execute_normal(self, spec: TaskSpec) -> dict:
        try:
            self._ensure_runtime_env(spec)
            func = self.core.load_function(spec.function.blob_id)
            self.core._record_transition(spec.task_id, "PENDING_ARGS_FETCH")
            args, kwargs = self._resolve_args(spec)
            self.core.set_task_context(spec.task_id)
            self._register_running(spec.task_id, spec.function.repr_name)
            self.core._record_transition(spec.task_id, "RUNNING")
            try:
                # inside the RUNNING window so injected straggle shows up
                # in stall_probe age and trips the raylet watchdog
                failpoints.fire("worker.task.run",
                                detail=os.environ.get("RAY_TPU_NODE_ID"))
                with _maybe_span(spec):
                    if spec.runtime_env and spec.runtime_env.get(
                            "container"):
                        from .runtime_env import run_task_in_container

                        values = run_task_in_container(
                            spec.runtime_env["container"], func, args,
                            kwargs,
                            env_vars=spec.runtime_env.get("env_vars"))
                    else:
                        values = func(*args, **kwargs)
            finally:
                self._unregister_running(spec.task_id)
                self.core.clear_task_context()
            return self._ok_reply(spec, values)
        except BaseException as e:  # noqa: BLE001
            return {"results": [], "error": self._seal_error(spec, e)}

    def cancel(self, task_id, force: bool) -> bool:
        """Interrupt a running task: TaskCancelledError is raised at the next
        bytecode boundary of its thread (force: the process exits). A task
        still in startup (function load / arg fetch) is marked so it raises
        the moment it registers."""
        if force:
            if self.blackbox_rec is not None:
                self.blackbox_rec.close(clean=True)
            threading.Timer(0.02, lambda: os._exit(1)).start()
            return True
        thread = self._running.get(task_id)
        if thread is None or not thread.is_alive():
            if task_id in self._recently_done_set:
                # already sealed (hedge loser, or cancel racing normal
                # completion): nothing to interrupt, nothing to park
                return True
            self._cancel_requested.add(task_id)
            return False
        import ctypes

        n = ctypes.pythonapi.PyThreadState_SetAsyncExc(
            ctypes.c_ulong(thread.ident),
            ctypes.py_object(exc.TaskCancelledError))
        return n == 1

    def execute_streaming(self, spec: TaskSpec, push) -> dict:
        """Run a generator task, sealing + reporting each item eagerly
        (ref: _raylet.pyx:1138-1225 streaming generator returns). ``push``
        delivers one ordered frame to the owner and blocks until written."""
        import inspect

        small_limit = global_config().object_store_small_object_threshold
        budget = self._gen_budgets[spec.task_id] = _GenBudget(
            spec.backpressure_items)
        index = 0

        def _emit(data: bytes) -> None:
            nonlocal index
            index += 1
            oid = ObjectID.for_return(spec.task_id, index)
            self.core.store.put(oid, data)
            self._notify_sealed(oid, len(data))
            push({"task_id": spec.task_id, "index": index, "object_id": oid,
                  "data": data if len(data) <= small_limit else None,
                  "done": False, "worker_address": self.core.address})

        try:
            try:
                self._ensure_runtime_env(spec)
                func = self.core.load_function(spec.function.blob_id)
                self.core._record_transition(spec.task_id,
                                             "PENDING_ARGS_FETCH")
                args, kwargs = self._resolve_args(spec)
                self.core.set_task_context(spec.task_id)
                self._register_running(spec.task_id,
                                       spec.function.repr_name)
                self.core._record_transition(spec.task_id, "RUNNING")
                try:
                    out = func(*args, **kwargs)
                    items = out if inspect.isgenerator(out) else iter([out])
                    for value in items:
                        _emit(ser.serialize(value))
                        budget.wait_for_budget(index)
                finally:
                    self._unregister_running(spec.task_id)
                    self.core.clear_task_context()
            except BaseException as e:  # noqa: BLE001 — errors ride the stream
                _emit(ser.serialize_error(e))
            push({"task_id": spec.task_id, "done": True, "total": index,
                  "worker_address": self.core.address})
            return {"results": [], "error": None}
        finally:
            self._gen_budgets.pop(spec.task_id, None)

    def execute_actor_creation(self, spec: TaskSpec) -> dict:
        try:
            import inspect

            self._ensure_runtime_env(spec)
            cls = self.core.load_function(spec.function.blob_id)
            if hasattr(cls, "__ray_tpu_actor_class__"):
                cls = cls.__ray_tpu_actor_class__
            args, kwargs = self._resolve_args(spec)
            self.actor_instance = cls(*args, **kwargs)
            self.actor_id = spec.actor_id
            # async actors: any coroutine method promotes the actor to an
            # asyncio runtime — methods interleave at await points, bounded
            # by max_concurrency (ref: _raylet.pyx async actor path /
            # core_worker fiber.h; reference default concurrency is 1000)
            self.actor_async = any(
                inspect.iscoroutinefunction(m)
                for _, m in inspect.getmembers(type(self.actor_instance),
                                               inspect.isfunction))
            if self.actor_async:
                concurrency = (spec.actor_max_concurrency
                               if spec.actor_max_concurrency > 0 else 1000)
                self._actor_loop_obj = asyncio.new_event_loop()
                self._actor_sem = None  # created on the actor loop
                self._actor_concurrency = concurrency

                def _loop_main():
                    asyncio.set_event_loop(self._actor_loop_obj)
                    self._actor_sem = asyncio.Semaphore(concurrency)
                    self._actor_loop_obj.run_forever()

                t = threading.Thread(target=_loop_main, daemon=True,
                                     name="actor_asyncio")
                t.start()
                self._actor_threads.append(t)
            else:
                n_threads = max(1, spec.actor_max_concurrency or 1)
                for i in range(n_threads):
                    t = threading.Thread(target=self._actor_loop, daemon=True,
                                         name=f"actor_exec_{i}")
                    t.start()
                    self._actor_threads.append(t)
            return {"results": [], "error": None}
        except BaseException as e:  # noqa: BLE001
            return {"results": [], "error": self._seal_error(spec, e)}

    async def execute_actor_task_async(self, spec: TaskSpec) -> dict:
        """One actor task on the actor's asyncio loop. Blocking work
        (plasma arg fetch, large-result sealing) goes to the thread pool
        so thousands of calls can park at await points — but the COMMON
        async call (small VALUE args, one small return) runs entirely on
        the loop: two run_in_executor hops per call were the async
        lane's throughput ceiling (~4.5k/s vs ~10.6k/s sync; each hop is
        a thread handoff both ways)."""
        loop = asyncio.get_event_loop()
        while self._actor_sem is None:  # loop thread still starting
            await asyncio.sleep(0.001)
        async with self._actor_sem:
            try:
                # run_coroutine_threadsafe gave this task its own Context,
                # so the binding is visible to this coroutine only
                self.core.set_async_task_context(spec.task_id)
                method = _resolve_actor_method(
                    self.actor_instance, spec.function.method_name)
                if all(a.kind == ArgKind.VALUE for a in spec.args):
                    # pure-value args: deserialization is loop-cheap
                    args, kwargs = self._resolve_args(spec)
                else:
                    args, kwargs = await loop.run_in_executor(
                        self.pool, self._resolve_args, spec)
                with _maybe_span(spec):
                    values = method(*args, **kwargs)
                    if asyncio.iscoroutine(values):
                        values = await values
                small = global_config().object_store_small_object_threshold
                if spec.num_returns == 1 and _cheap_size_bound(values, small):
                    data = ser.serialize(values)
                    if len(data) <= small:
                        oid = ObjectID.for_return(spec.task_id, 1)
                        return {"results": [(oid, data)], "sealed": [],
                                "error": None}
                    # the bound was optimistic (e.g. a dict that pickles
                    # big): only the plasma write leaves the loop
                    def _seal_large():
                        oid = ObjectID.for_return(spec.task_id, 1)
                        self.core.store.put(oid, data)
                        self._notify_sealed(oid, len(data))
                        return {"results": [(oid, None)],
                                "sealed": [(oid, len(data))], "error": None}
                    return await loop.run_in_executor(self.pool, _seal_large)
                return await loop.run_in_executor(
                    self.pool, lambda: self._ok_reply(spec, values))
            except BaseException as e:  # noqa: BLE001
                return {"results": [],
                        "error": await loop.run_in_executor(
                            self.pool, self._seal_error, spec, e)}

    def _actor_loop(self):
        while True:
            item = self._actor_queue.get()
            if item is None:
                return
            spec, reply_cb = item
            reply = self._execute_actor_task(spec)
            reply_cb(reply)

    def _execute_actor_task(self, spec: TaskSpec) -> dict:
        try:
            method = _resolve_actor_method(
                self.actor_instance, spec.function.method_name)
            args, kwargs = self._resolve_args(spec)
            self.core.set_task_context(spec.task_id)
            # stall-sentinel annotation only (not self._running — actor
            # cancellation semantics stay unchanged)
            self._running_since[spec.task_id] = (
                threading.get_ident(), spec.function.repr_name,
                time.time())
            try:
                with _maybe_span(spec):
                    values = method(*args, **kwargs)
            finally:
                self._unregister_running(spec.task_id)
                self.core.clear_task_context()
            if asyncio.iscoroutine(values):
                values = asyncio.get_event_loop_policy().new_event_loop().run_until_complete(values)
            return self._ok_reply(spec, values)
        except BaseException as e:  # noqa: BLE001
            return {"results": [], "error": self._seal_error(spec, e)}


async def _amain():
    session = os.environ["RAY_TPU_SESSION"]
    raylet_socket = os.environ["RAY_TPU_RAYLET_SOCKET"]
    gcs_socket = os.environ["RAY_TPU_GCS_SOCKET"]
    node_id = NodeID.from_hex(os.environ["RAY_TPU_NODE_ID"])
    worker_id = WorkerID.from_random()
    cfg = global_config()

    if "/" in raylet_socket:
        session_dir = os.path.dirname(raylet_socket)
        my_socket = os.path.join(session_dir, f"worker_{worker_id.hex()[:16]}.sock")
    else:
        my_socket = "127.0.0.1:0"  # TCP node: serve on an ephemeral port

    store_ns = os.environ.get("RAY_TPU_STORE_DIR", session)
    store = SharedObjectStore(store_ns, cfg.object_store_memory_bytes, create_dir=False)
    # the core worker shares this process's running loop
    from .rpc import EventLoopThread

    loop = asyncio.get_event_loop()

    class _LoopShim:
        """EventLoopThread interface over the already-running worker loop."""

        def __init__(self, loop):
            self.loop = loop
            # _amain's loop runs on the worker's main thread; callers
            # (e.g. kill_actor) compare against .thread to pick the
            # non-deadlocking submission path, same as EventLoopThread
            self.thread = threading.main_thread()

        def run(self, coro, timeout=None):
            import concurrent.futures as cf

            if threading.current_thread() is threading.main_thread():
                # called from the loop thread itself — must never happen for
                # blocking calls; execute as a task and let caller await
                raise RuntimeError("blocking io.run on loop thread")
            fut = asyncio.run_coroutine_threadsafe(coro, self.loop)
            return fut.result(timeout)

        def spawn(self, coro):
            return asyncio.run_coroutine_threadsafe(coro, self.loop)

        def stop(self):
            pass

    core = CoreWorker(
        mode="worker",
        session_name=session,
        gcs_address=gcs_socket,
        raylet_address=raylet_socket,
        job_id=JobID.from_int(0),
        node_id=node_id,
        store=store,
        io=_LoopShim(loop),
        worker_id=worker_id,
    )
    core.address = my_socket
    await core._connect()
    # user code inside tasks reaches the runtime through the module-level API
    from .. import _worker_api

    _worker_api._core = core

    raylet = RpcClient(raylet_socket)
    await raylet.connect()

    executor = TaskExecutor(core, raylet)
    # read AFTER _connect(): _system_config overrides land there
    if cfg.profiling_sample_hz > 0:
        executor.start_ambient_sampler(cfg.profiling_sample_hz)
    blackbox_rec = None
    if cfg.blackbox_enabled:
        # black-box flight ring: running on the MAIN thread here, so the
        # SIGTERM/SIGABRT dump handlers actually install (unlike raylet/
        # GCS, which live on an event-loop thread and rely on the
        # survivor sweep); a SIGKILL'd worker leaves its last flushed
        # flight file for the raylet to promote on disconnect
        from .config import TEMP_ROOT
        from . import blackbox
        from ..util import metrics as _metrics

        def _bb_inflight():
            now = time.time()
            return [
                {"kind": "task", "task_id": tid.hex(), "fn": fn,
                 "age_s": round(now - t0, 3)}
                for tid, (_, fn, t0) in
                list(executor._running_since.items())
            ]

        blackbox_rec = blackbox.FlightRecorder(
            "worker", os.path.join(TEMP_ROOT, session),
            ident=worker_id.hex(), node_id=node_id.hex(),
            ring_size=cfg.blackbox_ring_size,
            flush_interval_s=cfg.blackbox_flush_interval_s,
            inflight_provider=_bb_inflight,
            stacks_provider=lambda: stacks.flight_snapshot(
                executor._running_since),
            metrics_provider=lambda: _metrics.snapshot_local())
        blackbox_rec.start()
        executor.blackbox_rec = blackbox_rec
        logging.getLogger("ray_tpu").addHandler(
            blackbox.RingLogHandler(blackbox_rec))
    if cfg.tracemalloc_enabled:
        import tracemalloc

        if not tracemalloc.is_tracing():
            tracemalloc.start()
    server = RpcServer(my_socket, name=f"worker-{worker_id.hex()[:8]}")
    shutdown_event = asyncio.Event()

    async def handle_push_task(payload, conn):
        spec: TaskSpec = cloudpickle.loads(payload)
        if not spec.actor_creation and not spec.is_actor_task():
            # worker-start mark: transitions-only (never the top-level
            # `state` field — a flush race with the owner's terminal
            # event must not clobber FINISHED/FAILED)
            core._record_transition(spec.task_id, "WORKER_STARTED")
        if spec.actor_creation:
            core.job_id = spec.job_id
            core.current_task_id = spec.task_id
            # who waits for this actor goes on waiting while its
            # constructor runs (core_worker._wait_actor_alive)
            await core.gcs.call("actor_constructing",
                                {"actor_id": spec.actor_id}, timeout=30)
            reply = await loop.run_in_executor(executor.pool,
                                               executor.execute_actor_creation, spec)
            if reply["error"] is None:
                await core.gcs.call("actor_alive", {
                    "actor_id": spec.actor_id,
                    "address": my_socket,
                    "node_id": node_id,
                })
            return reply
        if spec.is_actor_task():
            if getattr(executor, "actor_async", False):
                afut = asyncio.run_coroutine_threadsafe(
                    executor.execute_actor_task_async(spec),
                    executor._actor_loop_obj)
                return await asyncio.wrap_future(afut)
            fut = loop.create_future()

            def reply_cb(result, fut=fut):
                loop.call_soon_threadsafe(
                    lambda: fut.set_result(result) if not fut.done() else None)

            executor._actor_queue.put((spec, reply_cb))
            return await fut
        core.job_id = spec.job_id
        if spec.streaming:
            def push(frame, conn=conn):
                # called from the generator thread; blocking on the loop-side
                # write keeps frames ordered and paces the producer
                asyncio.run_coroutine_threadsafe(
                    conn.push("generator_item", frame), loop).result()

            return await loop.run_in_executor(
                executor.pool, executor.execute_streaming, spec, push)
        return await loop.run_in_executor(executor.pool, executor.execute_normal, spec)

    async def handle_cancel_task(payload, conn):
        return executor.cancel(payload["task_id"], payload.get("force", False))

    async def handle_generator_ack(payload, conn):
        budget = executor._gen_budgets.get(payload["task_id"])
        if budget is not None:
            budget.ack(payload["consumed"])
        return True

    async def handle_kill_self(payload, conn):
        if executor.blackbox_rec is not None:
            executor.blackbox_rec.close(clean=True)
        loop.call_later(0.05, lambda: os._exit(0))
        return True

    def _lane_serve(sub, rep, kind: str):
        """Fast-lane server thread: pop task frames (single or batched)
        off the shm ring, execute, push replies
        (ray_tpu/_private/fastlane.py). Normal tasks run inline on this
        thread (the lane is one serial worker, like a leased worker in
        the reference); actor tasks route into the actor runtime so
        ordering and concurrency semantics match the asyncio path
        exactly."""
        import pickle as _pickle

        def send(seq: int, reply: dict) -> None:
            try:
                rep.push(_pickle.dumps((seq, reply), protocol=5),
                         timeout_ms=5000)
            except (BrokenPipeError, ValueError):
                pass

        async def _run_async_one(seq: int, spec) -> None:
            try:
                reply = await executor.execute_actor_task_async(spec)
            except BaseException as e:  # noqa: BLE001
                reply = {"results": [],
                         "error": executor._seal_error(spec, e)}
            send(seq, reply)

        async def _run_async_batch(items) -> None:
            # created in submission order on ONE loop tick, so per-caller
            # ordering of task STARTS matches the sync lane; awaits may
            # interleave (async-actor semantics)
            await asyncio.gather(*(
                _run_async_one(seq, spec) for seq, spec in items))

        def serve_batch_async(items) -> None:
            """One threadsafe loop wakeup per ring frame instead of one
            per call — the async lane's remaining per-call overhead."""
            asyncio.run_coroutine_threadsafe(
                _run_async_batch(items), executor._actor_loop_obj)

        def serve_one(seq: int, spec) -> None:
            if kind == "actor" and spec.is_actor_task():
                if getattr(executor, "actor_async", False):
                    serve_batch_async([(seq, spec)])
                else:
                    executor._actor_queue.put(
                        (spec, lambda reply, seq=seq: send(seq, reply)))
            else:
                core.job_id = spec.job_id
                send(seq, executor.execute_normal(spec))

        try:
            while True:
                try:
                    frame = sub.pop(timeout_ms=500)
                except (BrokenPipeError, ValueError):
                    break
                if frame is None:
                    continue
                try:
                    batch = _pickle.loads(frame)
                except Exception:
                    continue
                if not isinstance(batch, list):
                    batch = [batch]
                if (kind == "actor" and getattr(executor, "actor_async",
                                                False) and len(batch) > 1
                        and all(s.is_actor_task() for _, s in batch)):
                    serve_batch_async(batch)
                else:
                    for seq, spec in batch:
                        serve_one(seq, spec)
        finally:
            try:
                rep.close_write()
            except Exception:
                pass
            if kind == "task":
                # only this thread ever touched the rings: drop the
                # mappings (the owner unlinks the files). Actor lanes
                # skip this — in-flight calls may still push replies
                # from actor threads; the mappings die with the process.
                for ring in (sub, rep):
                    try:
                        ring.free()
                    except Exception:
                        pass

    async def handle_fastlane_attach(payload, conn):
        try:
            from .._native import Ring

            sub = Ring(payload["sub"])
            rep = Ring(payload["rep"])
        except Exception:
            return False
        threading.Thread(
            target=_lane_serve, args=(sub, rep, payload.get("kind", "task")),
            daemon=True, name="fastlane_serve").start()
        return True

    async def handle_health(payload, conn):
        return {"pid": os.getpid(), "actor": executor.actor_id}

    async def handle_dump_stacks(payload, conn):
        # runs on the event loop, not a task thread — the loop itself
        # stays responsive even while every executor thread is wedged,
        # which is exactly when this RPC matters
        return executor.dump_stacks()

    async def handle_stall_probe(payload, conn):
        return executor.stall_probe()

    async def handle_profile_start(payload, conn):
        return executor.profile_start(float(payload.get("hz", 100.0)))

    async def handle_profile_stop(payload, conn):
        # like dump_stacks: served from the event loop so a cluster
        # profile still answers while every executor thread is busy
        return executor.profile_stop()

    async def handle_memory_report(payload, conn):
        return core.local_memory_report()

    server.register("push_task", handle_push_task)
    server.register("cancel_task", handle_cancel_task)
    server.register("generator_ack", handle_generator_ack)
    server.register("kill_self", handle_kill_self)
    server.register("health", handle_health)
    server.register("dump_stacks", handle_dump_stacks)
    server.register("stall_probe", handle_stall_probe)
    server.register("profile_start", handle_profile_start)
    server.register("profile_stop", handle_profile_stop)
    server.register("memory_report", handle_memory_report)
    server.register("fastlane_attach", handle_fastlane_attach)
    # owner-serve: this worker's owned small objects (nested submissions)
    server.register("fetch_object", core._handle_fetch_object)
    # nested submissions from this worker can hedge too — the raylet
    # watchdog's hint must reach whatever process owns the task
    server.register("hedge_hint", core.handle_hedge_hint)
    executor.seal_batcher = SealBatcher(core, raylet)
    await server.start()
    try:
        my_socket = server.address  # resolved (TCP port 0)
        core.address = my_socket

        # register with raylet last — once registered, tasks may arrive
        raylet.on_push("shutdown", lambda payload: shutdown_event.set())
        # die with the raylet: an abrupt raylet death (SIGKILL, node
        # crash) sends no shutdown push, and an orphaned worker would
        # outlive the whole cluster (ref: core_worker shuts down when
        # the local raylet connection breaks). call_soon_threadsafe not
        # needed — the recv loop runs on this same loop.
        raylet.on_close = shutdown_event.set
        await raylet.call("register_worker", {
            "worker_id": worker_id,
            "pid": os.getpid(),
            "address": my_socket,
        })

        await shutdown_event.wait()
    finally:
        # a failed registration must still unbind the socket before the
        # process exits, or a fast raylet retry can hit a stale address
        await server.stop()
    if blackbox_rec is not None:
        blackbox_rec.close(clean=True)  # ordered shutdown: no corpse
    os._exit(0)


def main():
    try:
        asyncio.run(_amain())
    except KeyboardInterrupt:  # graftlint: ignore[swallow] — quiet ^C exit
        pass


if __name__ == "__main__":
    main()
