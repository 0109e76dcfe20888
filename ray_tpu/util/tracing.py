"""Tracing / profiling (ref: SURVEY §5.1 — the reference's opentelemetry
hooks + `ray timeline` chrome-trace export; device-plane profiling maps
to jax.profiler, whose traces open in Perfetto/XProf).

    ray_tpu.util.tracing.timeline("/tmp/timeline.json")  # chrome trace
    with ray_tpu.util.tracing.span("rt.engine.schedule"):  # one phase
        ...

``span`` is the one primitive every phase of the serving round and the
train step goes through. It writes the interval in two places: into a
running ``jax.profiler`` trace (a ``TraceAnnotation`` on the profiler's
clock, beside the device's operations), and, with ``RAY_TPU_TRACING=1``,
into the JSONL sink that ``timeline()`` renders. Names are fixed
(``rt.<lane>.<phase>``); the benchmark's readers match on them:

    rt.engine.{schedule,prefill.build,prefill.dispatch,prefill.sync,
               decode.draft,decode.build,decode.dispatch,release,
               decode.sync,append}                     llm/engine.py
    rt.pump.{fanout,idle,lull}                         llm/serve.py
    rt.train.step, rt.train.<phase>, rt.train.report   train/

This module never imports jax: the benchmark's driver imports ray_tpu
and may not touch the chip. A process that has not imported jax gets
only the JSONL half.
"""

from __future__ import annotations

import contextvars
import json
import os
import sys
import threading
import time
import uuid
from contextlib import contextmanager
from typing import Any, Dict, List, Optional

# ---------------------------------------------------------------- spans
# Distributed span propagation (ref: util/tracing/tracing_helper.py —
# _inject_tracing_into_function:326 wraps .remote() in a span and
# serializes the span context into the task spec; the executing worker
# re-hydrates it as the parent). The reference emits through
# opentelemetry; this environment has no otel SDK, so spans are recorded
# self-contained: one JSONL file per process in the session log dir,
# aggregated by collect_spans(). Each record:
#   {trace_id, span_id, parent_id, name, kind, start, end, pid}

_TRACE_ENV = "RAY_TPU_TRACING"
_ctx: contextvars.ContextVar = contextvars.ContextVar(
    "ray_tpu_trace_ctx", default=None)   # (trace_id, span_id) | None
_sink_lock = threading.Lock()
_sink = None  # opened spans-<pid>.jsonl file


def tracing_enabled() -> bool:
    return os.environ.get(_TRACE_ENV, "") == "1"


def _span_dir() -> Optional[str]:
    from .._private.config import session_log_dir
    from .. import _worker_api

    session = os.environ.get("RAY_TPU_SESSION", "")
    if not session and _worker_api._core is not None:
        session = _worker_api._core.session_name
    if not session:
        return None
    return session_log_dir(session)


def _emit_span(rec: Dict[str, Any]) -> None:
    global _sink
    with _sink_lock:
        if _sink is None:
            d = _span_dir()
            if d is None:
                return
            os.makedirs(d, exist_ok=True)
            _sink = open(os.path.join(d, f"spans-{os.getpid()}.jsonl"),
                         "a", buffering=1)
        _sink.write(json.dumps(rec) + "\n")


def current_trace_ctx(name: str) -> Optional[tuple]:
    """Submission hook: start a `submit` span under the current context
    and return (trace_id, span_id) to ride the task spec. None when
    tracing is off (zero overhead on the hot path)."""
    if not tracing_enabled():
        return None
    parent = _ctx.get()
    trace_id = parent[0] if parent else uuid.uuid4().hex
    span_id = uuid.uuid4().hex[:16]
    _emit_span({"trace_id": trace_id, "span_id": span_id,
                "parent_id": parent[1] if parent else None,
                "name": f"{name}.remote()", "kind": "submit",
                "start": time.time(), "end": time.time(),
                "pid": os.getpid()})
    return (trace_id, span_id)


def inject_trace_ctx(spec) -> None:
    """Attach a span context to an outgoing TaskSpec (no-op when
    tracing is off) — the single gate both submit paths share."""
    if tracing_enabled():
        spec.trace_ctx = current_trace_ctx(spec.function.repr_name)


@contextmanager
def task_span(trace_ctx: Optional[tuple], name: str):
    """Execution hook: run the task under a span parented to the
    submission span; nested .remote() calls inherit the context."""
    if trace_ctx is None:
        yield
        return
    trace_id, parent_id = trace_ctx
    span_id = uuid.uuid4().hex[:16]
    token = _ctx.set((trace_id, span_id))
    start = time.time()
    try:
        yield
    finally:
        _ctx.reset(token)
        _emit_span({"trace_id": trace_id, "span_id": span_id,
                    "parent_id": parent_id, "name": name,
                    "kind": "execute", "start": start,
                    "end": time.time(), "pid": os.getpid()})


def collect_spans() -> List[Dict[str, Any]]:
    """Aggregate span records from every process of the session."""
    d = _span_dir()
    if d is None or not os.path.isdir(d):
        return []
    out: List[Dict[str, Any]] = []
    for fname in sorted(os.listdir(d)):
        if not (fname.startswith("spans-") and fname.endswith(".jsonl")):
            continue
        try:
            with open(os.path.join(d, fname)) as f:
                for line in f:
                    line = line.strip()
                    if line:
                        out.append(json.loads(line))
        except OSError:
            continue
    return out


def record_lane_event(lane: str, name: str, start: float, end: float,
                      node_id: str = "", **args) -> None:
    """Record one object-plane I/O interval (transfer/spill/restore) in
    the span sink; timeline() renders these as per-process I/O lanes.
    No-op unless tracing is enabled — zero cost on the data plane."""
    if not tracing_enabled():
        return
    if not node_id:
        try:
            from .. import _worker_api

            if _worker_api._core is not None:
                node_id = _worker_api._core.node_id.hex()
        except Exception:
            node_id = ""
    _emit_span({"kind": "lane", "lane": lane, "name": name,
                "start": start, "end": end, "pid": os.getpid(),
                "node_id": node_id, "args": args})


# worker-side lifecycle states: slices for intervals ending in one of
# these render on the executing worker's track, the rest on the owner's
_WORKER_SIDE = ("WORKER_STARTED", "PENDING_ARGS_FETCH", "RUNNING",
                "OUTPUT_SEALED", "FINISHED", "FAILED")


class _TrackAllocator:
    """Stable int pid/tid assignment + chrome metadata events. Perfetto
    groups rows by process/thread; names ride ph:'M' records."""

    def __init__(self):
        self.pids: Dict[str, int] = {}
        self.tids: Dict[tuple, int] = {}
        self.meta: List[Dict[str, Any]] = []

    def pid(self, node_hex: str, label: Optional[str] = None) -> int:
        key = node_hex or "<unknown>"
        if key not in self.pids:
            self.pids[key] = len(self.pids) + 1
            self.meta.append({
                "name": "process_name", "ph": "M", "pid": self.pids[key],
                "args": {"name": label or (f"node {key[:12]}" if node_hex
                                           else "unknown node")}})
        return self.pids[key]

    def tid(self, pid: int, label: str) -> int:
        key = (pid, label)
        if key not in self.tids:
            self.tids[key] = len(self.tids) + 1
            self.meta.append({
                "name": "thread_name", "ph": "M", "pid": pid,
                "tid": self.tids[key], "args": {"name": label}})
        return self.tids[key]


def timeline(filename: Optional[str] = None) -> List[Dict[str, Any]]:
    """Export the cluster flight recorder as a chrome://tracing /
    Perfetto JSON array (ref: ray.timeline — dashboard's chrome-trace
    exporter). Per-node processes, per-worker threads; each completed
    task renders as one whole-task slice plus one slice per lifecycle
    phase (from the GCS state_transitions table, per-node clock offsets
    applied), with a flow event linking submit (owner track) to execute
    (worker track) across processes. Object-transfer/spill lane records
    (record_lane_event, tracing-gated) render as per-process I/O rows."""
    from . import state as state_api

    offsets = state_api.clock_offsets()
    tracks = _TrackAllocator()
    events: List[Dict[str, Any]] = []
    for task in state_api.list_tasks():
        trs = state_api.corrected_transitions(task, offsets)
        worker = task.get("worker_id") or ""
        common = {"task_id": task["task_id"], "state": task["state"],
                  **({"error": task["error"]} if task.get("error") else {})}
        if len(trs) < 2:
            # no recorded lifecycle (pre-transition record): fall back to
            # the flat start/end slice
            start, end = task.get("start_time"), task.get("end_time")
            if not start:
                continue
            pid = tracks.pid(task.get("node_id") or "")
            events.append({
                "name": task["name"], "cat": "task", "ph": "X",
                "ts": start * 1e6,
                "dur": max(((end or start) - start) * 1e6, 1.0),
                "pid": pid, "tid": tracks.tid(pid, "tasks"),
                "args": common})
            continue
        worker_trs = [t for t in trs if t["state"] in _WORKER_SIDE]
        exec_node = (worker_trs[0]["node_id"] if worker_trs
                     else (task.get("node_id") or ""))
        exec_pid = tracks.pid(exec_node)
        exec_tid = tracks.tid(
            exec_pid, f"worker {worker[:12]}" if worker else "tasks")
        owner_pid = tracks.pid(trs[0]["node_id"])
        owner_tid = tracks.tid(owner_pid, "driver")
        # whole-task slice on the executing worker's track (falls back to
        # the full transition span when no worker-side marks exist)
        span_trs = worker_trs if len(worker_trs) >= 2 else trs
        events.append({
            "name": task["name"], "cat": "task", "ph": "X",
            "ts": span_trs[0]["ts"] * 1e6,
            "dur": max((span_trs[-1]["ts"] - span_trs[0]["ts"]) * 1e6, 1.0),
            "pid": exec_pid, "tid": exec_tid,
            "args": {**common,
                     "node": exec_node[:12], "worker": worker[:12]}})
        # one slice per lifecycle phase interval
        for a, b in zip(trs, trs[1:]):
            phase = state_api.PHASE_OF_DEST.get(b["state"], "other")
            on_worker = b["state"] in _WORKER_SIDE and worker_trs
            pid = exec_pid if on_worker else owner_pid
            tid = exec_tid if on_worker else owner_tid
            events.append({
                "name": f"{task['name']}:{b['state'].lower()}",
                "cat": "phase", "ph": "X",
                "ts": a["ts"] * 1e6,
                "dur": max((b["ts"] - a["ts"]) * 1e6, 1.0),
                "pid": pid, "tid": tid,
                "args": {"task_id": task["task_id"], "phase": phase,
                         "from": a["state"], "to": b["state"]}})
        # flow event linking submit (owner) -> first worker-side mark
        if worker_trs:
            events.append({
                "name": "submit", "cat": "flow", "ph": "s",
                "id": task["task_id"], "ts": trs[0]["ts"] * 1e6,
                "pid": owner_pid, "tid": owner_tid})
            events.append({
                "name": "submit", "cat": "flow", "ph": "f", "bp": "e",
                "id": task["task_id"], "ts": worker_trs[0]["ts"] * 1e6,
                "pid": exec_pid, "tid": exec_tid})
    # object-plane I/O lanes (transfer/spill/restore span records)
    for rec in collect_spans():
        if rec.get("kind") != "lane":
            continue
        node = rec.get("node_id") or ""
        pid = (tracks.pid(node) if node
               else tracks.pid(f"io-{rec.get('pid')}",
                               label=f"io pid {rec.get('pid')}"))
        off = offsets.get(node, 0.0)
        events.append({
            "name": rec.get("name", rec.get("lane", "io")),
            "cat": "lane", "ph": "X",
            "ts": (rec["start"] + off) * 1e6,
            "dur": max((rec["end"] - rec["start"]) * 1e6, 1.0),
            "pid": pid,
            "tid": tracks.tid(pid, f"{rec.get('lane', 'io')} lane"),
            "args": dict(rec.get("args") or {})})
    events = tracks.meta + events
    if filename:
        with open(filename, "w") as f:
            json.dump(events, f)
    return events


class span:
    """One named host interval, ``with span("rt.engine.schedule"):``.

    Written as a ``jax.profiler.TraceAnnotation`` (on the profiler's
    clock whenever a trace is running; a flag check when none is) and,
    only when ``tracing_enabled()``, as a lane record in the JSONL sink
    with ``args`` (``request_id``, ``step``) so ``timeline()`` shows it.
    The lane is the name's second part (``rt.<lane>.<phase>``).
    ``seconds`` holds the interval's length after exit: callers that
    keep a counter of the same phase read it and take no clock of
    their own. Names are constants; nothing is formatted per call."""

    __slots__ = ("name", "args", "seconds", "_t0", "_wall0", "_annotation")
    _lane_record = True     # piece_span: the caller writes the record

    def __init__(self, name: str, **args):
        self.name = name
        self.args = args
        self.seconds = 0.0

    def _annotate(self, profiler):
        return profiler.TraceAnnotation(self.name, **self.args)

    def __enter__(self) -> "span":
        # jax is used where the process already has it, never imported
        profiler = sys.modules.get("jax.profiler")
        self._annotation = None
        if hasattr(profiler, "TraceAnnotation"):
            self._annotation = self._annotate(profiler)
            self._annotation.__enter__()
        self._wall0 = (time.time() if self._lane_record
                       and tracing_enabled() else 0.0)
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, *exc) -> bool:
        self.seconds = time.perf_counter() - self._t0
        if self._annotation is not None:
            self._annotation.__exit__(*exc)
        if self._wall0:
            parts = self.name.split(".")
            record_lane_event(parts[1] if len(parts) > 2 else self.name,
                              self.name, self._wall0,
                              self._wall0 + self.seconds, **self.args)
        return False


class step_span(span):
    """``span`` for one training step: a ``StepTraceAnnotation``, so the
    profiler's step view groups the device's work by ``step``."""

    __slots__ = ()

    def __init__(self, name: str, step: int):
        super().__init__(name, step=step)

    def _annotate(self, profiler):
        return profiler.StepTraceAnnotation(self.name,
                                            step_num=self.args["step"])


class piece_span(span):
    """``span`` for one piece of a long wait that is cut into pieces (an
    annotation that was open when the profiler started is lost, so a
    wait of seconds is many short ones): an annotation and ``seconds``,
    no lane record. The caller adds the pieces up and writes ONE
    ``record_lane_event`` for the whole wait."""

    __slots__ = ()
    _lane_record = False
