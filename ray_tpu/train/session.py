"""Worker-side training session: the `ray_tpu.train.report` surface
(ref: python/ray/train/_internal/session.py — the _TrainSession singleton
each worker's train_fn talks to; report flow in
train/v2/_internal/execution/worker_group/thread_runner.py).

One session per worker process, installed by TrainWorker before the user
function runs. ``report()`` hands metrics (and optionally a checkpoint
directory) to the worker actor, which the controller polls.

Goodput plane: the session owns this rank's :class:`StepTimeline` — a
"step" is the interval between consecutive ``report()`` calls, so
``report()`` closes the step, attributes the unaccounted remainder
(``init`` before the first report, ``idle`` after), observes the
``train_step_seconds{phase=...}`` histograms (the phases themselves are
``rt.train.*`` spans, born in the timeline), and queues a
:class:`TrainStepTelemetry` record for the controller to forward to the
GCS goodput ledger."""

from __future__ import annotations

import os
import threading
from contextlib import contextmanager
from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional

from ..util import tracing
from ._checkpoint import Checkpoint
from .telemetry import StepTimeline, TrainStepTelemetry


@dataclass
class TrainContext:
    world_size: int
    rank: int
    node_rank: int
    experiment_name: str
    coordinator_address: str = ""     # rank-0 host:port for jax.distributed
    restored_checkpoint: Optional[Checkpoint] = None
    # global step base (controller's checkpoints.max_step()): a restarted
    # gang numbers its steps past what is already persisted, so the GCS
    # ledger can tell replayed work (rework) from new steps
    start_step: int = 0


@dataclass
class _Report:
    metrics: Dict[str, Any]
    checkpoint: Optional[Checkpoint] = None
    step: int = 0
    telemetry: Optional[TrainStepTelemetry] = None


_step_hist = None


def _step_histogram():
    """Lazy metric registration (session import must stay light — the
    wire registry imports train.telemetry in every process)."""
    global _step_hist
    if _step_hist is None:
        from ..util import metrics as m

        _step_hist = m.Histogram(
            "train_step_seconds",
            "per-phase training step time (phase=total is the step wall)",
            boundaries=m.TRAIN_STEP_BUCKETS,
            tag_keys=("job", "phase"))
    return _step_hist


class _Session:
    def __init__(self, context: TrainContext):
        self.context = context
        self.reports: List[_Report] = []
        self.lock = threading.Lock()
        self._step = context.start_step
        from .._private.config import global_config

        self.telemetry_on = bool(global_config().train_telemetry_enabled)
        self.timeline = StepTimeline()
        self.timeline.step = self._step + 1
        self._node_id = os.environ.get("RAY_TPU_NODE_ID", "")
        self._first_closed = False
        # per-step stats accumulated by the instrumented step factory
        # (several step_fn calls may land between two report()s)
        self._tokens = 0
        self._flops = 0.0
        self._chips = 1
        self._compile_kind = ""
        self._recompile = False
        self._batch_shape = ""

    def note_step(self, tokens: int = 0, flops: float = 0.0,
                  chips: int = 0, compile_kind: str = "",
                  recompile: bool = False, batch_shape: str = "") -> None:
        with self.lock:
            self._tokens += int(tokens)
            self._flops += float(flops)
            if chips:
                self._chips = max(self._chips, int(chips))
            # "cold" outranks "cache_hit": if any call this step did
            # real XLA work, the step counts as a cold compile
            if compile_kind == "cold" or not self._compile_kind:
                self._compile_kind = compile_kind or self._compile_kind
            self._recompile = self._recompile or recompile
            if batch_shape:
                self._batch_shape = batch_shape

    def report(self, metrics: Dict[str, Any],
               checkpoint: Optional[Checkpoint]) -> None:
        with self.lock:
            self._step += 1
            telemetry = None
            if self.telemetry_on:
                with tracing.span("rt.train.report", step=self._step):
                    telemetry = self._close_step(self._step)
            self.reports.append(
                _Report(dict(metrics), checkpoint, self._step, telemetry))

    def _close_step(self, step: int) -> TrainStepTelemetry:
        # first interval covers session install -> first report: model
        # init, sharding, jax.distributed — its remainder is init badput
        remainder_as = "idle" if self._first_closed else "init"
        self._first_closed = True
        start, end, phases, _intervals = self.timeline.close(remainder_as)
        rec = TrainStepTelemetry(
            rank=self.context.rank, step=step, node_id=self._node_id,
            start_t=start, end_t=end, phases=phases,
            compile_kind=self._compile_kind, recompile=self._recompile,
            batch_shape=self._batch_shape, tokens=self._tokens,
            flops=self._flops, chips=self._chips)
        self._tokens, self._flops = 0, 0.0
        self._compile_kind, self._recompile = "", False
        self._batch_shape = ""
        try:
            self._observe(rec)
        except Exception:  # graftlint: ignore[swallow] — telemetry
            pass  # must never fail a training step
        return rec

    def _observe(self, rec: TrainStepTelemetry) -> None:
        step_hist = _step_histogram()
        job = self.context.experiment_name
        for name, secs in rec.phases.items():
            step_hist.observe(secs, tags={"job": job, "phase": name})
        step_hist.observe(max(0.0, rec.end_t - rec.start_t),
                          tags={"job": job, "phase": "total"})

    def drain(self) -> List[_Report]:
        """Hand pending reports to the poller and forget them — a long run
        reporting every step must not accumulate every metrics dict."""
        with self.lock:
            pending = self.reports
            self.reports = []
        return pending


_session: Optional[_Session] = None


def _init_session(context: TrainContext) -> _Session:
    global _session
    _session = _Session(context)
    return _session


def _shutdown_session() -> None:
    global _session
    _session = None


def _require_session() -> _Session:
    if _session is None:
        raise RuntimeError(
            "ray_tpu.train.report/get_context can only be called inside a "
            "training function launched by a Trainer")
    return _session


def report(metrics: Dict[str, Any],
           checkpoint: Optional[Checkpoint] = None) -> None:
    """Report metrics (and optionally a checkpoint) to the controller
    (ref: ray.train.report). Only rank 0's checkpoint is registered.
    Also closes the current telemetry step: phase attribution between
    two report() calls rides out as one TrainStepTelemetry record."""
    _require_session().report(metrics, checkpoint)


@contextmanager
def phase(name: str):
    """Attribute the enclosed work to a named step phase (``data_wait``,
    ``collective_sync``, ``checkpoint_save``, ...). No-op outside a
    session or with train_telemetry_enabled=False — safe to leave in
    production train functions."""
    session = _session
    if session is None or not session.telemetry_on:
        yield
        return
    with session.timeline.phase(name):
        yield


def get_context() -> TrainContext:
    """World/rank info for this training worker (ref: ray.train.get_context)."""
    return _require_session().context


def get_checkpoint() -> Optional[Checkpoint]:
    """The checkpoint to resume from, if the controller restored one
    (ref: ray.train.get_checkpoint)."""
    return _require_session().context.restored_checkpoint
