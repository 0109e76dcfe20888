"""Training goodput plane: per-step phase telemetry + badput ledger.

MegaScale's operating insight (PAPERS.md) is that at pod scale the
dominant wins come from *classifying* non-productive chip-time — compile,
data stalls, checkpoint stalls, straggler skew, restart rework — per step
and per host, not from shaving the compute kernels. This module is the
pure core of that plane:

* :class:`StepTimeline` — worker-side phase accounting for one training
  step (the interval between two ``train.report()`` calls). Phases are
  attributed explicitly (``train.phase("data_wait")``), by the
  instrumented step/place_batch wrappers (compile/compute/
  host_to_device), and the unattributed remainder closes to ``idle``
  (``init`` for the very first step) — so the partition always sums to
  the step wall.
* :class:`StepInstrumenter` — first call per batch signature is compile
  (cold vs persistent-cache hit via :func:`classify_compile`), later
  calls are compute; a NEW signature after the first is a recompile.
* :class:`TrainStepTelemetry` / :class:`TrainJobLedger` — the wire
  records (msgpack struct tags 18/19 in ``_private/wire.py``; all-default
  fields per the append-only schema-evolution rule).
* :class:`GoodputLedger` — the GCS-side per-job accounting fold:
  rank reports → productive-chip-seconds vs badput by cause, barrier
  straggler skew from clock-corrected per-rank start/finish deltas,
  high-water rework detection across gang restarts, per-step MFU and
  tok/s/chip from the step factory's model-flops estimate.

Everything here is stdlib-only and clock-injectable: the GCS imports it
without pulling jax, and tests drive it with synthetic clocks. Phases are
also ``util.tracing`` spans (``rt.train.<phase>``): in a process that has
jax they land in a running profiler trace, beside the device's work.
"""

from __future__ import annotations

import collections
import time
from contextlib import contextmanager
from dataclasses import dataclass, field
from typing import Any, Callable, Dict, List, Optional, Tuple

from ..util import tracing

# canonical per-step phases (the train_step_seconds{phase=...} label set;
# "total" is reserved for the whole-step wall histogram)
PHASES = ("data_wait", "host_to_device", "compile", "compute",
          "collective_sync", "checkpoint_save", "idle")

# phase -> badput bucket (MegaScale taxonomy). "compute" is the one
# productive phase; everything else is badput by cause. "init" and
# "rework"/"straggler" buckets are minted by the ledger itself.
BADPUT_OF_PHASE = {
    "data_wait": "data_stall",
    "host_to_device": "h2d",
    "compile": "compile",
    "collective_sync": "collective",
    "checkpoint_save": "ckpt_stall",
    "idle": "idle",
    "init": "init",
}


def estimate_flops_per_token(n_params: int) -> float:
    """Standard training-flops estimate: ~6 flops per parameter per
    token (fwd 2 + bwd 4; Kaplan et al. accounting). The step factory
    reports ``this * tokens`` per step so the ledger can compute MFU."""
    return 6.0 * float(n_params)


def classify_compile(duration_s: float, wrote_cache_entries: int,
                     hit_threshold_s: float = 0.5) -> str:
    """Cold compile vs persistent-cache hit for a first-call-per-shape.

    Ground truth when available: a compile that WROTE new entries into
    the persistent cache did real XLA work (cold). With no new entries
    the duration decides — a cache hit deserializes in well under the
    threshold, while a sub-``jax_persistent_cache_min_compile_time_secs``
    cold compile that wrote nothing is also fast and equally cheap, so
    misclassifying it as a hit costs nothing in the ledger."""
    if wrote_cache_entries > 0:
        return "cold"
    return "cache_hit" if duration_s < hit_threshold_s else "cold"


# ------------------------------------------------------------- wire records

@dataclass
class TrainStepTelemetry:
    """One rank's view of one training step (wire struct tag 18).

    ``start_t``/``end_t`` are the rank's LOCAL wall clock; the GCS
    applies ``NodeInfo.clock_offset`` (the collective-watchdog path)
    before folding, so cross-host skew is real skew, not NTP noise.
    All fields default (append-only wire evolution rule)."""

    rank: int = 0
    step: int = 0                  # global step number (start_step-based)
    node_id: str = ""
    start_t: float = 0.0
    end_t: float = 0.0
    phases: Dict[str, float] = field(default_factory=dict)
    compile_kind: str = ""         # "" | "cold" | "cache_hit"
    recompile: bool = False
    batch_shape: str = ""
    tokens: int = 0
    flops: float = 0.0
    chips: int = 1                 # local devices this rank drives


@dataclass
class TrainJobLedger:
    """API-shaped per-job goodput snapshot (wire struct tag 19): what
    ``state.train_status()`` / ``cli train`` / ``/api/train`` render.
    All fields default (append-only wire evolution rule)."""

    job: str = ""
    world_size: int = 0
    chips: int = 0                 # total chips across the gang
    started_at: float = 0.0
    updated_at: float = 0.0
    steps: int = 0
    productive_s: float = 0.0      # chip-seconds in compute
    badput_s: Dict[str, float] = field(default_factory=dict)
    tokens: int = 0
    flops: float = 0.0
    mfu: float = 0.0
    tok_per_s_per_chip: float = 0.0
    compile_count: int = 0
    cache_hit_count: int = 0
    recompile_count: int = 0
    rework_steps: int = 0
    restarts: int = 0
    rank_skew: Dict[str, float] = field(default_factory=dict)
    goodput_fraction: float = 0.0
    attributed_fraction: float = 0.0
    recent: List[Any] = field(default_factory=list)


# --------------------------------------------------------- worker-side timer

class StepTimeline:
    """Phase accounting for the interval between two ``report()`` calls.

    Single-threaded by design (lives on the train_fn thread). Phases may
    nest — time accrues to the innermost open phase, so the partition
    never double-counts. ``close()`` attributes the unaccounted
    remainder and resets for the next step. Every phase entered here is
    a ``tracing.span("rt.train.<name>", step=...)`` from enter to exit
    (an outer phase's span stays open under an inner one); intervals
    told after the fact (``record_interval``) are spanned where the work
    runs, in ``train/step.py``."""

    MAX_INTERVALS = 256            # per-step interval bound

    def __init__(self, clock: Callable[[], float] = time.time):
        self._clock = clock
        self._start = clock()
        self._acc: Dict[str, float] = {}
        self._stack: List[List] = []        # [name, resume_t, span]
        self.intervals: List[Tuple[str, float, float]] = []
        self.step = 0              # the step in progress; close() counts

    @contextmanager
    def phase(self, name: str):
        self.enter(name)
        try:
            yield
        finally:
            self.exit()

    def enter(self, name: str) -> None:
        now = self._clock()
        if self._stack:                     # pause the outer phase
            top = self._stack[-1]
            self._accrue(top[0], top[1], now)
            top[1] = now
        span = tracing.span("rt.train." + name, step=self.step)
        span.__enter__()
        self._stack.append([name, now, span])

    def exit(self) -> None:
        if not self._stack:
            return
        now = self._clock()
        name, resume, span = self._stack.pop()
        span.__exit__(None, None, None)
        self._accrue(name, resume, now)
        if self._stack:                     # resume the outer phase
            self._stack[-1][1] = now

    def record_interval(self, name: str, t0: float, t1: float) -> None:
        """Attribute an externally-timed interval (instrumented step_fn /
        place_batch wrappers)."""
        self._accrue(name, t0, t1)

    def _accrue(self, name: str, t0: float, t1: float) -> None:
        dt = max(0.0, t1 - t0)
        if dt <= 0.0:
            return
        self._acc[name] = self._acc.get(name, 0.0) + dt
        if len(self.intervals) < self.MAX_INTERVALS:
            self.intervals.append((name, t0, t1))

    def close(self, remainder_as: str = "idle"
              ) -> Tuple[float, float, Dict[str, float],
                         List[Tuple[str, float, float]]]:
        """End the step: returns (start, end, phases, intervals) with the
        unattributed remainder folded into ``remainder_as``, then resets
        so the next step starts at this step's end."""
        now = self._clock()
        # phases still open (user holds a phase() across report) accrue
        # up to the boundary and stay open into the next step
        for frame in self._stack:
            self._accrue(frame[0], frame[1], now)
            frame[1] = now
        start, end = self._start, now
        phases = dict(self._acc)
        remainder = (end - start) - sum(phases.values())
        if remainder > 0.0:
            phases[remainder_as] = phases.get(remainder_as, 0.0) + remainder
        intervals = self.intervals
        self._start = now
        self._acc = {}
        self.intervals = []
        self.step += 1
        return start, end, phases, intervals


class StepInstrumenter:
    """Compile/compute attribution for a jitted step callable.

    First call per batch signature is a compile (cold vs cache-hit via
    the persistent-cache entry delta + duration threshold); later calls
    with a known signature are compute. A new signature AFTER the first
    is a recompile — the silent step-time killer this plane exists to
    name. Pure and injectable: tests drive it with plain functions."""

    def __init__(self, clock: Callable[[], float] = time.time,
                 cache_entries: Callable[[], int] = lambda: 0,
                 hit_threshold_s: float = 0.5,
                 on_recompile: Optional[Callable[[str, str], None]] = None):
        self._clock = clock
        self._cache_entries = cache_entries
        self._hit_threshold_s = hit_threshold_s
        self._on_recompile = on_recompile
        self._seen: Dict[str, bool] = {}
        self._last_sig: Optional[str] = None
        self.last: Dict[str, Any] = {}

    def run(self, fn: Callable[[], Any], signature: str,
            block: Callable[[Any], Any] = lambda r: r) -> Any:
        new = signature not in self._seen
        recompile = new and bool(self._seen)
        before = self._cache_entries() if new else 0
        t0 = self._clock()
        out = block(fn())
        t1 = self._clock()
        if new:
            wrote = max(0, self._cache_entries() - before)
            kind = classify_compile(t1 - t0, wrote, self._hit_threshold_s)
            phase = "compile"
            self._seen[signature] = True
            if recompile and self._on_recompile is not None:
                self._on_recompile(self._last_sig or "", signature)
        else:
            kind, phase = "", "compute"
        self.last = {"phase": phase, "t0": t0, "t1": t1,
                     "compile_kind": kind, "recompile": recompile,
                     "signature": signature}
        self._last_sig = signature
        return out


# ----------------------------------------------------------- GCS-side ledger

class GoodputLedger:
    """Per-job fold of rank step reports into goodput accounting.

    Owned by the GCS (one per training job, keyed by experiment name);
    pure so tests drive it with synthetic records and clocks. A step
    folds when all ``world_size`` ranks have reported it: per-rank phase
    seconds × chips land in productive (compute) or a named badput
    bucket, barrier skew (each rank's gap to the slowest rank's
    start/finish envelope) lands in ``straggler``, and a step at or
    below the high-water mark — re-executed after a checkpoint restore —
    is pure ``rework``."""

    MAX_PENDING = 64               # in-flight (unfolded) steps kept
    HISTORY = 64                   # recent folded steps ring
    SKEW_EMA = 0.2                 # per-host straggler score smoothing

    def __init__(self, job: str, world_size: int = 1,
                 peak_flops_per_chip: float = 0.0,
                 clock: Callable[[], float] = time.time):
        self.job = job
        self.world_size = max(1, int(world_size))
        self.peak_flops_per_chip = float(peak_flops_per_chip)
        self._clock = clock
        self.started_at = clock()
        self.updated_at = self.started_at
        self.chips = 0
        self.steps = 0
        self.productive_s = 0.0
        self.badput_s: Dict[str, float] = {}
        self.wall_chip_s = 0.0     # denominator for attributed_fraction
        self.tokens = 0
        self.flops = 0.0
        self.mfu = 0.0
        self.tok_per_s_per_chip = 0.0
        self.compile_count = 0
        self.cache_hit_count = 0
        self.recompile_count = 0
        self.rework_steps = 0
        self.restarts = 0
        self.high_water = 0
        self.rank_skew: Dict[str, float] = {}
        self.recent: "collections.deque" = collections.deque(
            maxlen=self.HISTORY)
        self._pending: Dict[int, Dict[int, TrainStepTelemetry]] = {}

    # -- ingest ----------------------------------------------------------
    def add(self, rec: TrainStepTelemetry) -> None:
        self.updated_at = self._clock()
        if rec.compile_kind == "cold":
            self.compile_count += 1
        elif rec.compile_kind == "cache_hit":
            self.cache_hit_count += 1
        if rec.recompile:
            self.recompile_count += 1
        if rec.step <= 0:
            # init record: no barrier to wait for — account immediately
            chips = max(1, rec.chips)
            for name, secs in rec.phases.items():
                self._badput(BADPUT_OF_PHASE.get(name, name), secs * chips)
                self.wall_chip_s += secs * chips
            return
        slot = self._pending.setdefault(rec.step, {})
        slot[rec.rank] = rec
        if len(slot) >= self.world_size:
            self._fold(rec.step, self._pending.pop(rec.step))
        self._prune_pending()

    def restart(self, restore_step: int) -> int:
        """A gang restart restored from ``restore_step``: steps between
        there and the high-water mark WILL be re-executed. Returns the
        expected rework count; the actual chip-seconds are accounted as
        the replayed steps arrive (high-water detection)."""
        self.restarts += 1
        self._pending.clear()      # half-reported steps died with the gang
        return max(0, self.high_water - int(restore_step))

    # -- fold ------------------------------------------------------------
    def _badput(self, cause: str, chip_seconds: float) -> None:
        if chip_seconds > 0.0:
            self.badput_s[cause] = (self.badput_s.get(cause, 0.0)
                                    + chip_seconds)

    def _fold(self, step: int, ranks: Dict[int, TrainStepTelemetry]) -> None:
        recs = list(ranks.values())
        chips_total = sum(max(1, r.chips) for r in recs)
        self.chips = max(self.chips, chips_total)
        min_start = min(r.start_t for r in recs)
        max_end = max(r.end_t for r in recs)
        wall = max(0.0, max_end - min_start)
        if step <= self.high_water:
            # re-executed after a checkpoint restore: every chip-second
            # of the replay is rework, whatever phase it spent it in
            self.rework_steps += 1
            for r in recs:
                chip_s = max(0.0, r.end_t - r.start_t) * max(1, r.chips)
                self._badput("rework", chip_s)
                self.wall_chip_s += chip_s
            self.recent.append({"step": step, "wall_s": round(wall, 6),
                                "rework": True})
            return
        self.high_water = step
        self.steps += 1
        step_tokens = sum(r.tokens for r in recs)
        step_flops = sum(r.flops for r in recs)
        for r in recs:
            chips = max(1, r.chips)
            for name, secs in r.phases.items():
                if name == "compute":
                    self.productive_s += secs * chips
                else:
                    self._badput(BADPUT_OF_PHASE.get(name, name),
                                 secs * chips)
            # barrier skew: this rank's chips idle outside its own
            # [start, end] while the envelope is open (late start + early
            # finish, both against the gang envelope)
            skew = (max(0.0, r.start_t - min_start)
                    + max(0.0, max_end - r.end_t))
            self._badput("straggler", skew * chips)
            key = f"rank{r.rank}" + (f"@{r.node_id[:12]}"
                                     if r.node_id else "")
            prev = self.rank_skew.get(key)
            self.rank_skew[key] = (skew if prev is None else
                                   (1 - self.SKEW_EMA) * prev
                                   + self.SKEW_EMA * skew)
        self.wall_chip_s += wall * chips_total
        self.tokens += step_tokens
        self.flops += step_flops
        step_mfu = None
        if wall > 0.0 and chips_total > 0:
            if self.peak_flops_per_chip > 0.0 and step_flops > 0.0:
                step_mfu = step_flops / (wall * self.peak_flops_per_chip
                                         * chips_total)
                self.mfu = (step_mfu if self.steps == 1 else
                            0.7 * self.mfu + 0.3 * step_mfu)
            if step_tokens > 0:
                tps = step_tokens / (wall * chips_total)
                self.tok_per_s_per_chip = (
                    tps if self.steps == 1 else
                    0.7 * self.tok_per_s_per_chip + 0.3 * tps)
        self.recent.append({
            "step": step, "wall_s": round(wall, 6),
            "mfu": None if step_mfu is None else round(step_mfu, 4),
            "tokens": step_tokens,
            "phases": {k: round(v, 6) for k, v in sorted(
                self._merged_phases(recs).items())},
        })

    @staticmethod
    def _merged_phases(recs) -> Dict[str, float]:
        out: Dict[str, float] = {}
        for r in recs:
            for name, secs in r.phases.items():
                out[name] = out.get(name, 0.0) + secs
        return out

    def _prune_pending(self) -> None:
        while len(self._pending) > self.MAX_PENDING:
            # oldest incomplete step is the one a dead rank will never
            # finish — fold what arrived into straggler-free accounting
            # would misattribute, so it is dropped
            self._pending.pop(min(self._pending))

    # -- derived views ---------------------------------------------------
    def total_badput_s(self) -> float:
        return sum(self.badput_s.values())

    def goodput_fraction(self) -> Optional[float]:
        denom = self.productive_s + self.total_badput_s()
        return (self.productive_s / denom) if denom > 0.0 else None

    def attributed_fraction(self) -> Optional[float]:
        """Fraction of observed wall-chip-seconds the ledger named
        (productive or a badput cause) — the >=90% acceptance bar."""
        if self.wall_chip_s <= 0.0:
            return None
        return min(1.0, (self.productive_s + self.total_badput_s())
                   / self.wall_chip_s)

    def to_record(self) -> TrainJobLedger:
        return TrainJobLedger(
            job=self.job, world_size=self.world_size, chips=self.chips,
            started_at=self.started_at, updated_at=self.updated_at,
            steps=self.steps, productive_s=self.productive_s,
            badput_s=dict(self.badput_s), tokens=self.tokens,
            flops=self.flops, mfu=self.mfu,
            tok_per_s_per_chip=self.tok_per_s_per_chip,
            compile_count=self.compile_count,
            cache_hit_count=self.cache_hit_count,
            recompile_count=self.recompile_count,
            rework_steps=self.rework_steps, restarts=self.restarts,
            rank_skew={k: round(v, 6)
                       for k, v in sorted(self.rank_skew.items())},
            goodput_fraction=self.goodput_fraction() or 0.0,
            attributed_fraction=self.attributed_fraction() or 0.0,
            recent=list(self.recent))

    # -- durable observability (obs checkpoint join) ---------------------
    def dump(self) -> Dict[str, Any]:
        return {
            "version": 1, "job": self.job, "world_size": self.world_size,
            "peak_flops_per_chip": self.peak_flops_per_chip,
            "started_at": self.started_at, "updated_at": self.updated_at,
            "chips": self.chips, "steps": self.steps,
            "productive_s": self.productive_s,
            "badput_s": dict(self.badput_s),
            "wall_chip_s": self.wall_chip_s,
            "tokens": self.tokens, "flops": self.flops,
            "mfu": self.mfu,
            "tok_per_s_per_chip": self.tok_per_s_per_chip,
            "compile_count": self.compile_count,
            "cache_hit_count": self.cache_hit_count,
            "recompile_count": self.recompile_count,
            "rework_steps": self.rework_steps, "restarts": self.restarts,
            "high_water": self.high_water,
            "rank_skew": dict(self.rank_skew),
            "recent": [dict(r) for r in self.recent],
        }

    def load(self, state: Dict[str, Any]) -> None:
        self.world_size = max(1, int(state.get("world_size", 1)))
        self.peak_flops_per_chip = float(
            state.get("peak_flops_per_chip", self.peak_flops_per_chip))
        self.started_at = float(state.get("started_at", self.started_at))
        self.updated_at = float(state.get("updated_at", self.updated_at))
        self.chips = int(state.get("chips", 0))
        self.steps = int(state.get("steps", 0))
        self.productive_s = float(state.get("productive_s", 0.0))
        self.badput_s = dict(state.get("badput_s") or {})
        self.wall_chip_s = float(state.get("wall_chip_s", 0.0))
        self.tokens = int(state.get("tokens", 0))
        self.flops = float(state.get("flops", 0.0))
        self.mfu = float(state.get("mfu", 0.0))
        self.tok_per_s_per_chip = float(
            state.get("tok_per_s_per_chip", 0.0))
        self.compile_count = int(state.get("compile_count", 0))
        self.cache_hit_count = int(state.get("cache_hit_count", 0))
        self.recompile_count = int(state.get("recompile_count", 0))
        self.rework_steps = int(state.get("rework_steps", 0))
        self.restarts = int(state.get("restarts", 0))
        self.high_water = int(state.get("high_water", 0))
        self.rank_skew = dict(state.get("rank_skew") or {})
        self.recent = collections.deque(
            (dict(r) for r in state.get("recent") or []),
            maxlen=self.HISTORY)
