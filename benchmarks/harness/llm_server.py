"""The replica the serve cells deploy: the class of the program that the
configuration's family names, under the benchmark's eyes.

The family (``benchmarks/families/<name>.py``) says which class that is
(``LLMServer`` today) and with which arguments it is made; everything a
request meets is that class's own code. What ``Watchers`` adds only
watches, written once for every family: the engine-side clocks of
finished requests, the width and occupancy of every decode round, the
compile counters, the profiler (only the chip's holder can trace it) and
the reference check (only the chip's holder has the weights).
"""

from __future__ import annotations

import asyncio
import json
import os
import time
from typing import Any, Dict, Optional

from . import families


def replica_class(config: dict) -> type:
    """The class a serve cell deploys for this configuration: the
    watchers over the class its family names. It is made here and so is
    no attribute of a module: the replica's process gets it by value."""
    return type("BenchReplica",
                (Watchers, families.family_of(config).server_class()), {})


class Watchers:
    """Mixed in before the family's class: makes it with the family's
    arguments, then watches it."""

    def __init__(self, config_path: str, *, seed: int = 0):
        with open(config_path) as f:
            self.bench_config = json.load(f)
        self.bench_family = families.family_of(self.bench_config)
        args, kwargs = self.bench_family.server_arguments(
            self.bench_config, seed)
        super().__init__(*args, **kwargs)
        self._finished: list = []     # engine-side clocks, one row a request
        self._rounds: list = []       # (t, width, active slots, live tokens)
        self._occupancy: list = []    # (t, running slots, compiles), 10 Hz
        self._poll_task: Optional[asyncio.Task] = None
        self._trace_task: Optional[asyncio.Task] = None
        self._trace: Dict[str, Any] = {}
        self._watch_rounds()

    # ------------------------------------------------------------ watching
    def _watch_rounds(self) -> None:
        """Note every decode round's width as the engine decides it. The
        engine calls ``_burst_width`` once a round, in traced and untraced
        runs alike (a list append; both kinds of run do the same work).
        Where a later engine has no such method nothing is recorded, the
        readers of the rounds find nothing, and ``run.py`` refuses to
        print a result that lacks a declared metric."""
        inner = getattr(self.engine, "_burst_width", None)
        if inner is None:
            return

        def watched() -> int:
            width = inner()
            active = [s for s in self.engine.slots
                      if s is not None and s.ctx_len > 0]
            self._rounds.append((time.perf_counter(), width, len(active),
                                 sum(s.ctx_len for s in active)))
            return width

        self.engine._burst_width = watched

    def _observe_finished(self, state, now: float) -> None:
        self._finished.append((state.request_id, state.arrival_t,
                               state.first_token_t, now, len(state.output),
                               len(state.prompt)))
        super()._observe_finished(state, now)

    async def _poll_occupancy(self) -> None:
        while True:
            self._occupancy.append(
                (time.perf_counter(), self.engine.stats()["running"],
                 sum(self._compile_counts().values())))
            await asyncio.sleep(0.1)

    def _compile_counts(self) -> Dict[str, int]:
        from ray_tpu._private import device_plane

        return dict(device_plane.compilation_cache_stats())

    # --------------------------------------------------- the benchmark's API
    async def bench_window(self, payload: dict) -> Dict[str, Any]:
        """``{"op": "start"}`` forgets what was watched so far and starts
        the 10 Hz poll; with ``trace_dir`` it also traces the device from
        ``trace_after_s`` for ``trace_for_s`` seconds. ``{"op": "stop"}``
        returns everything watched since."""
        loop = asyncio.get_event_loop()
        if payload["op"] == "start":
            self._finished.clear()
            self._rounds.clear()
            self._occupancy.clear()
            self._trace = {}
            if self._poll_task is None:
                self._poll_task = loop.create_task(self._poll_occupancy())
            if payload.get("trace_dir"):
                self._trace_task = loop.create_task(self._trace_later(
                    payload["trace_dir"], float(payload["trace_after_s"]),
                    float(payload["trace_for_s"])))
            return {"t": time.perf_counter()}
        if self._poll_task is not None:
            self._poll_task.cancel()
            self._poll_task = None
        if self._trace_task is not None:
            await self._trace_task
            self._trace_task = None
        return {
            "t": time.perf_counter(),
            "finished": list(self._finished),
            "rounds": list(self._rounds),
            "occupancy": list(self._occupancy),
            "max_num_seqs": self.engine.ecfg.max_num_seqs,
            "trace": self._trace,
        }

    async def _trace_later(self, trace_dir: str, after_s: float,
                           for_s: float) -> None:
        import jax

        from . import trace

        loop = asyncio.get_event_loop()
        await asyncio.sleep(after_s)
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        options.host_tracer_level = 1
        t0 = time.perf_counter()
        jax.profiler.start_trace(trace_dir, profiler_options=options)
        await asyncio.sleep(for_s)
        t1 = time.perf_counter()
        # writing the trace out takes seconds: off the event loop
        await loop.run_in_executor(None, jax.profiler.stop_trace)
        reduced = await loop.run_in_executor(
            None, trace.reduce_directory, trace_dir)
        self._trace = {"t0": t0, "t1": t1, **reduced}

    async def bench_reference(self, payload: dict) -> Dict[str, Any]:
        """Margins of the tokens the engine chose for the probe prompts,
        against the family's plain reference on this replica's own
        weights."""
        import jax.numpy as jnp
        import numpy as np

        prompts = jnp.asarray(payload["prompts"], jnp.int32)
        answers = jnp.asarray(payload["answers"], jnp.int32)

        def run():
            with self._engine_lock:
                return np.asarray(families.chosen_token_margins(
                    self.bench_family.forward_logits, self.engine.params,
                    prompts, answers, self.bench_config)).tolist()

        t0 = time.perf_counter()
        margins = await asyncio.get_event_loop().run_in_executor(None, run)
        return {"margins": margins, "seconds": time.perf_counter() - t0}

    async def bench_device(self, _payload=None) -> Dict[str, Any]:
        import jax

        stats = [d.memory_stats() or {} for d in jax.local_devices()]
        dev = self._device
        return {"platform": dev.platform, "kind": dev.device_kind,
                "count": len(jax.devices()), "pid": os.getpid(),
                "memory_peak_bytes": max(
                    (s.get("peak_bytes_in_use", 0) for s in stats),
                    default=0),
                "compile_cache_dir": self._compile_cache_dir,
                "compile_cache": self._compile_counts()}
