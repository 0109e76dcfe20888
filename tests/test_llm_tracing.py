"""The serving round and the train step on the profiler's clock: the one
span primitive (util/tracing.span), the fixed ``rt.*`` names the
benchmark's readers match on, and the engine's always-on request clocks
and round counters (ISSUE 24). Traces are read with ``ProfileData``, as
``benchmarks/harness/trace.py`` reads the chip's."""

import asyncio
import glob
import json
import os
import subprocess
import sys

import jax
import pytest

from ray_tpu.llm import EngineConfig, LLMEngine, SamplingParams
from ray_tpu.llm.engine import PHASES
from ray_tpu.models import LLAMA_CONFIGS, init_params
from ray_tpu.train.telemetry import StepTimeline
from ray_tpu.util import tracing

CFG = LLAMA_CONFIGS["tiny"]
ENGINE_SPANS = ["rt.engine." + phase for phase in PHASES]
PUMP_SPANS = ["rt.pump.fanout", "rt.pump.idle"]
PROMPTS = [[5, 17, 99, 3], [7, 8, 9, 10, 11, 12], [1, 2, 3]]


@pytest.fixture(scope="module")
def tiny_params():
    return init_params(jax.random.PRNGKey(0), CFG)


def _host_events(trace_dir):
    """``[(thread, name, start_ns, end_ns)]`` of the host plane."""
    from jax.profiler import ProfileData

    path = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))[-1]
    events = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name != "/host:CPU":
            continue
        for line in plane.lines:
            events.extend((line.name, e.name, e.start_ns,
                           e.start_ns + e.duration_ns)
                          for e in line.events)
    return events


def _trace(trace_dir, work):
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    options.host_tracer_level = 1
    jax.profiler.start_trace(str(trace_dir), profiler_options=options)
    try:
        work()
    finally:
        jax.profiler.stop_trace()
    return _host_events(str(trace_dir))


def _watch_burst_width(engine):
    """Count ``_burst_width`` calls the way the benchmark's replica
    does: by replacing the method on the instance."""
    calls = []
    inner = engine._burst_width

    def watched():
        calls.append(inner())
        return calls[-1]

    engine._burst_width = watched
    return calls


@pytest.fixture(scope="module")
def served(tiny_params):
    """A few requests of unequal length through one engine: the finished
    states, the counters and the widths ``_burst_width`` returned."""
    engine = LLMEngine(tiny_params, CFG, EngineConfig(
        max_num_seqs=2, page_size=4, num_pages=64, max_seq_len=64,
        decode_burst=4))
    calls = _watch_burst_width(engine)
    ids = [engine.add_request(p, SamplingParams(temperature=0.0,
                                                max_tokens=3 + 2 * i))
           for i, p in enumerate(PROMPTS)]
    while engine.has_unfinished():
        engine.step()
    return ([engine.requests[rid] for rid in ids],
            engine.stats()["counters"], calls)


def test_request_clocks_are_ordered(served):
    states, _counters, _calls = served
    for s in states:
        assert s.finished and s.preemptions == 0
        assert 0.0 < s.arrival_t <= s.admit_t <= s.prefill_start_t \
            <= s.first_token_t
    # two slots, three requests: the third waited for a slot
    assert states[2].admit_t >= states[0].first_token_t


def test_round_counters(served):
    states, c, calls = served
    assert c["rounds"] == len(calls) > 0
    assert c["decode_steps"] == sum(calls)
    assert len(c["width_hist"]) == 4 + 1
    assert sum(c["width_hist"]) == c["rounds"]
    assert sum(i * n for i, n in enumerate(c["width_hist"])) \
        == c["decode_steps"]
    # every token but a request's first comes from a decode step
    assert c["active_slot_steps"] >= sum(len(s.output) - 1 for s in states)
    assert c["prefills"] == len(PROMPTS)
    assert c["prefill_tokens"] == sum(len(p) for p in PROMPTS)
    assert c["preemptions"] == 0
    assert list(c["host_s"]) == list(PHASES)
    assert all(v >= 0.0 for v in c["host_s"].values())
    assert c["host_s"]["decode.dispatch"] > 0.0


def test_counters_are_a_copy(served, tiny_params):
    engine = LLMEngine(tiny_params, CFG, EngineConfig(
        max_num_seqs=1, page_size=4, num_pages=32, max_seq_len=32))
    snapshot = engine.stats()["counters"]
    snapshot["width_hist"][1] = 99
    snapshot["host_s"]["append"] = 99.0
    fresh = engine.stats()["counters"]
    assert fresh["width_hist"][1] == 0 and fresh["host_s"]["append"] == 0.0
    # the keys the harness and the pump read stay where they were
    assert {"running", "waiting", "free_pages", "total_pages",
            "counters"} <= set(engine.stats())


def test_forced_preemption_is_counted(tiny_params):
    """Six usable pages of four tokens: two prompts fit, their answers
    do not, so the younger is preempted and prefilled again."""
    engine = LLMEngine(tiny_params, CFG, EngineConfig(
        max_num_seqs=2, page_size=4, num_pages=7, max_seq_len=24,
        decode_burst=2))
    ids = [engine.add_request(p, SamplingParams(temperature=0.0,
                                                max_tokens=12))
           for p in ([1, 2, 3, 4, 5, 6], [7, 8, 9, 10, 11, 12])]
    while engine.has_unfinished():
        engine.step()
    states = [engine.requests[rid] for rid in ids]
    counters = engine.stats()["counters"]
    assert all(s.finished and len(s.output) == 12 for s in states)
    assert counters["preemptions"] == sum(s.preemptions for s in states) > 0
    assert states[1].preemptions > 0        # the younger is the victim
    assert counters["prefills"] == 2 + counters["preemptions"]
    for s in states:    # the clocks keep their first reading
        assert s.arrival_t <= s.admit_t <= s.prefill_start_t \
            <= s.first_token_t


@pytest.fixture(scope="module")
def engine_trace(tiny_params, tmp_path_factory):
    engine = LLMEngine(tiny_params, CFG, EngineConfig(
        max_num_seqs=2, page_size=4, num_pages=64, max_seq_len=64))
    for p in PROMPTS:       # compile outside the trace
        engine.add_request(p, SamplingParams(temperature=0.0, max_tokens=4))
    while engine.has_unfinished():
        engine.step()

    def work():
        for p in PROMPTS:
            engine.add_request(p, SamplingParams(temperature=0.0,
                                                 max_tokens=4))
        while engine.has_unfinished():
            engine.step()

    return _trace(tmp_path_factory.mktemp("engine_trace"), work)


@pytest.mark.parametrize("name", ENGINE_SPANS)
def test_engine_phase_is_in_the_profiler_trace(engine_trace, name):
    assert any(e[1] == name for e in engine_trace)


def test_no_span_encloses_a_round(engine_trace):
    """The phases are siblings: every ``rt.`` event is one of the six,
    and none of them holds another (a parent over the round would take
    the name of every device idle gap from its children)."""
    ours = sorted((e for e in engine_trace if e[1].startswith("rt.")),
                  key=lambda e: e[2])
    assert {e[1] for e in ours} == set(ENGINE_SPANS)
    for (_, a, _, a_end), (_, b, b_start, _) in zip(ours, ours[1:]):
        assert a_end <= b_start, f"{a} overlaps {b}"


@pytest.fixture(scope="module")
def pump_trace(tmp_path_factory):
    from ray_tpu.llm.serve import LLMServer

    server = LLMServer("tiny", engine_config={
        "max_num_seqs": 2, "page_size": 4, "num_pages": 64,
        "max_seq_len": 64})
    states = []
    observe = server._observe_finished

    def keep(state, now):
        states.append(state)
        observe(state, now)

    server._observe_finished = keep

    async def serve_some():
        real_step = server.engine.step
        # one empty round, so that the pump idles once under the trace
        server.engine.step = lambda **kw: (
            setattr(server.engine, "step", real_step) or [])
        await asyncio.gather(*[server.completions(
            {"prompt_ids": p, "temperature": 0.0, "max_tokens": 4})
            for p in PROMPTS])
        return await server.stats()

    asyncio.run(serve_some())       # compile outside the trace
    states.clear()
    stats = {}
    events = _trace(tmp_path_factory.mktemp("pump_trace"),
                    lambda: stats.update(asyncio.run(serve_some())))
    return events, states, stats


@pytest.mark.parametrize("name", PUMP_SPANS)
def test_pump_phase_is_in_the_profiler_trace(pump_trace, name):
    events, _states, _stats = pump_trace
    assert any(e[1] == name for e in events)
    assert {e[1] for e in events if e[1].startswith("rt.")} \
        <= set(ENGINE_SPANS + PUMP_SPANS)


def test_pump_stamps_the_hand_over_and_passes_counters(pump_trace):
    _events, states, stats = pump_trace
    assert len(states) == len(PROMPTS)
    for s in states:    # the first token leaves after the round it came in
        assert s.first_token_t <= s.emit_t
    assert stats["pool"] == "mono"
    assert stats["counters"]["prefills"] == 2 * len(PROMPTS)


def test_finished_requests_feed_metrics_and_the_llm_lane(
        tmp_path, monkeypatch):
    """A replica short of pages, with RAY_TPU_TRACING=1: queue wait and
    preemptions reach the operator's metrics, and every request leaves
    its three intervals on the ``llm`` lane under its own id, beside the
    round's phases on the ``engine`` and ``pump`` lanes."""
    from ray_tpu.llm.serve import LLMServer
    from ray_tpu.util.metrics import snapshot_local

    monkeypatch.setenv("RAY_TPU_TRACING", "1")
    monkeypatch.setattr(tracing, "_sink", None)
    monkeypatch.setattr(tracing, "_span_dir", lambda: str(tmp_path))
    server = LLMServer("tiny", engine_config={
        "max_num_seqs": 2, "page_size": 4, "num_pages": 7,
        "max_seq_len": 24, "decode_burst": 2})
    before = snapshot_local("llm_")

    async def go():
        return await asyncio.gather(*[server.completions(
            {"prompt_ids": p, "temperature": 0.0, "max_tokens": 12})
            for p in ([1, 2, 3, 4, 5, 6], [7, 8, 9, 10, 11, 12])])

    outs = asyncio.run(go())
    assert all(len(o["choices"][0]["token_ids"]) == 12 for o in outs)
    after = snapshot_local("llm_")
    wait = "llm_queue_wait_seconds{__stat__=count,model=tiny,pool=mono}"
    assert after.get(wait, 0.0) - before.get(wait, 0.0) == 2, after
    preempted = "llm_preemptions_total{model=tiny,pool=mono}"
    assert after.get(preempted, 0.0) - before.get(preempted, 0.0) >= 1

    tracing._sink.close()
    (path,) = glob.glob(str(tmp_path / "spans-*.jsonl"))
    with open(path) as f:
        records = [json.loads(line) for line in f]
    by_lane = {}
    for r in records:
        by_lane.setdefault(r["lane"], []).append(r)
    assert {"llm", "engine", "pump"} <= set(by_lane)
    ids = {r["args"]["request_id"] for r in by_lane["llm"]}
    assert len(ids) == 2
    for rid in ids:
        mine = {r["name"]: r for r in by_lane["llm"]
                if r["args"]["request_id"] == rid}
        assert set(mine) == {"queue", "prefill", "decode"}
        assert mine["queue"]["end"] == pytest.approx(
            mine["prefill"]["start"])
        assert mine["prefill"]["end"] == pytest.approx(
            mine["decode"]["start"])
    assert {r["name"] for r in by_lane["engine"]} == set(
        ENGINE_SPANS + ["rt.engine.round"])
    assert "rt.pump.fanout" in {r["name"] for r in by_lane["pump"]}


def test_span_off_writes_nothing(tmp_path, monkeypatch):
    monkeypatch.delenv("RAY_TPU_TRACING", raising=False)
    monkeypatch.setattr(tracing, "_sink", None)
    monkeypatch.setattr(tracing, "_span_dir", lambda: str(tmp_path))
    with tracing.span("rt.engine.schedule") as sp:
        pass
    with tracing.step_span("rt.train.step", 3):
        pass
    assert sp.seconds >= 0.0
    assert os.listdir(tmp_path) == []


def test_span_on_writes_a_lane_record(tmp_path, monkeypatch):
    monkeypatch.setenv("RAY_TPU_TRACING", "1")
    monkeypatch.setattr(tracing, "_sink", None)
    monkeypatch.setattr(tracing, "_span_dir", lambda: str(tmp_path))
    with tracing.span("rt.engine.prefill.sync", request_id="abc"):
        pass
    with tracing.step_span("rt.train.step", 7):
        pass
    tracing._sink.close()
    (path,) = glob.glob(str(tmp_path / "spans-*.jsonl"))
    with open(path) as f:
        first, second = [json.loads(line) for line in f]
    assert (first["kind"], first["lane"], first["name"]) == (
        "lane", "engine", "rt.engine.prefill.sync")
    assert first["args"] == {"request_id": "abc"}
    assert first["start"] <= first["end"]
    assert (second["lane"], second["name"], second["args"]) == (
        "train", "rt.train.step", {"step": 7})


def test_tracing_module_does_not_import_jax():
    code = ("import sys; import ray_tpu.util.tracing as t; "
            "import ray_tpu.train.telemetry; "
            "s = t.span('rt.engine.append'); s.__enter__(); "
            "s.__exit__(None, None, None); "
            "sys.exit(1 if 'jax' in sys.modules else 0)")
    assert subprocess.run([sys.executable, "-c", code],
                          timeout=120).returncode == 0


@pytest.mark.parametrize("phase", ["data_wait", "collective_sync",
                                   "my_user_phase"])
def test_timeline_phases_are_train_spans(tmp_path, phase):
    """Canonical and user phases alike leave ``rt.train.<name>`` in the
    trace, a nested one inside its parent."""
    timeline = StepTimeline()
    timeline.step = 5

    def work():
        with timeline.phase("checkpoint_save"):
            with timeline.phase(phase):
                pass

    events = [e for e in _trace(tmp_path, work) if e[1].startswith("rt.")]
    assert sorted(e[1] for e in events) == sorted(
        ["rt.train.checkpoint_save", "rt.train." + phase])
    outer = next(e for e in events if e[1] == "rt.train.checkpoint_save")
    inner = next(e for e in events if e[1] == "rt.train." + phase)
    assert outer[2] <= inner[2] and inner[3] <= outer[3]
    _start, _end, phases, _intervals = timeline.close()
    assert set(phases) >= {"checkpoint_save"}
